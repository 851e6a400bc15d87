"""BatchNorm's training step in closed form (`ops/nn.py::_batch_norm_train`).

The forward takes both statistics in ONE pass over the activation, the
backward is two sibling sums and one elementwise expression. The numbers are
held to a float64 two-pass numpy reference; the mechanism — that no reduction
over the activation waits for another — is held on the jaxpr of the gradient,
which a CPU can read: the reductions whose operand is the 4-D activation sit
at two sequential levels, where autodiff of `jnp.mean` / `jnp.var` left five.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.ops.nn import _batch_norm

EPS, MOMENTUM = 2e-5, 0.9


def _vec(a, axis, ndim):
    shape = [1] * ndim
    shape[axis] = -1
    return a.reshape(shape)


def _reference(x, gamma, beta, dy, axis, fix_gamma):
    """The textbook two-pass BatchNorm and its gradients, float64 numpy."""
    x, gamma, beta, dy = (np.asarray(a, np.float64) for a in (x, gamma, beta, dy))
    axis %= x.ndim
    red = tuple(i for i in range(x.ndim) if i != axis)
    if fix_gamma:
        gamma = np.ones_like(gamma)
    mean = x.mean(axis=red)
    var = ((x - _vec(mean, axis, x.ndim)) ** 2).mean(axis=red)
    inv = 1.0 / np.sqrt(var + EPS)
    xhat = (x - _vec(mean, axis, x.ndim)) * _vec(inv, axis, x.ndim)
    out = xhat * _vec(gamma, axis, x.ndim) + _vec(beta, axis, x.ndim)
    dbeta = dy.sum(axis=red)
    dgamma = (dy * xhat).sum(axis=red)
    dxhat = dy * _vec(gamma, axis, x.ndim)
    dx = _vec(inv, axis, x.ndim) * (
        dxhat - dxhat.mean(axis=red, keepdims=True)
        - xhat * (dxhat * xhat).mean(axis=red, keepdims=True))
    if fix_gamma:
        dgamma = np.zeros_like(dgamma)
    return out, dx, dgamma, dbeta, mean, var


def _inputs(dtype, axis, shifted, seed=0):
    rng = np.random.RandomState(seed)
    c = 6
    shape = (4, c, 5, 3) if axis == 1 else (4, 5, 3, c)
    x = rng.normal(0, 1, shape)
    if shifted:  # channel means ~50x the spread
        x = x + _vec(rng.choice([-50.0, 50.0], c) + rng.normal(0, 5, c),
                     axis % 4, 4)
    x = jnp.asarray(x, dtype)
    gamma = jnp.asarray(rng.uniform(0.5, 1.5, c), jnp.float32)
    beta = jnp.asarray(rng.normal(0, 1, c), jnp.float32)
    dy = jnp.asarray(rng.normal(0, 1, shape), dtype)
    return x, gamma, beta, dy


def _train(x, gamma, beta, mm, mv, axis, fix_gamma, momentum=MOMENTUM):
    return _batch_norm(x, gamma, beta, mm, mv, eps=EPS, momentum=momentum,
                       fix_gamma=fix_gamma, axis=axis, _train=True)


@pytest.fixture
def traced():
    """Telemetry on for the test; `traced()` is what
    `batchnorm.train.closed_form` has gained since."""
    telemetry.enable()
    counter = telemetry.counter("batchnorm.train.closed_form")
    before = counter.value
    try:
        yield lambda: counter.value - before
    finally:
        telemetry.disable()


# one case a quantity, so that each counts
QUANTITIES = ("out", "dx", "dgamma", "dbeta", "moving_mean", "moving_var")


@pytest.fixture(scope="module")
def results():
    cache = {}

    def get(dtype, fix_gamma, axis, shifted):
        key = (dtype, fix_gamma, axis, shifted)
        if key in cache:
            return cache[key]
        x, gamma, beta, dy = _inputs(dtype, axis, shifted)
        c = gamma.shape[0]
        mm, mv = jnp.zeros(c, jnp.float32), jnp.ones(c, jnp.float32)
        if shifted:
            # the pivot's case: one warm-up update (at momentum 0, so it
            # lands) puts the moving mean on the channel means
            _, mm, mv = _train(x, gamma, beta, mm, mv, axis, fix_gamma, momentum=0.0)
        (out, new_mm, new_mv), vjp = jax.vjp(
            lambda x_, g_, b_: _train(x_, g_, b_, mm, mv, axis, fix_gamma),
            x, gamma, beta)
        dx, dgamma, dbeta = vjp((dy, jnp.zeros_like(new_mm), jnp.zeros_like(new_mv)))
        r_out, r_dx, r_dgamma, r_dbeta, r_mean, r_var = _reference(
            x.astype(jnp.float32), gamma, beta, dy.astype(jnp.float32), axis, fix_gamma)
        assert out.dtype == x.dtype and dx.dtype == x.dtype
        assert dgamma.dtype == gamma.dtype and dbeta.dtype == beta.dtype
        got = dict(out=out, dx=dx, dgamma=dgamma, dbeta=dbeta,
                   moving_mean=new_mm, moving_var=new_mv)
        want = dict(out=r_out, dx=r_dx, dgamma=r_dgamma, dbeta=r_dbeta,
                    moving_mean=MOMENTUM * np.asarray(mm) + (1 - MOMENTUM) * r_mean,
                    moving_var=MOMENTUM * np.asarray(mv) + (1 - MOMENTUM) * r_var)
        cache[key] = got, want
        return cache[key]

    return get


@pytest.mark.parametrize("quantity", QUANTITIES)
@pytest.mark.parametrize("shifted", [False, True], ids=["centred", "mean50x"])
@pytest.mark.parametrize("axis", [1, -1])
@pytest.mark.parametrize("fix_gamma", [False, True], ids=["gamma", "fix_gamma"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_matches_two_pass_float64(results, dtype, fix_gamma, axis, shifted, quantity):
    got, want = results(dtype, fix_gamma, axis, shifted)
    g = np.asarray(got[quantity].astype(jnp.float32), np.float64)
    w = want[quantity]
    # what is cast back to bfloat16 carries its 8 bits; the statistics and
    # the parameters' gradients are float32 whatever the input
    half = dtype == "bfloat16" and quantity in ("out", "dx")
    rtol = 1e-2 if half else 2e-4
    np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol * max(1.0, np.abs(w).max()))


@pytest.mark.parametrize("pivot, rtol", [("cold", 1e-2), ("warm", 1e-5)])
def test_what_the_pivot_is_worth(pivot, rtol):
    """`E[d^2] - E[d]^2` at |E[d]| = 50 spreads loses (50)^2 of float32's
    digits: with a cold moving mean (zeros: the plain `E[x^2] - E[x]^2`) the
    variance is good to ~1e-3, with the moving mean on the channel means to
    rounding. A moving mean at momentum 0.9 is within a spread of a mean 50
    spreads away after 38 steps."""
    x, gamma, beta, _ = _inputs("float32", 1, shifted=True)
    mm = jnp.zeros(6)
    if pivot == "warm":
        _, mm, _ = _train(x, gamma, beta, mm, jnp.ones(6), 1, False, momentum=0.0)
    _, _, var = _train(x, gamma, beta, mm, jnp.ones(6), 1, False, momentum=0.0)
    want = np.asarray(x, np.float64).var(axis=(0, 2, 3))
    np.testing.assert_allclose(np.asarray(var), want, rtol=rtol)


def _parent_inference(data, gamma, beta, moving_mean, moving_var, eps, fix_gamma, axis):
    """`_batch_norm`'s inference branch as the parent commit had it."""
    axis = axis % data.ndim
    if fix_gamma:
        gamma = jnp.ones_like(gamma)
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    xf = data.astype(jnp.float32)
    mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    inv = jax.lax.rsqrt(var + eps)
    out = (xf - mean.reshape(shape)) * inv.reshape(shape)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@pytest.mark.parametrize("mode", ["use_global_stats", "inference"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moving_statistics_path_is_the_parents(traced, dtype, mode):
    x, gamma, beta, _ = _inputs(dtype, 1, shifted=False)
    rng = np.random.RandomState(1)
    mm = jnp.asarray(rng.normal(0, 1, 6), jnp.float32)
    mv = jnp.asarray(rng.uniform(0.5, 2, 6), jnp.float32)
    kw = dict(use_global_stats=True, _train=True) if mode == "use_global_stats" \
        else dict(_train=False)
    out, new_mm, new_mv = _batch_norm(x, gamma, beta, mm, mv, eps=EPS,
                                      fix_gamma=False, **kw)
    assert traced() == 0
    want = _parent_inference(x, gamma, beta, mm, mv, EPS, False, 1)
    assert out.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(out.astype(jnp.float32)),
                                  np.asarray(want.astype(jnp.float32)))
    assert new_mm is mm and new_mv is mv


def test_statistics_carry_no_gradient():
    """Outputs 1 and 2 feed the moving statistics only (`batch_norm.cc`)."""
    x, gamma, beta, _ = _inputs("float32", 1, shifted=False)
    mm, mv = jnp.zeros(6), jnp.ones(6)
    dx = jax.grad(lambda x_: sum(jnp.sum(o) for o in
                                 _train(x_, gamma, beta, mm, mv, 1, False)[1:]))(x)
    assert not np.asarray(dx).any()


def test_second_order_gradient_runs():
    """The rule is ordinary jax code, so it differentiates again: the
    gradient of |dx|^2 against a float64 finite difference of it."""
    x, gamma, beta, dy = _inputs("float32", 1, shifted=False)
    mm, mv = jnp.zeros(6), jnp.ones(6)

    def first(x_):
        return jax.grad(lambda a: jnp.sum(
            _train(a, gamma, beta, mm, mv, 1, False)[0] * dy))(x_)

    def penalty(x_):
        return jnp.sum(first(x_) ** 2)

    g = np.asarray(jax.grad(penalty)(x), np.float64)
    assert np.isfinite(g).all() and np.abs(g).max() > 0

    def penalty64(x64):
        return (_reference(x64, gamma, beta, dy, 1, False)[1] ** 2).sum()

    x64 = np.asarray(x, np.float64)
    for idx in [(0, 0, 0, 0), (1, 2, 3, 1), (3, 5, 4, 2)]:
        h = np.zeros_like(x64)
        h[idx] = 1e-5
        fd = (penalty64(x64 + h) - penalty64(x64 - h)) / 2e-5
        np.testing.assert_allclose(g[idx], fd, rtol=2e-3, atol=1e-4)


# ---------------------------------------------------------------------------
# the mechanism: how many times, and in how many turns, the gradient's
# program reads the activation
# ---------------------------------------------------------------------------


def _sub_jaxprs(eqn):
    for v in eqn.params.values():
        for j in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(j, "jaxpr") and hasattr(j.jaxpr, "eqns"):
                yield j.jaxpr  # ClosedJaxpr
            elif hasattr(j, "eqns"):
                yield j


def _activation_reduction_levels(jaxpr, depth_in, levels, ndim):
    """Walk `jaxpr` with every call's body inlined. A variable's depth is
    the number of reductions over an `ndim`-D operand on the longest chain
    that leads to it; `levels` collects the depth of each such reduction."""
    depth = dict(zip(jaxpr.invars, depth_in))

    def of(v):
        return depth.get(v, 0) if hasattr(v, "count") else 0  # Literal: 0

    for eqn in jaxpr.eqns:
        d = max([of(v) for v in eqn.invars], default=0)
        subs = list(_sub_jaxprs(eqn))
        if subs:
            (sub,) = subs
            outs = _activation_reduction_levels(
                sub, [of(v) for v in eqn.invars][-len(sub.invars):], levels, ndim)
            for v, o in zip(eqn.outvars, outs):
                depth[v] = o
            continue
        if eqn.primitive.name.startswith("reduce_") and eqn.invars[0].aval.ndim == ndim:
            d += 1
            levels.append(d)
        for v in eqn.outvars:
            depth[v] = d
    return [of(v) for v in jaxpr.outvars]


def _levels_of_grad(fn, *args):
    closed = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1, 2)))(*args)
    levels = []
    _activation_reduction_levels(closed.jaxpr, [0] * len(closed.jaxpr.invars), levels, 4)
    return levels


def test_the_walker_counts_the_two_pass_form_at_five_levels():
    """The yardstick on the formulation it replaced: autodiff of
    `jnp.mean` / `jnp.var` reads the activation nine times at five levels
    (forward 1, 1, 2; backward four at 3, then 4, 5); the tenth is the
    loss's own sum."""
    x, gamma, beta, _ = _inputs("float32", 1, shifted=False)

    def two_pass(x_, g_, b_):
        mean = jnp.mean(x_, axis=(0, 2, 3))
        var = jnp.var(x_, axis=(0, 2, 3))
        out = (x_ - _vec(mean, 1, 4)) * _vec(jax.lax.rsqrt(var + EPS), 1, 4)
        return jnp.sum(jax.nn.relu(out * _vec(g_, 1, 4) + _vec(b_, 1, 4)))

    levels = _levels_of_grad(two_pass, x, gamma, beta)
    assert sorted(levels) == [1, 1, 2, 3, 3, 3, 3, 3, 4, 5], levels


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_gradient_reads_the_activation_at_two_levels(dtype):
    x, gamma, beta, _ = _inputs(dtype, 1, shifted=False)
    mm, mv = jnp.zeros(6), jnp.ones(6)

    def loss(x_, g_, b_):
        out = _train(x_, g_, b_, mm, mv, 1, False)[0]
        return jnp.sum(jax.nn.relu(out).astype(jnp.float32))

    levels = _levels_of_grad(loss, x, gamma, beta)
    # sum(d), sum(d^2) | sum(dy), sum(dy * xhat): nothing else but the
    # loss's own sum reads a 4-D operand
    assert max(levels) == 2, levels
    assert len(levels) <= 5, levels
    assert sorted(levels)[:2] == [1, 1], levels


def test_resnet50_training_step_counts_51(traced):
    """Every BatchNorm node of `resnet50_symbol`'s training step traces down
    the closed form, once: `batchnorm.train.closed_form` reads 51."""
    from mxnet_tpu.models.resnet import resnet50_symbol
    from mxnet_tpu.symbol.executor import _graph_fn

    sym = resnet50_symbol()
    arg_names, aux_names = sym.list_arguments(), sym.list_auxiliary_states()
    arg_shapes, _, aux_shapes = sym.infer_shape(data=(2, 3, 224, 224), softmax_label=(2,))
    args = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in arg_shapes)
    auxs = tuple(jax.ShapeDtypeStruct(s, jnp.float32) for s in aux_shapes)
    assert sum(n.endswith("moving_mean") for n in aux_names) == 51
    base = _graph_fn(sym, arg_names, aux_names, True)

    def step(key, args, auxs):
        outputs, vjp, aux_new = jax.vjp(lambda *a: base(key, a, auxs), *args, has_aux=True)
        return vjp(tuple(jnp.ones(o.shape, o.dtype) for o in outputs)), aux_new

    jax.eval_shape(step, jax.random.PRNGKey(0), args, auxs)
    assert traced() == 51


def test_gluon_resnet50_v1_counts_53_a_trace(traced):
    """The gluon cell's model holds 53 BatchNorm blocks; its hybridized
    training step traces the model a whole number of times."""
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon, nd
    from mxnet_tpu.gluon.model_zoo import vision

    net = vision.resnet50_v1(classes=10)
    net.initialize(mx.init.Xavier())

    def blocks(b):
        return isinstance(b, gluon.nn.BatchNorm) + sum(blocks(c) for c in b._children.values())

    assert blocks(net) == 53
    net.hybridize()
    with autograd.record():
        loss = net(nd.random.normal(0, 1, shape=(2, 3, 32, 32))).sum()
    loss.backward()
    loss.asnumpy()
    assert traced() > 0 and traced() % 53 == 0, traced()
