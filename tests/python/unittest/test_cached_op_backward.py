"""A hybridized call under ``autograd.record()`` keeps its inputs, not its
residuals, and ``backward()`` runs forward and pullback as one program.

Every case compares outputs and gradients of a small hybridized
conv-BN-dense net + loss with ``jax.value_and_grad`` of the same function
written in plain jax (float32: 1e-5; bfloat16 net against the float32
reference at the rounded weights: 2e-2, both of the value's scale), and
the three counters with what the path should count.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn

BATCH, CLASSES = 8, 5
TOL = {"float32": 1e-5, "bfloat16": 2e-2}
COUNTERS = ("autograd.fused_backward", "autograd.forced_forward",
            "autograd.recorded_calls_dropped")


def _counts():
    return {c: telemetry.counter(c).value for c in COUNTERS}


def _moved(before):
    return tuple(telemetry.counter(c).value - before[c] for c in COUNTERS)


def _reference(params, x, y, scale=1.0, weights=None):
    """The net and the loss in plain jax: sum of the (weighted) per-sample
    losses, and (per-sample losses, logits) beside it."""
    cw, cb, gamma, beta, dw, db = params
    h = jax.lax.conv_general_dilated(
        x, cw, (1, 1), ((1, 1), (1, 1)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision="highest") + cb[None, :, None, None]
    mean = h.mean((0, 2, 3), keepdims=True)
    var = ((h - mean) ** 2).mean((0, 2, 3), keepdims=True)
    h = (h - mean) / jnp.sqrt(var + 1e-5) * gamma[None, :, None, None] \
        + beta[None, :, None, None]
    h = jnp.maximum(h, 0).reshape(h.shape[0], -1)
    logits = jnp.dot(h, dw.T, precision="highest") + db
    logp = jax.nn.log_softmax(logits * scale, axis=-1)
    loss = -jnp.take_along_axis(logp, y.astype(jnp.int32)[:, None], 1)[:, 0]
    total = loss.sum() if weights is None else (loss * weights).sum()
    return total, (loss, logits)


class _Job:
    """Net, loss, data and the float32 reference of one dtype."""

    def __init__(self, dtype, hybrid_loss=True, grad_req="write", seed=0):
        rng = np.random.RandomState(seed)
        mx.random.seed(seed)
        self.dtype = dtype
        self.net = nn.HybridSequential()
        self.net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
                     nn.BatchNorm(in_channels=4), nn.Activation("relu"),
                     nn.Flatten(), nn.Dense(CLASSES, in_units=4 * 6 * 6))
        # before initialize(): a later switch of an initialized parameter
        # from write to add does not reach its arrays
        self.net.collect_params().setattr("grad_req", grad_req)
        self.net.initialize(mx.init.Xavier(magnitude=2.0))
        self.net.hybridize()
        self.net.cast(dtype)
        self.sce = gluon.loss.SoftmaxCrossEntropyLoss()
        if hybrid_loss:
            self.sce.hybridize()
        self.x = nd.array(rng.randn(BATCH, 3, 6, 6).astype(np.float32)) \
            .astype(dtype)
        self.y = nd.array((np.arange(BATCH) % CLASSES).astype(np.float32))
        by_name = self.net.collect_params()
        self.params = [p for n, p in by_name.items()
                       if not n.endswith(("running_mean", "running_var"))]
        assert len(self.params) == 6
        self.values = [jnp.asarray(p.data().asnumpy(), jnp.float32)
                       for p in self.params]

    def expected(self, **kw):
        x = jnp.asarray(self.x.asnumpy(), jnp.float32)
        y = jnp.asarray(self.y.asnumpy())
        (_, (loss, logits)), grads = jax.value_and_grad(
            _reference, has_aux=True)(self.values, x, y, **kw)
        return loss, logits, grads

    def close(self, got, want, what, scale=None):
        got = np.asarray(got.asnumpy() if hasattr(got, "asnumpy") else got,
                         np.float64)
        want = np.asarray(want, np.float64)
        err = np.abs(got - want).max() / (scale or np.abs(want).max())
        assert err <= TOL[self.dtype], f"{what}: {err:.3g} of its scale"

    def check(self, out, loss, grads=None, factor=1.0, **kw):
        want_loss, want_out, want_grads = self.expected(**kw)
        if out is not None:
            self.close(out, want_out, "logits")
        if loss is not None:
            self.close(loss, want_loss, "loss")
        grads = grads or [p.grad() for p in self.params]
        # one scale for all gradients: the convolution's bias, in front of
        # a batch norm, has a gradient of exactly zero
        scale = factor * max(float(jnp.abs(w).max()) for w in want_grads)
        for p, g, w in zip(self.params, grads, want_grads):
            if g is not None:
                self.close(g, w * factor, f"grad of {p.name}", scale)


def _fused(job):
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    loss.backward()
    job.check(out, loss)
    return 1, 0, 0


def _forced(job):
    with autograd.record():
        out = job.net(job.x)
        seen = out.asnumpy()        # read inside record(): forward-only
        loss = job.sce(out, job.y)
    loss.backward()
    job.check(out, loss)
    np.testing.assert_array_equal(seen, out.asnumpy())
    return 1, 1, 0


def _eager_between(job):
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out * 2, job.y)
    loss.backward()
    job.check(out, loss, scale=2.0)
    return 0, 1, 0


def _eager_loss(job):
    job = _Job(job.dtype, hybrid_loss=False)
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    loss.backward()
    job.check(out, loss)
    return 0, 1, 0


def _grad_add(job):
    job = _Job(job.dtype, grad_req="add")
    for _ in range(2):
        with autograd.record():
            out = job.net(job.x)
            loss = job.sce(out, job.y)
        loss.backward()
    job.check(out, loss, factor=2.0)
    return 2, 0, 0


def _grad_null(job):
    frozen = job.params[0]
    frozen.grad_req = "null"
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    loss.backward()
    job.check(out, loss, grads=[None] + [p.grad() for p in job.params[1:]])
    assert frozen.data().grad is None
    assert len(_backward_outputs(job.sce)) == 5 + 2
    return 1, 0, 0


def _head_grads(job):
    w = np.linspace(-1.0, 2.0, BATCH).astype(np.float32)
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    loss.backward(nd.array(w))
    job.check(out, loss, weights=jnp.asarray(w))
    return 1, 0, 0


def _retain_graph(job):
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    loss.backward(retain_graph=True)
    job.check(out, loss)
    for p in job.params:
        p.zero_grad()
    loss.backward()
    job.check(out, loss)
    return 2, 0, 0


def _autograd_grad(job):
    arrays = [p.data() for p in job.params]
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    grads = autograd.grad(loss, arrays)
    job.check(out, loss, grads=grads)
    for p in job.params:            # .grad buffers are not touched
        assert not p.grad().asnumpy().any()
    return 1, 0, 0


def _param_written(job):
    with autograd.record():
        out = job.net(job.x)
        loss = job.sce(out, job.y)
    for p in job.params:            # the recorded call keeps what it saw
        p.set_data(p.data() * 0 + 3)
    loss.backward()
    job.check(out, loss)
    return 1, 0, 0


def _chained_calls(job):
    """Two hybridized blocks under a plain Block and a hybridized loss:
    three recorded calls, one program."""
    head = job.net[-1]
    body = nn.HybridSequential()
    body.add(*list(job.net)[:-1])
    body.hybridize()
    with autograd.record():
        out = head(body(job.x))
        loss = job.sce(out, job.y)
    loss.backward()
    job.check(out, loss)
    keys = [k for k in job.sce._cached_op._cache.keys() if k[0] == "bwd"]
    assert len(keys) == 1 and len(keys[0][1]) == 3
    return 1, 0, 0


PATHS = [_fused, _forced, _eager_between, _eager_loss, _grad_add, _grad_null,
         _head_grads, _retain_graph, _autograd_grad, _param_written,
         _chained_calls]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("path", PATHS, ids=lambda f: f.__name__.strip("_"))
def test_matches_value_and_grad(path, dtype):
    job = _Job(dtype)
    before = _counts()
    assert path(job) == _moved(before)


def _backward_outputs(loss_block):
    """Shapes of everything the one backward program of ``loss_block``'s
    op returns, from its recorded argument shapes."""
    cache = loss_block._cached_op._cache
    (key,) = [k for k in cache.keys() if k[0] == "bwd"]
    args, kwargs = cache._entry_stats[key]["avals"]
    return jax.tree_util.tree_leaves(
        jax.eval_shape(cache._entries[key]._fn, *args, **kwargs))


def test_no_residual_is_an_output():
    """The program's outputs are the marked leaves' gradients and the
    outputs nobody had computed — and nothing else."""
    job = _Job("float32")
    with autograd.record():
        loss = job.sce(job.net(job.x), job.y)
    loss.backward()
    job.params[0].grad().asnumpy()  # the plain backward() waits for a read
    shapes = sorted(o.shape for o in _backward_outputs(job.sce))
    want = [p.shape for p in job.params] + [(BATCH, CLASSES), (BATCH,)]
    assert shapes == sorted(want)


def test_steady_step_traces_nothing():
    job = _Job("float32")
    trainer = gluon.Trainer(job.net.collect_params(), "sgd",
                            {"learning_rate": 0.1, "momentum": 0.9})

    def step():
        with autograd.record():
            out = job.net(job.x)
            loss = job.sce(out, job.y)
        loss.backward()
        trainer.step(BATCH)
        return out.asnumpy(), loss.asnumpy()

    step()
    step()
    traces = telemetry.counter("compile.jax_traces").value
    misses = job.net._cached_op._cache.misses + job.sce._cached_op._cache.misses
    step()
    assert telemetry.counter("compile.jax_traces").value == traces
    assert job.net._cached_op._cache.misses \
        + job.sce._cached_op._cache.misses == misses


def test_dropout_same_mask_forward_and_recompute():
    """The key is an argument taken at call time: the forward-only program
    and the recompute inside backward draw the same mask."""
    net = nn.HybridSequential()
    net.add(nn.Dropout(0.5))
    net.hybridize()
    x = nd.array(np.linspace(1.0, 2.0, 64 * 32, dtype=np.float32)
                 .reshape(64, 32))
    x.attach_grad()
    with autograd.record():
        out = net(x)
        forward = out.asnumpy()             # forced: forward-only program
    out.backward()                          # recomputes the forward inside
    mask = x.grad.asnumpy()                 # d out / d x = mask / (1 - p)
    assert 0.3 < (mask != 0).mean() < 0.7
    np.testing.assert_allclose(forward, x.asnumpy() * mask, rtol=1e-6)
    with autograd.record():
        out = net(x)
    out.backward()                          # unforced: filled by backward
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy() * x.grad.asnumpy(),
                               rtol=1e-6)
    assert (x.grad.asnumpy() != mask).any()  # a fresh key each call


def test_never_read_call_is_dropped_and_runs_nothing():
    job = _Job("float32")
    before = _counts()
    with autograd.record():
        out = job.net(job.x)
    assert out.shape == (BATCH, CLASSES) and out.dtype == np.float32
    with autograd.record():                 # a new outermost scope: new tape
        pass
    assert _moved(before) == (0, 0, 1)
    # the shapes came from the one trace: no program was built or run
    assert [k[0] for k in job.net._cached_op._cache.keys()] == ["jaxpr"]
    # the value is still there for whoever asks later
    job.close(out, job.expected()[1], "logits")
    assert _moved(before) == (0, 1, 1)


def test_shape_error_raises_at_the_call():
    job = _Job("float32")
    with pytest.raises(Exception):
        with autograd.record():
            job.net(nd.zeros((BATCH, 2, 6, 6)))


def test_donated_input_is_explained():
    job = _Job("float32")
    with autograd.record():
        out = job.net(job.x)
    job.x._data.delete()                    # what Trainer.step's donation does
    with pytest.raises(MXNetError, match="donated"):
        out.asnumpy()
