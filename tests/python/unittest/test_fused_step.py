"""Fused train step: one XLA computation per step with donated buffers.

Covers the fused-step PR end to end:
* numerical parity fused vs eager (SGD momentum / Adam, fp32 and
  bf16 multi-precision master weights) over >= 5 steps — the eager loop is
  the correctness reference;
* donation safety: buffers fetched after a donated in-place update;
* fallback triggers: kvstore updater, Monitor, MXNET_FUSED_STEP=0,
  non-fused optimizers;
* compile-cache accounting: a partial last batch is padded, so an epoch
  costs exactly the bucketed number of compile-cache misses — no
  per-epoch recompile churn.
"""
import os
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import optimizer as opt
from mxnet_tpu import telemetry
from mxnet_tpu.io.io import DataBatch, DataDesc, DataIter, pad_arrays


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _data(n=40, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1, 1, (n, dim)).astype(np.float32)
    Y = rng.randint(0, classes, (n,)).astype(np.float32)
    return X, Y


class _ShortLastBatchIter(DataIter):
    """Yields full batches then one SHORT final batch (no iterator-side
    padding) — the partial-last-batch shape churn the compile cache must
    absorb via Module's pad-up path."""

    def __init__(self, X, Y, batch_size):
        super().__init__(batch_size)
        self.X, self.Y = X, Y
        self.cursor = 0

    @property
    def provide_data(self):
        return [DataDesc("data", (self.batch_size,) + self.X.shape[1:])]

    @property
    def provide_label(self):
        return [DataDesc("softmax_label", (self.batch_size,))]

    def reset(self):
        self.cursor = 0

    def __next__(self):
        return self.next()

    def next(self):
        if self.cursor >= len(self.X):
            raise StopIteration
        end = min(self.cursor + self.batch_size, len(self.X))
        b = DataBatch(data=[mx.nd.array(self.X[self.cursor:end])],
                      label=[mx.nd.array(self.Y[self.cursor:end])],
                      pad=0)
        self.cursor = end
        return b


def _fit(fused, optimizer, optimizer_params, num_epoch=2, seed=7,
         batch_size=8, n=40, **fit_kw):
    os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
    try:
        mx.random.seed(seed)
        X, Y = _data(n=n)
        it = mx.io.NDArrayIter(X, Y, batch_size=batch_size, shuffle=False)
        m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        m.fit(it, num_epoch=num_epoch, optimizer=optimizer,
              optimizer_params=tuple(optimizer_params.items()),
              initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=2),
              **fit_kw)
        arg_p, _ = m.get_params()
        return m, {k: v.asnumpy() for k, v in arg_p.items()}
    finally:
        os.environ.pop("MXNET_FUSED_STEP", None)


# ---------------------------------------------------------------------------
# numerical parity: fused vs eager is the headline correctness contract
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("optimizer,params", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4}),
    ("sgd", {"learning_rate": 0.05}),
    ("adam", {"learning_rate": 0.01, "wd": 1e-4}),
    ("nag", {"learning_rate": 0.1, "momentum": 0.9}),
])
def test_module_fused_eager_parity(optimizer, params):
    """Trained weights agree over 2 epochs x 5 steps (>= 5 steps)."""
    _, fused_w = _fit(True, optimizer, params)
    _, eager_w = _fit(False, optimizer, params)
    assert fused_w.keys() == eager_w.keys()
    for k in fused_w:
        np.testing.assert_allclose(fused_w[k], eager_w[k],
                                   rtol=3e-5, atol=3e-6, err_msg=k)


@pytest.mark.parametrize("optimizer,kw", [
    ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
             "multi_precision": True, "rescale_grad": 0.25}),
    ("adam", {"learning_rate": 0.01, "multi_precision": True,
              "rescale_grad": 0.25}),
])
def test_updater_fused_parity_bf16_multi_precision(optimizer, kw):
    """bf16 weights + fp32 master copies: fused and eager Updater agree."""
    rng = np.random.RandomState(3)
    shapes = [(6, 5), (5,), (4, 6)]
    ws32 = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    gs = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    results = {}
    for fused in (True, False):
        os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
        try:
            o = opt.create(optimizer, **kw)
            u = opt.get_updater(o)
            ws = [mx.nd.array(w).astype("bfloat16") for w in ws32]
            for _ in range(5):
                u(list(range(len(ws))),
                  [mx.nd.array(g).astype("bfloat16") for g in gs], ws)
            results[fused] = [w.asnumpy().astype(np.float32) for w in ws]
            # master copies stay fp32
            for s in u.states.values():
                master = s[1] if optimizer == "sgd" else s[0]
                assert master.dtype == np.float32
        finally:
            os.environ.pop("MXNET_FUSED_STEP", None)
    for a, b in zip(results[True], results[False]):
        np.testing.assert_allclose(a, b, rtol=2e-2, atol=2e-2)


def test_updater_fused_parity_fp32():
    """Direct Updater parity, 5 steps, plain fp32 (the gluon Trainer path)."""
    rng = np.random.RandomState(1)
    shapes = [(4, 3), (3,), (5, 4)]
    gs = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    out = {}
    for fused in (True, False):
        os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
        try:
            o = opt.create("sgd", learning_rate=0.1, momentum=0.9, wd=1e-4)
            u = opt.get_updater(o)
            rng2 = np.random.RandomState(2)
            ws = [mx.nd.array(rng2.uniform(-1, 1, s).astype(np.float32))
                  for s in shapes]
            for _ in range(5):
                u(list(range(len(ws))), [mx.nd.array(g) for g in gs], ws)
            out[fused] = [w.asnumpy() for w in ws]
        finally:
            os.environ.pop("MXNET_FUSED_STEP", None)
    for a, b in zip(out[True], out[False]):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


# ---------------------------------------------------------------------------
# donation safety
# ---------------------------------------------------------------------------


def test_no_use_after_donate_on_fetch():
    """Weight/state buffers are donated into the fused step; every handle a
    user can hold (arg_dict entries, get_params copies, updater states) must
    stay fetchable afterwards."""
    m, _ = _fit(True, "sgd", {"learning_rate": 0.1, "momentum": 0.9})
    # handles taken BEFORE another fused step
    w_handle = m._exec.arg_dict[m._param_names[0]]
    state_handles = list(m._updater.states.values())
    X, Y = _data()
    batch = DataBatch(data=[mx.nd.array(X[:8])],
                      label=[mx.nd.array(Y[:8])])
    assert m.fused_step(batch)
    # fetches go through the swapped-in buffers — no use-after-donate
    v = w_handle.asnumpy()
    assert np.isfinite(v).all()
    for s in state_handles:
        leaves = s if isinstance(s, (tuple, list)) else [s]
        for leaf in leaves:
            if leaf is not None:
                assert np.isfinite(leaf.asnumpy()).all()
    arg_p, _ = m.get_params()
    for v in arg_p.values():
        assert np.isfinite(v.asnumpy()).all()


# ---------------------------------------------------------------------------
# fallback triggers
# ---------------------------------------------------------------------------


def _gauge(name):
    g = telemetry.get(name)
    return None if g is None else g.value


def test_fallback_env_var():
    m, _ = _fit(False, "sgd", {"learning_rate": 0.1})
    X, Y = _data()
    batch = DataBatch(data=[mx.nd.array(X[:8])], label=[mx.nd.array(Y[:8])])
    os.environ["MXNET_FUSED_STEP"] = "0"
    try:
        assert not m.fused_step(batch)
    finally:
        os.environ.pop("MXNET_FUSED_STEP", None)
    assert m.fused_step(batch)  # default: on


def test_fallback_kvstore():
    """A kvstore updater needs per-gradient visibility — eager path."""
    telemetry.enable()
    telemetry.reset()
    try:
        kv = mx.kv.create("local")
        m, w = _fit(True, "sgd", {"learning_rate": 0.1}, kvstore=kv)
        assert _gauge("step.fused") == 0
        assert m._kvstore is not None
        for v in w.values():
            assert np.isfinite(v).all()
    finally:
        telemetry.disable()
        telemetry.reset()


def test_fallback_monitor():
    """An installed Monitor needs per-output visibility — eager path."""
    telemetry.enable()
    telemetry.reset()
    try:
        mon = mx.monitor.Monitor(interval=1)
        m, _ = _fit(True, "sgd", {"learning_rate": 0.1}, monitor=mon)
        assert _gauge("step.fused") == 0
        assert not m._fused_step_ready()
    finally:
        telemetry.disable()
        telemetry.reset()


def test_fallback_unfused_optimizer():
    """Optimizers without a fused_update keep working via the eager loop."""
    telemetry.enable()
    telemetry.reset()
    try:
        m, w = _fit(True, "rmsprop", {"learning_rate": 0.01})
        assert _gauge("step.fused") == 0
        for v in w.values():
            assert np.isfinite(v).all()
    finally:
        telemetry.disable()
        telemetry.reset()


def test_momentum_zeroed_mid_run_keeps_state():
    """Setting opt.momentum = 0 after momentum states exist must keep
    updating the states (eager sgd_mom_update with mom=0 semantics), never
    null them — fused and eager stay in lockstep across the change."""
    rng = np.random.RandomState(4)
    shapes = [(4, 3), (5,)]
    gs = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    out = {}
    for fused in (True, False):
        os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
        try:
            o = opt.create("sgd", learning_rate=0.1, momentum=0.9)
            u = opt.get_updater(o)
            rng2 = np.random.RandomState(5)
            ws = [mx.nd.array(rng2.uniform(-1, 1, s).astype(np.float32))
                  for s in shapes]
            for step in range(6):
                if step == 3:
                    o.momentum = 0.0
                u(list(range(len(ws))), [mx.nd.array(g) for g in gs], ws)
            for s in u.states.values():
                assert s is not None and s.asnumpy() is not None
            out[fused] = [w.asnumpy() for w in ws]
        finally:
            os.environ.pop("MXNET_FUSED_STEP", None)
    for a, b in zip(out[True], out[False]):
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)


def test_fallback_untraceable_optimizer_subclass():
    """An Optimizer subclass inheriting fused_update_supported whose custom
    state the fused path can't unpack falls back to the eager loop (weights
    intact, no double-counted updates) instead of dying."""

    class WeirdSGD(opt.SGD):
        def __init__(self, **kw):
            super().__init__(**kw)
            self.aggregate_num = 0  # plain per-index eager updates

        def create_state(self, index, weight):
            return {"momentum": mx.nd.zeros(weight.shape)}  # opaque to fused

        def update(self, index, weight, grad, state):
            self._update_count(index)
            weight[:] -= self._get_lr(index) * grad * self.rescale_grad

        def update_multi_precision(self, index, weight, grad, state):
            self.update(index, weight, grad, state)

    o = WeirdSGD(learning_rate=0.1)
    u = opt.get_updater(o)
    ws = [mx.nd.array(np.ones((4, 4), np.float32)) for _ in range(3)]
    gs = [mx.nd.array(np.ones((4, 4), np.float32)) for _ in range(3)]
    for _ in range(3):
        u([0, 1, 2], [g.copy() for g in gs], ws)
    assert u._fused_disabled
    assert o.num_update == 3  # trace failure did not double-count
    np.testing.assert_allclose(ws[0].asnumpy(), np.ones((4, 4)) - 0.3,
                               rtol=1e-6)


def test_fused_gauge_on():
    telemetry.enable()
    telemetry.reset()
    try:
        _fit(True, "sgd", {"learning_rate": 0.1})
        assert _gauge("step.fused") == 1
        assert telemetry.counter("compile.cache_hits").value > 0
    finally:
        telemetry.disable()
        telemetry.reset()


# ---------------------------------------------------------------------------
# partial-last-batch padding + compile-cache accounting
# ---------------------------------------------------------------------------


def test_pad_arrays():
    a = mx.nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    (p,), pad = pad_arrays([a], 5)
    assert pad == 2 and p.shape == (5, 4)
    # recycled rows, spread evenly from the start — not one repeated row
    np.testing.assert_array_equal(p.asnumpy()[3], a.asnumpy()[0])
    np.testing.assert_array_equal(p.asnumpy()[4], a.asnumpy()[1])
    np.testing.assert_array_equal(p.asnumpy()[:3], a.asnumpy())
    (q,), pad0 = pad_arrays([a], 3)
    assert pad0 == 0 and q is a
    # pad larger than the batch wraps around
    (w,), padw = pad_arrays([a[0:1]], 4)
    assert padw == 3 and w.shape == (4, 4)
    np.testing.assert_array_equal(w.asnumpy()[3], a.asnumpy()[0])


def test_partial_last_batch_single_compile_entry():
    """An epoch with a short last batch costs exactly ONE fused-step compile
    (the padded shape) — not one per epoch, and no second shape bucket."""
    os.environ["MXNET_FUSED_STEP"] = "1"
    try:
        X, Y = _data(n=37)  # 4 full batches of 8 + one short batch of 5
        it = _ShortLastBatchIter(X, Y, batch_size=8)
        m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        m.fit(it, num_epoch=3, optimizer="sgd",
              optimizer_params=(("learning_rate", 0.1),),
              initializer=mx.init.Xavier())
        cache = m._exec._cache
        fused_keys = [k for k in cache.keys() if k[0] == "fused_step"]
        assert len(fused_keys) == 1, fused_keys
        assert cache.misses == 1
        # 3 epochs x 5 steps: every step after the first is a cache hit
        assert cache.hits == 3 * 5 - 1
    finally:
        os.environ.pop("MXNET_FUSED_STEP", None)


def test_partial_last_batch_outputs_and_metric_sliced():
    """Padded rows never leak: outputs come back at the true row count and
    the metric consumes exactly the real labels."""
    X, Y = _data(n=21)
    it = _ShortLastBatchIter(X, Y, batch_size=8)
    m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    m.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    m.init_params(mx.init.Xavier())
    m.init_optimizer(optimizer="sgd",
                     optimizer_params=(("learning_rate", 0.1),))
    metric = mx.metric.create("acc")
    n_rows = 0
    it.reset()
    for b in it:
        if not m.fused_step(b):
            m.forward_backward(b)
            m.update()
        outs = m.get_outputs()
        assert outs[0].shape[0] == b.label[0].shape[0]
        m.update_metric(metric, b.label)
        n_rows += b.label[0].shape[0]
    assert n_rows == 21
    assert metric.num_inst == 21  # metric saw the real rows only


def test_pad_after_reshape_uses_current_bound():
    """Padding must slice against the executor's CURRENT bound batch size,
    not the bind-time data_shapes (which an in-forward reshape leaves
    stale)."""
    X, Y = _data(n=40)
    m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    m.bind(data_shapes=[("data", (8, 8))], label_shapes=[("softmax_label", (8,))])
    m.init_params(mx.init.Xavier())
    # grow the batch: _make_feed reshapes the executor to batch 16
    big = DataBatch(data=[mx.nd.array(X[:16])], label=[mx.nd.array(Y[:16])])
    m.forward(big, is_train=False)
    assert m.get_outputs()[0].shape[0] == 16
    # now a SHORT batch of 10 pads up to the current bound (16), and the
    # outputs come back sliced to the true 10 rows
    short = DataBatch(data=[mx.nd.array(X[:10])], label=[mx.nd.array(Y[:10])])
    m.forward(short, is_train=False)
    assert m._pad == 6
    assert m.get_outputs()[0].shape[0] == 10


def test_persistent_small_batches_reshape_not_pad():
    """One short batch pads (the per-epoch tail); the SAME short shape
    twice in a row is a smaller-batch stream and reshapes to run natively
    instead of paying the bound-size forward every batch."""
    X, Y = _data(n=40)
    m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
    m.bind(data_shapes=[("data", (32, 8))],
           label_shapes=[("softmax_label", (32,))])
    m.init_params(mx.init.Xavier())
    small = lambda: DataBatch(data=[mx.nd.array(X[:8])],
                              label=[mx.nd.array(Y[:8])])
    m.forward(small(), is_train=False)
    assert m._pad == 24  # first short batch: padded
    m.forward(small(), is_train=False)
    assert m._pad == 0  # repeat: reshaped, running natively at 8
    assert m._exec.arg_dict["data"].shape[0] == 8
    m.forward(small(), is_train=False)
    assert m._pad == 0
    assert m.get_outputs()[0].shape[0] == 8


def test_partial_last_batch_parity_fused_vs_eager():
    """Padding + fused step and padding + eager step train identically."""
    res = {}
    for fused in (True, False):
        os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
        try:
            mx.random.seed(11)
            X, Y = _data(n=21, seed=5)
            it = _ShortLastBatchIter(X, Y, batch_size=8)
            m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
            m.fit(it, num_epoch=2, optimizer="sgd",
                  optimizer_params=(("learning_rate", 0.1), ("momentum", 0.9)),
                  initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=2))
            arg_p, _ = m.get_params()
            res[fused] = {k: v.asnumpy() for k, v in arg_p.items()}
        finally:
            os.environ.pop("MXNET_FUSED_STEP", None)
    for k in res[True]:
        np.testing.assert_allclose(res[True][k], res[False][k],
                                   rtol=3e-5, atol=3e-6, err_msg=k)


# ---------------------------------------------------------------------------
# CompileCache behavior
# ---------------------------------------------------------------------------


def test_compile_cache_counters():
    from mxnet_tpu.compile_cache import CompileCache

    telemetry.reset()
    c = CompileCache("test_cache")
    calls = []

    def build():
        calls.append(1)
        return lambda x: x + 1

    f1 = c.get_or_build(("k", 1), build)
    assert f1(1) == 2  # first call timed into compile.seconds
    f2 = c.get_or_build(("k", 1), build)
    assert f2(2) == 3
    c.get_or_build(("k", 2), build)
    assert len(calls) == 2
    assert c.hits == 1 and c.misses == 2 and len(c) == 2
    assert telemetry.counter("compile.cache_hits").value >= 1
    assert telemetry.counter("compile.cache_misses").value >= 2
    assert c.compile_seconds >= 0.0
    snap = telemetry.snapshot()
    assert "compile.cache_hit_ratio" in snap["derived"]
    telemetry.reset()


def test_compile_cache_stats_aggregate():
    from mxnet_tpu import compile_cache

    s = compile_cache.stats()
    assert set(s) == {"entries", "hits", "misses", "compile_seconds", "caches"}
    assert s["entries"] == sum(p["entries"] for p in s["caches"])


def test_jax_retrace_is_counted_where_the_ledger_is_blind():
    """A cached callable that jax re-traces (another shape here; a changed
    layout or commitment in PR 21) is a HIT for the CompileCache ledger —
    compile.jax_traces / compile.jax_backend_compiles / jax_events() see
    it. A call that re-uses the executable moves none of them."""
    import jax

    from mxnet_tpu import compile_cache
    from mxnet_tpu.compile_cache import CompileCache

    def value(name):
        m = telemetry.get(name)
        return m.value if m is not None else 0

    t_test = time.perf_counter()

    def counts():
        # the log is bounded: counted since this test began, or a worker
        # that has traced 16,384 times before it would see no new entry
        return (value("compile.jax_traces"),
                value("compile.jax_backend_compiles"),
                sum(1 for t, kind, _ in compile_cache.jax_events()
                    if kind == "jaxpr_trace" and t >= t_test))

    c = CompileCache("test_retrace")
    # a lax primitive: a jnp function is a nested jit with a trace event
    # of its own
    fn = c.get_or_build("k", lambda: jax.jit(lambda x: jax.lax.add(x, x)))
    x3, x4 = np.ones(3, np.float32), np.ones(4, np.float32)
    fn(x3)                                  # first call: trace + compile
    base = counts()
    s0 = value("compile.jax_seconds")
    assert np.asarray(c.get_or_build("k", None)(x3))[0] == 2.0  # cached
    assert counts() == base
    t_before = time.perf_counter()
    assert c.get_or_build("k", None)(x4).shape == (4,)   # forced re-trace
    t_after = time.perf_counter()
    traces, compiles, logged = counts()
    assert (traces - base[0], compiles - base[1], logged - base[2]) \
        == (1, 1, 1)
    assert c.misses == 1 and c.hits == 2    # the ledger saw only hits
    assert value("compile.jax_seconds") > s0
    t, kind, seconds = compile_cache.jax_events()[-1]
    assert kind == "backend_compile" and seconds > 0
    assert t_before <= t <= t_after         # perf_counter at its end
