"""Async dispatch pipeline (`mxnet_tpu/io/staging.py`, `BaseModule.fit`).

Pins the host-overlap correctness contract:

* **N-step bit-exact parity** — `fit` (staged device feeds, the metric one
  step late) produces BITWISE identical trained parameters AND identical
  epoch-end metric values to `_reference_loop`, a lockstep loop over the
  module's public calls, across SGD+Adam and the fused / ZeRO-1 / SPMD
  execution modes. `fit` reorders host work only — it must never change a
  bit of the device program's output.
* **Staged-buffer donation safety** — the `DeviceStager` ring refuses
  new work rather than recycle a buffer an in-flight step may still
  read; `take` matches batch identity; guards drop stale slots.
* **pad-buffer reuse** — `io._pad_index` returns the SAME device array
  for a repeated (rows, batch_size), bounded under shape churn.
* **Serving flush parity** — `DynamicBatcher`'s stage-ahead lane is
  bit-exact vs eager predict with ZERO steady-state compiles.
* **Lock discipline** — the staging thread's condition comes from
  `analysis.make_condition`, so an in-suite MXNET_DEBUG_SYNC-style run
  (analysis enabled BEFORE the stager exists) must come back with zero
  lock-order inversions or blocking hazards.
"""
import os
import threading

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import analysis, compile_cache, serving, telemetry
from mxnet_tpu import parallel as par
from mxnet_tpu.io import io as io_mod
from mxnet_tpu.io import staging
from mxnet_tpu.io.io import DataDesc
from mxnet_tpu.models import TransformerLM, TransformerLMConfig
from mxnet_tpu.serving import DynamicBatcher
from mxnet_tpu.serving.generation import GenerationEngine

DIM, CLASSES = 8, 4


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


class _env:
    """Scoped env toggles: the execution mode."""

    def __init__(self, mode="fused"):
        self.vals = {"MXNET_FUSED_STEP": "1",
                     "MXNET_ZERO1": "1" if mode == "zero1" else "",
                     "MXNET_ZERO1_NDEV": "2" if mode == "zero1" else "",
                     "MXNET_SPMD": "dp=2" if mode == "spmd" else ""}

    def __enter__(self):
        self.old = {k: os.environ.get(k) for k in self.vals}
        for k, v in self.vals.items():
            if v:
                os.environ[k] = v
            else:
                os.environ.pop(k, None)
        return self

    def __exit__(self, *a):
        for k, v in self.old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


_XAVIER = dict(rnd_type="gaussian", magnitude=2)


def _params(mod):
    arg_p, _ = mod.get_params()
    return {k: v.asnumpy() for k, v in arg_p.items()}


def _reference_loop(mod, it, metric, optimizer, opt_kw, num_epoch, tail_at):
    """The lockstep reference over the module's public calls: no stager,
    and each step's metric update applied where it is made."""
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             for_training=True)
    mod.init_params(initializer=mx.init.Xavier(**_XAVIER))
    mod.init_optimizer(optimizer=optimizer,
                       optimizer_params=tuple(opt_kw.items()))
    metric_tail = []
    for _ in range(num_epoch):
        metric.reset()
        for nbatch, batch in enumerate(it):
            if not mod.fused_step(batch):
                mod.forward_backward(batch)
                mod.update()
            with mx.metric.immediate():
                mod.update_metric(metric, batch.label)
            if nbatch == tail_at:
                metric_tail.append(metric.get_name_value())
        it.reset()
    return metric_tail


def _fit(mode="fused", optimizer="sgd", opt_kw=None, num_epoch=2,
         batch=8, n=40, seed=7, reference=False):
    """One run, through `fit` or through `_reference_loop`; returns (params,
    per-epoch final metric values)."""
    opt_kw = opt_kw or {"learning_rate": 0.1}
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (n, DIM)).astype(np.float32)
    Y = rng.randint(0, CLASSES, (n,)).astype(np.float32)
    tail_at = n // batch - 1
    with _env(mode):
        mx.random.seed(seed)
        it = mx.io.NDArrayIter(X, Y, batch_size=batch, shuffle=False)
        m = mx.mod.Module(_mlp_symbol(), context=mx.cpu())
        if reference:
            tail = _reference_loop(m, it, mx.metric.create("acc"), optimizer,
                                   opt_kw, num_epoch, tail_at)
        else:
            tail = []
            m.fit(it, num_epoch=num_epoch, optimizer=optimizer,
                  optimizer_params=tuple(opt_kw.items()),
                  initializer=mx.init.Xavier(**_XAVIER),
                  batch_end_callback=lambda p: p.nbatch == tail_at
                  and tail.append(p.eval_metric.get_name_value()))
        return _params(m), tail


@pytest.fixture
def tele():
    prev = telemetry.enabled()
    telemetry.enable()
    yield telemetry
    telemetry.enable(prev)


def _counter(name):
    m = telemetry.get(name)
    return m.value if m is not None else 0


# ---------------------------------------------------------------------------
# N-step bit-exact parity: THE overlap correctness contract
# ---------------------------------------------------------------------------


# the full 2-optimizer x 3-mode matrix runs in the ci/run.sh overlap
# gate; the tier-1 fast lane (-m 'not slow') keeps both optimizers and
# all three execution modes covered with the two heaviest combinations
# slow-marked
_SGD = ("sgd", {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4})
_ADAM = ("adam", {"learning_rate": 0.01, "wd": 1e-4})


@pytest.mark.parametrize("optimizer,opt_kw,mode", [
    pytest.param(*_SGD, "fused", id="fused-sgd"),
    pytest.param(*_ADAM, "fused", id="fused-adam"),
    pytest.param(*_SGD, "zero1", id="zero1-sgd"),
    pytest.param(*_ADAM, "zero1", id="zero1-adam",
                 marks=pytest.mark.slow),
    pytest.param(*_SGD, "spmd", id="spmd-sgd",
                 marks=pytest.mark.slow),
    pytest.param(*_ADAM, "spmd", id="spmd-adam"),
])
def test_fit_overlap_bit_exact_parity(optimizer, opt_kw, mode):
    """2 epochs x 5 steps: trained params BITWISE equal and epoch-end
    metric values identical between `fit` and the reference loop — per
    optimizer per execution mode (fused / ZeRO-1 sharded update / SPMD dp
    mesh)."""
    w_on, m_on = _fit(mode, optimizer, opt_kw)
    w_off, m_off = _fit(mode, optimizer, opt_kw, reference=True)
    assert w_on.keys() == w_off.keys()
    for k in w_on:
        assert w_on[k].dtype == w_off[k].dtype, k
        assert np.array_equal(w_on[k], w_off[k]), k
    # a read settles the step the metric kept: end-of-epoch metrics are
    # the lockstep values exactly, not one step behind
    assert m_on == m_off and len(m_on) == 2


def test_fit_overlap_runs_overlapped(tele):
    """The parity above must not pass vacuously: `fit` consumes staged
    device batches and leaves each step's metric update to lag, and the
    derived pipeline ratios come out."""
    staged0 = _counter("overlap.staged_batches")
    deferred0 = _counter("metric.deferred")
    _fit()
    assert _counter("overlap.staged_batches") > staged0
    assert _counter("metric.deferred") > deferred0
    snap = telemetry.snapshot()
    assert 0.0 <= snap["derived"]["io.stage_wait_ratio"] <= 1.0
    assert 0.0 <= snap["derived"]["io.pipeline_stall_ratio"] <= 1.0


def test_fit_overlap_partial_last_batch_parity():
    """n not divisible by batch: the short final batch rides the staged
    pad path (pad_arrays on the staging thread) — still bit-exact, and
    the step before it is settled after the short batch was fetched, by
    its own pad."""
    w_on, m_on = _fit(n=44)
    w_off, m_off = _fit(n=44, reference=True)
    for k in w_on:
        assert np.array_equal(w_on[k], w_off[k]), k
    assert m_on == m_off


class _BucketIter(mx.io.DataIter):
    """Batches whose bucket (the sequence length) differs between every
    two consecutive steps."""

    KEYS = (6, 3, 6, 3, 3, 6)

    def __init__(self, batch=4, seed=3):
        super().__init__(batch)
        rng = np.random.RandomState(seed)
        self.default_bucket_key = max(self.KEYS)
        self._batches = [mx.io.DataBatch(
            data=[mx.nd.array(rng.uniform(-1, 1, (batch, k, DIM))
                              .astype(np.float32))],
            label=[mx.nd.array(rng.randint(0, CLASSES, (batch,))
                               .astype(np.float32))],
            bucket_key=k,
            provide_data=[DataDesc("data", (batch, k, DIM))],
            provide_label=[DataDesc("softmax_label", (batch,))])
            for k in self.KEYS]
        self.provide_data = self._batches[0].provide_data
        self.provide_label = self._batches[0].provide_label
        self._at = 0

    def reset(self):
        self._at = 0

    def next(self):
        if self._at == len(self._batches):
            raise StopIteration
        self._at += 1
        return self._batches[self._at - 1]


def _bucket_sym(key):
    # the mean over the bucket's length keeps every parameter's shape
    data = mx.sym.Variable("data")
    pooled = mx.sym.mean(data, axis=1)
    fc1 = mx.sym.FullyConnected(pooled, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name="fc2")
    return (mx.sym.SoftmaxOutput(fc2, name="softmax"), ("data",),
            ("softmax_label",))


def test_fit_bucket_switch_between_steps_parity():
    """A `BucketingModule.fit` whose bucket changes between consecutive
    steps: step t's metric update is made after batch t + 1 was fetched and
    settles inside step t + 1's, when another bucket's module is current —
    parameters and epoch metric are the reference loop's bit for bit, and
    no switch forces a settle."""
    steps, epochs = len(_BucketIter.KEYS), 2
    opt_kw = {"learning_rate": 0.1, "momentum": 0.9}

    def bucketing():
        mx.random.seed(7)
        return mx.mod.BucketingModule(
            _bucket_sym, default_bucket_key=_BucketIter().default_bucket_key,
            context=mx.cpu())

    with _env():
        metric = mx.metric.create("acc")
        tail = []
        deferred0 = _counter("metric.deferred")
        late0 = _counter("metric.settled_late")
        mod = bucketing()
        mod.fit(_BucketIter(), num_epoch=epochs, eval_metric=metric,
                optimizer="sgd", optimizer_params=tuple(opt_kw.items()),
                initializer=mx.init.Xavier(**_XAVIER),
                batch_end_callback=lambda p: p.nbatch == steps - 1
                and tail.append(p.eval_metric.get_name_value()))
        assert _counter("metric.deferred") - deferred0 == epochs * steps
        assert _counter("metric.settled_late") - late0 == \
            epochs * (steps - 1)
        ref = bucketing()
        ref_tail = _reference_loop(ref, _BucketIter(),
                                   mx.metric.create("acc"), "sgd", opt_kw,
                                   epochs, steps - 1)
    w, w_ref = _params(mod), _params(ref)
    assert w.keys() == w_ref.keys()
    for k in w:
        assert np.array_equal(w[k], w_ref[k]), k
    assert tail == ref_tail and len(tail) == epochs


# ---------------------------------------------------------------------------
# pad-buffer reuse (satellite: preallocated per-bucket pad index)
# ---------------------------------------------------------------------------


def test_pad_index_id_stable_and_bounded():
    """The wrap-around gather index for a (rows, batch) bucket is built
    once: repeated short batches reuse the SAME array (no per-step
    allocation), and the cache stays bounded under shape churn."""
    io_mod._PAD_INDEX_CACHE.clear()
    a = io_mod._pad_index(3, 8)
    b = io_mod._pad_index(3, 8)
    assert a is b
    np.testing.assert_array_equal(
        np.asarray(a), [0, 1, 2, 0, 1, 2, 0, 1])
    # pad_arrays rides the cached index and recycles rows in order
    src = mx.nd.array(np.arange(6, dtype=np.float32).reshape(3, 2))
    (padded,), pad = io_mod.pad_arrays([src], 8)
    assert pad == 5 and padded.shape == (8, 2)
    np.testing.assert_array_equal(padded.asnumpy()[3:5], src.asnumpy()[:2])
    assert io_mod._pad_index(3, 8) is a  # consumption did not evict it
    for n in range(1, io_mod._PAD_INDEX_CACHE_MAX + 10):
        io_mod._pad_index(n, n + 1)
    assert len(io_mod._PAD_INDEX_CACHE) <= io_mod._PAD_INDEX_CACHE_MAX


# ---------------------------------------------------------------------------
# DeviceStager ring: donation safety discipline
# ---------------------------------------------------------------------------


def _prep(tag):
    return lambda: ({"data": tag}, 0)


def test_stager_refuses_full_ring_never_recycles_in_flight(tele):
    """depth=2 double buffer: with one slot staged and one in flight the
    ring REFUSES new work (lockstep fallback) instead of overwriting a
    buffer the in-flight step may still read; retire frees exactly one."""
    st = staging.DeviceStager(name="test.stager", depth=2)
    try:
        b1, b2, b3 = object(), object(), object()
        full0 = _counter("io.stage_ring_full")
        assert st.stage(b1, _prep("f1")) and st.stage(b2, _prep("f2"))
        assert not st.stage(b3, _prep("f3"))          # full: refused
        assert _counter("io.stage_ring_full") == full0 + 1
        feed, pad = st.take(b1)                       # b1 -> in flight
        assert feed == {"data": "f1"} and pad == 0
        assert st.occupancy() == (1, 1)
        assert not st.stage(b3, _prep("f3"))          # STILL full: b1 lives
        assert st.retire()                            # b1's step settled
        assert st.occupancy() == (1, 0)
        assert st.stage(b3, _prep("f3"))              # now there is room
        assert st.take(b2) is not None and st.take(b3) is not None
        assert st.retire() and st.retire() and not st.retire()
    finally:
        st.close()


def test_stager_identity_miss_guard_and_error_fall_back(tele):
    """take matches the batch OBJECT (a reordered consumer misses to
    lockstep); a failed guard re-check or a prep error drops the slot."""
    st = staging.DeviceStager(name="test.stager2", depth=2)
    try:
        fb0 = _counter("overlap.fallback_batches")
        b1 = object()
        assert st.stage(b1, _prep("f1"))
        assert st.take(object()) is None              # identity miss
        assert st.take(b1) is not None and st.retire()

        b2 = object()                                 # guard goes stale
        assert st.stage(b2, _prep("f2"), guard=lambda: False)
        assert st.take(b2) is None
        assert st.occupancy() == (0, 0)               # slot dropped

        def boom():
            raise RuntimeError("prep failed")

        b3 = object()                                 # prep error
        assert st.stage(b3, boom)
        assert st.take(b3) is None
        assert st.occupancy() == (0, 0)
        assert _counter("overlap.fallback_batches") == fb0 + 2
    finally:
        st.close()


def test_stager_close_is_terminal():
    st = staging.DeviceStager(name="test.stager3", depth=2)
    st.stage(object(), _prep("x"))
    st.close()
    assert not st.stage(object(), _prep("y"))
    assert st.occupancy() == (0, 0)


# ---------------------------------------------------------------------------
# serving: stage-ahead flush parity + zero steady-state compiles
# ---------------------------------------------------------------------------


def _predictor(seed=7):
    mod = mx.mod.Module(_mlp_symbol())
    mod.bind([DataDesc("data", (4, DIM))],
             [DataDesc("softmax_label", (4,))], for_training=False)
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier())
    return mod.as_predictor(buckets=(2, 4, 8))


@pytest.mark.slow
def test_batcher_overlap_flush_parity_zero_compiles(tele):
    """Stage-ahead batching: concurrent mixed-size requests are bit-exact
    vs eager predict, with ZERO new serving compiles after warmup."""
    pred = _predictor()
    serving.warmup(pred)
    rng = np.random.RandomState(42)
    sizes = [1, 2, 3, 4, 5, 7, 8, 1, 3, 8] * 6
    payloads = [rng.uniform(-1, 1, (s, DIM)).astype(np.float32)
                for s in sizes]
    refs = [pred.predict(p).asnumpy() for p in payloads]

    ledger0 = compile_cache.named_stats("serving")["misses"]
    results = [None] * len(payloads)
    errors = []
    with DynamicBatcher(pred, max_wait_ms=2) as srv:
        def client(t):
            try:
                futs = [(i, srv.submit(payloads[i]))
                        for i in range(t, len(payloads), 4)]
                for i, f in futs:
                    results[i] = f.result(timeout=60).asnumpy()
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=client, args=(t,))
                   for t in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    assert not errors, errors
    assert compile_cache.named_stats("serving")["misses"] == ledger0
    for i, ref in enumerate(refs):
        assert np.array_equal(results[i], ref), i


# ---------------------------------------------------------------------------
# generation: tick token parity
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_generation_overlap_token_parity():
    """The dispatch-then-bookkeep tick emits the SAME token streams as
    each request generated alone: the deadline sweep and admission scan
    inside the dispatch->commit window, and sessions sharing a slab,
    never move the math."""
    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(vocab_size=32, d_model=16, n_heads=2,
                              d_ff=32, n_layers=1, max_len=32,
                              dtype="float32")
    lm = TransformerLM(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(0))
    prompts = [[1, 2, 3, 4], [5, 6], [7, 8, 9]]
    with GenerationEngine(lm, params, max_slots=2, max_len=32,
                          buckets=(8,)) as eng:
        alone = [eng.generate(p, max_new_tokens=8) for p in prompts]
        streams = [eng.submit(p, max_new_tokens=8) for p in prompts]
        together = [s.result(timeout=300) for s in streams]
    assert together == alone


# ---------------------------------------------------------------------------
# lock discipline: the staging thread under the sync analyzer
# ---------------------------------------------------------------------------


def test_overlap_debug_sync_clean():
    """analysis enabled BEFORE any stager exists: a full fit (staging
    thread live, the metric one step late) must record ZERO
    lock-order inversions and ZERO blocking hazards."""
    was = analysis._enabled
    analysis.enable()
    analysis.reset()
    try:
        w_on, _ = _fit()
        assert w_on  # the run trained
        rep = analysis.report()
        assert rep["inversions"] == [], rep["inversions"]
        assert rep["hazards"] == [], rep["hazards"]
    finally:
        analysis.enable(was)
        analysis.reset()
