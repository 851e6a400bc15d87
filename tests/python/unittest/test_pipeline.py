"""GPipe pipeline-parallel training (`parallel/pipeline.py`,
`MXNET_PIPELINE_STAGES`): stage partition over the 'pp' mesh axis,
micro-batch schedule traced into the donated fused step, reverse pipeline
flow via vjp through the scan/ppermute ticks.

Pins the PR's acceptance contract:

* **Parity** — pp in {2, 4} training matches the unpipelined fused step
  to rel <= 1e-5 over >= 5 steps, SGD and Adam, including UNEVEN
  micro-batches (B not divisible by M: the trailing micro-batch pads with
  recycled rows, row-masked at the loss inputs so gradients match the
  full-batch reference exactly — loss-layer custom vjps emit regardless
  of the incoming cotangent, so output-slice masking alone is NOT enough
  and this is pinned explicitly).
* **Stage balance** — `partition_stages` cuts contiguously and balances
  parameter+activation weight (max stage cost bounded vs the mean).
* **Compile accounting** — exactly ONE CompileCache("pipeline") entry per
  (symbol, shapes, stages, microbatches) config; zero steady-state misses.
* **Bubble accounting** — `pipeline.bubble_ratio` == (S-1)/(M+S-1).
* **Fallback triggers** — aux-state graphs (BatchNorm), batch-divisive
  loss normalization, more stages than devices/nodes, more micro-batches
  than rows: all fall back to the UNPIPELINED fused step (training still
  works, `pipeline.steps` stays 0).
* **Composition** — pipeline + ZeRO-1 (update sharded over the same pp
  mesh) and pipeline + traced kvstore grad sync both keep parity.
"""
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import compile_cache, telemetry
from mxnet_tpu.parallel.pipeline import PipelineFallback, partition_stages


class _env:
    """Scoped env toggles for the pipeline gate (+ friends)."""

    def __init__(self, stages=0, micro=0, zero1=False, **extra):
        self.vals = {"MXNET_PIPELINE_STAGES": str(stages),
                     "MXNET_PIPELINE_MICROBATCHES": str(micro),
                     "MXNET_FUSED_STEP": "1",
                     "MXNET_ZERO1": "1" if zero1 else "0",
                     "MXNET_ZERO1_NDEV": "0"}
        self.vals.update({k: str(v) for k, v in extra.items()})

    def __enter__(self):
        self.old = {k: os.environ.get(k) for k in self.vals}
        os.environ.update(self.vals)

    def __exit__(self, *a):
        for k, v in self.old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def _mlp(hidden=(16, 16, 4)):
    n = mx.sym.Variable("data")
    for i, h in enumerate(hidden[:-1]):
        n = mx.sym.FullyConnected(n, num_hidden=h, name=f"fc{i}")
        n = mx.sym.Activation(n, act_type="relu" if i % 2 == 0 else "tanh")
    n = mx.sym.FullyConnected(n, num_hidden=hidden[-1], name="fc_out")
    return mx.sym.SoftmaxOutput(n, name="softmax")


def _data(n=48, dim=8, classes=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.uniform(-1, 1, (n, dim)).astype(np.float32)
    Y = rng.randint(0, classes, (n,)).astype(np.float32)
    return X, Y


def _fit(stages=0, micro=0, optimizer="sgd", batch=8, epochs=2, sym=None,
         zero1=False, kvstore=None, expect_pipeline=None, **extra):
    """Train; returns (module, {param: np.ndarray}). 2 epochs x 6 batches
    = 12 steps (>= 5, the acceptance floor)."""
    with _env(stages=stages, micro=micro, zero1=zero1, **extra):
        mx.random.seed(7)
        X, Y = _data()
        it = mx.io.NDArrayIter(X, Y, batch_size=batch, shuffle=False)
        m = mx.mod.Module(sym or _mlp(), context=mx.cpu())
        # a kvstore OBJECT: with one device a kvstore NAME creates no store
        # (and a list of several devices is refused by Module)
        m.fit(it, num_epoch=epochs, optimizer=optimizer,
              kvstore=mx.kv.create(kvstore) if kvstore else "local",
              optimizer_params=(("learning_rate", 0.1),),
              initializer=mx.init.Xavier(rnd_type="gaussian", magnitude=2))
        if expect_pipeline is None:
            expect_pipeline = stages >= 2
        if expect_pipeline:
            assert m._pipeline is not None and not m._pipeline_failed, \
                "pipeline schedule did not engage"
        else:
            assert m._pipeline is None
        arg_p, _ = m.get_params()
        return m, {k: v.asnumpy() for k, v in arg_p.items()}


def _assert_parity(ref, got, rel=1e-5, what=""):
    assert ref.keys() == got.keys()
    for k in ref:
        a, b = ref[k], got[k]
        err = np.abs(a - b).max() / max(np.abs(a).max(), 1e-8)
        assert err <= rel, (what, k, err)


# ---------------------------------------------------------------------------
# stage partition
# ---------------------------------------------------------------------------


def test_partition_stage_balance():
    """A deep uniform MLP must cut into contiguous stages whose costs are
    balanced: max stage cost <= 2x the mean (the linear-partition DP's
    bound for this uniform layout is much tighter; 2x guards regressions
    without over-pinning the cost model)."""
    sym = _mlp(hidden=(32, 32, 32, 32, 32, 32, 32, 4))
    specs = {"data": ((4, 8), np.float32),
             "softmax_label": ((4,), np.float32)}
    arg_shapes, _, _ = sym.infer_shape(data=(4, 8), softmax_label=(4,))
    for n, s in zip(sym.list_arguments(), arg_shapes):
        specs.setdefault(n, (tuple(s), np.float32))
    for S in (2, 4):
        plan = partition_stages(sym, S, specs,
                                batch_names=("data", "softmax_label"))
        assert plan.num_stages == S
        # stages tile EVERY compute node exactly once
        from mxnet_tpu.symbol.symbol import _topo_order

        n_compute = sum(1 for n in _topo_order(
            [n for n, _ in sym._outputs]) if not n.is_variable)
        assert sum(len(s) for s in plan.stages) == n_compute
        assert all(len(s) >= 1 for s in plan.stages)
        costs = plan.stage_costs
        assert max(costs) <= 2.0 * (sum(costs) / len(costs)), costs
        # contiguity: topo indices within each stage are increasing and
        # stages tile the compute-node sequence in order
        last = -1
        for stg in plan.stages:
            for node in stg:
                idx = plan.node_index[id(node)]
                assert idx > last
                last = idx
        # every cut carries at least one value
        assert len(plan.boundaries) == S - 1
        assert all(b for b in plan.boundaries)


def test_partition_rejects_tiny_graphs():
    sym = mx.sym.SoftmaxOutput(mx.sym.Variable("data"), name="softmax")
    specs = {"data": ((4, 4), np.float32),
             "softmax_label": ((4,), np.float32)}
    with pytest.raises(PipelineFallback):
        partition_stages(sym, 2, specs,
                         batch_names=("data", "softmax_label"))


# ---------------------------------------------------------------------------
# parity: pipelined == unpipelined fused step
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("optimizer", ["sgd", "adam"])
@pytest.mark.parametrize("stages,micro", [(2, 4), (4, 4), (4, 8)])
def test_parity_vs_unpipelined(optimizer, stages, micro):
    _, ref = _fit(0, optimizer=optimizer)
    _, got = _fit(stages, micro, optimizer=optimizer)
    _assert_parity(ref, got, what=f"{optimizer} pp={stages} M={micro}")


def test_grad_accumulation_uneven_microbatches():
    """B=8 split into M=3 micro-batches (3+3+2): the padded trailing
    micro-batch must contribute EXACTLY the real rows' gradients — parity
    with the unpipelined full-batch step pins the loss-input row mask
    (output-slice masking alone cannot stop a loss-layer custom vjp from
    emitting pad-row gradients)."""
    _, ref = _fit(0)
    _, got = _fit(2, 3)
    _assert_parity(ref, got, what="uneven M=3 over B=8")


def test_parity_on_multi_axis_mesh():
    """REGRESSION (latent until the SPMD PR): on a mesh with an extra
    axis beside 'pp' (the documented `MXNET_MESH_SHAPE='dp=2,pp=2'`
    composition) the schedule's shard_map replicates compute over the
    extra axis and the vjp transpose SUMS the identical per-coordinate
    cotangents — gradients came back scaled by the extra axis product.
    `PipelineContext.grad_correction` divides it back out; parity must
    hold on the 2-axis mesh."""
    import jax

    if jax.device_count() < 4:
        pytest.skip("needs 4 devices")
    _, ref = _fit(0)
    m, got = _fit(2, 4, MXNET_MESH_SHAPE="dp=2,pp=2")
    assert m._pipeline is not None and not m._pipeline_failed
    assert m._pipeline.grad_correction == 2
    _assert_parity(ref, got, what="pipeline on dp=2,pp=2 mesh")


def test_parity_composed_with_zero1():
    """ZeRO-1 shards the update over the pipeline's own mesh axis (one
    mesh per program); parity must hold with both engaged."""
    _, ref = _fit(0)
    m, got = _fit(2, 4, zero1=True)
    assert m._zero1 is not None and not m._zero1_failed
    _assert_parity(ref, got, what="pipeline+zero1")


def test_parity_composed_with_kvstore_grad_sync():
    """A traceable kvstore (device store, update_on_kvstore=0) keeps the
    bucketed grad sync INSIDE the pipelined step; parity must hold."""
    _, ref = _fit(0)
    m, got = _fit(2, 4, kvstore="device", MXNET_UPDATE_ON_KVSTORE=0)
    assert m._kvstore is not None
    _assert_parity(ref, got, what="pipeline+kvstore")


# ---------------------------------------------------------------------------
# compile accounting + bubble math
# ---------------------------------------------------------------------------


def test_one_compile_per_config_and_zero_steady_state():
    # named_stats("pipeline") totals are monotonic across every cache
    # ever named "pipeline" (each PipelineContext owns one, sized to its
    # module's lifetime), so deltas attribute compiles to THIS test
    sym = _mlp(hidden=(24, 12, 4))
    before = compile_cache.named_stats("pipeline")

    def misses():
        return compile_cache.named_stats("pipeline")["misses"] - \
            before["misses"]

    m, _ = _fit(2, 4, sym=sym)
    after_first = misses()
    assert after_first == 1, f"expected ONE pipeline compile, got {after_first}"
    # steady state: a SECOND epoch sweep on the live module re-serves the
    # executable — zero new compiles, context preserved
    ctx_before = m._pipeline
    with _env(stages=2, micro=4):
        X, Y = _data()
        m.fit(mx.io.NDArrayIter(X, Y, batch_size=8, shuffle=False),
              num_epoch=1, optimizer="sgd",
              optimizer_params=(("learning_rate", 0.1),))
    assert m._pipeline is ctx_before
    assert misses() == after_first
    _fit(2, 8, sym=sym)  # micro-batch count is part of the config key
    assert misses() == after_first + 1
    _fit(4, 8, sym=sym)  # stage count too
    assert misses() == after_first + 2


def test_bubble_ratio_gauge():
    was = telemetry.enabled()
    telemetry.enable()
    try:
        for S, M in ((2, 4), (4, 8)):
            m, _ = _fit(S, M)
            assert m._pipeline.bubble_ratio == pytest.approx(
                (S - 1) / (M + S - 1))
            assert telemetry.gauge("pipeline.bubble_ratio").value == \
                pytest.approx((S - 1) / (M + S - 1))
            assert telemetry.gauge("pipeline.stages").value == S
            assert telemetry.gauge("pipeline.microbatches").value == M
        assert telemetry.counter("pipeline.steps").value >= 5
    finally:
        telemetry.enable(was)


# ---------------------------------------------------------------------------
# fallback triggers — unsupported configs train fine, unpipelined
# ---------------------------------------------------------------------------


def _bn_mlp():
    n = mx.sym.Variable("data")
    n = mx.sym.FullyConnected(n, num_hidden=16, name="fc0")
    n = mx.sym.BatchNorm(n, name="bn0")
    n = mx.sym.Activation(n, act_type="relu")
    n = mx.sym.FullyConnected(n, num_hidden=4, name="fc1")
    return mx.sym.SoftmaxOutput(n, name="softmax")


def test_fallback_aux_states():
    """BatchNorm graphs (running-stat aux) are not micro-batch separable:
    the module must fall back to the unpipelined fused step and still
    train."""
    m, w = _fit(2, 4, sym=_bn_mlp(), expect_pipeline=False)
    assert m._pipeline_failed
    assert all(np.isfinite(v).all() for v in w.values())


def test_fallback_batch_normalized_loss():
    n = mx.sym.Variable("data")
    n = mx.sym.FullyConnected(n, num_hidden=16, name="fc0")
    n = mx.sym.Activation(n, act_type="relu")
    n = mx.sym.FullyConnected(n, num_hidden=4, name="fc1")
    sym = mx.sym.SoftmaxOutput(n, name="softmax", normalization="batch")
    m, _ = _fit(2, 4, sym=sym, expect_pipeline=False)
    assert m._pipeline_failed


def test_fallback_more_stages_than_devices():
    import jax

    too_many = len(jax.devices()) + 1
    m, _ = _fit(too_many, too_many, expect_pipeline=False)
    assert m._pipeline_failed


def test_fallback_more_microbatches_than_rows():
    m, _ = _fit(2, 16, expect_pipeline=False)  # batch=8 < M=16
    assert m._pipeline_failed


def test_context_rebuilds_on_rebind():
    """matches() compares the FULL bound arg signature: an executor bound
    at different feature shapes (same batch dim) must invalidate the
    context instead of reusing a stale plan whose trace would fail and
    permanently disable pipelining."""
    from mxnet_tpu.parallel.pipeline import PipelineContext

    with _env(stages=2, micro=4):
        sym = _mlp()
        m1 = mx.mod.Module(sym, context=mx.cpu())
        m1.bind(data_shapes=[("data", (8, 8))],
                label_shapes=[("softmax_label", (8,))])
        ctx = PipelineContext.build(sym, m1._exec, ["data"],
                                    ["softmax_label"])
        assert ctx.matches(m1._exec)
        m2 = mx.mod.Module(sym, context=mx.cpu())
        m2.bind(data_shapes=[("data", (8, 12))],
                label_shapes=[("softmax_label", (8,))])
        assert not ctx.matches(m2._exec)


def test_gate_off_no_context():
    m, _ = _fit(0, expect_pipeline=False)
    assert m._pipeline is None and not m._pipeline_failed


def test_fallback_parity_with_eager():
    """The fallback path's result is the plain fused step: identical to a
    run with the gate off."""
    _, ref = _fit(0, sym=_bn_mlp(), expect_pipeline=False)
    _, got = _fit(2, 4, sym=_bn_mlp(), expect_pipeline=False)
    _assert_parity(ref, got, rel=0.0, what="fallback == gate-off")
