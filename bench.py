"""Benchmark: ResNet-50 training throughput (img/s) on one chip.

Mirrors the reference's headline single-device number: ResNet-50 training,
batch 32, fp32 — 298.51 img/s on 1x V100 (`docs/faq/perf.md:227-237`,
BASELINE.md). Prints exactly ONE JSON line on stdout. The measurement runs
on the accelerator or not at all: with no chip the script exits non-zero
before building anything (``BENCH_FORCE_CPU=1`` runs the CPU smoke path
instead, and says ``"backend": "cpu"``), and a phase that fails is recorded
in its ``*_error`` field AND makes the exit code non-zero.

One process per chip: this script starts no child process, so nothing it
spawns can fight it for the device.

Timing: every lane is timed two ways (dual pacing, to be dropped by the
benchmark rebuild — ROADMAP A1 — now that `block_until_ready` is known to
block on the attached chip, see CHANGES.md PR 21):
    *_fetch    — N data-dependent chained steps, then `jax.device_get` of
                 the final loss; the measured round-trip cost of one fetch
                 is subtracted (`timing_basis: "value_fetch"`)
    *_dispatch — block_until_ready-paced

Four measurements per run:
  value / framework_fp32 — the PUBLIC-API path: hybridized gluon net +
      autograd.record + SoftmaxCrossEntropyLoss + Trainer.step (aggregated
      multi_sgd_mom_update), fed by the real NDArrayIter. This is what a
      user gets; the headline number.
  raw_fp32      — hand-rolled jax train step on the traced graph (upper
      bound; the gap to framework_fp32 is frontend overhead, the quantity
      the reference's CachedOp exists to kill, `cached_op.cc:889`).
  framework_bf16 — same public path with net.cast('bfloat16') + SGD
      multi_precision fp32 master weights (MXU-native dtype).
  mfu_* — XLA-counted FLOPs/step over the chip's measured peak (large-
      matmul microbench, itself fetch-timed) and over the nominal peak
      when the chip is known.

Env knobs:
  BENCH_FORCE_CPU=1   run the CPU smoke path (tiny shapes; not a device
                      measurement)
  BENCH_ITERS=N       override timed iteration count

The persistent compile cache is the framework's (`mxnet_tpu/compile_cache.py`:
`JAX_COMPILATION_CACHE_DIR` when set, else `.jax_cache/` in the checkout).
"""
import json
import os
import sys
import time
import traceback

_FORCE_CPU = os.environ.get("BENCH_FORCE_CPU", "") == "1"  # tpulint: disable=gate-discipline (backend must be forced before jax initialises; bench is a script entry, not a library import)
if _FORCE_CPU:
    import jax

    jax.config.update("jax_platforms", "cpu")

BASELINE_IMG_S = 298.51  # V100 fp32 b=32 training (BASELINE.md)

# BENCH-record schema: v1 = the r01–r05 era (flat keys, no run id);
# v2 adds schema_version, a monotonic run_id drawn from the perf ledger,
# per-lane roofline fields (mfu/mbu/roofline_bound/predicted_floor_s) and
# the observatory summary under "roofline"
BENCH_SCHEMA_VERSION = 2


# phase name -> deterministic trace id: stamped into the BENCH json AND
# the telemetry sidecar, so a number cross-references the tracing dump
# that produced it (the ids match the span trees when MXNET_TRACING=1)
_PHASE_TRACE_IDS = {}


def _phase_scope(name):
    """One measurement phase as a root tracing span with a trace id
    deterministic in (pid, phase). The id is recorded whether or not
    tracing is on (stamping is free); the span itself is a no-op when
    MXNET_TRACING is off, so the measured numbers are untouched."""
    try:
        from mxnet_tpu import tracing

        tid = tracing.deterministic_trace_id("bench", os.getpid(), name)
        _PHASE_TRACE_IDS[name] = tid
        return tracing.span(f"bench.{name}", cat="bench", trace_id=tid)
    except Exception:  # noqa: BLE001 — stamping must never sink the bench
        import contextlib

        return contextlib.nullcontext()


def _bench_stamp(backend=None):
    """The self-description block shared by the BENCH json and the
    telemetry sidecar: resolved backend and per-phase trace ids."""
    stamp = {"backend": backend}
    if _PHASE_TRACE_IDS:
        stamp["trace_ids"] = dict(_PHASE_TRACE_IDS)
    return stamp


def _roofline_stamp(lane, dst, mbu_headline=None):
    """Merge the observatory's roofline attribution for ``lane`` into a
    result dict: achieved MFU/MBU against the measured peaks, the
    predicted floor time, and which roofline term binds. Additive —
    attribution failure (cost analysis unavailable on some backends)
    never sinks the bench. ``mbu_headline`` names an extra alias for the
    MBU figure (the decode tick is bandwidth-bound by construction, so
    its headline is ``tick_mbu``)."""
    try:
        from mxnet_tpu import observatory

        if not observatory._enabled or not isinstance(dst, dict):
            return
        row = observatory.attribution(lane)
        if not row:
            return
        # publish the lane gauges NOW: the spmd phase resets the step
        # lane, so the sidecar snapshot must not depend on the final
        # summary() still seeing it
        observatory._publish_gauges(lane, row)
        for k in ("mfu", "mbu", "comm_fraction", "predicted_floor_s",
                  "measured_over_floor", "host_gap_us"):
            v = row.get(k)
            if isinstance(v, float):
                dst[k] = round(v, 6)
        if row.get("roofline_bound"):
            dst["roofline_bound"] = row["roofline_bound"]
        if mbu_headline and isinstance(dst.get("mbu"), float):
            dst[mbu_headline] = dst["mbu"]
    except Exception:  # noqa: BLE001 — attribution is additive
        pass


def _write_telemetry_snapshot(stamp=None):
    """Sidecar for the BENCH json: a telemetry snapshot of the measured
    run (engine pushes, kvstore bytes/latency, prefetch starvation), so a
    perf round gets the breakdown for free. `BENCH_TELEMETRY_OUT` sets the
    path ('0' disables); default lands next to this script. Render it with
    `tools/telemetry_report.py`. ``stamp`` (backend/trace ids) is
    merged in under ``"bench"`` so the sidecar is self-describing."""
    out = os.environ.get("BENCH_TELEMETRY_OUT")
    if out == "0":
        return None
    out = out or os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "BENCH_TELEMETRY.json")
    try:
        from mxnet_tpu import telemetry

        if telemetry._registry:
            path = telemetry.dump(out)
            if path and stamp:
                try:
                    with open(path) as f:
                        doc = json.load(f)
                    doc["bench"] = stamp
                    tmp = path + ".tmp"
                    with open(tmp, "w") as f:
                        json.dump(doc, f, indent=2)
                    os.replace(tmp, path)
                except Exception:  # noqa: BLE001 — stamp is additive
                    pass
            return path
    except Exception:  # noqa: BLE001 — telemetry must never sink the bench
        pass
    return None


def _emit(payload):
    print(json.dumps(payload))
    sys.stdout.flush()


def _require_backend():
    """The backend this run measures: the accelerator jax found, or "cpu"
    under BENCH_FORCE_CPU=1. No accelerator and no force: SystemExit — a
    CPU number is never emitted under the chip's name."""
    import jax

    backend = jax.default_backend()
    if backend == "cpu" and not _FORCE_CPU:
        raise SystemExit(
            "bench.py: jax found no accelerator (devices: "
            f"{jax.devices()}); set BENCH_FORCE_CPU=1 for the CPU smoke "
            "path")
    return backend


def _fetch_cost():
    """Measured host<->device round-trip cost of materialising one small
    array that is ALREADY computed — the constant subtracted from every
    value-fetch-timed window. min over repeats (we want the floor, not the
    mean: queue jitter only ever adds time)."""
    import jax
    import jax.numpy as jnp

    x = jnp.zeros((4,), jnp.float32) + 1.0
    jax.device_get(x)  # force materialised + one warm round trip
    costs = []
    for _ in range(5):
        t0 = time.perf_counter()
        jax.device_get(x)
        costs.append(time.perf_counter() - t0)
    return min(costs)


def _fetch_timed(run_n_steps, fetch_final, iters, batch, fetch_cost):
    """The honest timing window: t0 -> dispatch `iters` chained steps ->
    device_get the final value (blocks until all steps really executed)
    -> t1; subtract the measured round-trip constant."""
    import jax

    t0 = time.perf_counter()
    final = run_n_steps(iters)
    jax.device_get(fetch_final(final))
    dt = time.perf_counter() - t0 - fetch_cost
    dt = max(dt, 1e-9)
    return batch * iters / dt, dt


def raw_shapes(on_tpu):
    """Headline (batch, image_size) per backend."""
    return (32, 224) if on_tpu else (8, 32)


def build_raw_step(batch, size):
    """Construct the hand-rolled jax train step (resnet50 fwd+bwd+sgd-mom)
    and its inputs."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    import __graft_entry__ as g

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize()
    x = mx.nd.zeros((batch, 3, size, size))
    fwd, key, params = g._pure_forward(net, x, train=True)

    lr, momentum, wd = 0.1, 0.9, 1e-4
    momenta = [jnp.zeros_like(p) for p in params]

    def loss_fn(params, key, xb, yb):
        logits = fwd(key, *params, xb).astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, yb[:, None], axis=-1).mean()

    @jax.jit
    def train_step(params, momenta, key, xb, yb):
        loss, grads = jax.value_and_grad(loss_fn)(params, key, xb, yb)
        new_p, new_m = [], []
        for p, gr, m in zip(params, grads, momenta):
            gr = gr + wd * p
            m = momentum * m + gr
            new_p.append(p - lr * m)
            new_m.append(m)
        return new_p, new_m, loss

    rng = np.random.RandomState(0)
    xb = jnp.asarray(rng.uniform(-1, 1, (batch, 3, size, size)).astype(np.float32))
    yb = jnp.asarray(rng.randint(0, 1000, (batch,)).astype(np.int32))
    return train_step, params, momenta, key, xb, yb


def _measure_raw(on_tpu, fetch_cost):
    """Hand-rolled jax train step on the traced graph — the upper bound.
    Returns (img_s_fetch, img_s_dispatch, batch, size, iters, flops)."""
    import jax

    batch, size = raw_shapes(on_tpu)
    train_step, params, momenta, key, xb, yb = build_raw_step(batch, size)

    flops = None
    try:  # XLA's own FLOP count for one optimizer step (for the MFU figure)
        cost = train_step.lower(params, momenta, key, xb, yb).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        flops = float(cost.get("flops", 0.0)) or None
    except Exception:  # noqa: BLE001 — cost analysis is best-effort
        flops = None

    # warmup (compile) — drain the queue with a real fetch so queued warmup
    # work cannot bleed into the timed window. The first-step wall time is
    # reported separately (`raw_compile_s`): steady-state img/s must never
    # absorb the one-off compile.
    t_c0 = time.perf_counter()
    params, momenta, loss = train_step(params, momenta, key, xb, yb)
    jax.device_get(loss)
    compile_s = time.perf_counter() - t_c0
    params, momenta, loss = train_step(params, momenta, key, xb, yb)
    jax.device_get(loss)

    iters = int(os.environ.get("BENCH_ITERS", "20" if on_tpu else "3"))

    state = {"params": params, "momenta": momenta}

    def run_n(n):
        loss = None
        for _ in range(n):
            state["params"], state["momenta"], loss = train_step(
                state["params"], state["momenta"], key, xb, yb)
        return loss

    img_s_fetch, _ = _fetch_timed(run_n, lambda l: l, iters, batch, fetch_cost)

    # legacy dispatch pacing (comparability with earlier rounds)
    t0 = time.perf_counter()
    loss = run_n(iters)
    jax.block_until_ready(loss)
    img_s_disp = batch * iters / (time.perf_counter() - t0)
    jax.device_get(loss)  # drain before the next measurement starts
    return img_s_fetch, img_s_disp, batch, size, iters, flops, compile_s


def _measure_framework(on_tpu, fetch_cost, dtype="float32", fused=True):
    """The public-API path: hybridized gluon net + autograd + Trainer.step
    fed by NDArrayIter — what `example/gluon/image_classification.py` runs.
    ``fused=False`` pins MXNET_FUSED_STEP=0 for the measurement, so the
    emitted fused-vs-eager pair attributes `framework_vs_raw` movement to
    the fused update path specifically.
    Returns (img_s_fetch, img_s_dispatch, compile_s)."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, loss as gloss
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.io import NDArrayIter

    batch, size = raw_shapes(on_tpu)
    n_batches = 4

    net = vision.resnet50_v1(classes=1000)
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True)
    if dtype != "float32":
        net.cast(dtype)

    rng = np.random.RandomState(0)
    data = rng.uniform(-1, 1, (batch * n_batches, 3, size, size)).astype(np.float32)
    label = rng.randint(0, 1000, (batch * n_batches,)).astype(np.float32)
    train_iter = NDArrayIter(data, label, batch_size=batch, shuffle=False)

    sce = gloss.SoftmaxCrossEntropyLoss()
    sce.hybridize()  # the loss compiles like the net: one CachedOp, not
    # a handful of eager dispatches + tape nodes per step
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.1, "momentum": 0.9, "wd": 1e-4,
                       "multi_precision": dtype != "float32"})

    def one_epoch():
        last_loss = None
        n = 0
        train_iter.reset()
        for b in train_iter:
            x = b.data[0]
            y = b.label[0]
            if dtype != "float32":
                x = x.astype(dtype)
            with autograd.record():
                out = net(x)
                loss = sce(out, y)
            loss.backward()
            trainer.step(batch)
            last_loss = loss
            n += batch
        return last_loss, n

    # fetching an UPDATED WEIGHT (not the loss) is what forces the full
    # step: the final trainer.step's update executable is downstream of the
    # loss value, so a loss fetch would leave one update queued
    first_param = next(iter(net.collect_params().values()))

    def drain():
        jax.device_get(first_param.data()._data)

    prev_fused = os.environ.get("MXNET_FUSED_STEP")
    os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
    try:
        # warmup epoch (compiles fwd/bwd + update groups); its wall time is
        # the compile cost, reported separately from steady-state img/s
        t_c0 = time.perf_counter()
        last, _ = one_epoch()
        drain()
        compile_s = time.perf_counter() - t_c0

        iters = int(os.environ.get("BENCH_ITERS", "20" if on_tpu else "3"))
        epochs = max(1, (iters + n_batches - 1) // n_batches)
        total_imgs = epochs * n_batches * batch

        # --- value-fetch pacing: each step's params feed the next, so
        # fetching a weight written by the final update forces every step
        def run_all(_n):
            for _ in range(epochs):
                one_epoch()
            return first_param

        img_s_fetch, _ = _fetch_timed(
            run_all, lambda p: p.data()._data, 1, total_imgs, fetch_cost)

        # --- legacy dispatch pacing
        t0 = time.perf_counter()
        run_all(1)
        jax.block_until_ready(first_param.data()._data)
        img_s_disp = total_imgs / (time.perf_counter() - t0)
        drain()
    finally:
        if prev_fused is None:
            os.environ.pop("MXNET_FUSED_STEP", None)
        else:
            os.environ["MXNET_FUSED_STEP"] = prev_fused
    return img_s_fetch, img_s_disp, compile_s


def _measure_module(on_tpu, fetch_cost, fused=True):
    """The SYMBOLIC public-API path: `Module` on a symbolic ResNet-50
    (`mxnet_tpu.models.resnet`), same batch/data/optimizer as
    `_measure_framework`. With ``fused=True`` every step is
    `Module.fused_step` — forward+backward+optimizer as ONE donated-buffer
    XLA computation per step (what `Module.fit` runs since the fused-step
    PR); ``fused=False`` pins MXNET_FUSED_STEP=0 and drives the eager
    forward_backward()+update() decomposition, so the pair attributes the
    whole-step-fusion win. Returns (img_s_fetch, img_s_dispatch, compile_s).

    NOTE: the measurement scaffolding (env pin, warm-up compile timing,
    fetch- then dispatch-paced loops) deliberately mirrors
    `_measure_framework` line for line — the emitted ratios compare across
    the two paths, so any change to the timing basis must be applied to
    BOTH functions or the attribution numbers silently skew."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.models.resnet import resnet50_symbol

    batch, size = raw_shapes(on_tpu)
    n_batches = 4
    # image_shape picks the stem; the imagenet stem always, to match the
    # gluon/raw network even on the small CPU-smoke images
    sym = resnet50_symbol(num_classes=1000, image_shape=(3, 224, 224))
    mod = mx.mod.Module(sym)

    rng = np.random.RandomState(0)
    data = rng.uniform(-1, 1, (batch * n_batches, 3, size, size)).astype(np.float32)
    label = rng.randint(0, 1000, (batch * n_batches,)).astype(np.float32)
    train_iter = NDArrayIter(data, label, batch_size=batch, shuffle=False)

    mod.bind(data_shapes=train_iter.provide_data,
             label_shapes=train_iter.provide_label)
    mod.init_params(mx.init.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),
                                         ("momentum", 0.9), ("wd", 1e-4)))

    first_name = mod._param_names[0]

    def drain():
        jax.device_get(mod._exec.arg_dict[first_name]._data)

    def one_epoch():
        train_iter.reset()
        for b in train_iter:
            if not fused:
                mod.forward_backward(b)
                mod.update()
            elif not mod.fused_step(b):
                raise RuntimeError(
                    "Module.fused_step declined the batch: the fused lane "
                    "would have timed the eager path")

    prev_fused = os.environ.get("MXNET_FUSED_STEP")
    os.environ["MXNET_FUSED_STEP"] = "1" if fused else "0"
    try:
        t_c0 = time.perf_counter()
        one_epoch()
        drain()
        compile_s = time.perf_counter() - t_c0

        iters = int(os.environ.get("BENCH_ITERS", "20" if on_tpu else "3"))
        epochs = max(1, (iters + n_batches - 1) // n_batches)
        total_imgs = epochs * n_batches * batch

        def run_all(_n):
            for _ in range(epochs):
                one_epoch()
            return None

        img_s_fetch, _ = _fetch_timed(
            run_all, lambda _: mod._exec.arg_dict[first_name]._data,
            1, total_imgs, fetch_cost)

        t0 = time.perf_counter()
        run_all(1)
        jax.block_until_ready(mod._exec.arg_dict[first_name]._data)
        img_s_disp = total_imgs / (time.perf_counter() - t0)
        drain()
    finally:
        if prev_fused is None:
            os.environ.pop("MXNET_FUSED_STEP", None)
        else:
            os.environ["MXNET_FUSED_STEP"] = prev_fused
    return img_s_fetch, img_s_disp, compile_s


def _measure_lazy(on_tpu):
    """Eager-vs-lazy on the plain per-op imperative fp32 path — the lane
    the fused step refuses (Monitor, custom ops, gluon imperative, eager
    inference). BENCH_r05's framework_vs_raw 0.883 measured the whole
    gluon train loop; this lane isolates the per-op dispatch tax that
    number carries by driving a dispatch-bound imperative MLP chain
    (dot+bias+relu per layer, every op a separate `invoke_nd`) with the
    SAME code under `MXNET_LAZY=0` (one jitted XLA program per op — the
    eager basis) and `MXNET_LAZY=1` (one fused jitted program per
    segment). Reports segment count, mean ops/segment, cold compile
    seconds separated from steady state, and asserts
    steady_state_compiles == 0 after warmup."""
    import numpy as np

    from mxnet_tpu import compile_cache, nd, telemetry
    from mxnet_tpu.lazy import graph as lazy_graph

    layers, width, batch = 8, 128, 16
    rng = np.random.RandomState(0)
    ws = [nd.array(rng.uniform(-0.2, 0.2, (width, width)).astype(np.float32))
          for _ in range(layers)]
    bs = [nd.array(rng.uniform(-0.1, 0.1, (width,)).astype(np.float32))
          for _ in range(layers)]
    x = nd.array(rng.uniform(-1, 1, (batch, width)).astype(np.float32))

    def step():
        h = x
        for w, b in zip(ws, bs):
            h = nd.relu(nd.dot(h, w) + b)  # 3 invoke_nd dispatches/layer
        # the materialization barrier: one concrete-value fetch per step
        return float(nd.sum(h).asnumpy())

    iters = max(30, int(os.environ.get("BENCH_ITERS", "3")) * 10)
    prev = os.environ.get("MXNET_LAZY")
    out = {"basis": "imperative_mlp_fp32 (per-op eager vs lazy capture)",
           "layers": layers, "width": width, "batch": batch, "iters": iters}
    try:
        def timed_window():
            # best-of-3 windows: host scheduling jitter only ever ADDS
            # time, and this dispatch-bound lane is all host time
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    step()
                best = min(best, time.perf_counter() - t0)
            return best

        os.environ["MXNET_LAZY"] = "0"
        step(); step()  # per-op warmup (compiles each one-op executable)
        ref = step()
        eager_s = timed_window()

        os.environ["MXNET_LAZY"] = "1"
        cold0 = compile_cache.named_stats("lazy")
        t0 = time.perf_counter()
        val = step(); step()  # cold: segment compiles land here
        cold_s = time.perf_counter() - t0
        warm0 = compile_cache.named_stats("lazy")
        segs0 = telemetry.counter("lazy.segments").value
        ops0 = telemetry.counter("lazy.ops_captured").value
        lazy_s = timed_window()
        warm1 = compile_cache.named_stats("lazy")
        if abs(val - ref) > 1e-4 * max(1.0, abs(ref)):
            raise RuntimeError(f"lazy/eager mismatch: {val} vs {ref}")
        steady_compiles = warm1["misses"] - warm0["misses"]
        segs = telemetry.counter("lazy.segments").value - segs0
        ops = telemetry.counter("lazy.ops_captured").value - ops0
        assert steady_compiles == 0, \
            f"lazy steady state compiled {steady_compiles} programs"
        out.update(
            eager_steps_per_s=round(iters / max(eager_s, 1e-9), 1),
            lazy_steps_per_s=round(iters / max(lazy_s, 1e-9), 1),
            lazy_vs_eager=round(eager_s / max(lazy_s, 1e-9), 3),
            segments=segs,
            mean_ops_per_segment=round(ops / max(segs, 1), 1),
            cold_wall_s=round(cold_s, 3),
            cold_compile_s=round(
                warm0["compile_seconds"] - cold0["compile_seconds"], 3),
            segment_compiles=warm0["misses"] - cold0["misses"],
            steady_state_compiles=steady_compiles,
        )
    finally:
        if prev is None:
            os.environ.pop("MXNET_LAZY", None)
        else:
            os.environ["MXNET_LAZY"] = prev
    return out


def _measure_lazy_fused(on_tpu):
    """Rewrite-on vs rewrite-off on a fusion-friendly lazy chain — the
    lane that isolates what lazy/rewrite.py itself buys, holding the
    capture machinery constant (MXNET_LAZY=1 in BOTH modes, only
    MXNET_LAZY_REWRITE flips). The chain is built so every default rule
    family fires: dense+bias+relu per layer (dense_bias_act), an
    add-of-zeros_like (identity), duplicated MATERIALIZED sum(tanh(abs))
    branches (CSE halves live output buffers AND host wrap cost — XLA
    CSEs the compute but must keep both output buffers; map_reduce then
    merges the surviving chain). Stamps the rewrite-off/on wall ratio and
    the node shrink ratio, asserts steady_state_compiles == 0 in both
    modes and EXACT compile accounting: one compile per signature per
    mode (rewritten keys never collide with unrewritten), zero on warm
    replay. All four rules here are bit-parity rules, so the two modes
    must agree bit-for-bit. On a host-dispatch-bound CPU run the steady
    wall ratio sits near 1.0 (recording dominates and is identical by
    design) — the deterministic rewrite win there is compile_speedup
    (smaller program through XLA) and shrink_ratio; on TPU the smaller
    replay program is also the faster one."""
    import numpy as np

    from mxnet_tpu import compile_cache, nd, telemetry

    layers, width, batch = 6, 128, 16
    rng = np.random.RandomState(0)
    ws = [nd.array(rng.uniform(-0.2, 0.2, (width, width)).astype(np.float32))
          for _ in range(layers)]
    bs = [nd.array(rng.uniform(-0.1, 0.1, (width,)).astype(np.float32))
          for _ in range(layers)]
    x = nd.array(rng.uniform(-1, 1, (batch, width)).astype(np.float32))

    def step():
        h = x
        for w, b in zip(ws, bs):
            h = nd.relu(nd.dot(h, w) + b)  # dense_bias_act collapses these
        h = h + nd.zeros_like(h)           # identity rule eliminates
        y1 = nd.sum(nd.tanh(nd.abs(h)))    # map_reduce merges the chain
        y2 = nd.sum(nd.tanh(nd.abs(h)))    # CSE dedups the duplicate
        return float(y1.asnumpy()) + float(y2.asnumpy())

    iters = max(30, int(os.environ.get("BENCH_ITERS", "3")) * 10)
    prev = {k: os.environ.get(k) for k in ("MXNET_LAZY",
                                           "MXNET_LAZY_REWRITE")}
    out = {"basis": "lazy_fused_chain_fp32 (rewrite-on vs rewrite-off, "
                    "MXNET_LAZY=1 both)",
           "layers": layers, "width": width, "batch": batch, "iters": iters}
    try:
        def timed_window():
            best = float("inf")
            for _ in range(3):
                t0 = time.perf_counter()
                for _ in range(iters):
                    step()
                best = min(best, time.perf_counter() - t0)
            return best

        os.environ["MXNET_LAZY"] = "1"

        def mode(rewrite_on):
            os.environ["MXNET_LAZY_REWRITE"] = "1" if rewrite_on else "0"
            cold0 = compile_cache.named_stats("lazy")
            pre0 = telemetry.counter("lazy.rewrite.nodes_pre").value
            post0 = telemetry.counter("lazy.rewrite.nodes_post").value
            t0 = time.perf_counter()
            val = step(); step()  # cold: this mode's signatures compile
            cold_s = time.perf_counter() - t0
            warm0 = compile_cache.named_stats("lazy")
            wall = timed_window()
            warm1 = compile_cache.named_stats("lazy")
            steady = warm1["misses"] - warm0["misses"]
            assert steady == 0, (
                f"lazy_fused rewrite={rewrite_on} steady state compiled "
                f"{steady} programs")
            return {"val": val, "wall_s": wall,
                    "cold_wall_s": round(cold_s, 3),
                    "cold_compile_s": round(
                        warm0["compile_seconds"] - cold0["compile_seconds"],
                        3),
                    "segment_compiles": warm0["misses"] - cold0["misses"],
                    "nodes_pre":
                        telemetry.counter("lazy.rewrite.nodes_pre").value
                        - pre0,
                    "nodes_post":
                        telemetry.counter("lazy.rewrite.nodes_post").value
                        - post0}

        off = mode(False)
        on = mode(True)
        if on["val"] != off["val"]:  # bit-parity rules only in this chain
            raise RuntimeError(
                f"lazy_fused rewrite parity broke: {on['val']} vs "
                f"{off['val']}")
        # exact accounting: each mode cold-compiles its own signature
        # once (rewritten keys are disjoint from unrewritten), warm
        # replays compile nothing
        assert off["segment_compiles"] == 1 and on["segment_compiles"] == 1, \
            (off["segment_compiles"], on["segment_compiles"])
        shrink = 0.0
        if on["nodes_pre"] > 0:
            shrink = (on["nodes_pre"] - on["nodes_post"]) / on["nodes_pre"]
        assert shrink > 0, \
            f"rewriter eliminated nothing on the fusion-friendly chain"
        out.update(
            rewrite_off_steps_per_s=round(
                iters / max(off["wall_s"], 1e-9), 1),
            rewrite_on_steps_per_s=round(iters / max(on["wall_s"], 1e-9), 1),
            rewrite_speedup=round(off["wall_s"] / max(on["wall_s"], 1e-9),
                                  3),
            compile_speedup=round(
                off["cold_compile_s"] / max(on["cold_compile_s"], 1e-9), 3),
            shrink_ratio=round(shrink, 3),
            nodes_pre=on["nodes_pre"], nodes_post=on["nodes_post"],
            cold_compile_s_off=off["cold_compile_s"],
            cold_compile_s_on=on["cold_compile_s"],
            segment_compiles=on["segment_compiles"]
            + off["segment_compiles"],
            steady_state_compiles=0,
        )
    finally:
        for k, v in prev.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    return out


def _measure_spmd(on_tpu):
    """spmd lane: the GSPMD-sharded fused step (MXNET_SPMD,
    parallel/spmd.py) vs the replicated one on a small all-divisible MLP.
    Needs >= 2 devices (the CI bench smoke runs single-device and records
    the skip); picks tp=2 at 2-3 devices, tp=2,fsdp=2 at >= 4. Reports
    measured per-device param+optimizer-state bytes vs the replicated
    total (the 1/N capability claim), steady-state step time both ways,
    whole-run parity, cold compile seconds separated, and asserts zero
    steady-state compiles on the "spmd" cache. CAVEAT on virtual-CPU
    meshes: every "device" is a host thread, so spmd_vs_replicated < 1
    is expected — the load-bearing numbers are the byte ratio and the
    compile invariant (the MULTICHIP_r08 caveat)."""
    import numpy as np

    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache
    from mxnet_tpu.parallel.partition import nbytes_on_device

    ndev = jax.device_count()
    if ndev < 2:
        return {"skipped": f"needs >= 2 devices, have {ndev}"}
    spec = "tp=2,fsdp=2" if ndev >= 4 else "tp=2"
    batch, dim, hidden, classes = 32, 64, 128, 8
    steps = max(6, int(os.environ.get("BENCH_ITERS", "3")) * 2)

    def mlp():
        n = mx.sym.Variable("data")
        for i in range(3):
            n = mx.sym.FullyConnected(n, num_hidden=hidden,
                                      name=f"bspmd_fc{i}")
            n = mx.sym.Activation(n, act_type="relu")
        n = mx.sym.FullyConnected(n, num_hidden=classes, name="bspmd_out")
        return mx.sym.SoftmaxOutput(n, name="softmax")

    class _Batch:
        def __init__(self, X, Y):
            self.data = [mx.nd.array(X)]
            self.label = [mx.nd.array(Y)]

    def drive(spmd_spec):
        saved = {k: os.environ.get(k)
                 for k in ("MXNET_SPMD", "MXNET_SPMD_FSDP_MIN_SIZE",
                           "MXNET_FUSED_STEP")}
        if spmd_spec:
            os.environ["MXNET_SPMD"] = spmd_spec
            os.environ["MXNET_SPMD_FSDP_MIN_SIZE"] = "1"
        else:
            os.environ.pop("MXNET_SPMD", None)
        os.environ["MXNET_FUSED_STEP"] = "1"
        try:
            mx.random.seed(5)
            rng = np.random.RandomState(0)
            m = mx.mod.Module(mlp(), context=mx.Context("cpu"))
            m.bind([("data", (batch, dim))],
                   [("softmax_label", (batch,))])
            m.init_params(initializer=mx.init.Xavier())
            m.init_optimizer(kvstore=None, optimizer="sgd",
                             optimizer_params=(("learning_rate", 0.05),
                                               ("momentum", 0.9)))
            X = rng.uniform(-1, 1, (batch, dim)).astype(np.float32)
            Y = rng.randint(0, classes, (batch,)).astype(np.float32)
            cold0 = compile_cache.named_stats("spmd")
            t0 = time.perf_counter()
            assert m.fused_step(_Batch(X, Y)), "fused step fell back"
            cold_s = time.perf_counter() - t0
            warm0 = compile_cache.named_stats("spmd")
            times = []
            for _ in range(steps):
                t0 = time.perf_counter()
                assert m.fused_step(_Batch(X, Y))
                for w in m._exec.arg_dict.values():
                    w.wait_to_read()
                times.append(time.perf_counter() - t0)
            warm1 = compile_cache.named_stats("spmd")
            if spmd_spec:
                assert m._spmd is not None and not m._spmd_failed, \
                    "spmd path did not engage"
            per_dev = total = 0
            for name in m._param_names:
                a = m._exec.arg_dict[name]._data
                per_dev += nbytes_on_device(a)
                total += int(a.size) * a.dtype.itemsize
            arg_p, _ = m.get_params()
            steady = sorted(times)[len(times) // 2]
            inventory = None
            if spmd_spec:
                # hlolint collective inventory of the COMPILED sharded
                # step (AOT re-lower while the per-context cache is
                # alive) — tools/bench_compare.py treats per-step
                # collective bytes growing >10% at the same mesh spec as
                # a hard regression
                from mxnet_tpu import analysis

                inv = analysis.cache_inventory("spmd")
                inventory = {
                    "mesh": spmd_spec,
                    "collective_bytes": inv["collective_bytes"],
                    "collectives": {k: v["bytes"]
                                    for k, v in inv["collectives"].items()},
                }
            return ({k: v.asnumpy() for k, v in arg_p.items()}, steady,
                    per_dev, total, cold_s,
                    warm0["compile_seconds"] - cold0["compile_seconds"],
                    warm1["misses"] - warm0["misses"], inventory)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v

    w_rep, t_rep, _, total, _, _, _, _ = drive("")
    w_sh, t_sh, per_dev, total, cold_wall, cold_compile, steady, \
        inventory = drive(spec)
    assert steady == 0, f"spmd steady state compiled {steady} programs"
    parity = max(float(np.abs(w_sh[k] - w_rep[k]).max() /
                       max(np.abs(w_rep[k]).max(), 1e-8)) for k in w_rep)
    return {
        "basis": f"module_fused MXNET_SPMD={spec} vs replicated "
                 f"({ndev} devices)",
        "spec": spec,
        "step_time_replicated_s": round(t_rep, 5),
        "step_time_spmd_s": round(t_sh, 5),
        "spmd_vs_replicated": round(t_rep / max(t_sh, 1e-9), 3),
        "param_bytes_per_device": per_dev,
        "param_bytes_replicated": total,
        "param_bytes_ratio": round(per_dev / max(total, 1), 4),
        "parity_rel": parity,
        "cold_wall_s": round(cold_wall, 3),
        "cold_compile_s": round(cold_compile, 3),
        "steady_state_compiles": steady,
        "hlolint": inventory,
    }


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an ascending-sorted list (shared by the
    serving and generation probes so their p50/p99 are comparable)."""
    return sorted_vals[min(len(sorted_vals) - 1,
                           int(round(q / 100.0 * (len(sorted_vals) - 1))))]


def _measure_serving(on_tpu):
    """serving_throughput probe: closed-loop clients firing ragged-size
    requests at a `serving.DynamicBatcher` over a small MLP Predictor —
    reports req/s plus client-measured p50/p99 end-to-end latency, with
    the cold (warmup compile) seconds separated from warm steady state
    exactly as the fused-step PR separated compile from throughput. The
    net is small ON PURPOSE: this measures the batching/admission plane
    (coalescing, padding, queueing), not matmul throughput — and it
    asserts the serving cache stayed cold-free (`steady_state_compiles`
    must be 0; a nonzero value is a bucket-churn regression)."""
    import threading

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import serving
    from mxnet_tpu.io.io import DataDesc

    dim, classes = 64, 8
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=128, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name="fc2")
    sym = mx.sym.SoftmaxOutput(fc2, name="softmax")
    mod = mx.mod.Module(sym)
    mod.bind([DataDesc("data", (8, dim))], [DataDesc("softmax_label", (8,))],
             for_training=False)
    mod.init_params(mx.init.Xavier())

    buckets = (2, 4, 8, 16)
    pred = mod.as_predictor(buckets=buckets)
    warm = serving.warmup(pred)  # the cold phase: every bucket compiles here
    misses_warm = pred.cache.misses

    n_clients = 4
    per_client = int(os.environ.get(
        "BENCH_SERVING_REQS", "200" if on_tpu else "100"))
    sizes = [1, 2, 3, 5, 8, 11]
    rng = np.random.RandomState(0)
    payloads = {s: rng.uniform(-1, 1, (s, dim)).astype(np.float32)
                for s in set(sizes)}
    lat = [[] for _ in range(n_clients)]

    def closed_loop(fn, record):
        errors = []

        def client(k):
            try:
                for i in range(per_client):
                    s = sizes[(k + i) % len(sizes)]
                    t = time.perf_counter()
                    fn(payloads[s])
                    if record:
                        lat[k].append(time.perf_counter() - t)
            except Exception as e:  # noqa: BLE001 — re-raised below: a
                # dead client thread must become a serving_error entry,
                # not silently-partial req/s and percentile numbers
                errors.append(e)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        if errors:
            raise errors[0]
        return time.perf_counter() - t0

    with serving.DynamicBatcher(pred, max_wait_ms=1.0) as srv:
        # warm-in: thread pools, first-call paths, allocator — untimed
        # (the compile cold phase was already separated out by warmup())
        for s in sizes:
            srv.predict(payloads[s])
        wall = closed_loop(srv.predict, record=True)

        # mid-bench rolling swap sub-phase: the same closed-loop traffic
        # keeps firing while the predictor hot-swaps to a second weight
        # version and back — measuring the req/s dip a live swap costs.
        # The contract under measurement: zero errors, zero new compiles
        # (same shapes reuse every warmed bucket executable)
        mod_b = mx.mod.Module(sym)
        mod_b.bind([DataDesc("data", (8, dim))],
                   [DataDesc("softmax_label", (8,))], for_training=False)
        mx.random.seed(99)
        mod_b.init_params(mx.init.Xavier())
        arg_b, aux_b = mod_b.get_params()
        arg_b = {k: v.asnumpy() for k, v in arg_b.items()}
        aux_b = {k: v.asnumpy() for k, v in aux_b.items()}
        arg_a, aux_a = mod.get_params()
        arg_a = {k: v.asnumpy() for k, v in arg_a.items()}
        aux_a = {k: v.asnumpy() for k, v in aux_a.items()}

        stamps = []
        stamp_lock = threading.Lock()
        misses_preswap = pred.cache.misses
        total = n_clients * per_client

        def stamped_predict(x):
            srv.predict(x)
            with stamp_lock:
                stamps.append(time.perf_counter())

        def swapper():
            # flip forward once traffic is flowing, back once it has
            # clearly settled — two live swaps inside the timed window
            # (deadline-bounded so a dead client loop can't wedge us)
            deadline = time.perf_counter() + 600
            for frac, (a, x) in ((0.3, (arg_b, aux_b)),
                                 (0.65, (arg_a, aux_a))):
                while time.perf_counter() < deadline:
                    with stamp_lock:
                        if len(stamps) >= total * frac:
                            break
                    time.sleep(0.002)
                pred.swap_weights(a, x)

        sw = threading.Thread(target=swapper, daemon=True)
        sw.start()
        swap_wall = closed_loop(stamped_predict, record=False)
        sw.join()
        swap_compiles = pred.cache.misses - misses_preswap
        assert swap_compiles == 0, \
            f"weight swap recompiled {swap_compiles} executables"
        assert pred.stats()["weights_version"] == 2

        # dip shape from completion timestamps: req/s per window (the
        # window scales with the phase so sparse CPU traffic doesn't
        # alias empty buckets into a fake full-depth dip); depth vs the
        # median window, duration = time spent below 90% of it. The
        # trailing partial window is dropped — it only reflects drain
        win = max(0.1, swap_wall / 12.0)
        t_first = stamps[0]
        counts = {}
        for t in stamps:
            counts[int((t - t_first) / win)] = counts.get(
                int((t - t_first) / win), 0) + 1
        n_win = max(max(counts), 1) if counts else 1
        rates = [counts.get(i, 0) / win for i in range(n_win)]
        base = sorted(rates)[len(rates) // 2]
        dip_depth = (max(0.0, 1.0 - min(rates) / base) if base > 0
                     else 0.0)
        dip_ms = (sum(win for r in rates if r < 0.9 * base) * 1e3
                  if base > 0 else 0.0)

    all_lat = sorted(x for per in lat for x in per)
    # the comparison point: the same clients hammering the lock-shared
    # Predictor directly (no queue, no coalescing). With sub-ms CPU
    # compute the batcher's thread handoffs are visible against this; with
    # real accelerator compute the coalescing wins (docs/faq/perf.md)
    direct_wall = closed_loop(pred.predict, record=False)
    return {
        "metric": "serving_throughput",
        "requests": total,
        "clients": n_clients,
        "req_per_s": round(total / wall, 1),
        "p50_ms": round(_pct(all_lat, 50) * 1e3, 3),
        "p99_ms": round(_pct(all_lat, 99) * 1e3, 3),
        "direct_req_per_s": round(total / direct_wall, 1),
        "cold_compile_s": round(warm["seconds"], 3),
        "warmup_compiles": warm["compiles"],
        "steady_state_compiles": pred.cache.misses - misses_warm,
        "buckets": list(buckets),
        "swap_req_per_s": round(total / swap_wall, 1),
        "swap_dip_depth": round(dip_depth, 3),
        "swap_dip_ms": round(dip_ms, 1),
        "swap_errors": 0,          # closed_loop raised otherwise
        "swap_steady_state_compiles": swap_compiles,
        "swaps": 2,
    }


def _measure_generation(on_tpu):
    """generation_throughput probe: concurrent ragged streaming sessions
    through the continuous-batching `serving.generation.GenerationEngine`
    over a small TransformerLM — tokens/s, time-to-first-token p50/p99,
    and the O(1) claim measured directly: per-token decode latency
    FLATNESS (median inter-token gap late in a long generation over the
    median early — a fixed-shape slab decode must hold this near 1.0,
    where an O(T) re-forward path grows linearly). Cold compile seconds
    (warmup) are separated from warm steady state, and the probe asserts
    the 'generation' compile cache stayed cold-free afterwards
    (`steady_state_compiles` must be 0 — nonzero means admission or
    eviction churned a shape, the regression continuous batching exists
    to prevent).

    Two scale-out lanes ride the same probe:
    * **speculative** — the workload re-runs through an engine with
      `spec_k=4` and the n-gram draft; reports `spec_tokens_per_s`, the
      `spec_vs_plain` speedup and `accepted_tokens_per_tick` (committed
      tokens per live slot per verify tick — plain decode's floor is
      1.0, so > 1 is the headline). Greedy output is bit-exact with the
      plain lane by construction, so the speedup is free of quality
      caveats. On CPU the verify's k+1-fold compute usually outweighs
      the dispatch savings (see docs/faq/perf.md "when speculation
      loses") — the tokens/tick number is the hardware-independent one.
    * **prefix cache** — clients share one system prompt with ragged
      tails through a `prefix_cache=True` engine; reports
      `prefix_hit_ratio` (target (N-1)/N) and `prefix_ttft_p50_ms`
      (fork + suffix prefill) next to the cold `ttft_p50_ms` above.
    Both lanes assert zero steady-state compiles on their own engines."""
    import threading

    import numpy as np

    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    from mxnet_tpu.serving.generation import GenerationEngine, NgramDraft

    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, d_ff=128, n_layers=2,
        max_len=128, dtype="bfloat16" if on_tpu else "float32")
    lm = TransformerLM(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(0))
    slots, buckets = 8, (8, 16, 32)
    # with-block: a dead client or flatness failure must still close the
    # engine (scheduler thread, KV slab + its census provider) or it
    # pollutes the later bench phases sharing this process
    with GenerationEngine(lm, params, max_slots=slots, max_len=cfg.max_len,
                          buckets=buckets) as eng:
        warm = serving.warmup(eng)  # cold phase: prefill ladder + decode
        misses_warm = eng.cache.misses

        n_clients = 4
        per_client = int(os.environ.get(
            "BENCH_GENERATION_SESSIONS", "12" if on_tpu else "6"))
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, cfg.vocab_size, int(l)).astype(np.int32)
                   for l in rng.randint(3, 24, size=64)]
        lock = threading.Lock()
        ttfts, tokens_done, errors = [], [0], []

        def client(k):
            try:
                for i in range(per_client):
                    p = prompts[(k * per_client + i) % len(prompts)]
                    t0 = time.perf_counter()
                    stream = eng.submit(p, max_new_tokens=16)
                    first = next(stream)
                    dt = time.perf_counter() - t0
                    toks = [first] + list(stream)
                    with lock:
                        ttfts.append(dt)
                        tokens_done[0] += len(toks)
            except Exception as e:  # noqa: BLE001 — re-raised below: a dead
                # client must become a generation_error entry, not silently-
                # partial tokens/s numbers
                errors.append(e)

        threads = [threading.Thread(target=client, args=(k,))
                   for k in range(n_clients)]
        t0 = time.perf_counter()
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        wall = time.perf_counter() - t0
        if errors:
            raise errors[0]

        # O(1) flatness: one long stream, inter-token gap late vs early
        gaps, t_prev = [], time.perf_counter()
        for _ in eng.submit(prompts[0][:4], max_new_tokens=96):
            now = time.perf_counter()
            gaps.append(now - t_prev)
            t_prev = now
        third = max(len(gaps) // 3, 1)
        early = sorted(gaps[1:1 + third])
        late = sorted(gaps[-third:])
        flatness = late[len(late) // 2] / max(early[len(early) // 2], 1e-9)

        steady = eng.cache.misses - misses_warm
        slab_mb = eng.kv_slab_bytes() / 2 ** 20
    assert steady == 0, f"steady-state generation compiles: {steady}"
    ttfts.sort()

    def _counter(name):
        m = telemetry.get(name)
        return float(m.value) if m is not None else 0.0

    # speculative lane: the same ragged workload, one engine with the
    # n-gram draft proposing 4 tokens per tick
    spec_k = 4
    n_spec = min(n_clients * per_client, 16)
    com0 = _counter("serving.generation.spec.committed")
    vs0 = _counter("serving.generation.spec.verified_slots")
    with GenerationEngine(lm, params, max_slots=slots, max_len=cfg.max_len,
                          buckets=buckets, spec_k=spec_k,
                          draft=NgramDraft()) as spec_eng:
        serving.warmup(spec_eng)
        m0 = spec_eng.cache.misses
        t0 = time.perf_counter()
        spec_streams = [spec_eng.submit(prompts[i % len(prompts)],
                                        max_new_tokens=16)
                        for i in range(n_spec)]
        spec_out = [s.result(timeout=120) for s in spec_streams]
        spec_wall = time.perf_counter() - t0
        spec_steady = spec_eng.cache.misses - m0
    assert spec_steady == 0, \
        f"steady-state speculative compiles: {spec_steady}"
    committed = _counter("serving.generation.spec.committed") - com0
    vslots = _counter("serving.generation.spec.verified_slots") - vs0
    spec_tps = sum(len(o) for o in spec_out) / max(spec_wall, 1e-9)

    # plain engine over the SAME closed-loop shape, for an apples-to-
    # apples spec_vs_plain wall ratio (the threaded run above has
    # different client dynamics)
    with GenerationEngine(lm, params, max_slots=slots, max_len=cfg.max_len,
                          buckets=buckets) as plain_eng:
        serving.warmup(plain_eng)
        t0 = time.perf_counter()
        plain_streams = [plain_eng.submit(prompts[i % len(prompts)],
                                          max_new_tokens=16)
                         for i in range(n_spec)]
        plain_out = [s.result(timeout=120) for s in plain_streams]
        plain_wall = time.perf_counter() - t0
    # the TOKEN SEQUENCES, not counts: with no eos both lanes always
    # emit max_new_tokens, so a count comparison could never fail
    assert plain_out == spec_out, \
        "speculative lane diverged from plain greedy"

    # prefix-cache lane: every client shares one 16-token system prompt
    ph0 = _counter("serving.generation.prefix.hits")
    pm0 = _counter("serving.generation.prefix.misses")
    sys_prompt = rng.randint(1, cfg.vocab_size, 16).astype(np.int32)
    n_pref = min(n_clients * per_client, 24)
    pref_prompts = [np.concatenate([sys_prompt,
                                    rng.randint(1, cfg.vocab_size,
                                                1 + int(l)).astype(np.int32)])
                    for l in rng.randint(1, 8, size=n_pref)]
    with GenerationEngine(lm, params, max_slots=slots, max_len=cfg.max_len,
                          buckets=buckets, prefix_cache=True,
                          prefix_min_tokens=8) as pref_eng:
        serving.warmup(pref_eng)
        m0 = pref_eng.cache.misses
        pref_ttfts = []
        for p in pref_prompts:
            t0 = time.perf_counter()
            stream = pref_eng.submit(p, max_new_tokens=8)
            next(stream)
            pref_ttfts.append(time.perf_counter() - t0)
            stream.result(timeout=120)
        pref_steady = pref_eng.cache.misses - m0
    assert pref_steady == 0, f"steady-state prefix compiles: {pref_steady}"
    hits = _counter("serving.generation.prefix.hits") - ph0
    misses = _counter("serving.generation.prefix.misses") - pm0
    hit_ttfts = sorted(pref_ttfts[1:]) or [0.0]

    return {
        "metric": "generation_throughput",
        "sessions": n_clients * per_client,
        "clients": n_clients,
        "tokens": tokens_done[0],
        "tokens_per_s": round(tokens_done[0] / wall, 1),
        "ttft_p50_ms": round(_pct(ttfts, 50) * 1e3, 3),
        "ttft_p99_ms": round(_pct(ttfts, 99) * 1e3, 3),
        "per_token_latency_flatness": round(flatness, 3),
        "cold_compile_s": round(warm["seconds"], 3),
        "warmup_compiles": warm["compiles"],
        "steady_state_compiles": steady,
        "slots": slots,
        "buckets": list(buckets),
        "max_len": cfg.max_len,
        "kv_slab_mb": round(slab_mb, 2),
        "spec_k": spec_k,
        "spec_tokens_per_s": round(spec_tps, 1),
        "spec_vs_plain": round(plain_wall / max(spec_wall, 1e-9), 3),
        "accepted_tokens_per_tick": round(committed / max(vslots, 1.0), 3),
        "spec_steady_state_compiles": spec_steady,
        "prefix_hit_ratio": round(hits / max(hits + misses, 1.0), 3),
        "prefix_ttft_p50_ms": round(_pct(hit_ttfts, 50) * 1e3, 3),
        "prefix_steady_state_compiles": pref_steady,
    }


def _measure_qos(on_tpu):
    """qos_isolation probe: an interactive tenant's TTFT under a batch
    tenant's flood, with and without the QoS layer.

    Three phases on the same tiny TransformerLM engine shape:

    * **unloaded** — interactive sessions alone; TTFT p50/p99 baseline.
    * **FIFO flood** — QoS off: 2x-slots batch sessions saturate the
      slab AND the queue, then an interactive trickle queues behind
      them. FIFO makes its TTFT the flood's drain time — the
      multi-tenant failure this lane exists to demonstrate (recorded as
      ``fifo_interactive_ttft_p99_ms``; it grows with flood depth).
    * **QoS flood** — the same flood through an engine built under an
      installed registry (``latency:interactive; bulk:batch``): the
      queue reorders by class, the engine parks a batch session per
      park slot (``preemptions`` counts them), and the trickle's
      ``interactive_ttft_p99_ms`` stays within a small multiple of the
      unloaded baseline (``ttft_degradation``, direction-pinned by
      ``tools/bench_compare.py``).

    Asserts zero steady-state compiles on the QoS engine: park/preempt/
    resume ride the warmed fork executable, so multi-tenancy adds no
    compile churn (``qos_steady_state_compiles``)."""
    import threading

    import numpy as np

    import jax
    from mxnet_tpu import parallel as par
    from mxnet_tpu import serving, telemetry
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    from mxnet_tpu.serving import qos
    from mxnet_tpu.serving.generation import GenerationEngine

    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(
        vocab_size=256, d_model=64, n_heads=4, d_ff=128, n_layers=2,
        max_len=128, dtype="bfloat16" if on_tpu else "float32")
    lm = TransformerLM(cfg, mesh)
    params = lm.init_params(jax.random.PRNGKey(0))
    slots, buckets = 4, (8, 16, 32)
    rng = np.random.RandomState(0)
    flood_n = 2 * slots
    trickle_n = 5
    flood_prompts = [rng.randint(1, cfg.vocab_size, 12).astype(np.int32)
                     for _ in range(flood_n)]
    inter_prompts = [rng.randint(1, cfg.vocab_size, 6).astype(np.int32)
                     for _ in range(trickle_n)]

    def _counter(name):
        m = telemetry.get(name)
        return float(m.value) if m is not None else 0.0

    def _trickle(eng, tenant=None):
        ttfts = []
        for p in inter_prompts:
            t0 = time.perf_counter()
            stream = eng.submit(p, max_new_tokens=4, tenant=tenant)
            next(stream)
            ttfts.append(time.perf_counter() - t0)
            stream.result(timeout=120)
        return sorted(ttfts)

    def _flood(eng, tenant=None):
        return [eng.submit(p, max_new_tokens=32, tenant=tenant)
                for p in flood_prompts]

    # phase 1+2: QoS OFF (installed None overrides any ambient
    # MXNET_QOS_SPEC) — unloaded baseline, then the FIFO pathology
    qos.install(None)
    with GenerationEngine(lm, params, max_slots=slots, max_len=cfg.max_len,
                          buckets=buckets) as eng:
        serving.warmup(eng)
        base = _trickle(eng)
        streams = _flood(eng)
        fifo = _trickle(eng)
        for s in streams:
            s.result(timeout=120)

    # phase 3: the same flood with the QoS layer active (installed
    # registry, not env — the lane must not perturb later phases)
    pre0 = _counter("serving.generation.preemptions")
    qos.install(qos.TenantRegistry(qos.parse_spec(
        "latency:interactive;bulk:batch")))
    try:
        with GenerationEngine(lm, params, max_slots=slots,
                              max_len=cfg.max_len, buckets=buckets) as eng:
            serving.warmup(eng)
            misses_warm = eng.cache.misses
            streams = _flood(eng, tenant="bulk")
            loaded = _trickle(eng, tenant="latency")
            for s in streams:
                s.result(timeout=120)
            steady = eng.cache.misses - misses_warm
    finally:
        qos.clear()
    assert steady == 0, f"steady-state qos compiles: {steady}"
    preemptions = _counter("serving.generation.preemptions") - pre0

    return {
        "metric": "qos_isolation",
        "slots": slots,
        "park_slots": 1,
        "flood_sessions": flood_n,
        "interactive_sessions": trickle_n,
        "unloaded_ttft_p50_ms": round(_pct(base, 50) * 1e3, 3),
        "unloaded_ttft_p99_ms": round(_pct(base, 99) * 1e3, 3),
        "interactive_ttft_p50_ms": round(_pct(loaded, 50) * 1e3, 3),
        "interactive_ttft_p99_ms": round(_pct(loaded, 99) * 1e3, 3),
        "fifo_interactive_ttft_p99_ms": round(_pct(fifo, 99) * 1e3, 3),
        "ttft_degradation": round(
            _pct(loaded, 99) / max(_pct(base, 99), 1e-9), 3),
        "fifo_ttft_degradation": round(
            _pct(fifo, 99) / max(_pct(base, 99), 1e-9), 3),
        "preemptions": int(preemptions),
        "qos_steady_state_compiles": steady,
    }


def _measure_overlap(on_tpu):
    """Overlap on/off sub-lanes: the SAME host-heavy workloads driven
    twice — lockstep (``MXNET_OVERLAP=0``) then overlapped (``=1``) —
    stamping each mode's roofline ``host_gap_us`` so the delta
    attributes what the async dispatch pipeline actually hid. Three
    planes:

    * **train** — a small-MLP ``Module.fit`` (device staging + deferred
      metric sync points); asserts BIT-EQUAL final params across modes
      and zero steady-state compiles in both;
    * **serving** — closed-loop clients over a ``DynamicBatcher``
      (stage-ahead of the next flush); asserts bit-equal probe outputs
      and zero steady-state compiles;
    * **generation** — a micro ``GenerationEngine`` run (tick
      bookkeeping between decode dispatch and block); asserts identical
      per-session token streams.

    The host-gap direction (on < off) is recorded per plane —
    ``tools/bench_compare.py`` enforces it cross-run; a CPU smoke run's
    tiny-shape deltas can sit inside scheduler noise, so the lane
    records rather than asserts the inequality."""
    import threading

    import numpy as np

    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import compile_cache, observatory, serving
    from mxnet_tpu import parallel as par
    from mxnet_tpu.io import NDArrayIter
    from mxnet_tpu.io.io import DataDesc
    from mxnet_tpu.models import TransformerLM, TransformerLMConfig
    from mxnet_tpu.serving.generation import GenerationEngine

    # the train model must have REAL device time (a few ms/step even on
    # CPU): overlap hides host work behind in-flight compute, so a
    # dispatch-bound micro-model would leave nothing to hide and the
    # measured gap delta would be pure scheduler noise. The float64
    # source arrays force a genuine per-batch host cast — exactly the
    # feed-prep work the staging thread moves off the critical path
    dim, classes, batch, n_batches = 512, 8, 256, 8
    hidden = 512

    def mlp(nh=hidden):
        data = mx.sym.Variable("data")
        fc1 = mx.sym.FullyConnected(data, num_hidden=nh, name="fc1")
        act = mx.sym.Activation(fc1, act_type="relu")
        fc2 = mx.sym.FullyConnected(act, num_hidden=classes, name="fc2")
        return mx.sym.SoftmaxOutput(fc2, name="softmax")

    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (batch * n_batches, dim))
    Y = rng.randint(0, classes, (batch * n_batches,)).astype(np.float64)
    epochs = max(4, int(os.environ.get("BENCH_ITERS", "3")))

    def gap_fields(dst, off, on):
        go, gn = off.get("host_gap_us"), on.get("host_gap_us")
        if isinstance(go, (int, float)) and isinstance(gn, (int, float)):
            dst["host_gap_delta_us"] = round(go - gn, 1)
            dst["host_gap_reduced"] = bool(gn < go)

    def train_mode(overlap):
        os.environ["MXNET_OVERLAP"] = "1" if overlap else "0"
        mx.random.seed(7)
        observatory.reset("step")
        mod = mx.mod.Module(mlp())
        it = NDArrayIter(X, Y, batch_size=batch, shuffle=False)
        marks = {}

        def at_epoch_end(epoch, _sym, _arg, _aux):
            if epoch == 0:
                # end of the cold epoch: every executor compile has
                # landed, the steady-state window (and a fresh step
                # lane) begins here
                marks["misses"] = compile_cache.named_stats(
                    "executor")["misses"]
                marks["t0"] = time.perf_counter()
                observatory.reset("step")

        mod.fit(it, num_epoch=epochs + 1, optimizer="adam",
                optimizer_params=(("learning_rate", 1e-3),),
                initializer=mx.init.Xavier(),
                epoch_end_callback=at_epoch_end)
        warm_s = time.perf_counter() - marks["t0"]
        steady = compile_cache.named_stats(
            "executor")["misses"] - marks["misses"]
        assert steady == 0, \
            f"overlap={overlap} train steady state compiled {steady}"
        # min-basis gap: the EWMA wall under a pipelined loop counts
        # waiting-for-device time that IS overlapped compute, and CPU
        # scheduler spikes land asymmetrically; the per-mode BEST step
        # (min wall − min exec) is the reproducible floor the overlap
        # either closes or doesn't
        st = observatory.lanes().get("step") or {}
        arg, _aux = mod.get_params()
        out = {"steps_per_s": round(
                   epochs * n_batches / max(warm_s, 1e-9), 1),
               "steady_state_compiles": steady,
               "host_gap_basis": "min"}
        if st.get("wall_s_min") and st.get("exec_s_min"):
            out["host_gap_us"] = round(max(
                st["wall_s_min"] - st["exec_s_min"], 0.0) * 1e6, 1)
        return out, {k: v.asnumpy() for k, v in arg.items()}

    def serving_mode(overlap):
        os.environ["MXNET_OVERLAP"] = "1" if overlap else "0"
        mx.random.seed(11)
        mod = mx.mod.Module(mlp())
        mod.bind([DataDesc("data", (8, dim))],
                 [DataDesc("softmax_label", (8,))], for_training=False)
        mod.init_params(mx.init.Xavier())
        pred = mod.as_predictor(buckets=(2, 4, 8))
        serving.warmup(pred)
        m0 = pred.cache.misses
        observatory.reset("serving")
        payload = np.random.RandomState(5).uniform(
            -1, 1, (3, dim)).astype(np.float32)
        n_clients = 4
        per_client = int(os.environ.get(
            "BENCH_OVERLAP_REQS", "60" if on_tpu else "40"))
        errors = []
        with serving.DynamicBatcher(pred, max_wait_ms=1.0) as srv:
            for _ in range(3):
                srv.predict(payload)          # warm-in, untimed

            def client(_k):
                try:
                    for _ in range(per_client):
                        srv.predict(payload)
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)

            threads = [threading.Thread(target=client, args=(k,))
                       for k in range(n_clients)]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t0
            if errors:
                raise errors[0]
            probe_out = np.asarray(srv.predict(payload))
        steady = pred.cache.misses - m0
        assert steady == 0, \
            f"overlap={overlap} serving steady state compiled {steady}"
        row = observatory.attribution("serving") or {}
        out = {"req_per_s": round(n_clients * per_client / wall, 1),
               "steady_state_compiles": steady}
        if isinstance(row.get("host_gap_us"), float):
            out["host_gap_us"] = round(row["host_gap_us"], 1)
        return out, probe_out

    def generation_mode(overlap):
        os.environ["MXNET_OVERLAP"] = "1" if overlap else "0"
        mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
        cfg = TransformerLMConfig(vocab_size=32, d_model=16, n_heads=2,
                                  d_ff=32, n_layers=1, max_len=32,
                                  dtype="float32")
        lm = TransformerLM(cfg, mesh)
        params = lm.init_params(jax.random.PRNGKey(0))
        eng = GenerationEngine(lm, params, max_slots=2, max_len=32,
                               buckets=(8,))
        try:
            eng.generate([1, 2, 3], max_new_tokens=4)   # cold compiles
            m0 = eng.cache.misses
            observatory.reset("generation.tick")
            t0 = time.perf_counter()
            streams = [eng.submit([1, 2, 3, 4], max_new_tokens=16),
                       eng.submit([2, 3], max_new_tokens=16)]
            toks = [s.result(timeout=300) for s in streams]
            wall = time.perf_counter() - t0
            steady = eng.cache.misses - m0
        finally:
            eng.close()
        assert steady == 0, \
            f"overlap={overlap} generation steady state compiled {steady}"
        row = observatory.attribution("generation.tick") or {}
        out = {"tokens_per_s": round(
                   sum(len(t) for t in toks) / max(wall, 1e-9), 1),
               "steady_state_compiles": steady}
        if isinstance(row.get("host_gap_us"), float):
            out["host_gap_us"] = round(row["host_gap_us"], 1)
        return out, toks

    out = {"basis": "same workload, only MXNET_OVERLAP flips",
           "train": {}, "serving": {}, "generation": {}}
    prev = os.environ.get("MXNET_OVERLAP")
    try:
        t_off, p_off = train_mode(0)
        t_on, p_on = train_mode(1)
        assert set(p_off) == set(p_on)
        for k in p_off:
            assert p_off[k].dtype == p_on[k].dtype and \
                np.array_equal(p_off[k], p_on[k]), \
                f"train param {k} diverged under overlap"
        out["train"] = {"off": t_off, "on": t_on, "parity": "bit-exact"}
        gap_fields(out["train"], t_off, t_on)

        s_off, o_off = serving_mode(0)
        s_on, o_on = serving_mode(1)
        assert o_off.dtype == o_on.dtype and np.array_equal(o_off, o_on), \
            "serving probe output diverged under overlap"
        out["serving"] = {"off": s_off, "on": s_on, "parity": "bit-exact"}
        gap_fields(out["serving"], s_off, s_on)

        g_off, k_off = generation_mode(0)
        g_on, k_on = generation_mode(1)
        assert k_off == k_on, "generation token streams diverged"
        out["generation"] = {"off": g_off, "on": g_on,
                             "parity": "bit-exact"}
        gap_fields(out["generation"], g_off, g_on)
    finally:
        if prev is None:
            os.environ.pop("MXNET_OVERLAP", None)
        else:
            os.environ["MXNET_OVERLAP"] = prev
    return out


def _measure_peak_flops(on_tpu, fetch_cost):
    """Measured MXU peak: sustained FLOP/s of a chained large bf16 matmul,
    value-fetch timed (each matmul consumes the previous result, so the
    final fetch forces the whole chain)."""
    import jax
    import jax.numpy as jnp

    n = 8192 if on_tpu else 1024
    a = jnp.ones((n, n), jnp.bfloat16)
    f = jax.jit(lambda a, b: a @ b)
    out = f(a, a)
    jax.device_get(out[:1, :1])  # compile + drain
    reps = 8 if on_tpu else 2
    t0 = time.perf_counter()
    for _ in range(reps):
        out = f(a, out)
    jax.device_get(out[:1, :1])
    dt = max(time.perf_counter() - t0 - fetch_cost, 1e-9)
    return 2.0 * n ** 3 * reps / dt


# nominal per-chip bf16 peaks (public spec sheets) for known device kinds
_NOMINAL_PEAK = {
    "TPU v2": 46e12, "TPU v3": 123e12, "TPU v4": 275e12,
    "TPU v5 lite": 197e12, "TPU v5e": 197e12, "TPU v5p": 459e12,
    "TPU v6 lite": 918e12, "TPU v6e": 918e12,
}


def main():
    backend = _require_backend()
    import mxnet_tpu as mx

    # the lanes name no device: they build on the DEFAULT context, which is
    # mx.cpu(0) — the host — even on a machine with a chip
    with (mx.cpu() if backend == "cpu" else mx.tpu(0)):
        return _measure_all(backend)


def _measure_all(backend):
    result = {
        "metric": "resnet50_train_img_per_sec",
        "value": 0.0,
        "unit": "img/s",
        "vs_baseline": 0.0,
        "timing_basis": "value_fetch",
    }
    try:
        result.update(_bench_stamp(backend))
        on_tpu = backend not in ("cpu",)
        # metrics breakdown of the measured run (sidecar json). The run is
        # measured WITH telemetry on (a handful of flag checks + clock
        # reads per step — noise against a training step), and the result
        # says so: BENCH_TELEMETRY_OUT=0 restores the uninstrumented
        # configuration for a strict baseline comparison.
        if os.environ.get("BENCH_TELEMETRY_OUT") != "0":
            try:
                from mxnet_tpu import telemetry

                telemetry.enable()
                result["telemetry_enabled"] = True
            except Exception:  # noqa: BLE001
                pass
        # roofline observatory: per-lane wall/exec observation is a dict
        # update per step (noise), attribution + the measured-peak probes
        # run AFTER each phase's timed window
        if os.environ.get("MXNET_OBSERVATORY") != "0":
            try:
                from mxnet_tpu import observatory

                observatory.enable()
                result["observatory_enabled"] = True
            except Exception:  # noqa: BLE001
                pass
        fetch_cost = _fetch_cost()
        result["fetch_cost_ms"] = round(fetch_cost * 1e3, 3)
        with _phase_scope("raw_fp32"):
            raw_fetch, raw_disp, batch, size, iters, flops, raw_compile_s = \
                _measure_raw(on_tpu, fetch_cost)
        with _phase_scope("framework_fp32"):
            fw_fetch, fw_disp, fw_compile_s = _measure_framework(
                on_tpu, fetch_cost, "float32", fused=True)
        result.update(
            value=round(fw_fetch, 2),
            vs_baseline=round(fw_fetch / BASELINE_IMG_S, 3),
            backend=backend,
            batch=batch,
            image_size=size,
            iters=iters,
            raw_fp32=round(raw_fetch, 2),
            raw_fp32_dispatch=round(raw_disp, 2),
            raw_compile_s=round(raw_compile_s, 2),
            framework_fp32=round(fw_fetch, 2),
            framework_fp32_dispatch=round(fw_disp, 2),
            framework_fp32_compile_s=round(fw_compile_s, 2),
            framework_gluon_vs_raw=round(fw_fetch / raw_fetch, 3),
        )
        # the SYMBOLIC public path: Module.fused_step — one XLA computation
        # per train step (the fused-step PR's tentpole). This is the
        # framework's fastest public path, so framework_vs_raw is defined on
        # it (basis recorded explicitly; the gluon ratio stays alongside).
        try:
            with _phase_scope("module_fused"):
                mf_fetch, mf_disp, mf_compile_s = _measure_module(
                    on_tpu, fetch_cost, fused=True)
            result["framework_module_fused"] = round(mf_fetch, 2)
            result["framework_module_fused_dispatch"] = round(mf_disp, 2)
            result["framework_module_compile_s"] = round(mf_compile_s, 2)
            result["framework_vs_raw"] = round(mf_fetch / raw_fetch, 3)
            result["framework_vs_raw_basis"] = "module_fused"
            result["framework_vs_raw_note"] = (
                "basis changed in the fused-step PR: r01-r05 measured the "
                "gluon path, continued as framework_gluon_vs_raw")
            # roofline attribution for the fused step, stamped NOW —
            # before module_eager's fit loop dilutes the step lane's wall
            # EWMA with eager walls
            _roofline_stamp("step", result)
        except Exception:  # noqa: BLE001
            result["module_error"] = traceback.format_exc(limit=3).strip().splitlines()[-1]
            result["framework_vs_raw"] = round(fw_fetch / raw_fetch, 3)
            result["framework_vs_raw_basis"] = "gluon (module path failed)"
        else:
            # eager comparison in its OWN guard: its failure must not
            # contradict the already-recorded module_fused basis keys
            try:
                with _phase_scope("module_eager"):
                    me_fetch, me_disp, me_compile_s = _measure_module(
                        on_tpu, fetch_cost, fused=False)
                result["framework_module_eager"] = round(me_fetch, 2)
                result["framework_module_eager_compile_s"] = round(
                    me_compile_s, 2)
                # the tentpole attribution: same Module, same data, same
                # timing basis — only the whole-step fusion differs
                result["fused_vs_eager"] = round(mf_fetch / me_fetch, 3)
            except Exception:  # noqa: BLE001
                result["module_eager_error"] = \
                    traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # gluon eager (MXNET_FUSED_STEP=0) comparison point: the delta
            # to framework_fp32 is attributable to the fused optimizer
            # update (Updater._fused_call) alone
            with _phase_scope("gluon_eager"):
                eg_fetch, eg_disp, eg_compile_s = _measure_framework(
                    on_tpu, fetch_cost, "float32", fused=False)
            result["framework_fp32_eager"] = round(eg_fetch, 2)
            result["framework_fp32_eager_dispatch"] = round(eg_disp, 2)
            result["framework_fp32_eager_compile_s"] = round(eg_compile_s, 2)
            result["gluon_fused_vs_eager"] = round(fw_fetch / eg_fetch, 3)
        except Exception:  # noqa: BLE001
            result["eager_error"] = traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            with _phase_scope("framework_bf16"):
                bf_fetch, bf_disp, _bf_compile_s = _measure_framework(
                    on_tpu, fetch_cost, "bfloat16")
            result["framework_bf16"] = round(bf_fetch, 2)
            result["framework_bf16_dispatch"] = round(bf_disp, 2)
        except Exception:  # noqa: BLE001
            result["bf16_error"] = traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # the serving plane: req/s + tail latency through the dynamic
            # micro-batcher, warm (post-warmup) vs cold compile separated;
            # lands in the BENCH json and — via the serving.* histograms —
            # in the BENCH_TELEMETRY.json sidecar
            with _phase_scope("serving"):
                result["serving"] = _measure_serving(on_tpu)
            _roofline_stamp("serving", result.get("serving"))
        except Exception:  # noqa: BLE001
            result["serving_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # the generation plane: tokens/s + TTFT + per-token latency
            # flatness through the continuous-batching engine, cold
            # (prefill ladder + decode compiles) separated from warm
            with _phase_scope("generation"):
                result["generation"] = _measure_generation(on_tpu)
            # the decode tick moves KV cache, not FLOPs: MBU is the
            # honest utilisation figure, so it gets the tick_mbu headline
            _roofline_stamp("generation.tick", result.get("generation"),
                            mbu_headline="tick_mbu")
        except Exception:  # noqa: BLE001
            result["generation_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # multi-tenant QoS: interactive TTFT under a batch flood,
            # FIFO vs priority-classed admission + preemptive parking —
            # the isolation number plus a zero-steady-compile assertion
            with _phase_scope("qos"):
                result["qos"] = _measure_qos(on_tpu)
        except Exception:  # noqa: BLE001
            result["qos_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # overlap on/off sub-lanes: the same train/serving/generation
            # workloads with only MXNET_OVERLAP flipping — the measured
            # host-gap delta plus bit-parity and zero-steady-compile
            # assertions (runs AFTER the headline lanes so its lane
            # resets can't disturb their attribution stamps)
            with _phase_scope("overlap"):
                result["overlap"] = _measure_overlap(on_tpu)
        except Exception:  # noqa: BLE001
            result["overlap_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # the lazy plane: per-op eager vs deferred-segment capture on
            # the plain fp32 imperative path (MXNET_LAZY=1), zero
            # steady-state compiles asserted; lazy.* counters land in the
            # BENCH_TELEMETRY sidecar
            with _phase_scope("lazy"):
                result["lazy"] = _measure_lazy(on_tpu)
        except Exception:  # noqa: BLE001
            result["lazy_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # the rewrite plane: same capture machinery, only
            # MXNET_LAZY_REWRITE flips — isolates the lazy/rewrite.py win
            # (node shrink + merged outputs) with exact compile
            # accounting in both modes
            with _phase_scope("lazy_fused"):
                result["lazy_fused"] = _measure_lazy_fused(on_tpu)
        except Exception:  # noqa: BLE001
            result["lazy_fused_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            # the spmd plane: GSPMD-sharded fused step (MXNET_SPMD) vs
            # replicated — measured 1/N param residency + compile
            # invariant; skips (recorded) on single-device runs
            try:
                from mxnet_tpu import observatory

                # fresh step lane: the spmd phase re-drives fused_step and
                # must not inherit the single-device phase's EWMAs
                observatory.reset("step")
            except Exception:  # noqa: BLE001
                pass
            with _phase_scope("spmd"):
                result["spmd"] = _measure_spmd(on_tpu)
            if isinstance(result.get("spmd"), dict) and \
                    "skipped" not in result["spmd"]:
                _roofline_stamp("step", result["spmd"])
        except Exception:  # noqa: BLE001
            result["spmd_error"] = \
                traceback.format_exc(limit=3).strip().splitlines()[-1]
        try:
            import jax

            peak = _measure_peak_flops(on_tpu, fetch_cost)
            result["measured_peak_tflops"] = round(peak / 1e12, 1)
            if flops:
                result["flops_per_step"] = flops
                # MFU against the bf16 MXU peak must use the bf16 run —
                # dividing an fp32 workload by a bf16 peak understates it
                bf16 = result.get("framework_bf16")
                if bf16:
                    result["mfu_basis"] = "framework_bf16"
                    mfu_rate = flops * bf16 / batch
                else:
                    result["mfu_basis"] = "raw_fp32 (vs bf16 peak: lower bound)"
                    mfu_rate = flops * raw_fetch / batch
                result["mfu_vs_measured_peak"] = round(mfu_rate / peak, 4)
                kind = jax.devices()[0].device_kind
                result["device_kind"] = kind
                nominal = next((v for k, v in _NOMINAL_PEAK.items()
                                if k.lower() in kind.lower()), None)
                if nominal:
                    result["mfu_vs_nominal_peak"] = round(mfu_rate / nominal, 4)
        except Exception:  # noqa: BLE001
            result["mfu_error"] = traceback.format_exc(limit=3).strip().splitlines()[-1]
    except Exception:  # noqa: BLE001 — a bench crash must still emit JSON
        result["error"] = traceback.format_exc(limit=5).strip().splitlines()[-1]
    # the observatory's full report (measured peaks + per-lane roofline
    # rows) rides along; summary() also refreshes the lane gauges the
    # telemetry sidecar snapshots below
    try:
        from mxnet_tpu import observatory

        if observatory._enabled:
            result["roofline"] = observatory.summary()
    except Exception:  # noqa: BLE001 — the report is additive
        pass
    # re-stamp: trace ids accumulated as phases ran, and the headline
    # backend may have resolved after the first stamp
    result["schema_version"] = BENCH_SCHEMA_VERSION
    stamp = _bench_stamp(result.get("backend"))
    stamp["schema_version"] = BENCH_SCHEMA_VERSION
    # cross-run perf ledger: every run appends one record (run_id is the
    # ledger's monotonic counter, stamped back into the BENCH json and
    # the telemetry sidecar). MXNET_PERF_LEDGER=0 disables, any other
    # value overrides the default bench_ledger.jsonl at the repo root
    # (PERF_LEDGER.jsonl is the driver's record; nothing here writes it).
    if os.environ.get("MXNET_PERF_LEDGER") != "0":
        try:
            from tools import perf_ledger

            result["run_id"] = perf_ledger.next_run_id()
            stamp["run_id"] = result["run_id"]
            lrec = perf_ledger.record_from_bench(dict(result, **stamp),
                                                 source="bench.py")
            lrec["run_id"] = result["run_id"]
            perf_ledger.append(lrec)
            result["perf_ledger"] = perf_ledger.ledger_path()
        except Exception:  # noqa: BLE001 — the ledger never sinks the bench
            pass
    result.update(stamp)
    snap_path = _write_telemetry_snapshot(stamp=stamp)
    if snap_path:
        result["telemetry_snapshot"] = snap_path
    _emit(result)
    failed = sorted(k for k in result if k == "error" or k.endswith("_error"))
    if failed:
        print(f"bench.py: failed phases: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
