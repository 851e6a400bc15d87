"""Telemetry layer: registry semantics, histogram quantiles, hot-path
instrumentation (engine, prefetch, kvstore, checkpoints) including under
fault injection, the atexit dump, and the profiler trace merge
(mxnet_tpu/telemetry.py; ISSUE 2 acceptance criteria)."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import engine, profiler, resilience, telemetry
from mxnet_tpu.io import NDArrayIter
from mxnet_tpu.io.io import PrefetchingIter

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..", ".."))


@pytest.fixture(autouse=True)
def _fresh_registry():
    """Each test runs against an enabled, empty registry and leaves the
    process-global state the way it found it."""
    was = telemetry.enabled()
    telemetry.reset()
    telemetry.enable()
    yield
    telemetry.reset()
    telemetry.enable(was)


# ---------------------------------------------------------------------------
# Registry semantics
# ---------------------------------------------------------------------------


def test_counter_gauge_get_or_create():
    c = telemetry.counter("t.c")
    c.inc()
    c.inc(4)
    assert telemetry.counter("t.c") is c
    assert c.value == 5
    g = telemetry.gauge("t.g")
    g.set(7)
    g.inc(2)
    g.dec()
    assert telemetry.gauge("t.g").value == 8
    with pytest.raises(TypeError):
        telemetry.gauge("t.c")  # kind mismatch is an error, not a shadow
    assert telemetry.get("t.missing") is None


def test_registry_thread_safety():
    c = telemetry.counter("t.threads")

    def work():
        for _ in range(1000):
            c.inc()
            telemetry.histogram("t.threads_h").record(1.0)

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 8000
    assert telemetry.histogram("t.threads_h").count == 8000


def test_histogram_quantiles_and_reservoir_bound():
    h = telemetry.Histogram("t.h", reservoir=256)
    for v in range(1, 1001):  # 1..1000 uniformly
        h.record(float(v))
    snap = h.snapshot()
    assert snap["count"] == 1000
    assert snap["min"] == 1.0 and snap["max"] == 1000.0
    assert abs(snap["avg"] - 500.5) < 1e-9
    assert len(h._reservoir) == 256  # bounded: O(reservoir), not O(samples)
    # reservoir quantiles are approximate; uniform data should land close
    assert 350 < snap["p50"] < 650
    assert snap["p95"] > 800
    assert snap["p99"] >= snap["p95"] >= snap["p50"]
    assert telemetry.Histogram("t.empty").snapshot()["p50"] is None
    # one sorted copy serves several quantiles (the fit hot-loop spelling)
    p50, p99 = h.quantiles(50, 99)
    assert p99 >= p50


def test_histogram_zero_reservoir_keeps_exact_stats():
    """MXNET_TELEMETRY_RESERVOIR=0 disables quantiles only — snapshot and
    the export paths must not crash on the empty reservoir."""
    h = telemetry.Histogram("t.zero", reservoir=0)
    telemetry._registry["t.zero"] = h  # as if created via histogram()
    for v in (1.0, 2.0, 3.0):
        h.record(v)
    snap = h.snapshot()
    assert snap["count"] == 3 and snap["sum"] == 6.0
    assert snap["min"] == 1.0 and snap["max"] == 3.0
    assert snap["p50"] is None and snap["p99"] is None
    assert h.percentile(50) is None
    assert "t.zero" in telemetry.dumps()  # full export path survives


def test_disabled_paths_record_nothing(tmp_path):
    telemetry.disable()
    telemetry.reset()
    mx.nd.save(str(tmp_path / "off.params"), {"a": mx.nd.ones((2, 2))})
    engine.wait_all()
    mx.nd.load(str(tmp_path / "off.params"))
    snap = telemetry.snapshot()
    # compile.* counters are unconditional by design (compile_cache.py):
    # the first nd.ones of a process is a jax trace + compile, which
    # compile.jax_* counts with the telemetry plane off
    gated = {k for k in snap["counters"] if not k.startswith("compile.")}
    assert gated == set() and snap["histograms"] == {}


# ---------------------------------------------------------------------------
# Instrumentation points
# ---------------------------------------------------------------------------


def test_engine_and_checkpoint_metrics(tmp_path):
    p = str(tmp_path / "ck.params")
    mx.nd.save(p, {"w": mx.nd.array(np.ones((16, 16), np.float32))})
    engine.wait_all()
    mx.nd.load(p)
    snap = telemetry.snapshot()
    assert snap["counters"]["engine.pushes"] >= snap["counters"]["engine.io_pushes"] >= 1
    lat = snap["histograms"]["engine.push_run_latency_us"]
    assert lat["count"] >= 1 and lat["sum"] > 0
    assert snap["counters"]["checkpoint.saves"] == 1
    assert snap["counters"]["checkpoint.save_bytes"] == 16 * 16 * 4
    assert snap["counters"]["checkpoint.load_bytes"] == 16 * 16 * 4
    assert snap["histograms"]["checkpoint.write_us"]["count"] == 1
    assert snap["histograms"]["checkpoint.load_us"]["count"] == 1
    assert snap["gauges"]["engine.queue_depth"] == 0  # drained


def test_retry_counter_fires_under_fault_injection(tmp_path):
    """A transient EIO on the checkpoint write burns one retry and lands in
    io.retries; the write still succeeds (resilience contract)."""
    p = str(tmp_path / "flaky.params")
    with resilience.fault_scope("point=write,path=*flaky.params,nth=1,error=EIO"):
        mx.nd.save(p, {"a": mx.nd.ones((4, 4))})
        engine.wait_all()
    assert telemetry.counter("io.retries").value >= 1
    assert "a" in mx.nd.load(p)


def test_retry_exhausted_counter(tmp_path):
    with resilience.fault_scope("point=write,path=*dead.params,times=inf,error=EIO"):
        with pytest.raises(OSError):
            resilience.retry_call(
                mx.ndarray.utils._write_file, str(tmp_path / "dead.params"),
                [], [], retries=1, backoff=0.001)
    assert telemetry.counter("io.retry_exhausted").value == 1
    assert telemetry.counter("io.retries").value == 1


def test_crc_fallback_counter(tmp_path):
    """A torn newest epoch falls back to the previous one AND counts the
    event — the resilience behavior is now measurable."""
    from mxnet_tpu import model

    prefix = str(tmp_path / "m")
    arg = {"w": mx.nd.ones((4, 4))}
    model.save_checkpoint(prefix, 1, None, arg, {})
    with resilience.fault_scope("point=write,path=*-0002.params,truncate=48,times=inf"):
        model.save_checkpoint(prefix, 2, None, arg, {})
        engine.wait_all()
    _, arg2, _, epoch = model.load_checkpoint(prefix, return_epoch=True)
    assert epoch == 1
    assert telemetry.counter("checkpoint.crc_fallback").value >= 1
    assert telemetry.counter("checkpoint.corrupt").value >= 1


def test_prefetch_wait_and_starvation_ratio():
    it = PrefetchingIter(
        NDArrayIter(np.ones((32, 8), np.float32), np.zeros(32), batch_size=8),
        use_engine=False)
    for _ in it:
        pass
    snap = telemetry.snapshot()
    assert snap["histograms"]["io.prefetch_wait_us"]["count"] >= 4
    assert snap["counters"]["io.prefetch_wait_us_total"] > 0
    ratio = snap["derived"]["io.starvation_ratio"]
    assert 0.0 < ratio <= 1.0


def test_kvstore_metrics():
    kv = mx.kv.create("local")
    kv.init("w", mx.nd.ones((8, 4)))
    kv.push("w", [mx.nd.ones((8, 4))])
    out = mx.nd.zeros((8, 4))
    kv.pull("w", out=[out])
    snap = telemetry.snapshot()
    assert snap["counters"]["kvstore.push_bytes"] == 8 * 4 * 4
    assert snap["counters"]["kvstore.pull_bytes"] == 8 * 4 * 4
    assert snap["histograms"]["kvstore.push_us"]["count"] == 1
    assert snap["histograms"]["kvstore.pull_us"]["count"] == 1


def test_fit_step_breakdown_and_speedometer_surface():
    """The acceptance-criteria run: a short fit() over a prefetching
    iterator records the per-step breakdown, engine/prefetch metrics, and
    hands step_stats (with p50/p99) to batch-end callbacks."""
    data = np.random.uniform(-1, 1, (48, 10)).astype(np.float32)
    label = (np.random.uniform(0, 1, 48) > 0.5).astype(np.float32)
    train = PrefetchingIter(
        NDArrayIter(data, label, batch_size=8), use_engine=False)
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    m = mx.mod.Module(net, context=mx.cpu())
    seen = []
    m.fit(train, num_epoch=2, batch_end_callback=seen.append,
          optimizer_params=(("learning_rate", 0.1),))
    assert seen and all(p.step_stats is not None for p in seen)
    last = seen[-1].step_stats
    for key in ("data_ms", "fwdbwd_ms", "update_ms", "sync_ms",
                "total_ms", "hist"):
        assert key in last
    # quantiles are on-demand (consumers sort only on their log ticks)
    p50, p99 = last["hist"].quantiles(50, 99)
    assert p99 >= p50 > 0
    snap = telemetry.snapshot()
    assert snap["histograms"]["step.total_us"]["count"] == 12
    assert snap["histograms"]["step.fwdbwd_us"]["sum"] > 0
    assert snap["histograms"]["io.prefetch_wait_us"]["count"] >= 12


def test_speedometer_logs_step_latency(caplog):
    import logging

    from mxnet_tpu.callback import Speedometer, _logger

    _logger()  # first-init (attaches handler, sets NOTSET) must happen
    # BEFORE caplog.at_level or it would clobber caplog's level

    h = telemetry.Histogram("t.speedo_us")
    h.record(1500.0)
    h.record(4000.0)

    class P:
        epoch, nbatch, eval_metric = 0, 1, None
        step_stats = {"hist": h}

    s = Speedometer(batch_size=2, frequent=1)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.callback"):
        s(P())  # init tick
        P.nbatch = 2
        s(P())
    assert any("step-p50" in r.message and "step-p99" in r.message
               for r in caplog.records)


# ---------------------------------------------------------------------------
# Export paths
# ---------------------------------------------------------------------------


def test_dumps_snapshot_roundtrip_and_table():
    telemetry.counter("x.count").inc(3)
    telemetry.histogram("x.lat_us").record(1500.0)
    snap = json.loads(telemetry.dumps())
    assert snap["counters"]["x.count"] == 3
    assert snap["histograms"]["x.lat_us"]["count"] == 1
    table = telemetry.dumps_table(snap)
    assert "Telemetry Statistics" in table
    assert "x.count" in table and "x.lat_us" in table
    assert "p99 (ms)" in table
    with pytest.raises(ValueError):
        telemetry.dumps_table(snap, sort_by="bogus")


def test_atomic_dump_file(tmp_path):
    telemetry.counter("y.count").inc()
    path = telemetry.dump(str(tmp_path / "telemetry.json"))
    doc = json.loads(open(path).read())
    assert doc["counters"]["y.count"] == 1
    assert not os.path.exists(path + ".tmp~")


def test_atexit_dump_via_env(tmp_path):
    """MXNET_TELEMETRY_DUMP writes a snapshot at interpreter exit."""
    out = str(tmp_path / "exit_snapshot.json")
    code = (
        "import mxnet_tpu as mx\n"
        "mx.nd.save(%r, {'a': mx.nd.ones((2, 2))})\n"
        "from mxnet_tpu import engine\n"
        "engine.wait_all()\n" % str(tmp_path / "z.params"))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu", MXNET_TELEMETRY="1",
               MXNET_TELEMETRY_DUMP=out)
    r = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    doc = json.loads(open(out).read())
    assert doc["counters"]["checkpoint.saves"] == 1
    assert doc["histograms"]["checkpoint.write_us"]["count"] == 1


def test_profiler_trace_merge(tmp_path):
    """telemetry counters ride profiler.dump() as chrome-trace 'C' events,
    on the same timeline as host scopes."""
    telemetry.counter("m.count").inc(2)
    telemetry.histogram("m.lat_us").record(10.0)
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, aggregate_stats=False)
    profiler.start()
    mx.nd.dot(mx.nd.ones((4, 4)), mx.nd.ones((4, 4)))
    profiler.stop()
    profiler.dump()
    doc = json.loads(open(fname).read())
    tele = {e["name"]: e for e in doc["traceEvents"]
            if e.get("cat") == "telemetry"}
    assert tele["telemetry/m.count"]["ph"] == "C"
    assert tele["telemetry/m.count"]["args"]["value"] == 2
    assert tele["telemetry/m.lat_us"]["args"]["count"] == 1
    assert any(e.get("cat") == "dispatch" for e in doc["traceEvents"])


def test_trace_events_not_merged_when_disabled(tmp_path):
    telemetry.counter("n.count").inc()
    telemetry.disable()
    fname = str(tmp_path / "prof.json")
    profiler.set_config(filename=fname, aggregate_stats=False)
    profiler.start()
    mx.nd.relu(mx.nd.ones((2, 2)))
    profiler.stop()
    profiler.dump()
    doc = json.loads(open(fname).read())
    assert not [e for e in doc["traceEvents"] if e.get("cat") == "telemetry"]


def test_log_summary_thread(caplog):
    import logging
    import time

    telemetry.counter("z.beat").inc()
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        t = telemetry.start_log_thread(interval=0.05)
        assert t is not None
        time.sleep(0.3)
        telemetry.stop_log_thread()
    assert any("telemetry summary" in r.message for r in caplog.records)


def test_report_tool_renders_snapshot(tmp_path):
    telemetry.counter("r.count").inc(9)
    telemetry.histogram("r.lat_us").record(2000.0)
    path = telemetry.dump(str(tmp_path / "snap.json"))
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         path],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO)
    assert r.returncode == 0, r.stderr[-2000:]
    assert "r.count" in r.stdout and "r.lat_us" in r.stdout
    assert "Telemetry Statistics" in r.stdout


# ---------------------------------------------------------------------------
# Snapshot schema stability + Prometheus text hardening (health/SLO PR)
# ---------------------------------------------------------------------------


def test_snapshot_schema_stability():
    """Pin the snapshot schema that tools/telemetry_report.py AND the SLO
    tracker both parse: the top-level keys and the histogram quantile
    fields. A refactor that renames any of these silently breaks every
    snapshot consumer — this test makes it loud."""
    telemetry.counter("schema.c").inc(3)
    telemetry.gauge("schema.g").set(1.5)
    telemetry.histogram("schema.h").record(123.0)
    from mxnet_tpu.compile_cache import CompileCache

    cache = CompileCache("schema_test")
    cache.get_or_build(("k",), lambda: (lambda: None))
    snap = telemetry.snapshot()
    # top-level contract
    for key in ("ts", "pid", "counters", "gauges", "histograms", "derived",
                "compile_caches"):
        assert key in snap, f"snapshot lost top-level key {key!r}"
    assert isinstance(snap["counters"], dict)
    assert isinstance(snap["gauges"], dict)
    assert isinstance(snap["histograms"], dict)
    # histogram field contract (telemetry_report columns, SLO quantile
    # stats, bench sidecar consumers)
    h = snap["histograms"]["schema.h"]
    assert set(h) == {"count", "sum", "min", "max", "avg",
                      "p50", "p95", "p99"}
    # the empty-histogram shape is part of the contract too
    telemetry.histogram("schema.empty")
    h0 = telemetry.snapshot()["histograms"]["schema.empty"]
    assert h0["count"] == 0 and h0["p99"] is None
    # per-name compile ledger rows carry hits/misses/compile_seconds
    row = snap["compile_caches"]["schema_test"]
    for key in ("hits", "misses", "compile_seconds"):
        assert key in row
    # round-trips through JSON (the dump/report path)
    json.loads(json.dumps(snap))


def _parse_prom(text):
    """Minimal text-exposition parser: every non-comment line must be
    `name[{labels}] value` with a float-parseable value."""
    samples = []
    for line in text.strip().splitlines():
        if not line or line.startswith("#"):
            continue
        name_part, _, value = line.rpartition(" ")
        assert name_part, f"malformed sample line: {line!r}"
        float(value)  # +Inf/-Inf/NaN all parse
        if "{" in name_part:
            assert name_part.endswith("}"), f"unclosed labels: {line!r}"
            name, _, labels = name_part.partition("{")
            assert '"' in labels  # values quoted
        else:
            name = name_part
        assert name.replace("_", "").replace(":", "").isalnum(), \
            f"bad metric name {name!r}"
        samples.append((name, value))
    return samples


def test_prom_text_escapes_malformed_names_and_values():
    """Metric names with exposition-hostile characters, non-finite
    values, and quantile-less histograms (reservoir size 0) must all
    render as parseable Prometheus text — the current-output-was-
    unescaped-interpolation satellite."""
    telemetry.counter('weird"metric\nwith\\stuff').inc(2)
    telemetry.gauge("g.inf").set(float("inf"))
    telemetry.gauge("g.nan").set(float("nan"))
    telemetry.gauge("g.string").set("not-a-number")  # must be SKIPPED
    h = telemetry.Histogram("h.noquant", reservoir=0)
    with telemetry._registry_lock:
        telemetry._registry["h.noquant"] = h
    h.record(5.0)  # count/sum exist, quantiles are None
    text = telemetry.prom_text(refresh_memory=False)
    samples = _parse_prom(text)
    names = {n for n, _ in samples}
    assert "mxnet_weird_metric_with_stuff" in names
    assert ("mxnet_g_inf", "+Inf") in samples
    assert any(n == "mxnet_g_nan" and v == "NaN" for n, v in samples)
    assert not any("g_string" in n for n in names), \
        "a string-valued gauge leaked into the exposition"
    # the quantile-less histogram emits sum/count but no `None` sample
    assert "None" not in text
    assert "mxnet_h_noquant_count" in names


def test_prom_label_escaping_helper():
    assert telemetry._prom_label('a"b') == 'a\\"b'
    assert telemetry._prom_label("a\\b") == "a\\\\b"
    assert telemetry._prom_label("a\nb") == "a\\nb"


# ---------------------------------------------------------------------------
# CompileCache.entry_memory / entry_collectives: the lazy AOT analysis that
# /memory, memory.census()'s worst_executable_temp_bytes and chip_smoke read
# ---------------------------------------------------------------------------


def test_entry_memory_of_a_donated_entry():
    import jax
    import jax.numpy as jnp

    from mxnet_tpu.compile_cache import CompileCache

    cache = CompileCache("entry_memory_test")
    fn = cache.get_or_build("k", lambda: jax.jit(
        lambda w, x: (w + x.sum(), jnp.tanh(x) @ x.T), donate_argnums=(0,)))
    w = jnp.ones((256, 128), jnp.float32)
    x = jnp.ones((64, 128), jnp.float32)
    assert cache.memory_stats() == []          # nothing analysed before a call
    w_bytes, x_bytes, out2_bytes = w.nbytes, x.nbytes, 64 * 64 * 4
    fn(w, x)
    assert cache.memory_stats() == []          # ... nor by the call itself
    mem = cache.entry_memory("k")
    assert mem["argument_bytes"] == w_bytes + x_bytes
    # both outputs, plus the result tuple's own few bytes
    assert 0 <= mem["output_bytes"] - (w_bytes + out2_bytes) <= 64
    assert mem["alias_bytes"] == w_bytes       # the donated weight, in place
    assert mem["temp_bytes"] >= x_bytes        # tanh(x) lives somewhere
    assert mem["peak_bytes"] == (mem["argument_bytes"] + mem["output_bytes"]
                                 + mem["temp_bytes"] - mem["alias_bytes"])
    assert cache.entry_memory("k") is mem      # memoised: one AOT pass
    assert cache.memory_stats() == [dict(mem, key="'k'")]
    assert cache.entry_collectives("k") == {}  # one device: nothing to send
    assert cache.entry_memory("never built") is None
    assert cache.entry_collectives("never built") is None


def test_entry_memory_failure_is_memoised():
    import jax.numpy as jnp

    from mxnet_tpu.compile_cache import CompileCache

    class NoLower:
        def __call__(self, x):
            return x

    class LowerRaises(NoLower):
        lowered = 0

        def lower(self, *args, **kwargs):
            LowerRaises.lowered += 1
            raise RuntimeError("cannot be lowered")

    cache = CompileCache("entry_memory_failure_test")
    cache.get_or_build("plain", NoLower)(jnp.ones(3))
    cache.get_or_build("raises", LowerRaises)(jnp.ones(3))
    # a callable that is not a jit has nothing to analyse, and costs nothing
    assert cache.entry_memory("plain") is None
    assert cache.entry_collectives("plain") is None
    # a lowering that fails is tried once: the AOT pass is expensive and a
    # second try would not go better
    assert cache.entry_memory("raises") is None
    assert cache.entry_memory("raises") is None
    assert cache.entry_collectives("raises") is None
    assert LowerRaises.lowered == 1
    assert cache.memory_stats(compute=True) == []


def test_entry_collectives_of_a_two_device_psum():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from mxnet_tpu.compile_cache import CompileCache

    if len(jax.devices()) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(jax.devices()[:2]), ("dp",))
    cache = CompileCache("entry_collectives_test")
    fn = cache.get_or_build("psum", lambda: jax.jit(jax.shard_map(
        lambda a: jax.lax.psum(a, "dp"), mesh=mesh, in_specs=P("dp"),
        out_specs=P())))
    a = jax.device_put(jnp.ones((1024, 4), jnp.float32),
                       NamedSharding(mesh, P("dp")))
    assert fn(a).shape == (512, 4)
    # scanned first by a plain memory scrape (no HLO text parsed), the
    # inventory is still there when asked for: bytes per participant
    mem = cache.entry_memory("psum")
    assert mem["argument_bytes"] == 512 * 4 * 4
    assert cache.entry_collectives("psum") == {
        "all-reduce": {"count": 1, "bytes": 512 * 4 * 4}}
    assert cache.entry_memory("psum") == mem
