"""Fleet health & SLO layer: rolling objectives, liveness/readiness,
stall watchdog with diagnostic capture, event journal, autoscale signal
(mxnet_tpu/health.py + mxnet_tpu/serving/health.py; ISSUE 11).

Covers:
* the event journal (bounded ring, disabled no-op, chrome-trace instant
  merge into profiler dumps);
* SLO spec parsing (units, relative `K*p50` thresholds, errors) and the
  tracker (violations, multi-window burn rate, budget exhaustion, the
  rate-kind warmup grace, /slo report);
* progress beacons + the stall watchdog (rolling-median threshold,
  one-shot diagnostic capture with stacks + worst-tick tree + telemetry
  snapshot + compile ledger, recovery re-arming);
* per-object liveness/readiness (engine warmup/watermark/stall/drain,
  batcher worker, close() deregistration) and the /healthz //readyz
  /slo //events HTTP endpoints;
* router drain semantics: unready engines stop receiving placements,
  live sessions finish, re-admission on recovery (journal transitions);
* fit-step and lazy-flush progress beacons;
* the autoscale signal (demand-driven desired_engines, change-driven
  callbacks);
* ``memory.headroom_bytes`` (capacity − census − worst warmed
  executable's temp bytes) and the default SLO row burning on negative
  projected headroom;
* the chaos acceptance run: one wedged engine in a 3-replica router —
  watchdog bundle, drain, zero drops on healthy engines, /readyz flip
  after recovery, SLO burn reported;
* zero overhead with MXNET_HEALTH off: no threads, no journal, no
  beacon traffic (subprocess pin).
"""
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import health, memory, serving, telemetry, tracing
from mxnet_tpu import parallel as par
from mxnet_tpu.models import TransformerLM, TransformerLMConfig
from mxnet_tpu.serving.generation import GenerationEngine, GenerationRouter

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", "..", ".."))
VOCAB = 32


@pytest.fixture(autouse=True)
def _fresh_health(monkeypatch):
    """Each test runs with health+telemetry enabled over empty state and
    leaves the process globals as found. The background monitor threads
    are parked (long watchdog interval, SLO thread off) so every sweep
    in these tests is an explicit, deterministic check_beacons()/
    evaluate() call."""
    monkeypatch.setenv("MXNET_HEALTH_WATCHDOG_S", "30")
    monkeypatch.setenv("MXNET_SLO_INTERVAL_S", "0")
    was_h, was_t = health.enabled(), telemetry.enabled()
    health.reset()
    telemetry.reset()
    telemetry.enable()
    health.enable()
    yield
    health.reset()
    telemetry.reset()
    health.enable(was_h)
    telemetry.enable(was_t)


def _model(max_len=32, n_layers=1, d_model=16, seed=0):
    mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
    cfg = TransformerLMConfig(vocab_size=VOCAB, d_model=d_model, n_heads=2,
                              d_ff=2 * d_model, n_layers=n_layers,
                              max_len=max_len, dtype="float32")
    lm = TransformerLM(cfg, mesh)
    return lm, lm.init_params(jax.random.PRNGKey(seed))


@pytest.fixture(scope="module")
def lm32():
    return _model()


def _prompts(n, lo=2, hi=8, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


def _counter(name):
    m = telemetry.get(name)
    return m.value if m is not None else 0


# ---------------------------------------------------------------------------
# Event journal
# ---------------------------------------------------------------------------


def test_journal_records_and_bounds():
    for i in range(600):
        health.event("spam", i=i)
    evs = health.events()
    assert len(evs) == 512              # MXNET_HEALTH_EVENTS default ring
    assert evs[-1]["i"] == 599          # newest kept, oldest dropped
    assert evs[0]["i"] == 599 - 511
    assert health.events(n=3)[-1]["kind"] == "spam"
    assert _counter("health.events") >= 600


def test_journal_disabled_is_noop():
    health.disable()
    try:
        assert health.event("nope") is None
        assert health.events() == []
    finally:
        health.enable()


def test_journal_merges_into_profiler_dump():
    from mxnet_tpu import profiler

    health.event("unit_test_marker", detail="x")
    doc = profiler.peek_doc()
    marks = [e for e in doc["traceEvents"]
             if e.get("name") == "health/unit_test_marker"]
    assert marks and marks[0]["ph"] == "i"
    assert marks[0]["args"]["detail"] == "x"


# ---------------------------------------------------------------------------
# SLO spec parsing + tracker
# ---------------------------------------------------------------------------


def test_slo_spec_parsing():
    objs = health.parse_spec(
        "serving.e2e_us:p99<250ms; compile.cache_misses:rate<=0;"
        "step.total_us:p99<8*p50; q.depth:value>=2;x.lat:avg<1.5s")
    assert [o.metric for o in objs] == \
        ["serving.e2e_us", "compile.cache_misses", "step.total_us",
         "q.depth", "x.lat"]
    assert objs[0].threshold == 250e3          # ms -> us
    assert objs[4].threshold == 1.5e6          # s -> us
    assert objs[2].rel_stat == "p50" and objs[2].threshold == 8.0
    assert objs[3].stat == "value" and objs[3].op == ">="
    # defaults exist and parse (incl. the headroom row)
    assert len(health.parse_spec("")) == 5
    keys = [o.metric for o in health.parse_spec("")]
    assert "memory.headroom_bytes" in keys
    for bad in ("nocolon", "m:p99<<1", "m:p99<abc", "m:weird<1"):
        with pytest.raises(ValueError):
            health.parse_spec(bad)


def test_slo_violation_burn_and_exhaustion():
    h = telemetry.histogram("t.lat_us")
    for _ in range(50):
        h.record(1000.0)                       # p99 = 1000us
    tr = health.SloTracker(
        objectives=health.parse_spec("t.lat_us:p99<2ms"),
        windows=(1.0, 10.0), budget=0.5, grace_s=0.0)
    now = 1000.0
    rep = tr.evaluate(now=now)
    (obj,) = rep["objectives"]
    assert obj["ok"] and rep["healthy"]
    assert telemetry.gauge("slo.t.lat_us_p99.ok").value == 1
    # violate: record a tail past the threshold
    for _ in range(200):
        h.record(9000.0)
    rep = tr.evaluate(now=now + 0.5)
    (obj,) = rep["objectives"]
    assert not obj["ok"] and not rep["healthy"]
    assert obj["value"] > obj["threshold"] == 2000.0
    # short window: 1 bad of 2 samples, budget 0.5 -> burn 1.0
    assert obj["burn_short"] == pytest.approx(1.0)
    assert telemetry.gauge("slo.t.lat_us_p99.ok").value == 0
    # keep violating until the LONG window burns the whole budget
    rep = tr.evaluate(now=now + 0.8)
    rep = tr.evaluate(now=now + 2.5)   # short window now all-bad
    (obj,) = rep["objectives"]
    assert obj["burn_short"] == pytest.approx(2.0)  # 100% bad / 0.5 budget
    assert rep["exhausted"] is (obj["burn_long"] >= 1.0)
    if rep["exhausted"]:
        assert not health.budget_ok() or health._tracker is not tr
        # the process-level readiness veto uses the process tracker
        health._tracker = tr
        ok, probes = health.readiness()
        assert not ok and not probes["slo.budget"]["ok"]
        health._tracker = None


def test_slo_rate_objective_and_grace():
    c = telemetry.counter("t.misses")
    tr = health.SloTracker(
        objectives=health.parse_spec("t.misses:rate<=0"),
        windows=(1.0, 10.0), budget=0.5, grace_s=5.0)
    now = 2000.0
    tr.started_at = now     # align grace with this test's fake clock
    rep = tr.evaluate(now=now)
    assert rep["objectives"][0]["ok"]          # no rate yet (vacuous)
    c.inc(3)
    rep = tr.evaluate(now=now + 0.5)
    assert rep["in_grace"] and rep["objectives"][0]["ok"], \
        "warmup compiles inside the grace window must not breach"
    tr.grace_s = 0.0
    c.inc(3)
    rep = tr.evaluate(now=now + 1.0)
    obj = rep["objectives"][0]
    assert not obj["ok"] and obj["value"] > 0


def test_slo_rate_sees_first_increment_of_new_counter():
    """A counter CREATED between evaluations (e.g. the first
    health.stalls ever) must register as a rate, not vanish because it
    had no previous sample — counters are monotonic from 0."""
    tr = health.SloTracker(
        objectives=health.parse_spec("t.fresh:rate<=0"),
        windows=(1.0, 10.0), budget=0.5, grace_s=0.0)
    tr.started_at = 0.0
    tr.evaluate(now=10.0)                      # t.fresh does not exist yet
    telemetry.counter("t.fresh").inc()         # first increment EVER
    rep = tr.evaluate(now=10.5)
    obj = rep["objectives"][0]
    assert obj["value"] == pytest.approx(2.0)  # 1 event / 0.5s
    assert not obj["ok"]


def test_slo_report_shape():
    rep = health.slo_report()
    assert rep["enabled"]
    assert {"budget", "windows_s", "objectives", "healthy",
            "stalls"} <= set(rep)
    health.disable()
    try:
        assert health.slo_report() == {"enabled": False}
    finally:
        health.enable()


# ---------------------------------------------------------------------------
# Beacons + watchdog + diagnostic capture
# ---------------------------------------------------------------------------


def test_beacon_median_gap_and_recovery_cycle(monkeypatch, tmp_path):
    monkeypatch.setenv("MXNET_HEALTH_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_HEALTH_STALL_FACTOR", "3")
    monkeypatch.setenv("MXNET_HEALTH_STALL_FLOOR_S", "0.05")
    b = health.beacon("t.progress")
    b.arm()
    for _ in range(5):
        time.sleep(0.01)
        b.touch()
    assert 0.0 < b.median_gap() < 0.05
    assert health.check_beacons() == []        # progressing: no stall
    stalls0 = _counter("health.stalls")
    time.sleep(0.12)                           # > max(3*median, floor)
    fired = health.check_beacons()
    assert [x.name for x in fired] == ["t.progress"]
    assert b.stalled and b.stall_count == 1
    assert _counter("health.stalls") - stalls0 == 1
    # one-shot: a second sweep while still stalled does not re-fire
    assert health.check_beacons() == []
    assert _counter("health.stalls") - stalls0 == 1
    # the bundle
    path = health.last_bundle()
    assert path and os.path.dirname(path) == str(tmp_path)
    doc = json.load(open(path))
    for key in ("threads", "telemetry", "compile_caches", "events",
                "beacon", "reason"):
        assert key in doc, f"bundle missing {key}"
    assert doc["reason"] == "stall:t.progress"
    assert doc["beacon"]["name"] == "t.progress"
    assert any("test_health" in "".join(frames)
               for frames in doc["threads"].values()), \
        "all-thread stacks must include this test's frame"
    assert os.path.exists(path + ".stacks.txt")      # faulthandler text
    # recovery: progress clears the stall and journals it
    assert b.touch() is True
    assert not b.stalled
    kinds = [e["kind"] for e in health.events()]
    assert "watchdog_stall" in kinds and "watchdog_recovered" in kinds
    # and the next silence can fire again (re-armed one-shot)
    time.sleep(0.12)
    assert health.check_beacons() == [b]


def test_idle_beacon_never_stalls(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_STALL_FLOOR_S", "0.01")
    b = health.beacon("t.idle")
    b.arm()
    b.touch()
    b.idle()                                   # nothing pending
    time.sleep(0.05)
    assert health.check_beacons() == []
    assert not b.stalled


def test_rearm_after_idle_restarts_silence_clock(monkeypatch):
    """An idle->armed transition must NOT inherit the stale last-progress
    stamp: an engine idle for an hour that just received work has been
    silent for zero seconds, not an hour (review finding)."""
    monkeypatch.setenv("MXNET_HEALTH_STALL_FLOOR_S", "0.05")
    b = health.beacon("t.rearm")
    b.arm()
    b.touch()
    b.idle()
    time.sleep(0.1)                            # long idle gap
    b.arm()                                    # new work arrives
    assert health.check_beacons() == [], \
        "idle time counted as stall silence after re-arm"
    assert b.silence() < 0.05


def test_beacon_rebinds_owner_on_name_reuse():
    """Names recur (lazy beacons key on recycled thread ids): get-or-
    create with a NEW owner must re-bind the weakref, or the dead-owner
    prune drops a beacon a live owner still touches."""
    class Owner:
        pass

    o1 = Owner()
    b = health.beacon("t.rebind", owner=o1)
    o2 = Owner()
    assert health.beacon("t.rebind", owner=o2) is b
    del o1
    assert b.owner is o2
    b.arm()
    assert health.check_beacons() == []        # not pruned: owner lives
    assert health.beacons().get("t.rebind") is b


# ---------------------------------------------------------------------------
# Liveness / readiness
# ---------------------------------------------------------------------------


def test_engine_readiness_lifecycle(lm32):
    lm, params = lm32
    eng = GenerationEngine(lm, params, max_slots=2, max_len=32,
                           buckets=(8,), start=False)
    assert eng.healthy()[0]
    ok, reason = eng.ready()
    assert not ok and "warmup" in reason       # nothing compiled yet
    eng.warm()
    assert eng.ready()[0]
    # the process registries see the same probes
    ok, probes = health.readiness()
    assert probes[eng.health_name]["ok"]
    eng._beacon.stalled = True                 # watchdog verdict
    assert not eng.ready()[0]
    eng._beacon.stalled = False
    eng.close()
    assert not eng.ready()[0]                  # draining
    # closed engines leave the registries (must not pin /readyz)
    ok, probes = health.readiness()
    assert eng.health_name not in probes


def test_engine_queue_watermark(monkeypatch, lm32):
    lm, params = lm32
    eng = GenerationEngine(lm, params, max_slots=1, max_len=32,
                           buckets=(8,), max_queue=10, start=False)
    eng.warm()
    for _ in range(9):                         # 9/10 >= 0.8 watermark
        eng.submit([1, 2], max_new_tokens=1)
    ok, reason = eng.ready()
    assert not ok and "watermark" in reason
    for _ in range(32):
        eng._tick_once()
        if not eng._has_work():
            break
    assert eng.ready()[0]
    eng.close()


def test_batcher_probes_and_close_deregisters():
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    pred = serving.Predictor(
        net, {"fc_weight": mx.nd.ones((2, 4)), "fc_bias": mx.nd.zeros(2)},
        data_shapes=[("data", (1, 4))], buckets=(2, 4))
    assert not pred._warmed
    ok, probes = health.readiness()
    assert not probes[pred.health_name]["ok"]  # warmup not run
    # traffic-compiled counts as warmed (review finding): a deployment
    # that skipped warmup() but serves fine must not 503 forever
    pred.predict(mx.nd.ones((1, 4)))
    ok, probes = health.readiness()
    assert probes[pred.health_name]["ok"]
    pred._execs.clear()                        # back to cold for the rest
    with serving.DynamicBatcher(pred) as srv:
        name = srv.health_name
        assert srv.healthy()[0]
        assert not srv.ready()[0]              # predictor not warmed
        serving.warmup(pred)
        assert srv.ready()[0] and pred._warmed
        ok, probes = health.readiness()
        assert probes[pred.health_name]["ok"] and probes[name]["ok"]
    ok, probes = health.readiness()
    assert name not in probes                  # close() deregistered


def test_http_health_endpoints(lm32):
    lm, params = lm32
    eng = GenerationEngine(lm, params, max_slots=1, max_len=32,
                           buckets=(8,), start=False)
    eng.warm()
    server = telemetry.start_http_server(port=0)
    port = server.server_address[1]
    try:
        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}", timeout=10) as r:
                    return r.status, json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode())

        code, body = get("/healthz")
        assert code == 200 and body["ok"] and body["health_enabled"]
        code, body = get("/readyz")
        assert code == 200 and body["ok"]
        assert body["probes"][eng.health_name]["ok"]
        eng._beacon.stalled = True
        code, body = get("/readyz")
        assert code == 503 and not body["ok"]
        assert not body["probes"][eng.health_name]["ok"]
        eng._beacon.stalled = False
        code, body = get("/slo")
        assert code == 200 and body["enabled"] and "objectives" in body
        health.event("endpoint_marker", x=1)
        code, body = get("/events")
        assert code == 200
        assert any(e["kind"] == "endpoint_marker" for e in body)
    finally:
        telemetry.stop_http_server()
        eng.close()


# ---------------------------------------------------------------------------
# Router drain / re-admit
# ---------------------------------------------------------------------------


def test_router_drains_unready_and_readmits(lm32):
    lm, params = lm32
    engines = [GenerationEngine(lm, params, max_slots=4, max_len=32,
                                buckets=(8,)) for _ in range(3)]
    router = GenerationRouter(engines)
    serving.warmup(router)
    engines[0]._beacon.stalled = True          # watchdog verdict
    streams = [router.submit(p, max_new_tokens=2)
               for p in _prompts(12, seed=3)]
    for s in streams:
        assert len(s.result(timeout=60)) == 2
    assert engines[0].sessions_submitted == 0, \
        "a drained engine received placements"
    assert sum(e.sessions_submitted for e in engines) == 12
    kinds = [e["kind"] for e in health.events()]
    assert "engine_drain" in kinds
    assert telemetry.gauge("health.ready_engines").value == 2
    # recovery re-admits
    engines[0]._beacon.stalled = False
    streams = [router.submit(p, max_new_tokens=2)
               for p in _prompts(9, seed=4)]
    for s in streams:
        s.result(timeout=60)
    assert engines[0].sessions_submitted > 0
    assert "engine_undrain" in [e["kind"] for e in health.events()]
    router.close()


def test_router_all_unready_falls_back(lm32):
    lm, params = lm32
    engines = [GenerationEngine(lm, params, max_slots=2, max_len=32,
                                buckets=(8,)) for _ in range(2)]
    router = GenerationRouter(engines)
    serving.warmup(router)
    for e in engines:
        e._beacon.stalled = True
    s = router.submit([1, 2], max_new_tokens=2)   # availability wins
    assert len(s.result(timeout=60)) == 2
    assert "fleet_all_unready" in [e["kind"] for e in health.events()]
    router.close()


# ---------------------------------------------------------------------------
# fit-step and lazy-flush beacons
# ---------------------------------------------------------------------------


def test_fit_step_beacon():
    from mxnet_tpu.io import NDArrayIter

    data = np.random.uniform(-1, 1, (32, 6)).astype(np.float32)
    label = (np.random.uniform(0, 1, 32) > 0.5).astype(np.float32)
    train = NDArrayIter(data, label, batch_size=8)
    x = mx.sym.Variable("data")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(x, num_hidden=2, name="fc"), name="softmax")
    m = mx.mod.Module(net, context=mx.cpu())
    m.fit(train, num_epoch=2, optimizer_params=(("learning_rate", 0.1),))
    b = health.beacons().get("fit.step")
    assert b is not None
    assert b.touches == 8                      # 2 epochs x 4 steps
    assert not b.active, "fit must idle its beacon on exit"
    assert b.median_gap() is not None


def test_lazy_flush_beacon_and_events(monkeypatch):
    from mxnet_tpu.lazy import graph as lazy_graph

    monkeypatch.setenv("MXNET_LAZY", "1")
    lazy_graph._tls.graph = None
    g = lazy_graph.graph_for_thread()
    a = mx.nd.array(np.ones((4,), np.float32))
    b = a + 1.0
    c = b * 2.0
    beacon = g._flush_beacon()
    assert beacon.active, "a pending segment must arm the flush beacon"
    np.testing.assert_allclose(c.asnumpy(), 4.0)   # barrier -> flush
    assert beacon.touches >= 1
    assert not beacon.active
    mx.nd.waitall()


# ---------------------------------------------------------------------------
# Autoscale signal
# ---------------------------------------------------------------------------


class _FakeEngine:
    def __init__(self, live, queued, slots=4):
        self.live_slots = live
        self.queue_depth = queued
        self.max_slots = slots


def test_autoscale_signal_and_callbacks(monkeypatch):
    monkeypatch.setenv("MXNET_HEALTH_TARGET_FILL", "0.75")
    calls = []
    health.on_autoscale(lambda desired, info: calls.append((desired, info)))
    # demand 2 over one 4-slot engine at 0.75 fill -> 1 engine
    assert health.autoscale_signal([_FakeEngine(2, 0)]) == 1
    assert telemetry.gauge("health.desired_engines").value == 1
    assert calls and calls[-1][0] == 1
    # demand 11 -> ceil(11/3) = 4 engines
    assert health.autoscale_signal(
        [_FakeEngine(4, 7)]) == 4
    assert calls[-1][0] == 4 and calls[-1][1]["demand"] == 11
    n_calls = len(calls)
    health.autoscale_signal([_FakeEngine(4, 7)])   # unchanged: no callback
    assert len(calls) == n_calls
    assert [e["kind"] for e in health.events()].count("autoscale") >= 2


def test_autoscale_from_registered_fleet(lm32):
    lm, params = lm32
    engines = [GenerationEngine(lm, params, max_slots=2, max_len=32,
                                buckets=(8,), start=False)
               for _ in range(2)]
    router = GenerationRouter(engines)     # registers itself as a fleet
    assert health.autoscale_signal() == 1  # idle fleet wants the minimum
    assert health.slo_report()["desired_engines"] == 1
    router.close()


# ---------------------------------------------------------------------------
# memory headroom + the default SLO row
# ---------------------------------------------------------------------------


def test_memory_headroom_and_negative_headroom_slo(monkeypatch):
    snap = memory.census()
    # CPU devices report no bytes_limit: headroom stays unpublished
    # unless the capacity override is set
    if "capacity_bytes" not in snap:
        assert telemetry.get("memory.headroom_bytes") is None
    monkeypatch.setenv("MXNET_DEVICE_HBM_BYTES", str(1 << 40))
    snap = memory.census()
    assert snap["capacity_bytes"] == 1 << 40
    assert "worst_executable_temp_bytes" in snap
    assert snap["headroom_bytes"] > 0                  # 1 TiB covers a test
    assert telemetry.get("memory.headroom_bytes").value == \
        snap["headroom_bytes"]

    # negative projected headroom burns the default SLO row
    monkeypatch.setenv("MXNET_DEVICE_HBM_BYTES", "1")
    snap = memory.census()
    assert snap["headroom_bytes"] < 0
    was = health.enabled()
    health.reset()
    health.enable()
    try:
        tr = health.tracker()
        rep = tr.evaluate()
        obj = next(o for o in rep["objectives"]
                   if o["spec"].startswith("memory.headroom_bytes:"))
        assert not obj["ok"]
        # and with a sane capacity the same row recovers
        monkeypatch.setenv("MXNET_DEVICE_HBM_BYTES", str(1 << 40))
        memory.census()
        rep = tr.evaluate()
        obj = next(o for o in rep["objectives"]
                   if o["spec"].startswith("memory.headroom_bytes:"))
        assert obj["ok"]
    finally:
        health.reset()
        health.enable(was)


def test_report_tool_health_line(tmp_path, capsys):
    telemetry.gauge("slo.t.lat_us_p99.ok").set(0)
    telemetry.gauge("slo.t.lat_us_p99.burn_short").set(3.5)
    telemetry.gauge("slo.ok.obj.ok").set(1)
    telemetry.counter("health.stalls").inc(2)
    telemetry.counter("health.events").inc(7)
    telemetry.gauge("health.desired_engines").set(4)
    path = tmp_path / "snap.json"
    path.write_text(telemetry.dumps())
    from tools import telemetry_report

    assert telemetry_report.main([str(path)]) == 0
    out = capsys.readouterr().out
    assert "health:" in out
    assert "VIOLATED: t.lat_us_p99 (burn 3.5x)" in out
    assert "stalls 2" in out and "autoscale wants 4" in out


# ---------------------------------------------------------------------------
# Chaos acceptance: wedged engine in a 3-replica fleet
# ---------------------------------------------------------------------------


def test_chaos_wedged_engine_acceptance(monkeypatch, tmp_path):
    """One engine artificially wedged mid-decode: the watchdog detects
    the stall and writes a diagnostic bundle (stacks + worst-tick tree +
    snapshot), the router drains the wedged engine while every session
    on the healthy engines completes with zero drops, /readyz flips back
    after recovery, and the SLO tracker reports the burn."""
    monkeypatch.setenv("MXNET_HEALTH_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_HEALTH_STALL_FLOOR_S", "0.25")
    monkeypatch.setenv("MXNET_HEALTH_STALL_FACTOR", "4")
    monkeypatch.setenv("MXNET_SLO_SPEC", "health.stalls:rate<=0")
    monkeypatch.setenv("MXNET_SLO_WINDOWS", "5,30")
    monkeypatch.setenv("MXNET_SLO_BUDGET", "1.0")
    monkeypatch.setenv("MXNET_SLO_GRACE_S", "0")
    was_tracing = tracing.enabled()
    tracing.enable()
    lm, params = _model()
    engines = [GenerationEngine(lm, params, max_slots=4, max_len=32,
                                buckets=(8, 16)) for _ in range(3)]
    router = GenerationRouter(engines)
    serving.warmup(router)
    telemetry.counter("health.stalls")         # rate baseline exists
    tr = health.tracker()
    tr.evaluate()

    # wedge engine 0: its fused decode blocks until released
    release = threading.Event()
    orig = engines[0]._decode_fn

    def wedged():
        fn = orig()

        def blocked(*a, **k):
            release.wait(30)
            return fn(*a, **k)

        return blocked

    engines[0]._decode_fn = wedged
    victim = engines[0].submit([1, 2, 3], max_new_tokens=3)

    server = telemetry.start_http_server(port=0)
    port = server.server_address[1]

    def readyz():
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/readyz", timeout=10) as r:
                return r.status
        except urllib.error.HTTPError as e:
            return e.code

    try:
        # 1. watchdog detects the stall (deterministic sweeps)
        deadline = time.monotonic() + 15
        while not engines[0]._beacon.stalled \
                and time.monotonic() < deadline:
            health.check_beacons()
            time.sleep(0.05)
        assert engines[0]._beacon.stalled, "watchdog never saw the wedge"
        assert _counter("health.stalls") >= 1

        # 2. the diagnostic bundle exists and carries the forensics
        bundle = health.last_bundle()
        assert bundle and os.path.exists(bundle)
        doc = json.load(open(bundle))
        assert "worst_tick" in doc and "worst_step" in doc
        assert doc["telemetry"]["counters"]["serving.generation.sessions"] >= 1
        assert any("blocked" in "".join(frames)
                   for frames in doc["threads"].values()), \
            "the bundle's stacks must show the wedged decode frame"

        # 3. concurrent traffic: the router drains the wedged engine,
        # every session on healthy engines completes, zero drops
        streams = [router.submit(p, max_new_tokens=3)
                   for p in _prompts(24, seed=7)]
        results = [s.result(timeout=60) for s in streams]
        assert all(len(r) == 3 for r in results)
        assert engines[0].sessions_submitted == 1, \
            "the router kept placing on the wedged engine"
        assert "engine_drain" in [e["kind"] for e in health.events()]

        # 4. not ready while wedged, and the SLO tracker reports the burn
        assert readyz() == 503
        rep = tr.evaluate()
        (obj,) = rep["objectives"]
        assert not obj["ok"] and obj["burn_short"] > 0

        # 5. recovery: release the wedge; the victim finishes, the
        # beacon recovers, the router re-admits, /readyz flips back
        release.set()
        assert len(victim.result(timeout=60)) == 3
        deadline = time.monotonic() + 15
        while (engines[0]._beacon.stalled or readyz() != 200) \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        assert not engines[0]._beacon.stalled
        assert readyz() == 200
        assert "watchdog_recovered" in [e["kind"] for e in health.events()]
        s = router.submit([1, 2], max_new_tokens=2)
        assert len(s.result(timeout=60)) == 2
        assert engines[0].ready()[0]
    finally:
        release.set()
        telemetry.stop_http_server()
        router.close()
        tracing.enable(was_tracing)
        tracing.reset()


# ---------------------------------------------------------------------------
# Zero overhead when off
# ---------------------------------------------------------------------------


def test_disabled_zero_overhead_subprocess():
    """With MXNET_HEALTH unset (a fresh interpreter): no monitor thread
    is ever created, the journal stays empty, engine/fit hot paths never
    touch a beacon, and no health.* metric exists — the hot-path cost is
    exactly one attribute read per site."""
    code = r"""
import threading, numpy as np, jax
import mxnet_tpu as mx
from mxnet_tpu import health, telemetry
from mxnet_tpu import parallel as par
from mxnet_tpu.models import TransformerLM, TransformerLMConfig
from mxnet_tpu.serving.generation import GenerationEngine

assert not health.enabled()
mesh = par.create_mesh(devices=jax.devices()[:1], dp=1)
cfg = TransformerLMConfig(vocab_size=16, d_model=16, n_heads=2, d_ff=32,
                          n_layers=1, max_len=16, dtype="float32")
lm = TransformerLM(cfg, mesh)
params = lm.init_params(jax.random.PRNGKey(0))
eng = GenerationEngine(lm, params, max_slots=2, max_len=16, buckets=(8,))
out = eng.generate([1, 2, 3], max_new_tokens=3)
assert len(out) == 3
eng.close()
names = [t.name for t in threading.enumerate()]
assert not any("health" in n for n in names), names
assert health.events() == []
assert eng._beacon.touches == 0 and not eng._beacon.active
assert telemetry.get("health.stalls") is None
assert telemetry.get("health.events") is None
# probes are opt-in: with the layer off, /healthz//readyz never 503
assert health.liveness() == (True, {})
assert health.readiness() == (True, {})
print("ZERO_OVERHEAD_OK")
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXNET_HEALTH", None)
    env.pop("MXNET_TELEMETRY", None)
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, r.stderr[-3000:]
    assert "ZERO_OVERHEAD_OK" in r.stdout
