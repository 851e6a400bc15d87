"""program_spans.py: innermost-span attribution (a gap cut at span boundaries),
self time and `uncovered` on hand-made intervals; the same on the device plane of the trace recorded on the
v5e (`recorded_v5e.xplane.pb`) with synthetic `mx:` intervals laid over its
host sleeps; and the four readers of PR 23 on a trace that holds no `mx:`
span (a program from before that PR), where each reports nothing."""
import os
import sys
import types

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))

import harness  # noqa: E402
import program_spans as ps  # noqa: E402
import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")
READERS = ("dispatch_exposed_ms_per_step", "loop_exposed_ms_per_step",
           "tick_host_exposed_ms", "retraces_in_window")


def nested():
    """One thread: `call` 0-10 holding `gather` 1-3 and `dispatch` 3-8 (which
    holds `jit` 4-5); another thread: `prefetch` 6-7.5."""
    return ps.ProgramSpans([
        ("call", 0.0, 10.0, 0), ("gather", 1.0, 3.0, 0),
        ("dispatch", 3.0, 8.0, 0), ("jit", 4.0, 5.0, 0),
        ("prefetch", 6.0, 7.5, 1)])


def test_a_gap_is_cut_at_span_boundaries_and_goes_to_the_innermost():
    sp = nested()
    approx = pytest.approx
    assert sp.split_gap(4.2, 4.8) == {"jit": approx(0.6)}
    # a gap that outlasts the inner span is cut where it ends
    assert sp.split_gap(3.5, 5.5) == {"dispatch": approx(1.0),
                                      "jit": approx(1.0)}
    assert sp.split_gap(0.5, 3.5) == {"call": approx(0.5),
                                      "gather": approx(2.0),
                                      "dispatch": approx(0.5)}
    assert sp.split_gap(8.5, 9.5) == {"call": approx(1.0)}
    # what no span covers is uncovered, with its size
    assert sp.split_gap(9.0, 12.0) == {"call": approx(1.0),
                                       ps.UNCOVERED: approx(2.0)}
    assert sp.split_gap(11.0, 12.0) == {ps.UNCOVERED: approx(1.0)}
    # across threads the shorter span is the more specific one
    assert sp.split_gap(5.5, 8.0) == {"dispatch": approx(1.0),
                                      "prefetch": approx(1.5)}
    for s, e in ((0.0, 10.0), (-3.0, 14.0), (4.5, 4.6)):
        assert sum(sp.split_gap(s, e).values()) == approx(e - s)


def test_self_time_is_less_the_direct_children():
    got = nested().seconds()
    assert got["call"] == (1, pytest.approx(10.0), pytest.approx(3.0))
    assert got["dispatch"] == (1, pytest.approx(5.0), pytest.approx(4.0))
    assert got["gather"] == (1, pytest.approx(2.0), pytest.approx(2.0))
    assert got["jit"] == (1, pytest.approx(1.0), pytest.approx(1.0))
    assert got["prefetch"] == (1, pytest.approx(1.5), pytest.approx(1.5))
    # repeated names add up; spans that overlap without nesting on one line
    # (two threads the profiler put on one line) are both kept whole
    sp = ps.ProgramSpans([("a", 0.0, 2.0, 0), ("a", 3.0, 4.0, 0),
                          ("b", 3.5, 5.0, 0)])
    assert sp.seconds() == {"a": (2, pytest.approx(3.0), pytest.approx(3.0)),
                            "b": (1, pytest.approx(1.5), pytest.approx(1.5))}
    assert sp.count("a") == 2 and sp.count("a", 2.5, 9.0) == 1


def synthetic_trace():
    """Two steps of 10 ms: busy 0-6 ms, idle 6-10 ms while the bench
    annotation `record_forward` covers 6-9.5 ms; and a 10 us turn-around."""
    ops, mods, ann = [], [], []
    for k in range(2):
        t = 0.010 * k
        ops += [("fusion.1", t, t + 0.003), ("fusion.2", t + 0.00301, t + 0.006)]
        mods.append(("jit_step(1)", t, t + 0.006))
        ann.append(("record_forward", t + 0.006, t + 0.0095))
    ops.append(("fusion.1", 0.020, 0.021))
    mods.append(("jit_step(1)", 0.020, 0.021))
    return tr.ReducedTrace([tr.DeviceTrace(0, ops, mods)], ann, "gluon-loop")


def test_idle_seconds_by_bench_label_and_program_span():
    trace = synthetic_trace()
    # the program's spans under the first gap (6-10 ms) only
    sp = ps.ProgramSpans([("cached_op.call", 0.0059, 0.0098, 0),
                          ("cached_op.gather", 0.0060, 0.0090, 0)])
    idle = sp.idle_by_span(trace)
    assert idle[("record_forward", "cached_op.gather")] == pytest.approx(0.003)
    assert idle[("record_forward", "cached_op.call")] == pytest.approx(0.0008)
    assert idle[("record_forward", ps.UNCOVERED)] == pytest.approx(0.0042)
    assert idle[(ps.BETWEEN_OPS, ps.BETWEEN_OPS)] == pytest.approx(2e-5)
    assert sum(idle.values()) == pytest.approx(trace.idle_s(0))
    assert sp.exposed_s(trace, ("cached_op.",)) == pytest.approx(0.0038)
    assert sp.exposed_s(trace, ("step.", "trainer.")) == 0
    lines = sp.table(trace)
    assert "cached_op.gather 0.0030" in lines[0]
    assert "uncovered 0.0042" in lines[0]
    label = [l for l in lines if l.startswith("bench label record_forward")]
    assert label and "47.5% under a named span" in label[0]
    assert "uncovered 0.0042s" in label[0]


def test_synthetic_spans_over_the_recorded_device_plane():
    """The recorded trace idles while the host sleeps 4 ms inside
    `bench:sleep`. Laying a synthetic `step.sync` over each sleep and a
    `step` over each round attributes that idle time to `step.sync`, the
    innermost, and its edges to `step`; without the inner spans all of it
    falls to `step`; a sleep that no span covers is `uncovered`."""
    trace = tr.load(RECORDED, n_devices=1, host_label="fixture-loop")
    sleeps = [(a, b) for name, a, b in trace.annotations if name == "sleep"]
    assert len(sleeps) == 6
    slept = dict(trace.idle_by_label(0))["sleep"]
    spans = []
    for a, b in sleeps[:5]:                 # the sixth sleep stays bare
        spans += [("step", a - 0.002, b + 0.0005, 0),
                  ("step.sync", a - 1e-5, b + 1e-5, 0)]
    sp = ps.ProgramSpans(spans)
    idle = sp.idle_by_span(trace)
    assert sum(idle.values()) == pytest.approx(trace.idle_s(0))
    by_sleep = {name: v for (label, name), v in idle.items()
                if label == "sleep"}
    assert sum(by_sleep.values()) == pytest.approx(slept)
    assert by_sleep["step.sync"] > 0.7 * slept
    # the bare sleep, if the device was idle under it, is uncovered
    assert set(by_sleep) <= {"step.sync", "step", ps.UNCOVERED}
    assert sp.exposed_s(trace, ("step.sync",)) == \
        pytest.approx(by_sleep["step.sync"])
    outer_only = ps.ProgramSpans([s for s in spans if s[0] == "step"])
    by_sleep = {name: v for (label, name), v
                in outer_only.idle_by_span(trace).items() if label == "sleep"}
    assert by_sleep["step"] > 0.7 * slept and "step.sync" not in by_sleep
    assert outer_only.seconds()["step"][0] == 5


def fake_run(path):
    return types.SimpleNamespace(tracer=types.SimpleNamespace(
        xplane_path=lambda: path))


def test_a_trace_without_mx_spans_reads_as_nothing(monkeypatch, capsys):
    """The recorded trace is from before PR 23: `load` gives None, every
    reader that rests on `mx:` spans reports nothing (and does not raise),
    and `for_run` loads the file once a process."""
    assert ps.load(RECORDED) is None
    trace = tr.load(RECORDED, n_devices=1, host_label="fixture-loop")
    obs = {"trace": trace, "traced_step_s": 0.01, "window": (0.0, 1.0)}
    run = fake_run(RECORDED)
    monkeypatch.setattr(ps, "_loaded", {})
    loads = []
    real = ps.load
    monkeypatch.setattr(ps, "load", lambda p: loads.append(p) or real(p))
    for name in READERS[:3]:
        assert harness.load_plugin("layer_metrics", name).read(obs, run) \
            is None
    assert loads == [RECORDED]
    assert capsys.readouterr().out.count("[spans]") == 1
    # no trace at all (a run that was not traced): nothing, and no load
    assert ps.for_run({}, fake_run(None)) is None
    assert ps.main([RECORDED]) == 1


def test_retraces_reader_counts_the_programs_own_ledger(monkeypatch):
    from mxnet_tpu import compile_cache

    reader = harness.load_plugin("layer_metrics", "retraces_in_window")
    obs = {"window": (10.0, 20.0)}
    events = [(9.0, "jaxpr_trace", 0.1), (12.0, "jaxpr_trace", 0.2),
              (13.0, "backend_compile", 1.0), (19.5, "jaxpr_trace", 0.1),
              (21.0, "jaxpr_trace", 0.1)]
    monkeypatch.setattr(compile_cache, "jax_events", lambda: events)
    assert reader.read(obs, None) == 2
    monkeypatch.setattr(compile_cache, "jax_events", lambda: [])
    assert reader.read(obs, None) == 0
    assert reader.read({}, None) is None                # a serving cell
    # a program from before PR 23 keeps no such ledger
    monkeypatch.delattr(compile_cache, "jax_events")
    assert reader.read(obs, None) is None


def test_readers_divide_by_the_steps_and_ticks_of_the_window(monkeypatch):
    trace = synthetic_trace()           # window 21 ms, idle 8.02 ms
    spans = ps.ProgramSpans([
        ("cached_op.gather", 0.0060, 0.0100, 0),        # first gap: 4 ms
        ("step.sync", 0.0160, 0.0200, 0),               # second gap: 4 ms
        ("generation.tick", 0.0000, 0.0100, 1),
        ("generation.tick", 0.0100, 0.0205, 1),
        ("generation.commit.fetch", 0.0161, 0.0199, 1)])
    run = fake_run("a-path")
    monkeypatch.setattr(ps, "_loaded", {"a-path": spans})
    obs = {"trace": trace, "traced_step_s": 0.0105}     # 2 steps
    read = lambda name: harness.load_plugin(  # noqa: E731
        "layer_metrics", name).read(obs, run)
    assert read("dispatch_exposed_ms_per_step") == pytest.approx(2.0)
    # of the second gap (16-20 ms) the shorter commit.fetch takes 16.1-19.9,
    # step.sync the 0.2 ms around it
    assert read("loop_exposed_ms_per_step") == pytest.approx(0.1)
    # 3.8 ms under generation.* over two ticks (the first gap is gather's,
    # which is shorter than the tick around it)
    assert read("tick_host_exposed_ms") == pytest.approx(1.9)
    obs["traced_step_s"] = None         # a window too short to hold two steps
    assert read("dispatch_exposed_ms_per_step") is None
    assert read("loop_exposed_ms_per_step") is None
    # no tick in the window: nothing to divide by
    monkeypatch.setattr(ps, "_loaded", {"a-path": ps.ProgramSpans(
        [s for s in spans.spans if s[0] != "generation.tick"])})
    assert read("tick_host_exposed_ms") is None
