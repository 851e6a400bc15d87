"""trace_reduce.py: the interval arithmetic on hand-made intervals, and the
whole reduction on a small trace recorded on the v5e
(`recorded_v5e.xplane.pb`, made by record_fixture.py: six executions of
`jit_fixture_matmul`, three of `jit_fixture_add`, a 4 ms host sleep after
each round inside the annotation `bench:sleep`)."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import trace_reduce as tr  # noqa: E402

RECORDED = os.path.join(HERE, "recorded_v5e.xplane.pb")


def test_merge_total_subtract_gaps():
    m = tr.merge([(5, 7), (0, 2), (1, 3), (7, 8), (10, 10)])
    assert m == [(0, 3), (5, 8)]
    assert tr.total(m) == 6
    assert tr.subtract([(0, 10)], m) == [(3, 5), (8, 10)]
    assert tr.subtract(m, [(1, 6)]) == [(0, 1), (6, 8)]
    assert tr.subtract(m, []) == m
    assert tr.gaps(m, 0, 9) == [(3, 5), (8, 9)]


def synthetic():
    """Two steps of 10 ms: compute 0-6, an all-reduce 5-8 (1 ms hidden under
    compute, 2 ms exposed), idle 8-10 while the host is in `data`."""
    ops, mods = [], []
    for k in range(2):
        t = 0.010 * k
        ops += [("fusion.1", t, t + 0.004), ("convolution.2", t + 0.004,
                                             t + 0.006),
                ("all-reduce.3", t + 0.005, t + 0.008)]
        mods.append(("jit_step(123)", t, t + 0.008))
    ann = [("data", 0.0081, 0.0099), ("data", 0.0181, 0.0195)]
    return tr.ReducedTrace([tr.DeviceTrace(0, ops, mods)], ann, "the-loop")


def test_busy_idle_and_collectives_on_hand_made_steps():
    t = synthetic()
    assert t.window_s == pytest.approx(0.018)
    assert t.busy_s == pytest.approx(0.016)
    assert t.idle_s(0) == pytest.approx(0.002)
    assert t.collective_s(0) == pytest.approx(0.006)
    assert t.exposed_collective_s(0) == pytest.approx(0.004)
    assert len(t.launches(0)) == 2
    assert t.module_durations(r"^jit_step") == pytest.approx([0.008, 0.008])
    assert dict(t.idle_by_label(0)) == pytest.approx({"data": 0.002})
    ops = dict(t.op_seconds(0))
    assert ops["all-reduce.3"] == pytest.approx(0.006)
    assert ops["fusion.1"] == pytest.approx(0.008)
    b = t.breakdown()
    assert b["device_ops"][0][0] == "fusion.1"
    assert b["idle_gaps"] == [["data", pytest.approx(0.002)]]


def test_uncovered_gap_takes_the_host_label():
    t = synthetic()
    t.annotations = []
    assert dict(t.idle_by_label(0)) == pytest.approx({"the-loop": 0.002})


def test_busy_is_averaged_over_the_chips_used():
    a = tr.DeviceTrace(0, [("f", 0.0, 0.010)], [])
    b = tr.DeviceTrace(1, [("f", 0.0, 0.004)], [])
    t = tr.ReducedTrace([b, a], [])
    assert t.window_s == pytest.approx(0.010)
    assert t.busy_s == pytest.approx(0.007)
    assert t.devices[0].index == 0


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(RECORDED):
        pytest.fail("recorded_v5e.xplane.pb is missing beside this test")
    return tr.load(RECORDED, n_devices=1, host_label="host")


def test_recorded_trace_programs(recorded):
    t = recorded
    assert len(t.devices) == 1 and t.devices[0].index == 0
    mat = t.module_durations(r"^jit_fixture_matmul")
    add = t.module_durations(r"^jit_fixture_add")
    assert len(mat) == 6 and len(add) == 3
    # eight 2048^3 bf16 matmuls: 137 GFLOP, 0.7 ms at the 197 TFLOP/s peak
    assert all(0.0006 < d < 0.01 for d in mat)
    assert all(d < min(mat) for d in add)
    assert len(t.launches(0)) == 9
    # every operation lies inside an execution of a program
    mods = tr.merge((s, e) for _, s, e in t.devices[0].modules)
    assert tr.total(tr.subtract(t.devices[0].busy(), mods)) < 1e-5


def test_recorded_trace_busy_and_idle(recorded):
    t = recorded
    assert 0 < t.busy_s < t.window_s
    assert t.busy_s == pytest.approx(
        sum(t.module_durations(r"^jit_fixture")), rel=0.1)
    # five 4 ms sleeps lie between the first and the last operation
    idle = dict(t.idle_by_label(0))
    assert idle["sleep"] >= 5 * 0.004 * 0.9
    assert t.idle_s(0) == pytest.approx(sum(idle.values()))
    assert t.idle_s(0) >= 0.02
    assert t.collective_s(0) == 0 and t.exposed_collective_s(0) == 0
    b = t.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and b["idle_gaps"][0][0] == "sleep"
