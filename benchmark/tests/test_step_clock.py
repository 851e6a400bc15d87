"""train_common.StepClock and `train_images_per_s`: work completed per second
of the window, so a stall costs its whole length; the window opens after the
settle steps and holds a whole number of steps."""
import os
import sys
import types

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import train_common  # noqa: E402
from end_to_end import train_images_per_s  # noqa: E402


def drive(monkeypatch, periods, warmup=4, seconds=2.0):
    """Feeds StepClock steps of the given lengths on a fake clock."""
    now = [100.0]
    monkeypatch.setattr(train_common.time, "perf_counter", lambda: now[0])
    tracer = types.SimpleNamespace(
        maybe_start=lambda elapsed: None, maybe_stop=lambda: None,
        active=False, started_at=None, stopped_at=None)
    run = types.SimpleNamespace(
        seconds=seconds, tracer=tracer, t_process_start=90.0,
        events=types.SimpleNamespace(backend_compiles=0))
    clock = train_common.StepClock(run, warmup)
    for i, p in enumerate(periods):
        now[0] += p
        if clock.step_done(i):
            break
    return clock.observations(8), run


def test_window_opens_after_the_settle_steps_and_counts_whole_steps(monkeypatch):
    # 4 warm-up steps, a short run-ahead step, then steady 1/8 s steps
    obs, run = drive(monkeypatch, [1.0] * 4 + [0.015625] + [0.125] * 40)
    t0, t1 = obs["window"]
    assert t0 == 100.0 + 4.0 + 0.015625 + 0.125             # after step 5
    assert obs["attempted"] == len(obs["step_ends"]) == 16
    assert t1 - t0 == 2.0
    assert train_images_per_s.read(obs, run) == 64.0
    assert abs(obs["setup_s"] - (t0 - 90.0)) < 1e-9


def test_a_stall_costs_its_whole_length(monkeypatch):
    steady, run = drive(monkeypatch, [1.0] * 4 + [0.125] * 60)
    periods = [1.0] * 4 + [0.125] * 60
    periods[12] = 0.625                     # one step stalls for half a second
    stalled, _ = drive(monkeypatch, periods)
    # 16 steps in 2.0 s against 12 (6 + the stalled one + 5) in 2.0 s; the
    # median step is the same in both
    assert train_images_per_s.read(steady, run) == 64.0
    assert train_images_per_s.read(stalled, run) == 48.0
