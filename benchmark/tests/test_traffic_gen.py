"""The traffic generator: the same seed gives the same requests, the offered
load is the same for every seed, and the lengths and arrivals have the
statistics the traffic file states."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import traffic_gen  # noqa: E402

CHAT = json.load(open(os.path.join(os.path.dirname(HERE), "traffic",
                                   "chat.json")))
VOCAB = 50257


def summary(plan):
    return [(r["phase"], round(r["due_s"], 9), r["prompt"].tobytes(),
             r["max_new_tokens"]) for r in plan]


def test_same_seed_same_requests_other_seed_other_requests():
    a = traffic_gen.plan(CHAT, VOCAB, 7, 20)
    b = traffic_gen.plan(CHAT, VOCAB, 7, 20)
    c = traffic_gen.plan(CHAT, VOCAB, 8, 20)
    assert summary(a) == summary(b)
    assert summary(a) != summary(c)


def test_offered_load_is_the_same_for_every_seed():
    rate = CHAT["arrivals"]["rate_per_s"]
    plans = [traffic_gen.plan(CHAT, VOCAB, s, 20) for s in range(4)]
    for p in plans:
        win = [r for r in p if r["phase"] == "window"]
        lead = [r for r in p if r["phase"] == "lead_in"]
        assert len(win) == round(rate * 20)
        assert len(lead) == round(rate * CHAT["arrivals"]["lead_in_s"])
        assert all(0 <= r["due_s"] < 20 for r in win)
        assert all(-CHAT["arrivals"]["lead_in_s"] <= r["due_s"] < 0
                   for r in lead)
        assert [r["due_s"] for r in p] == sorted(r["due_s"] for r in p)
    # the multiset of (prompt, output) lengths of the window never changes
    def lengths(p):
        return sorted((len(r["prompt"]), r["max_new_tokens"])
                      for r in p if r["phase"] == "window")
    tokens = [sum(a + b for a, b in lengths(p)) for p in plans]
    assert len(set(tokens)) == 1
    assert sorted(len(r["prompt"]) for r in plans[0] if r["phase"] == "window") \
        == sorted(len(r["prompt"]) for r in plans[1] if r["phase"] == "window")


def test_length_statistics():
    p = traffic_gen.plan(dict(CHAT, arrivals=dict(CHAT["arrivals"],
                                                  rate_per_s=50.0)),
                         VOCAB, 1, 40)
    win = [r for r in p if r["phase"] == "window"]
    prompts = np.array([len(r["prompt"]) for r in win])
    outs = np.array([r["max_new_tokens"] for r in win])
    pl, ol = CHAT["prompt_len"], CHAT["output_len"]
    assert prompts.min() >= pl["min"] and prompts.max() <= pl["max"]
    assert outs.min() >= 1 and outs.max() <= ol["max"]
    assert abs(np.median(prompts) - pl["median"]) <= 0.03 * pl["median"]
    assert abs(np.median(outs) - ol["median"]) <= 0.05 * ol["median"]
    # log-normal: the log of the unclipped middle has the stated sigma
    mid = prompts[(prompts > pl["min"]) & (prompts < pl["max"])]
    assert abs(np.log(mid).std() - pl["sigma"]) < 0.12
    assert (prompts + outs).max() <= CHAT["max_total"]
    for r in win:
        assert r["prompt"].dtype == np.int32
        assert 0 <= r["prompt"].min() and r["prompt"].max() < VOCAB


def test_arrivals_are_uniform():
    rng = np.random.default_rng(0)
    t = traffic_gen.arrival_times(rng, 4000, 100.0)
    assert (np.diff(t) >= 0).all() and 0 <= t[0] and t[-1] < 100
    gaps = np.diff(t)
    # conditioned Poisson: gaps are close to exponential (cv ~ 1)
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1


def test_normal_quantile():
    assert abs(traffic_gen._normal_quantile(0.5)) < 1e-9
    assert abs(traffic_gen._normal_quantile(0.975) - 1.959964) < 1e-5
    assert abs(traffic_gen._normal_quantile(0.01) + 2.326348) < 1e-5


def test_the_cell_rate_is_the_recorded_sweeps():
    """The chat cell's rate is 0.8 x the knee that the committed rule finds
    in the sweep as the chip recorded it."""
    import sweep_knee

    rec = json.load(open(os.path.join(os.path.dirname(HERE), "sweeps",
                                      "gpt2xl_chat.v5e.json")))
    knee, rate = sweep_knee.knee(rec["rows"], rec["seconds"])
    assert (knee, rate) == (rec["knee_per_s"], rec["cell_rate_per_s"])
    assert CHAT["arrivals"]["rate_per_s"] == rate
