"""The CPU rehearsal of `olmohybrid_workers32`, the cell PR 42 added: the
closed-loop runner, `HybridLM` built as the Olmo-Hybrid block through
`GenerationEngine`, the reference and the two probes end to end at a tiny
size, as `test_rehearsal_granite.py` does for its cell. A file of its own
because `common.tiny_copy` shrinks only the files it names, and a
`model_config` PR may not edit it: this one shrinks the new configuration and
traffic file itself (same structure: period "lllF" twice, dk != dv, 6 linear
heads and 3 attention heads, a ragged last chunk).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "olmohybrid_workers32"
TINY_OLMO = dict(
    hidden_size=48, intermediate_size=96, num_attention_heads=3,
    num_key_value_heads=3, num_hidden_layers=8, linear_num_key_heads=6,
    linear_num_value_heads=6, linear_key_head_dim=8,
    linear_value_head_dim=16, gdn_chunk_size=8, vocab_size=211,
    max_position_embeddings=256, dtype="float32")
TINY_WORKERS = dict(
    workers={"count": 4, "lead_in_s": 1.0, "ramp_s": 0.4,
             "pool_requests": 32},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, probe={"max_new_tokens": 8},
    trace={"after_s": 0.3, "seconds": 0.8})


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_olmo"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs",
                                  "olmo_hybrid_7b_l16.json"), **TINY_OLMO)
    common.edit_json(os.path.join(bench, "traffic", "workers32_2k.json"),
                     **TINY_WORKERS)
    return root


def expected(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


def test_the_cell_is_listed_where_its_readers_are_right():
    assert expected("end_to_end") == {"itl_p90_ms", "setup_s"}
    assert expected("per_layer") == {
        "batch_occupancy_pct", "prefill_share_of_tick_pct", "decode_ms_p50",
        "prefill_ms_p50", "tick_host_exposed_ms", "tick_scope_coverage_pct",
        "prefill_ms_per_bucket_ktoken", "gdn_decode_hbm_roofline_pct",
        "gdn_state_ms_per_tick", "gdn_state_update_roofline_pct",
        "gdn_attend_ms_per_tick", "gdn_attend_roofline_pct",
        "gdn_chunk_ms_per_ktoken"}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("olmo_hybrid_7b_l16", "workers32_2k", 1)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 9 and len(BENCH["configs"]) == 8


@pytest.mark.parametrize("trace", [0, 1])
def test_olmo_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "6 linear layers of 6 heads x 8 x 16 + 2 full layers of 3 K/V " \
           "heads of 16" in out
    assert "probe:" in out and "state of 6 linear layers" in out
    assert "K/V rows of 2 full layers" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of this PR's
        # device-trace metrics find nothing of this model there and say
        # nothing; the counter's reader reads the engine's own counters
        assert "batch_occupancy_pct" in names
        assert not {n for n in names if n.startswith("gdn_")}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
