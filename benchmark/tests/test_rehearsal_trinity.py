"""The CPU rehearsal of `trinity_workers32`, the cell PR 39 added: the
closed-loop runner, the window/full attention expert model built as the afmoe
block through `GenerationEngine`, the reference, the near-tie accounting and
the K/V probe end to end at a tiny size, as `test_rehearsal_mellum.py` does
for its cell. A file of its own because `common.tiny_copy` shrinks only the
files it names, and a `model_config` PR may not edit it: this one shrinks the
new configuration and traffic file itself (same structure: 5 layers — window,
window, window, full, window —, 1 of them dense, 8 experts of which this share
holds 4, top-2, 3 query heads a K/V head, a shared expert; prompts longer than
the window of 8, so that every ring wraps).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "trinity_workers32"
TINY_TRINITY = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=6, num_key_value_heads=2, head_dim=16,
    num_experts=4, published={"num_experts": 8}, num_experts_per_tok=2,
    sliding_window=8, vocab_size=211, max_position_embeddings=256,
    dtype="float32")
TINY_WORKERS = dict(
    workers={"count": 4, "lead_in_s": 1.0, "ramp_s": 0.4,
             "pool_requests": 32},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, kv_probe={"min_prompt": 24, "max_new_tokens": 8},
    trace={"after_s": 0.3, "seconds": 0.8})


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_trinity"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs", "trinity_large_ep8.json"),
                     **TINY_TRINITY)
    common.edit_json(os.path.join(bench, "traffic", "workers32_agent.json"),
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
        "decode_dense_ms_per_tick", "prefill_ms_per_bucket_ktoken",
        "kv128_attend_ms_per_tick", "kv128_attend_roofline_pct",
        "swa_moe_expert_ms_per_tick", "swa_moe_expert_roofline_pct",
        "swa_prefill_attend_mxu_pct", "afmoe_decode_hbm_roofline_pct",
        "afmoe_experts_hit_pct", "afmoe_gate_norm_ms_per_tick"}
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("trinity_large_ep8", "workers32_agent", 1)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 8 and len(BENCH["configs"]) == 7


@pytest.mark.parametrize("trace", [0, 1])
def test_trinity_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "4 of 8 experts from 0 in 4 expert layers behind 1 dense" in out
    assert "4 window layers of 8 + 1 full layers" in out
    assert "3 query heads a K/V head" in out
    assert "K/V probe" in out and "4 window members (8 rows)" in out
    assert "layers 0-1 (no routing upstream)" in out
    assert "router near-ties" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of the
        # device-trace metrics find no operation of this model there and
        # say nothing; the counters' readers read the engine's own
        assert {"batch_occupancy_pct", "afmoe_experts_hit_pct"} <= names
        assert 0 < result["metrics"]["afmoe_experts_hit_pct"]["value"] <= 100
        assert not {n for n in names if n.startswith((
            "kv128_", "swa_prefill", "swa_moe_expert_", "afmoe_decode",
            "afmoe_gate"))}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
