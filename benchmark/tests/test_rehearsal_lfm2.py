"""The CPU rehearsal of `lfm2moe_workers64`, the cell PR 47 added: the
closed-loop runner, `HybridLM` built as the LFM2 expert block through
`GenerationEngine`, the reference and the probe end to end at a tiny size, as
`test_rehearsal_olmo.py` does for its cell. A file of its own because
`common.tiny_copy` shrinks only the files it names, and a `model_config` PR
may not edit it: this one shrinks the new configuration and traffic file
itself (same structure: the published pattern's first 8 layers, 2 dense
layers, 8 experts of which 2 a token, 2 queries a K/V head).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "lfm2moe_workers64"
TINY_LFM2 = dict(
    hidden_size=64, intermediate_size=96, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, num_hidden_layers=8,
    num_experts=8, num_experts_per_tok=2, vocab_size=211,
    max_position_embeddings=256, dtype="float32")
TINY_WORKERS = dict(
    workers={"count": 4, "lead_in_s": 1.5, "ramp_s": 0.4,
             "pool_requests": 32},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, probe={"min_prompt": 24, "max_new_tokens": 8},
    trace={"after_s": 0.3, "seconds": 0.8})
NEW = {"lfm2_decode_hbm_roofline_pct", "lfm2_expert_ms_per_tick",
       "lfm2_expert_roofline_pct", "lfm2_attend_ms_per_tick",
       "lfm2_attend_roofline_pct", "lfm2_shortconv_ms_per_tick",
       "lfm2_experts_hit_pct", "lfm2_prefill_attend_ms_per_ktoken"}


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_lfm2"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs", "lfm2_8b_a1b_l12.json"),
                     **TINY_LFM2)
    common.edit_json(os.path.join(bench, "traffic", "workers64_rag.json"),
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
        "prefill_ms_per_bucket_ktoken"} | NEW
    for m in BENCH["per_layer"]:
        if m["name"] in NEW:
            assert (m["workloads"], m["moves"]) == ([CELL], "itl_p90_ms")
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == ("lfm2_8b_a1b_l12", "workers64_rag", 1)
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 1
    assert len(BENCH["workloads"]) == 10 and len(BENCH["configs"]) == 9
    assert BENCH["workloads"][-1] is cell       # appended, nothing moved


@pytest.mark.parametrize("trace", [0, 1])
def test_lfm2_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "6 conv layers of 3 taps + 2 attention layers of 4 queries over " \
           "2 K/V heads of 16, 8 experts of 32 (top 2) in 6 expert layers " \
           "behind 2 dense" in out
    assert "probe:" in out and "windows of 6 conv layers" in out
    assert "K/V rows of 2 attention layers" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of this PR's
        # device-trace metrics find nothing of this model there and say
        # nothing; the counters' readers read the engine's own counters
        assert {"batch_occupancy_pct", "lfm2_experts_hit_pct"} <= names
        assert 0 < result["metrics"]["lfm2_experts_hit_pct"]["value"] <= 100
        assert not {n for n in names if n.startswith("lfm2_")
                    and n.endswith(("_per_tick", "_per_ktoken"))}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
