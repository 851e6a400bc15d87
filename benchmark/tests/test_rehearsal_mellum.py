"""The CPU rehearsal of `mellum2_workers32`, the cell PR 33 added: the
closed-loop runner, the window/full attention expert model through
`GenerationEngine`, the reference, the near-tie accounting and the K/V probe
end to end at a tiny size, as `test_rehearsal_sarvam.py` does for its cell. A
file of its own because `common.tiny_copy` shrinks only the files it names,
and a `model_config` PR may not edit it: this one shrinks the new
configuration and traffic file itself (same structure: two periods of three
window layers and a full one, 8 experts all held, top-2, YaRN-corrected
rotary positions on the full layers; prompts longer than the window of 8, so
that every ring wraps).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "mellum2_workers32"
TINY_MELLUM = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    num_experts=8, num_experts_per_tok=2, sliding_window=8,
    vocab_size=211, max_position_embeddings=256, dtype="float32",
    rope_parameters={"full_attention": {
        "rope_type": "yarn", "rope_theta": 10000, "factor": 16,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782}})
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
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_mellum"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs", "mellum2_12b_l8.json"),
                     **TINY_MELLUM)
    common.edit_json(os.path.join(bench, "traffic", "workers32_code.json"),
                     **TINY_WORKERS)
    return root


def expected(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_mellum_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "8 of 8 experts from 0" in out
    assert "6 window layers of 8 + 2 full layers" in out
    assert "K/V probe" in out and "6 window members (8 rows)" in out
    assert "router near-ties" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of the
        # device-trace metrics find no operation of this model there and
        # say nothing; the counters' readers read the engine's own
        assert {"batch_occupancy_pct", "swa_moe_experts_hit_pct"} <= names
        assert 0 < result["metrics"]["swa_moe_experts_hit_pct"]["value"] \
            <= 100
        assert not {n for n in names if n.startswith(("kv128_", "swa_prefill",
                                                      "swa_moe_expert_",
                                                      "swa_moe_decode"))}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
