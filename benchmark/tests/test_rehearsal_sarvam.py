"""The CPU rehearsal of `sarvam105b_workers32`, the cell PR 31 added: the
closed-loop runner, the latent-attention expert model through
`GenerationEngine`, the reference, the near-tie accounting and the latent
probe end to end at a tiny size, as `test_rehearsal_granite.py` does for its
cell. A file of its own because `common.tiny_copy` shrinks only the files it
names, and a `model_config` PR may not edit it: this one shrinks the new
configuration and traffic file itself (same structure: one leading dense
layer, a chip's share of the experts — 8 of 16 from the 4th — top-4, a
shared expert, YaRN-corrected rotary positions).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "sarvam105b_workers32"
TINY_SARVAM = dict(
    hidden_size=64, intermediate_size=128, moe_intermediate_size=32,
    num_attention_heads=4, kv_lora_rank=128, qk_nope_head_dim=16,
    qk_rope_head_dim=8, v_head_dim=16, q_head_dim=24, head_dim=136,
    num_hidden_layers=3, num_experts=8, num_experts_per_tok=4,
    vocab_size=211, max_position_embeddings=256, dtype="float32",
    rope_scaling={"original_max_position_embeddings": 64},
    published={"num_experts": 16}, share={"expert_first": 4})
TINY_WORKERS = dict(
    workers={"count": 4, "lead_in_s": 1.0, "ramp_s": 0.4,
             "pool_requests": 32},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, latent_probe={"max_new_tokens": 8},
    trace={"after_s": 0.3, "seconds": 0.8})


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_sarvam"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs", "sarvam_105b_ep4.json"),
                     **TINY_SARVAM)
    common.edit_json(os.path.join(bench, "traffic", "workers32_long.json"),
                     **TINY_WORKERS)
    return root


def expected(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_sarvam_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "8 of 16 experts from 4" in out
    assert "latent probe" in out and "rows of 3 layers" in out
    assert "router near-ties" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of the
        # device-trace metrics find no operation of this model there and
        # say nothing; the counters' readers read the engine's own
        assert {"batch_occupancy_pct", "experts_hit_pct"} <= names
        assert 0 < result["metrics"]["experts_hit_pct"]["value"] <= 100
        assert not {n for n in names if n.startswith(("moe_", "mla_"))}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
