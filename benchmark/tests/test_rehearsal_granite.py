"""The CPU rehearsal of `granite4h_workers32`, the cell PR 26 added: the
closed-loop runner, the hybrid model through `GenerationEngine`, the reference
and the state probe end to end at a tiny size, as `test_rehearsal.py` does for
the cells it knows. A file of its own because `common.tiny_copy` shrinks only
the files it names, and a `model_config` PR may not edit it: this one shrinks
the new configuration and traffic file itself (same structure: period "mmAm",
4 queries a K/V head, a ragged last chunk).
"""
import json
import os

import pytest

import common

BENCH = json.load(open(os.path.join(common.REPO, "BENCHMARK.json")))
CELL = "granite4h_workers32"
TINY_GRANITE = dict(
    hidden_size=64, intermediate_size=128, shared_intermediate_size=128,
    num_attention_heads=8, num_key_value_heads=2, attention_multiplier=0.125,
    num_hidden_layers=4,
    layer_types=["mamba", "mamba", "attention", "mamba"], mamba_n_heads=4,
    mamba_d_head=32, mamba_d_state=16, mamba_chunk_size=8, vocab_size=211,
    max_position_embeddings=256, dtype="float32")
TINY_WORKERS = dict(
    workers={"count": 4, "lead_in_s": 1.0, "ramp_s": 0.4,
             "pool_requests": 32},
    prompt_len={"median": 20, "min": 4, "max": 60},
    output_len={"median": 8, "min": 4, "max": 16}, max_total=128,
    engine={"max_slots": 4, "max_len": 128, "buckets": [16, 64]},
    parity_requests=2, state_probe={"max_new_tokens": 8},
    trace={"after_s": 0.3, "seconds": 0.8})


@pytest.fixture(scope="module")
def copy_root(tmp_path_factory):
    root = common.tiny_copy(tmp_path_factory.mktemp("bench_copy_granite"))
    bench = os.path.join(root, "benchmark")
    common.edit_json(os.path.join(bench, "configs",
                                  "granite_4_0_h_micro.json"), **TINY_GRANITE)
    common.edit_json(os.path.join(bench, "traffic", "workers32.json"),
                     **TINY_WORKERS)
    return root


def expected(kind):
    return {m["name"] for m in BENCH[kind]
            if "workloads" not in m or CELL in m["workloads"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_granite_cell_runs_on_cpu_at_tiny_size(copy_root, trace):
    rc, result, out, err = common.steered_run(copy_root, CELL, trace)
    assert rc == 0, (out[-3000:], err[-3000:])
    assert result is not None, out[-2000:]
    assert result["correct"] is True, out[-3000:]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert "state probe" in out and "recurrent state of 3 layers" in out
    kind = "per_layer" if trace else "end_to_end"
    names = set(result["metrics"])
    assert names <= expected(kind)
    if trace:
        # the recorded trace is another program's: the readers of this
        # PR's metrics find nothing there and say nothing, the counter's
        # reader reads the engine's own counters
        assert "batch_occupancy_pct" in names
        assert not {n for n in names if n.startswith("ss")}
    else:
        assert names == {"itl_p90_ms", "setup_s"}
        assert result["also"]["serve_tokens_per_s"] > 0
        assert result["also"]["requests_submitted"] >= result["attempted"]
