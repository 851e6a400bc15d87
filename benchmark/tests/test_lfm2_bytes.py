"""The yardstick of the LFM2 expert cell (`lfm2_bytes.py`) against the model's
own shapes and ISSUE 47's sums."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import lfm2_bytes  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "lfm2_8b_a1b_l12.json")))
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_param_count_and_cache_are_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    lm = HybridLM(HybridLMConfig.from_config(CONFIG, max_len=8192),
                  par.create_mesh(devices=jax.devices()[:1], dp=1))
    assert lfm2_bytes.layer_counts(CONFIG) == (9, 3) \
        == (lm.n_recurrent, lm.n_attention)
    assert lfm2_bytes.expert_layers(CONFIG) == 10 == lm.n_expert_layers
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert lfm2_bytes.param_count(CONFIG) == n
    assert lfm2_bytes.weight_bytes(CONFIG) == sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in shapes.values())
    assert abs(n / 1e9 - 3.93) < 0.005
    cache = jax.eval_shape(lambda: lm.init_cache(64, 8192))
    assert lm.members == ("k", "v", "conv", "routed")
    assert [(c.shape, str(c.dtype)) for c in cache] == [
        ((64, 3, 8, 8192, 64), "bfloat16"), ((64, 3, 8, 8192, 64), "bfloat16"),
        ((64, 9, 2, 2048), "bfloat16"), ((64, 10, 4), "int32")]
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert sizes[0] + sizes[1] == 64 * 8192 \
        * lfm2_bytes.kv_bytes_per_position(CONFIG)
    assert sizes[2] == 64 * lfm2_bytes.window_bytes_per_slot(CONFIG)
    assert sizes[3] == 64 * lfm2_bytes.routed_bytes_per_slot(CONFIG)
    assert sum(sizes) == lfm2_bytes.cache_bytes(CONFIG, 64, 8192)
    memory = CONFIG["memory"]
    assert memory["weights_bytes"] == lfm2_bytes.weight_bytes(CONFIG)
    assert memory["total_bytes"] == lfm2_bytes.weight_bytes(CONFIG) \
        + sum(sizes)
    assert memory["total_bytes"] >= 0.25 * 16e9     # the floor; 69%


def test_the_issues_sums():
    c = CONFIG
    assert round(lfm2_bytes.expert_bytes(c) / 1e6, 2) == 22.02
    assert round(32 * lfm2_bytes.expert_bytes(c) / 1e6, 1) == 704.6
    assert round(2 * lfm2_bytes.dense_mlp_param_count(c) / 1e6, 1) == 88.1
    assert round(2 * lfm2_bytes.conv_operator_param_count(c) / 1e6, 1) == 33.6
    assert round(2 * lfm2_bytes.attention_param_count(c) / 1e6, 1) == 21.0
    assert round(lfm2_bytes.weight_bytes(c) / 1e9, 2) == 7.86
    assert lfm2_bytes.kv_bytes_per_row(c) == 2048
    assert lfm2_bytes.kv_bytes_per_position(c) == 6144
    assert round(lfm2_bytes.cache_bytes(c, 64, 8192) / 1e9, 2) == 3.23
    assert round(64 * lfm2_bytes.window_bytes_per_slot(c) / 1e6, 1) == 4.7
    assert round(c["memory"]["total_bytes"] / 1e9, 2) == 11.08
    # the whole model: two chips' worth
    whole = dict(c, num_hidden_layers=24)
    assert round(lfm2_bytes.param_count(whole) / 1e9, 2) == 8.34
    assert round(lfm2_bytes.weight_bytes(whole) / 1e9, 1) == 16.7
    # a tick at 64 live slots, ~3,950 live rows a slot, every expert hit:
    # "9.41 GB = 11.5 ms", its shares as the issue has them
    rows = 64 * 3950 * 3
    tick = lfm2_bytes.decode_tick_min_bytes(c, 64, 320, rows)
    assert 9.40e9 < tick < 9.43e9
    assert round(tick / 819e9 * 1e3, 1) == 11.5
    assert round(100 * lfm2_bytes.experts_min_bytes(c, 320) / tick) == 75
    assert round(100 * lfm2_bytes.attend_min_bytes(c, rows) / tick) == 16
    assert lfm2_bytes.attend_min_bytes(c, rows) == rows * 2048
    # dead slots and unhit experts cost nothing
    assert lfm2_bytes.decode_tick_min_bytes(c, 0, 0, 0) \
        == lfm2_bytes.replicated_bytes(c)
    assert lfm2_bytes.decode_tick_min_bytes(c, 1, 1, 1) \
        - lfm2_bytes.replicated_bytes(c) \
        == lfm2_bytes.expert_bytes(c) + 2048 + 2 * 9 * 2 * 2048 * 2
    # the causal triangle of an 8,192 bucket in 32 heads of 64
    assert lfm2_bytes.prefill_attend_flops(c, 8192) \
        == 4 * (8192 * 8193 // 2) * 32 * 64


def test_the_configuration_holds_the_catalogs_numbers():
    """Every key of the catalog row's `config` under the same key but the
    one `reduced` names; the pattern carried whole."""
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"],
            CONFIG["published"]["num_hidden_layers"]) == (12, 24)
    assert len(CONFIG["layer_types"]) == 24
    assert CONFIG["layer_types"][:12].count("conv") == 9
    for key in ("deployment", "memory", "assumed", "departures"):
        assert CONFIG[key]
    if not os.path.exists(CATALOG):
        return
    row = next(json.loads(line) for line in open(CATALOG)
               if '"LFM2-8B-A1B"' in line)
    assert CONFIG["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key != "num_hidden_layers":
            assert CONFIG[key] == value, key
    assert row["config"]["num_hidden_layers"] == 24
