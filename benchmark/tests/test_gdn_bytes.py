"""The yardstick of the gated-delta-rule hybrid cell (`gdn_bytes.py`) against
the model's own shapes and ISSUE 42's sums."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import gdn_bytes  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "olmo_hybrid_7b_l16.json")))


def test_param_count_and_cache_are_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    lm = HybridLM(HybridLMConfig.from_config(CONFIG, max_len=2048),
                  par.create_mesh(devices=jax.devices()[:1], dp=1))
    assert gdn_bytes.layer_counts(CONFIG) == (12, 4) \
        == (lm.n_recurrent, lm.n_attention)
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert gdn_bytes.param_count(CONFIG) == n
    assert abs(n / 1e9 - 4.10) < 0.005
    cache = jax.eval_shape(lambda: lm.init_cache(32, 2048))
    assert [c.shape for c in cache] == [
        (32, 4, 30, 2048, 128), (32, 4, 30, 2048, 128), (32, 12, 96, 5760),
        (32, 12, 3, 11520)]
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert sizes[2] == 32 * gdn_bytes.state_bytes_per_slot(CONFIG)
    assert sizes[3] == 32 * gdn_bytes.conv_bytes_per_slot(CONFIG)
    assert sizes[0] + sizes[1] == 32 * 2048 \
        * gdn_bytes.kv_bytes_per_position(CONFIG)
    memory = CONFIG["memory"]
    assert memory["weights_bytes"] == gdn_bytes.weight_bytes(CONFIG)
    assert memory["total_bytes"] == gdn_bytes.weight_bytes(CONFIG) \
        + sum(sizes)


def test_the_issues_sums():
    c = CONFIG
    assert round(gdn_bytes.linear_mixer_param_count(c) / 1e6, 2) == 88.75
    assert round(gdn_bytes.full_mixer_param_count(c) / 1e6, 2) == 58.99
    assert round(gdn_bytes.mlp_param_count(c) / 1e6, 2) == 126.82
    assert round(gdn_bytes.weight_bytes(c) / 1e9, 2) == 8.20
    assert gdn_bytes.kv_bytes_per_row(c) == 15360
    assert gdn_bytes.kv_bytes_per_position(c) == 61440
    assert gdn_bytes.state_page_bytes(c) == 30 * 96 * 192 * 4
    assert round(32 * gdn_bytes.state_bytes_per_slot(c) / 1e6) == 849
    assert round(32 * gdn_bytes.conv_bytes_per_slot(c) / 1e6) == 27
    assert round(c["memory"]["total_bytes"] / 1e9, 1) == 13.1
    # a tick at ~950 live rows a slot: "11.0 GB", its shares as the issue has
    rows = 32 * 950 * 4
    tick = gdn_bytes.decode_tick_min_bytes(c, 32, rows)
    assert 10.9e9 < tick < 11.2e9
    linear = gdn_bytes.linear_mixer_weight_bytes(c) \
        + gdn_bytes.state_update_min_bytes(c, 32)
    assert round(100 * linear / tick) == 35
    assert round(100 * gdn_bytes.attend_min_bytes(c, rows) / tick) == 17
    assert gdn_bytes.state_update_min_bytes(c, 32) \
        == 2 * 32 * 12 * 30 * 96 * 192 * 4
    # dead slots cost nothing; the embedding table is read by row
    assert gdn_bytes.decode_tick_min_bytes(c, 0, 0) \
        == gdn_bytes.weight_bytes(c) - 100352 * 3840 * 2


def test_the_configuration_holds_the_catalogs_numbers():
    """Every number of the published config under the same key but the one
    `reduced` names; the pattern carried whole."""
    assert CONFIG["reduced"] == ["num_hidden_layers"]
    assert (CONFIG["num_hidden_layers"],
            CONFIG["published"]["num_hidden_layers"]) == (16, 32)
    assert len(CONFIG["layer_types"]) == 32
    assert CONFIG["layer_types"][:4] == ["linear_attention"] * 3 \
        + ["full_attention"]
    assert {k: CONFIG[k] for k in (
        "hidden_size", "intermediate_size", "num_attention_heads",
        "num_key_value_heads", "vocab_size", "max_position_embeddings",
        "rms_norm_eps", "linear_num_key_heads", "linear_num_value_heads",
        "linear_key_head_dim", "linear_value_head_dim",
        "linear_conv_kernel_dim")} == dict(
        hidden_size=3840, intermediate_size=11008, num_attention_heads=30,
        num_key_value_heads=30, vocab_size=100352,
        max_position_embeddings=65536, rms_norm_eps=1e-6,
        linear_num_key_heads=30, linear_num_value_heads=30,
        linear_key_head_dim=96, linear_value_head_dim=192,
        linear_conv_kernel_dim=4)
    assert CONFIG["rope_parameters"] == {"rope_theta": None}
    assert CONFIG["tie_word_embeddings"] is False
