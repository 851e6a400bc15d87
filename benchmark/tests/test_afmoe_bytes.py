"""The yardstick of the afmoe cell (`afmoe_bytes.py`) against the model's own
shapes and ISSUE 39's sums, and the readers the cell shares with
`mellum2_workers32` (`swa_moe_bytes.py`, `swa_moe_ops.py`) against it: the same
kernel is read by the same yardstick in both cells."""
import json
import os
import sys
import types

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import afmoe_bytes  # noqa: E402
import swa_moe_bytes  # noqa: E402
import swa_moe_ops  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "trinity_large_ep8.json")))
MELLUM = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "mellum2_12b_l8.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


def test_param_count_is_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig

    lm = WindowMoELM(WindowMoELMConfig.from_config(CONFIG, max_len=16384),
                     par.create_mesh(devices=jax.devices()[:1], dp=1))
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    assert afmoe_bytes.param_count(CONFIG) == sum(
        int(np.prod(v.shape)) for v in shapes.values())
    assert afmoe_bytes.weight_bytes(CONFIG) == sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in shapes.values())
    cache = jax.eval_shape(lambda: lm.init_cache(32, 16384))
    assert [c.shape for c in cache] == [
        (32, 1, 8, 16384, 128), (32, 1, 8, 16384, 128),
        (32, 4, 8, 4096, 128), (32, 4, 8, 4096, 128), (32, 4, 4)]
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert (sizes[0] + sizes[1], sizes[2] + sizes[3]) \
        == afmoe_bytes.cache_bytes(CONFIG, 32, 16384)
    assert afmoe_bytes.layer_counts(CONFIG) == (
        len(lm.full_layers), len(lm.window_layers)) == (1, 4)
    assert afmoe_bytes.expert_layers(CONFIG) == lm.cfg.n_expert_layers == 4


def test_the_issues_sums():
    # "attention of a layer ... 62.9 M | 125.8 MB" (+ the head norms' 256)
    assert round(afmoe_bytes.attention_param_count(CONFIG) / 1e6, 1) == 62.9
    assert round(afmoe_bytes.dense_mlp_param_count(CONFIG) / 1e6, 1) == 113.2
    assert round(afmoe_bytes.expert_bytes(CONFIG) / 1e6, 1) == 56.6
    assert round(afmoe_bytes.shared_expert_param_count(CONFIG) / 1e6,
                 1) == 28.3
    assert round(afmoe_bytes.router_param_count(CONFIG) * 4 / 1e6, 1) == 3.1
    # "1 dense layer + 4 expert layers + vocabulary slice 4.32 B | 8.65 GB":
    # the acceptance criterion's 8.6-8.7 GB
    assert round(afmoe_bytes.param_count(CONFIG) / 1e9, 2) == 4.32
    assert 8.6e9 < afmoe_bytes.weight_bytes(CONFIG) < 8.7e9
    assert afmoe_bytes.kv_bytes_per_row(CONFIG) == 4096
    full, ring = afmoe_bytes.cache_bytes(CONFIG, 32, 16384)
    assert (round(full / 1e9, 3), round(ring / 1e9, 3)) == (2.147, 2.147)
    # "12.94 GB of 16" stand before a prefill's temporaries (12.945)
    assert round((afmoe_bytes.weight_bytes(CONFIG) + full + ring) / 1e9,
                 3) == 12.945
    # "dense weights ... 1.24 GB": 5 attentions, 4 shared experts, the dense
    # MLP, the head's slice, norms and routers
    assert round(afmoe_bytes.decode_tick_min_bytes(CONFIG, 0, 0) / 1e9,
                 2) == 1.25
    # "hit experts 4 x 12.7 x 56.6 MB = 2.88 GB"
    assert round(afmoe_bytes.experts_min_bytes(CONFIG, 4 * 12.7) / 1e9,
                 2) == 2.88
    # "K/V rows 32 x (7,000 + 4 x 3,900) x 4,096 B = 2.96 GB", "7.1 GB at
    # 819 GB/s is 8.6 ms"
    rows = 32 * (7000 + 4 * 3900)
    assert round(rows * 4096 / 1e9, 2) == 2.96
    tick = afmoe_bytes.decode_tick_min_bytes(CONFIG, 4 * 12.7, rows)
    assert 7.0e9 < tick < 7.2e9
    assert 8.5e-3 < tick / PEAKS["hbm_bytes_per_s"] < 8.8e-3
    # 6 query heads a K/V head: 6 FLOPs a cache byte, under the ridge (240)
    assert afmoe_bytes.attend_flops_per_row(CONFIG) == 6 * 4096
    assert afmoe_bytes.attend_min_seconds(CONFIG, rows, PEAKS) \
        == rows * 4096 / PEAKS["hbm_bytes_per_s"]


def test_the_shared_readers_are_right_for_this_configuration():
    """The metrics the cell shares with mellum's read only keys that are
    true of this file, so `swa_moe_bytes`' functions give this block's
    numbers: a row 4,096 B, an expert 56.6 MB, the pairs of a band of
    4,096; `swa_moe_ops.applies` admits the configuration."""
    run = types.SimpleNamespace(config=CONFIG)
    assert swa_moe_ops.applies(run) and afmoe_bytes.applies(run)
    assert not afmoe_bytes.applies(types.SimpleNamespace(config=MELLUM))
    assert swa_moe_bytes.kv_bytes_per_row(CONFIG) == 2 * 8 * 128 * 2
    assert swa_moe_bytes.expert_bytes(CONFIG) == 3 * 3072 * 3072 * 2
    assert swa_moe_bytes.attend_flops_per_row(CONFIG) == 48 * 4 * 128
    assert swa_moe_bytes.layer_counts(CONFIG) == (1, 4)
    assert swa_moe_bytes.band_pairs(16384, 4096) \
        == 4096 * 4097 // 2 + (16384 - 4096) * 4096
    assert swa_moe_bytes.band_pairs(2048, 4096) == 2048 * 2049 // 2
    want = 48 * 4 * 128 * (swa_moe_bytes.band_pairs(8192)
                           + 4 * swa_moe_bytes.band_pairs(8192, 4096))
    assert swa_moe_bytes.prefill_attend_flops(CONFIG, 8192) == want
    # what mellum's own reader of the whole tick would miss here: the gate,
    # the dense MLP, the shared experts and the second pair of norms
    missed = afmoe_bytes.decode_tick_min_bytes(CONFIG, 0, 0) \
        - swa_moe_bytes.decode_tick_min_bytes(CONFIG, 0, 0)
    assert round(missed / 1e9, 2) == 0.64   # 0.19 + 0.23 + 0.23, the norms


def test_experts_hit_reader_counts_expert_layers():
    from layer_metrics import afmoe_experts_hit_pct

    run = types.SimpleNamespace(config=CONFIG)
    tele = {"tick_slots": 32 * 100, "experts_hit": 51 * 100,
            "kv_rows_live_full": 1, "kv_rows_live_window": 1}
    obs = {"telemetry": tele, "max_slots": 32}
    assert round(afmoe_experts_hit_pct.read(obs, run), 2) \
        == round(100 * 51 / 128, 2)
    assert afmoe_experts_hit_pct.read({"max_slots": 32}, run) is None
    assert afmoe_experts_hit_pct.read(
        obs, types.SimpleNamespace(config=MELLUM)) is None


def test_the_file_keeps_every_published_number():
    """Every number of the catalog's `config` under the same key, but the
    keys `reduced` lists; no width among them."""
    if not os.path.exists(CATALOG):
        import pytest

        pytest.skip("no catalog here")
    for line in open(CATALOG):
        row = json.loads(line)
        if row["name"] == "Trinity-Large-Preview":
            break
    assert CONFIG["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if CONFIG.get(k) != v}
    assert differs == set(CONFIG["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "num_experts", "vocab_size"}
    assert {k: row["config"][k] for k in differs} == {
        k: CONFIG["published"][k] for k in differs}
    assert CONFIG["vocab_size"] * 8 == row["config"]["vocab_size"]
    assert CONFIG["num_experts"] * CONFIG["share"]["chips_per_layer"] \
        == CONFIG["published"]["num_experts"]
