"""The yardstick of the window/full attention expert cell (`swa_moe_bytes.py`)
against the model's own shapes and ISSUE 33's sums, and the rule by which
`swa_moe_ops.py` recognises the decode and the prefill attention kernels in a
trace, on instruction texts of the program compiled for the v5e."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import moe_ops  # noqa: E402
import ssm_ops  # noqa: E402
import swa_moe_bytes  # noqa: E402
import swa_moe_ops  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "mellum2_12b_l8.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]


def test_param_count_is_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig

    lm = WindowMoELM(WindowMoELMConfig.from_config(CONFIG, max_len=16384),
                     par.create_mesh(devices=jax.devices()[:1], dp=1))
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    assert swa_moe_bytes.param_count(CONFIG) == sum(
        int(np.prod(v.shape)) for v in shapes.values())
    assert swa_moe_bytes.weight_bytes(CONFIG) == sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in shapes.values())
    cache = jax.eval_shape(lambda: lm.init_cache(32, 16384))
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert (sizes[0] + sizes[1], sizes[2] + sizes[3]) \
        == swa_moe_bytes.cache_bytes(CONFIG, 32, 16384)
    assert sizes[4] == 32 * 8 * 8 * 4
    assert swa_moe_bytes.layer_counts(CONFIG) == (
        len(lm.full_layers), len(lm.window_layers)) == (2, 6)


def test_the_issues_sums():
    assert round(swa_moe_bytes.attention_param_count(CONFIG) / 1e6,
                 2) == 21.23
    assert round(swa_moe_bytes.expert_bytes(CONFIG) / 1e6, 2) == 12.39
    assert round(swa_moe_bytes.router_param_count(CONFIG) / 1e6, 3) == 0.147
    # "weights 7.59 GB" (+- 1%: the acceptance criterion)
    assert abs(swa_moe_bytes.weight_bytes(CONFIG) / 7.59e9 - 1) < 0.01
    assert swa_moe_bytes.kv_bytes_per_row(CONFIG) == 2048
    full, ring = swa_moe_bytes.cache_bytes(CONFIG, 32, 16384)
    assert (round(full / 1e9, 2), round(ring / 1e9, 2)) == (2.15, 0.40)
    # "with every layer pre-paying max_len, 8.59 GB of rows"
    assert round(8 * 32 * 16384 * 2048 / 1e9, 2) == 8.59
    # "attention weights, routers and the head once 0.80 GB"
    assert round(swa_moe_bytes.decode_tick_min_bytes(CONFIG, 0, 0) / 1e9,
                 2) == 0.80
    # "505 of 512 expert copies = 6.25 GB ... 8.4 GB"
    assert round(swa_moe_bytes.experts_min_bytes(CONFIG, 505) / 1e9,
                 2) == 6.26
    rows = 32 * 7070 * 2 + 32 * 1024 * 6
    tick = swa_moe_bytes.decode_tick_min_bytes(CONFIG, 505, rows)
    assert 8.3e9 < tick < 8.5e9
    # 8 FLOPs a cache byte is under the v5e's ridge (240): bytes bind
    assert swa_moe_bytes.attend_flops_per_row(CONFIG) == 8 * 2048
    assert swa_moe_bytes.attend_min_seconds(CONFIG, rows, PEAKS) \
        == rows * 2048 / PEAKS["hbm_bytes_per_s"]
    fast = dict(PEAKS, hbm_bytes_per_s=PEAKS["hbm_bytes_per_s"] * 100)
    assert swa_moe_bytes.attend_min_seconds(CONFIG, rows, fast) \
        == rows * 16384 / PEAKS["bf16_flops_per_s"]


def test_the_bands_flops():
    assert swa_moe_bytes.band_pairs(5) == 15
    assert swa_moe_bytes.band_pairs(5, 2) == 1 + 2 * 4
    assert swa_moe_bytes.band_pairs(5, 8) == 15
    brute = sum(1 for q in range(40) for k in range(40)
                if q - 8 < k <= q)
    assert swa_moe_bytes.band_pairs(40, 8) == brute
    # "4 L W H hd = 0.27 TFLOP against 2 L^2 H hd = 2.2" a layer at 16,384
    per_pair = 32 * 4 * 128
    assert round(swa_moe_bytes.band_pairs(16384, 1024) * per_pair / 1e12,
                 2) == 0.27
    assert round(swa_moe_bytes.band_pairs(16384) * per_pair / 1e12, 1) == 2.2
    assert swa_moe_bytes.prefill_attend_flops(CONFIG, 16384) == per_pair * (
        2 * swa_moe_bytes.band_pairs(16384)
        + 6 * swa_moe_bytes.band_pairs(16384, 1024))


OPS = [
    ("%kv128_attend.5 = (f32[32,4,8,128]{3,2,1,0:T(8,128)}, "
     "bf16[32,6,4,1024,128]{4,3,2,1,0:T(8,128)(2,1)}, bf16[32,6,4,1024,128]"
     "{4,3,2,1,0}) custom-call(%a, %b), custom_call_target=\"tpu_custom_call\"",
     0.0, 1.0),
    ("%kv128_attend.6 = (f32[32,4,8,128]{3,2,1,0}, bf16[32,2,4,16384,128]"
     "{4,3,2,1,0}, bf16[32,2,4,16384,128]{4,3,2,1,0}) custom-call(%a, %b)",
     1.0, 1.5),
    ("%gmm.1 = bf16[256,1792]{1,0:T(8,128)(2,1)} custom-call(%x.1, %w.1), "
     "custom_call_target=\"tpu_custom_call\"", 2.0, 2.5),
    ("%gmm.2 = bf16[256,2304]{1,0} custom-call(%x.2, %w.2)", 2.5, 3.0),
    ("%fusion.9 = bf16[32,98304]{1,0} fusion(bf16[32,2304] %a)", 3.0, 4.0),
    # two prefills: a window and a full layer's kernel of the 4,096 bucket,
    # one kernel of the 8,192 bucket
    ("%swa_prefill_attend.1 = bf16[32,4096,128]{2,1,0:T(8,128)(2,1)} "
     "custom-call(%q, %k, %v)", 10.0, 10.5),
    ("%swa_prefill_attend.2 = bf16[32,4096,128]{2,1,0} custom-call(%q, %k, "
     "%v)", 10.5, 12.0),
    ("%swa_prefill_attend.1 = bf16[32,8192,128]{2,1,0} custom-call(%q, %k, "
     "%v)", 20.0, 21.0),
    ("%gmm.7 = bf16[32768,1792]{1,0} custom-call(%x)", 12.0, 13.0),
]


def test_kernels_are_told_by_their_names(monkeypatch):
    decode, prefill = [(0.0, 5.0)], [(9.0, 14.0), (19.0, 22.0)]
    assert ssm_ops._seconds(OPS, swa_moe_ops.KV128_ATTEND, decode) == 1.5
    assert ssm_ops._seconds(OPS, moe_ops.GROUPED_PRODUCT, decode) == 1.0
    assert ssm_ops._seconds(OPS, swa_moe_ops.SWA_PREFILL_ATTEND,
                            prefill) == 3.0

    class Run:
        config = CONFIG

        class tracer:
            @staticmethod
            def xplane_path():
                return "somewhere"

    monkeypatch.setattr(ssm_ops, "_device_ops", lambda path: OPS)
    monkeypatch.setattr(ssm_ops, "engine_programs",
                        lambda trace: (decode, prefill))
    obs = {"trace": None}
    assert swa_moe_ops.kv128_attend_seconds(obs, Run) == (1.5, 1)
    assert swa_moe_ops.grouped_product_seconds(obs, Run) == (1.0, 1)
    assert swa_moe_ops.prefill_attend(obs, Run) == (3.0, {4096: 2, 8192: 1})
    # another family's configuration: nothing to read
    Run.config = {"kv_lora_rank": 512, "moe_intermediate_size": 2048}
    assert swa_moe_ops.kv128_attend_seconds(obs, Run) is None
    assert swa_moe_ops.prefill_attend(obs, Run) is None


def test_counters_a_tick():
    obs = {"max_slots": 32, "telemetry": {
        "tick_slots": 32 * 100, "experts_hit": 100 * 505,
        "kv_rows_live_full": 100 * 450000,
        "kv_rows_live_window": 100 * 196608}}
    assert swa_moe_ops.counted_in_window(obs) == (100, 505, 646608)
    assert swa_moe_ops.counted_in_window({"telemetry": {}}) is None
    assert swa_moe_ops.counted_in_window({}) is None
    # the parent's engine has no such counter: nothing to read
    assert swa_moe_ops.counted_in_window({"max_slots": 32, "telemetry": {
        "tick_slots": 3200, "experts_hit": 5, "latent_rows_live": 7}}) is None
