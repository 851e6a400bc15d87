"""The yardstick of the latent-attention expert cell (`moe_bytes.py`) against
the model's own shapes and ISSUE 31's sums, and the rule by which
`moe_ops.py` recognises the grouped product and the latent decode attention
in a trace, on instruction texts of the program compiled for the v5e."""
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import moe_bytes  # noqa: E402
import moe_ops  # noqa: E402
import ssm_ops  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "sarvam_105b_ep4.json")))
PEAKS = json.load(open(os.path.join(BENCH_DIR, "peaks.json")))["TPU v5 lite"]


def test_param_count_is_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import LatentMoELM, LatentMoELMConfig

    lm = LatentMoELM(LatentMoELMConfig.from_config(CONFIG, max_len=16384),
                     par.create_mesh(devices=jax.devices()[:1], dp=1))
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    assert moe_bytes.param_count(CONFIG) == sum(
        int(np.prod(v.shape)) for v in shapes.values())
    assert moe_bytes.weight_bytes(CONFIG) == sum(
        int(np.prod(v.shape)) * v.dtype.itemsize for v in shapes.values())
    cache = jax.eval_shape(lambda: lm.init_cache(32, 16384))
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert sizes[0] + sizes[1] == moe_bytes.latent_slab_bytes(
        CONFIG, 32, 16384)
    assert sizes[2] == 32 * 4 * 8 * 4


def test_the_issues_sums():
    assert round(moe_bytes.attention_param_count(CONFIG) / 1e6, 1) == 94.6
    assert round(moe_bytes.expert_bytes(CONFIG) / 1e6, 1) == 50.3
    assert round(moe_bytes.weight_bytes(CONFIG) / 1e9, 2) == 9.08
    assert moe_bytes.latent_bytes_per_row(CONFIG) == 1152
    assert round(moe_bytes.latent_slab_bytes(CONFIG, 32, 16384) / 1e9,
                 2) == 3.02
    assert moe_bytes.attend_flops_per_row(CONFIG) == 64 * 2176
    # "replicated weights 1.55 GB + head 0.54"
    assert round(moe_bytes.replicated_param_count(CONFIG) * 2 / 1e9,
                 2) == 2.09
    tick = moe_bytes.decode_tick_min_bytes(CONFIG, 4 * 28, 32 * 7200)
    assert 8.9e9 < tick < 9.2e9                     # "about 9.0 GB"
    assert moe_bytes.experts_min_bytes(CONFIG, 112) == 112 * 3 * 4096 \
        * 2048 * 2
    # 121 FLOPs a cache byte is under the v5e's ridge (240): bytes bind
    rows = 32 * 7200
    assert moe_bytes.attend_min_seconds(CONFIG, rows, PEAKS) \
        == rows * 5 * 1152 / PEAKS["hbm_bytes_per_s"]
    fast = dict(PEAKS, hbm_bytes_per_s=PEAKS["hbm_bytes_per_s"] * 10)
    assert moe_bytes.attend_min_seconds(CONFIG, rows, fast) \
        == rows * 5 * 64 * 2176 / PEAKS["bf16_flops_per_s"]


OPS = [
    ("%latent_attend.10 = f32[32,64,512]{2,1,0:T(8,128)S(1)} custom-call("
     "%bitcast.33, %fusion.17), custom_call_target=\"tpu_custom_call\"",
     0.0, 1.0),
    ("%ragged-dot-metadata.3 = (s32[33]{0:T(128)S(1)}, s32[32]{0}) "
     "custom-call(%get-tuple-element.51)", 1.0, 1.1),
    ("%ragged-dot-none.7 = bf16[256,4096]{1,0:T(8,128)(2,1)S(1)} "
     "custom-call(%get-tuple-element.32, %params__l1_experts_in__.1)",
     1.1, 2.1),
    ("%ragged-dot-none.6 = bf16[256,4096]{1,0} custom-call("
     "%slice_multiply_fusion.3, %params__l1_experts_out__.1)", 2.5, 3.0),
    ("%gmm.1 = bf16[256,4096]{1,0:T(8,128)(2,1)} custom-call(%x.1, %w.1), "
     "custom_call_target=\"tpu_custom_call\"", 4.2, 4.6),
    ("%fusion.9 = bf16[32,65536]{1,0} fusion(bf16[32,4096] %a)", 3.0, 4.0),
    ("%dynamic_update_slice.351 = bf16[32,5,16384,512]{3,2,1,0} "
     "dynamic-update-slice(%p, %row)", 4.0, 4.1),
    # a prefill's grouped product: outside the decode executions
    ("%ragged-dot-none.2 = bf16[32768,4096]{1,0} custom-call(%x)",
     10.0, 12.0),
]


def test_grouped_product_and_latent_attention_are_told_by_their_names():
    decode = [(0.0, 5.0)]
    assert ssm_ops._seconds(OPS, moe_ops.LATENT_ATTEND, decode) == 1.0
    assert abs(ssm_ops._seconds(OPS, moe_ops.GROUPED_PRODUCT, decode)
               - 2.0) < 1e-9
    assert abs(ssm_ops._seconds(OPS, moe_ops.GROUPED_PRODUCT,
                                [(0.0, 5.0), (9.0, 13.0)]) - 4.0) < 1e-9


def test_counters_a_tick():
    obs = {"max_slots": 32, "telemetry": {
        "tick_slots": 32 * 100, "experts_hit": 100 * 111,
        "latent_rows_live": 100 * 230000}}
    assert moe_ops.routed_in_window(obs) == (100, 111, 230000)
    assert moe_ops.routed_in_window({"telemetry": {}}) is None
    assert moe_ops.routed_in_window({}) is None
