"""The yardstick of the hybrid cell (`ssm_bytes.py`) against the model's own
shapes and ISSUE 26's sums, and the rule by which `ssm_ops.py` recognises the
state-space operations in a trace, on instruction texts seen on the v5e."""
import json
import os
import re
import sys

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
sys.path[:0] = [BENCH_DIR, os.path.dirname(BENCH_DIR)]

import ssm_bytes  # noqa: E402
import ssm_ops  # noqa: E402

CONFIG = json.load(open(os.path.join(BENCH_DIR, "configs",
                                     "granite_4_0_h_micro.json")))


def test_param_count_is_the_models():
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig

    lm = HybridLM(HybridLMConfig.from_config(CONFIG),
                  par.create_mesh(devices=jax.devices()[:1], dp=1))
    shapes = jax.eval_shape(lm.init_params, jax.random.PRNGKey(0))
    n = sum(int(np.prod(v.shape)) for v in shapes.values())
    assert ssm_bytes.hybrid_param_count(CONFIG) == n
    assert abs(n / 1e9 - 3.19) < 0.01
    cache = jax.eval_shape(lambda: lm.init_cache(32, 4096))
    sizes = [int(np.prod(c.shape)) * c.dtype.itemsize for c in cache]
    assert sizes[2] == 32 * ssm_bytes.ssm_state_bytes_per_slot(CONFIG)
    assert sizes[3] == 32 * ssm_bytes.conv_state_bytes_per_slot(CONFIG)
    assert sizes[0] + sizes[1] == 32 * 4096 \
        * ssm_bytes.kv_bytes_per_position(CONFIG)


def test_the_issues_sums():
    assert round(ssm_bytes.hybrid_weight_bytes(CONFIG) / 1e9, 2) == 6.38
    assert round(32 * ssm_bytes.ssm_state_bytes_per_slot(CONFIG) / 1e9,
                 2) == 2.42
    assert ssm_bytes.kv_bytes_per_position(CONFIG) == 8192
    assert round(ssm_bytes.mamba_weight_bytes(CONFIG) / 1e9, 2) == 1.86
    tick = ssm_bytes.hybrid_decode_tick_min_bytes(CONFIG, 32, 32 * 1200)
    assert 11.4e9 < tick < 11.8e9                 # "about 11.6 GB"
    assert ssm_bytes.state_update_min_bytes(CONFIG, 32) \
        == 2 * 32 * 36 * 64 * 64 * 128 * 4
    assert ssm_bytes.ssd_scan_flops(CONFIG, 1024) \
        == 4 * ssm_bytes.ssd_scan_flops(CONFIG, 256)


OPS = [
    ("%mamba_state_update.18 = (f32[32,64,64]{2,1,0:T(8,128)S(1)}, "
     "f32[32,36,64,64,128]{4,3,2,1,0:T(8,128)}) custom-call(%bitcast.32, "
     "%cache_2_.1), custom_call_target=\"tpu_custom_call\"", 0.0, 1.0),
    ("%select_dynamic-update-slice_fusion.4 = f32[32,36,64,64,128]{4,3,2,1,0}"
     " fusion(%p)", 1.0, 1.5),
    ("%fusion.7 = f32[32,64,64]{2,1,0} fusion(f32[32,64,64,128]{3,2,1,0} "
     "%bitcast.111)", 1.5, 2.0),
    ("%decode_update_attend.2 = (f32[32,1,2048], bf16[32,4,8,64,4096]) "
     "custom-call()", 2.0, 3.0),
    ("%fusion.9 = bf16[32,16384]{1,0} fusion(bf16[32,2048] %a)", 3.0, 4.0),
    ("%while.3 = (s32[], f32[64,64,128]{2,1,0}) while((s32[], "
     "f32[64,64,128]) %tuple.1), condition=%c, body=%b", 10.0, 14.0),
    ("%fusion.11 = f32[256,256,64]{2,1,0} fusion(f32[256,64] %cs)", 10.5,
     11.5),
    ("%fusion.12 = bf16[1024,8512]{1,0} fusion(bf16[1024,2048] %u)", 14.0,
     15.0),
]


def test_state_and_scan_operations_are_told_by_their_arrays():
    state = r"f32\[32,(?:\d+,)?64,64,128\]"
    scan = (r" while\(|f32\[(?:\d+,)?256,256,64\]"
            r"|f32\[(?:\d+,)?64,64,128\]")
    decode, prefill = [(0.0, 4.0)], [(9.0, 16.0)]
    # the kernel, the in-place update and the read-out; not the K/V kernel
    assert ssm_ops._seconds(OPS, state, decode) == 2.0
    # the while covers its body: counted once, kept to the prefill programs
    assert ssm_ops._seconds(OPS, scan, prefill) == 4.0
    assert ssm_ops._seconds(OPS, scan, [(9.0, 12.0)]) == 2.0
    assert not re.search(scan, "%d = f32[32,36,64,64,128] "
                               "dynamic-update-slice(f32[1,1,64,64,128] %s)")
