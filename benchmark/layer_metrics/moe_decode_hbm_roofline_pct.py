"""`moe_decode_hbm_roofline_pct` — layer: kernels. The latent-attention
expert model's decode program's share of its memory roofline: the least
bytes a tick must move (`moe_bytes.decode_tick_min_bytes`: the replicated
weights and the head once, each HIT expert once, the live latent rows of
every layer; from the window's `experts_hit` and `latent_rows_live` a tick,
which the decode program's own routing feeds) over the published HBM
bandwidth, over `decode_ms_p50`. Should move `itl_p90_ms`.
"""
import numpy as np

import moe_bytes
import moe_ops
import serve_programs


def read(obs, run):
    if "kv_lora_rank" not in run.config:
        return None
    routed = moe_ops.routed_in_window(obs)
    decode, _ = serve_programs.split(obs["trace"])
    if routed is None or not decode:
        return None
    least = moe_bytes.decode_tick_min_bytes(run.config, routed[1], routed[2])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
