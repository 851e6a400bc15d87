"""`ssm_decode_hbm_roofline_pct` — layer: kernels. The hybrid model's decode
program's share of its memory roofline: the least bytes a tick must move
(`ssm_bytes.hybrid_decode_tick_min_bytes`: every weight once, each live slot's
recurrent and convolution state read and written once, the live K/V rows; from
the window's live state slots a tick and mean live positions) over the
published HBM bandwidth, over `decode_ms_p50`. Should move `itl_p90_ms`.
"""
import numpy as np

import serve_programs
import ssm_bytes
import ssm_ops


def read(obs, run):
    if "mamba_n_heads" not in run.config or "mean_live_positions" not in obs:
        return None
    live = ssm_ops.decodes_in_window(obs)
    decode, _ = serve_programs.split(obs["trace"])
    if live is None or not decode:
        return None
    least = ssm_bytes.hybrid_decode_tick_min_bytes(
        run.config, live[1], obs["mean_live_positions"])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
