"""`decode_hbm_roofline_pct` — layer: kernels. The decode program's share of its
memory roofline: the least bytes a tick must move (every weight once plus the
live K/V rows of the live slots, flops.py, from the window's mean live
positions) over the published HBM bandwidth, over `decode_ms_p50`. The decode
tick is bound by bytes, not FLOPs. Should move `itl_p90_ms`.
"""


import numpy as np

import flops
import serve_programs


def read(obs, run):
    if "mean_live_positions" not in obs:
        return None
    decode, _ = serve_programs.split(obs["trace"])
    if not decode:
        return None
    least = flops.gpt2_decode_tick_min_bytes(run.config,
                                             obs["mean_live_positions"])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
