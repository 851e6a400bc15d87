"""`gdn_decode_hbm_roofline_pct` — layer: kernels. The gated-delta-rule
hybrid's decode program's share of its memory roofline: the least bytes a
tick must move (`gdn_bytes.decode_tick_min_bytes`: every weight once but the
embedding table, each live slot's recurrent and convolution state read and
written once, the live K/V rows of the full layers; from the window's live
state slots and live K/V rows a tick, which the engine counts) over the
published HBM bandwidth, over `decode_ms_p50`. Should move `itl_p90_ms`.
"""
import numpy as np

import gdn_bytes
import gdn_ops
import serve_programs


def read(obs, run):
    counted = gdn_ops.counted_in_window(obs)
    if not gdn_ops.applies(run) or counted is None:
        return None
    decode, _ = serve_programs.split(obs["trace"])
    if not decode:
        return None
    least = gdn_bytes.decode_tick_min_bytes(run.config, counted[1],
                                            counted[2])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
