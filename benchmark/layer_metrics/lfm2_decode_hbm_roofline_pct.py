"""`lfm2_decode_hbm_roofline_pct` — layer: kernels. The LFM2 expert block's
decode program's share of its memory roofline, the WHOLE tick's: the least
bytes a tick must move (`lfm2_bytes.decode_tick_min_bytes`: every replicated
weight once — the tied table is read as the head —, each HIT expert once, the
live K/V rows at 2,048 B a layer, each live slot's windows in and out; from
the window's live slots, experts hit and live K/V rows a tick, which the
engine counts) over the published HBM bandwidth, over `decode_ms_p50`. Should
move `itl_p90_ms`.
"""
import numpy as np

import lfm2_bytes
import lfm2_ops
import serve_programs


def read(obs, run):
    counted = lfm2_ops.counted_in_window(obs, run)
    if counted is None:
        return None
    decode, _ = serve_programs.split(obs["trace"])
    if not decode:
        return None
    least = lfm2_bytes.decode_tick_min_bytes(run.config, *counted[1:])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
