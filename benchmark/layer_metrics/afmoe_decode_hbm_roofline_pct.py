"""`afmoe_decode_hbm_roofline_pct` — layer: kernels. The afmoe block's decode
program's share of its memory roofline: the least bytes a tick must move
(`afmoe_bytes.decode_tick_min_bytes`: attention with its gate, the norms, the
dense MLP, the routers, the shared experts and the head's slice once, each HIT
expert once, the live K/V rows of the full member and of the rings; from the
window's `experts_hit`, `kv_rows_live_full` and `kv_rows_live_window` a tick,
which the decode program's own routing and positions feed) over the published
HBM bandwidth, over `decode_ms_p50`: the share of the WHOLE tick. Should move
`itl_p90_ms`.
"""
import numpy as np

import afmoe_bytes
import serve_programs
import swa_moe_ops


def read(obs, run):
    if not afmoe_bytes.applies(run):
        return None
    counted = swa_moe_ops.counted_in_window(obs)
    decode, _ = serve_programs.split(obs["trace"])
    if counted is None or not decode:
        return None
    least = afmoe_bytes.decode_tick_min_bytes(run.config, counted[1],
                                              counted[2])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / float(np.median(decode))
