"""`gdn_attend_roofline_pct` — layer: kernels. The full layers' decode
attention's share of its memory roofline: the live K/V rows' bytes (15,360 B a
row a layer at the published 30 K/V heads of 128; `gdn_bytes.
attend_min_bytes`, from the rows the live slots attend a tick —
`serving.generation.kv_rows_live_full`) over the published HBM bandwidth, over
`gdn_attend_ms_per_tick`. One query a K/V head is 1 FLOP a cache byte, far
under the chip's ridge (240), so bytes bind. Should move `itl_p90_ms`.
"""
import gdn_bytes
import gdn_ops


def read(obs, run):
    found = gdn_ops.kv128_attend_seconds(obs, run)
    counted = gdn_ops.counted_in_window(obs)
    if found is None or counted is None:
        return None
    seconds, executions = found
    least = gdn_bytes.attend_min_bytes(run.config, counted[2])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
