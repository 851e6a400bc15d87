"""`lfm2_attend_roofline_pct` — layer: kernels. The decode attention's share
of its memory roofline in the LFM2 expert block: the live K/V rows' bytes
(2,048 B a row a layer at the published 8 K/V heads of 64;
`lfm2_bytes.attend_min_bytes`, from the rows the live slots attend a tick —
`serving.generation.kv_rows_live_full`) over the published HBM bandwidth, over
`lfm2_attend_ms_per_tick`. Four queries a K/V head are 4 FLOPs a cache byte,
far under the chip's ridge (240), so bytes bind. Should move `itl_p90_ms`.
"""
import lfm2_bytes
import lfm2_ops


def read(obs, run):
    found = lfm2_ops.slab_attend_seconds(obs, run)
    counted = lfm2_ops.counted_in_window(obs, run)
    if found is None or counted is None:
        return None
    seconds, executions = found
    least = lfm2_bytes.attend_min_bytes(run.config, counted[3])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
