"""`kv128_attend_roofline_pct` — layer: kernels. The decode attention's share
of its roofline: the larger of the live K/V rows' bytes (2,048 B a row a
layer) over the published HBM bandwidth and their FLOPs over the published
bf16 peak (`swa_moe_bytes.attend_min_seconds`, from the rows the live slots
attend a tick in the full members and in the rings —
`serving.generation.kv_rows_live_full` + `kv_rows_live_window`), over
`kv128_attend_ms_per_tick`. 8 query heads a K/V head is 8 FLOPs a cache byte
(16 a cached number), far under the ridge of 240, so bytes bind. Should move
`itl_p90_ms`.
"""
import swa_moe_bytes
import swa_moe_ops


def read(obs, run):
    found = swa_moe_ops.kv128_attend_seconds(obs, run)
    counted = swa_moe_ops.counted_in_window(obs)
    if found is None or counted is None:
        return None
    seconds, executions = found
    least = swa_moe_bytes.attend_min_seconds(run.config, counted[2],
                                             run.peaks)
    return 100.0 * least / (seconds / executions)
