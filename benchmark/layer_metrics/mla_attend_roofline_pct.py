"""`mla_attend_roofline_pct` — layer: kernels. The latent decode attention's
share of its roofline: the larger of the live latent rows' bytes over the
published HBM bandwidth and their FLOPs (every head scores a row and adds its
latent: `moe_bytes.attend_flops_per_row`) over the published bf16 peak
(`moe_bytes.attend_min_seconds`, from the rows the live slots attend a tick —
`serving.generation.latent_rows_live`), over `mla_attend_ms_per_tick`. Should
move `itl_p90_ms`.
"""
import moe_bytes
import moe_ops


def read(obs, run):
    found = moe_ops.latent_attend_seconds(obs, run)
    routed = moe_ops.routed_in_window(obs)
    if found is None or routed is None:
        return None
    seconds, executions = found
    least = moe_bytes.attend_min_seconds(run.config, routed[2], run.peaks)
    return 100.0 * least / (seconds / executions)
