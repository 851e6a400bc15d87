"""`moe_expert_roofline_pct` — layer: kernels. The grouped product's share of
its memory roofline: each HIT expert's weights read once
(`moe_bytes.experts_min_bytes`, from the held experts with at least one token
a tick that the engine counts from the decode program's own routing —
`serving.generation.experts_hit`) over the published HBM bandwidth, over
`moe_expert_ms_per_tick`. At a few tokens an expert the product is bound by
bytes. Should move `itl_p90_ms`.
"""
import moe_bytes
import moe_ops


def read(obs, run):
    found = moe_ops.grouped_product_seconds(obs, run)
    routed = moe_ops.routed_in_window(obs)
    if found is None or routed is None:
        return None
    seconds, executions = found
    least = moe_bytes.experts_min_bytes(run.config, routed[1])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
