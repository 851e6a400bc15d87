"""`swa_moe_expert_roofline_pct` — layer: kernels. The grouped product's share
of its memory roofline in the window/full attention expert model: each HIT
expert's weights read once (`swa_moe_bytes.experts_min_bytes`, from the
experts with at least one token a tick that the engine counts from the decode
program's own routing — `serving.generation.experts_hit`) over the published
HBM bandwidth, over `swa_moe_expert_ms_per_tick`. At ~4 tokens an expert the
product is bound by bytes. Should move `itl_p90_ms`.
"""
import swa_moe_bytes
import swa_moe_ops


def read(obs, run):
    found = swa_moe_ops.grouped_product_seconds(obs, run)
    counted = swa_moe_ops.counted_in_window(obs)
    if found is None or counted is None:
        return None
    seconds, executions = found
    least = swa_moe_bytes.experts_min_bytes(run.config, counted[1])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
