"""`lfm2_expert_roofline_pct` — layer: kernels. The grouped products' share of
their memory roofline in the LFM2 expert block: each HIT expert's weights read
once (`lfm2_bytes.experts_min_bytes`: 22.02 MB an expert, from the experts with
at least one token a tick that the engine counts from the decode program's own
routing — `serving.generation.experts_hit`) over the published HBM bandwidth,
over `lfm2_expert_ms_per_tick`. At 8 tokens an expert the product is 8 FLOPs a
weight byte, far under the chip's ridge (240), so bytes bind. Should move
`itl_p90_ms`.
"""
import lfm2_bytes
import lfm2_ops


def read(obs, run):
    found = lfm2_ops.grouped_product_seconds(obs, run)
    counted = lfm2_ops.counted_in_window(obs, run)
    if found is None or counted is None:
        return None
    seconds, executions = found
    least = lfm2_bytes.experts_min_bytes(run.config, counted[2])
    return 100.0 * least / run.peaks["hbm_bytes_per_s"] \
        / (seconds / executions)
