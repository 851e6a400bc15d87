"""`swa_moe_expert_ms_per_tick` — layer: kernels. Device time of the expert
layers' grouped product a decode execution of the window/full attention
expert model: the grouped-matmul operations of the decode program
(swa_moe_ops.py: the Pallas `gmm` on the chip; two products a layer), summed
over the traced window's decode executions and divided by their number.
Should move `itl_p90_ms`.
"""
import swa_moe_ops


def read(obs, run):
    found = swa_moe_ops.grouped_product_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
