"""`lfm2_expert_ms_per_tick` — layer: kernels. Device time of the expert
layers' grouped products a decode execution of the LFM2 expert block: the
grouped-matmul operations of the decode program (lfm2_ops.py: the Pallas
`gmm` on the chip; two products an expert layer), summed over the traced
window's decode executions and divided by their number. Should move
`itl_p90_ms`.
"""
import lfm2_ops


def read(obs, run):
    found = lfm2_ops.grouped_product_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
