"""`kv128_attend_ms_per_tick` — layer: kernels. Device time of the decode
attention over the K/V slabs with heads of 128 a decode execution: the Pallas
kernel `kv128_attend` (swa_moe_ops.py; one call a layer, full members and
rings together), summed over the traced window's decode executions and
divided by their number. Should move `itl_p90_ms`.
"""
import swa_moe_ops


def read(obs, run):
    found = swa_moe_ops.kv128_attend_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
