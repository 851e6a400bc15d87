"""`gdn_attend_ms_per_tick` — layer: kernels. Device time of the full layers'
decode attention a decode execution: the Pallas kernel `kv128_attend` at 30
K/V heads with one query each (gdn_ops.py; one call a full layer), summed over
the traced window's decode executions and divided by their number. Should move
`itl_p90_ms`.
"""
import gdn_ops


def read(obs, run):
    found = gdn_ops.kv128_attend_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
