"""`lfm2_attend_ms_per_tick` — layer: kernels. Device time of the attention
layers' decode attention a decode execution of the LFM2 expert block: the
Pallas slab kernel `decode_update_attend` at 32 queries over 8 K/V heads of 64
(lfm2_ops.py; one call an attention layer), summed over the traced window's
decode executions and divided by their number. Should move `itl_p90_ms`.
"""
import lfm2_ops


def read(obs, run):
    found = lfm2_ops.slab_attend_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
