"""`mla_attend_ms_per_tick` — layer: kernels. Device time of the latent
decode attention a decode execution: the Pallas kernel `latent_attend`
(moe_ops.py; one call a layer), summed over the traced window's decode
executions and divided by their number. Should move `itl_p90_ms`.
"""
import moe_ops


def read(obs, run):
    found = moe_ops.latent_attend_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
