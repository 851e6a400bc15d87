"""`ssm_state_ms_per_tick` — layer: kernels. Device time of the recurrent
state's update a decode execution: the operations of the decode program that
hold the state slab (ssm_ops.py; on the chip the Pallas kernel
`mamba_state_update`, one call a Mamba layer), summed over the traced window's
decode executions and divided by their number. Should move `itl_p90_ms`.
"""
import ssm_ops


def read(obs, run):
    found = ssm_ops.state_update_seconds(obs, run)
    if found is None:
        return None
    seconds, executions = found
    return seconds / executions * 1e3
