"""`mamba_state_scope_ms_per_tick` — layer: kernels. Device time of the
recurrent state's update a decode execution, found by the scope the model
opens around it (`mamba.state_update`; program_scopes.py) and not by the
state's shape and dtype: the twin of `ssm_state_ms_per_tick`. Should move
`itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    return program_scopes.decode_ms(obs, run, {"mamba.state_update"})
