"""`gdn_state_ms_per_tick` — layer: kernels. Device time of the gated delta
rule's state update a decode execution, found by the scope the model opens
around it (`gdn.state_update`; program_scopes.py: on the chip the Pallas
kernel `gdn_state_update`, one call a linear layer, and the few operations
that lay its operands out). Should move `itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    return program_scopes.decode_ms(obs, run, {"gdn.state_update"})
