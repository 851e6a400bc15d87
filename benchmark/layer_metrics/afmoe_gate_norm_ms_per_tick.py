"""`afmoe_gate_norm_ms_per_tick` — layer: model step. Device time a decode
execution of what the afmoe block adds to every layer beside its products:
the scopes `attn.gate` (the output gate's sigmoid and product), `attn.qknorm`
(the head norms of queries and keys) and `norm` (four RMSNorms a layer and the
final one) of the decode program (program_scopes.py), over the decode
executions of the traced window. Small operations bound by latency, not by
bytes. Should move `itl_p90_ms`.
"""
import afmoe_bytes
import program_scopes

GATE_NORM = {"attn.gate", "attn.qknorm", "norm"}


@program_scopes.reader
def read(obs, run):
    if not afmoe_bytes.applies(run):
        return None
    return program_scopes.decode_ms(obs, run, GATE_NORM)
