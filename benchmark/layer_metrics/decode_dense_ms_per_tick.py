"""`decode_dense_ms_per_tick` — layer: model step. Device time a decode
execution of the dense weight products no kernel metric names: the scopes
`attn.project`, `attn.out`, `mlp`, `head`, `embed`, `mla.project`,
`mla.absorb`, `moe.shared`, `moe.route`, `mamba.project` and `mamba.out`
(program_scopes.py), over the decode executions of the traced window. Should
move `itl_p90_ms`.
"""
import program_scopes

DENSE = {"attn.project", "attn.out", "mlp", "head", "embed", "mla.project",
         "mla.absorb", "moe.shared", "moe.route", "mamba.project",
         "mamba.out"}


@program_scopes.reader
def read(obs, run):
    return program_scopes.decode_ms(obs, run, DENSE)
