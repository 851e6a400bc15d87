"""`gdn_chunk_ms_per_ktoken` — layer: kernels. Device time of the chunked
delta rule of the prefill programs per 1,000 prompt tokens, found by its scope
(`gdn.chunk`; program_scopes.py: the triangular solves of every chunk and the
scan that carries the state, in XLA). Time and tokens are those of the same
prefills: over the admissions whose prefill execution the trace holds, the
scope's device time in those executions over the sum of the
`mx:generation.prefill` spans' `tokens` stat. A prefill delays every live
session's next token, so it should move `itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    times = program_scopes.for_run(obs, run)
    if times is None:
        return None
    found = times.prefill_per_token(
        lambda path: program_scopes.outermost(path) == "gdn.chunk")
    if found is None or not found[0]:
        return None
    seconds, tokens = found
    return seconds * 1e3 / (tokens / 1e3)
