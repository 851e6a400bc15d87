"""`lfm2_prefill_attend_ms_per_ktoken` — layer: kernels. Device time of the
prefill programs' attention per 1,000 prompt tokens, found by its scope
(`attn.prefill`; program_scopes.py: for heads of 64 the blockwise running
softmax in XLA past the score budget, one score matrix under it —
`HybridLM.prefill_blockwise`). Time and tokens are those of the same prefills:
over the admissions whose prefill execution the trace holds, the scope's
device time in those executions over the sum of the `mx:generation.prefill`
spans' `tokens` stat. A prefill delays every live session's next token, so it
should move `itl_p90_ms`.
"""
import lfm2_ops
import program_scopes


@program_scopes.reader
def read(obs, run):
    if not lfm2_ops.applies(run):
        return None
    times = program_scopes.for_run(obs, run)
    if times is None:
        return None
    found = times.prefill_per_token(
        lambda path: program_scopes.outermost(path) == "attn.prefill")
    if found is None or not found[0]:
        return None
    seconds, tokens = found
    return seconds * 1e3 / (tokens / 1e3)
