"""`mamba_ssd_scope_ms_per_ktoken` — layer: kernels. Device time of the
chunked state-space scan of the prefill programs per 1,000 prompt tokens, the
scan found by its scope (`mamba.ssd`; program_scopes.py): the twin of
`ssd_scan_ms_per_ktoken`. Time and tokens are those of the same prefills: over
the admissions whose prefill execution the trace holds (the executions
inside a `mx:generation.prefill` span), the scan's device time in those
executions over the sum of the spans' `tokens` stat (the original
scales a counter by executions seen over prefills counted, which takes every
prefill for an average one). Should move `itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    times = program_scopes.for_run(obs, run)
    if times is None:
        return None
    found = times.prefill_per_token(
        lambda path: program_scopes.outermost(path) == "mamba.ssd")
    if found is None or not found[0]:
        return None
    seconds, tokens = found
    return seconds * 1e3 / (tokens / 1e3)
