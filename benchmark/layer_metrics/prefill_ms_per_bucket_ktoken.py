"""`prefill_ms_per_bucket_ktoken` — layer: model step. Device time of the
prefill executions per 1,000 tokens of the buckets they were padded to: over
the admissions whose prefill execution the trace holds, the device time of
the prefill executions inside each `mx:generation.prefill` span, over the sum
of those spans' `bucket` stat (program_scopes.py). Steady where
`prefill_ms_p50` is whichever bucket came. Should move `itl_p90_ms`.

Listed for the closed-loop serving cells, whose 3 s window always holds
admissions. Not for `gpt2xl_chat`: where its engine idles the device's part
of the 2 s profiler window can be a fifth of a second with no prefill in it
(1 traced run in 11 of PR 37), and a metric's `workloads` name the cells in
which its reader finds something to read in every traced run; the `[scopes]`
table of that cell's log still says its admissions and their tokens.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    times = program_scopes.for_run(obs, run)
    if times is None:
        return None
    found = times.prefill_per_bucket()
    if found is None:
        return None
    seconds, buckets, _ = found
    return seconds * 1e3 / (buckets / 1e3)
