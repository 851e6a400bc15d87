"""`prefill_share_of_tick_pct` — layer: serving scheduler. Device time of the
prefill programs over the device time of all the engine's programs (decode and
prefill) inside the profiler window (device trace, line `XLA Modules`). Should
move `itl_p90_ms`.

Not from the engine's `prefill_us`/`tick_us` histograms, which ISSUE 22
proposed: both clocks stop after a host fetch, but under the default overlap
order (`MXNET_OVERLAP=1`) the tick dispatches the decode first and the prefill
queues behind it on the device, so `prefill_us` starts while that decode is
still running and reads one decode plus the prefill — 49% of the tick time on
the v5e where the device trace says 1.4% (PR 22).
"""
import serve_programs


def read(obs, run):
    decode, prefill = serve_programs.split(obs["trace"])
    if not decode:
        return None
    return 100.0 * sum(prefill) / (sum(decode) + sum(prefill))
