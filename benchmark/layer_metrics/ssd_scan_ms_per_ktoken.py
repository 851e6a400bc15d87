"""`ssd_scan_ms_per_ktoken` — layer: kernels. Device time of the chunked
state-space scan of the prefill programs per 1,000 prompt tokens: the scan's
operations (ssm_ops.py) inside the prefill executions of the traced window,
over the prompt tokens those prefills held — the engine's counter
`serving.generation.prefill_tokens` between the profiler's start and stop,
scaled by executions seen over prefills counted where a prefill straddles an
edge. A prefill delays every live session's next token, so it should move
`itl_p90_ms`.
"""
import ssm_ops


def read(obs, run):
    found = ssm_ops.ssd_scan_seconds(obs, run)
    tele = obs.get("trace_telemetry")
    if found is None or not tele or not tele.get("prefills") \
            or not tele.get("prefill_tokens"):
        return None
    seconds, executions = found
    tokens = tele["prefill_tokens"] * executions / tele["prefills"]
    return seconds * 1e3 / (tokens / 1e3)
