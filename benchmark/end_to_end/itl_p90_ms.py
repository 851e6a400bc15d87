"""`itl_p90_ms` — 90th percentile over all gaps between consecutive output
tokens of the requests due inside the window, stamped by the client-side sweep
of the streams (host clock, 0.5 ms resolution).
"""


from harness import percentile


def read(obs, run):
    if "itl_ms" not in obs:
        return None
    return percentile(obs["itl_ms"], 90)
