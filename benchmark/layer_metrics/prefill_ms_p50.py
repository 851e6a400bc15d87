"""`prefill_ms_p50` — layer: model step. Median device duration of the prefill
programs, all buckets together (device trace; every `jit_fn` executable but
the most-executed one, which is the decode). A prefill stalls every live
stream, so it should move `itl_p90_ms` (and the unbounded `ttft_p90_ms`).
"""


import numpy as np

import serve_programs


def read(obs, run):
    _, prefill = serve_programs.split(obs["trace"])
    return float(np.median(prefill)) * 1e3 if prefill else None
