"""`decode_ms_p50` — layer: model step. Median device duration of the decode
program (device trace, line `XLA Modules`). The engine jits prefill and decode
under the same name (`fn`), so the decode program is told apart as the `jit_fn`
executable with the most executions in the window. Should move `itl_p90_ms`.
"""


import numpy as np

import serve_programs


def read(obs, run):
    decode, _ = serve_programs.split(obs["trace"])
    return float(np.median(decode)) * 1e3 if decode else None
