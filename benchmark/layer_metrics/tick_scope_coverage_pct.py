"""`tick_scope_coverage_pct` — layer: kernels. Of device 0's operation time
inside the executions of the engine's programs (decode and prefill) in the
traced window, the share whose issuer is known: 100 less the share that is
`unscoped` (program_scopes.py; XLA's own asynchronous copies count as known,
as in `step_scope_coverage_pct`). Higher is better. Should move `itl_p90_ms`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    times = program_scopes.for_run(obs, run)
    if times is None or not times.engine():
        return None
    return program_scopes.issued_pct(times.engine())
