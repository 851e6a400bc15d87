"""`step_scope_coverage_pct` — layer: kernels. Of device 0's operation time
inside the step program's executions of the traced window, the share whose
issuer is known (program_scopes.py: every `XLA Ops` event goes to the
`jax.named_scope` its instruction was issued under): 100 less the share that
is `unscoped`. XLA's own asynchronous copies (`async-copy`: the staging
through fast memory and the weight prefetches, which no scope of the program
could name) count as known and are a row of their own in the `[scopes]`
table. Higher is better: what is `unscoped` no other by-scope metric can
see. Should move `train_images_per_s`.
"""
import program_scopes


@program_scopes.reader
def read(obs, run):
    times = program_scopes.for_run(obs, run)
    if times is None or times.step is None:
        return None
    return program_scopes.issued_pct([times.step])
