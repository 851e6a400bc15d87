"""`retraces_in_window` — layer: compile cache. jax `jaxpr_trace` events between
the window's opening and its close, from the program's own ledger of jax's
`monitoring` events (`mxnet_tpu.compile_cache.jax_events()`, program counter).
A re-trace that then hits jax's executable cache compiles nothing, so
`compiles_in_window` cannot see it; it still costs the host the whole Python
trace of the step. Must be 0. None for a program without that ledger. Should
move `train_images_per_s`.
"""


def read(obs, run):
    from mxnet_tpu import compile_cache

    events = getattr(compile_cache, "jax_events", None)
    if events is None or "window" not in obs:
        return None
    t0, t1 = obs["window"]
    return sum(1 for t, kind, _ in events()
               if kind == "jaxpr_trace" and t0 <= t <= t1)
