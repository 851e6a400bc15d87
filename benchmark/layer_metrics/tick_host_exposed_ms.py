"""`tick_host_exposed_ms` — layer: serving scheduler. Device-0 idle time that
lies under the engine's tick spans — `mx:generation.*` (sweep, decode dispatch,
admit > prefill > prefill.fetch, commit > commit.fetch) — over the
`mx:generation.tick` spans that start inside the traced window (device trace;
attribution in program_spans.py). What the scheduler's host work adds to every
tick, so it should move `itl_p90_ms`. None for a program that writes no `mx:`
span.
"""
import program_spans


def read(obs, run):
    spans = program_spans.for_run(obs, run)
    if spans is None:
        return None
    tr = obs["trace"]
    ticks = spans.count("generation.tick", tr.t0, tr.t1)
    if not ticks:
        return None
    return spans.exposed_s(tr, ("generation.",)) / ticks * 1e3
