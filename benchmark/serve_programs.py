"""Which executions in a serving trace are the decode program and which the
prefill programs. `GenerationEngine` jits both as a function called `fn`, so
the trace shows `jit_fn(<fingerprint>)` for every one of them; the decode runs
every tick and each prefill bucket only when a request of that size arrives,
so the executable with the most executions is the decode. (Named scopes on the
programs are a job for the `tracing` PR; PERF.md, Open questions.)
"""
import re

ENGINE_PROGRAM = re.compile(r"^jit_fn\(")


def split(trace, device=0):
    """`(decode_durations_s, prefill_durations_s)` on `device`."""
    groups = {}
    for name, s, e in trace.devices[device].modules:
        if ENGINE_PROGRAM.match(name):
            groups.setdefault(name, []).append(e - s)
    if not groups:
        return [], []
    decode = max(groups, key=lambda k: len(groups[k]))
    prefill = [d for k, v in groups.items() if k != decode for d in v]
    return groups[decode], prefill
