"""Which device operations of a serving trace belong to the state-space
layers. The profiler's `XLA Ops` line names an event by its whole HLO
instruction — `%mamba_state_update.18 = (f32[32,64,64], f32[32,36,64,64,128])
custom-call(...)` — and carries no scope, so an operation is recognised by
the arrays it holds (seen on the v5e, PR 26):

* the state update of a decode tick is every operation of the decode program
  whose instruction holds the recurrent-state slab, whole or one layer's page
  (`f32[slots, ·, heads, head_dim, d_state]`): the Pallas kernel
  `mamba_state_update` on the chip, an in-place dynamic-update-slice fusion
  and the read-out fusion in XLA;
* the chunked scan of a prefill is its `while` over chunks or — where a
  one-chunk scan was unrolled — the operations that hold a `[chunk, chunk,
  heads]` decay matrix or a `[heads, head_dim, d_state]` carried state.

`trace_reduce.load` keeps only an operation's name, so this reads the file
again. Every function returns None where it finds nothing to read (another
program, a trace without a device plane).
"""
import functools
import re

import serve_programs
import trace_reduce


@functools.lru_cache(maxsize=1)
def _device_ops(path, device=0):
    """`(instruction text, start_s, end_s)` of every `XLA Ops` event (kept
    for the next reader of the same file: three metrics read it)."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name == trace_reduce.OP_LINE:
                    return trace_reduce._events(line)
    return None


def engine_programs(trace, device=0):
    """`(decode, prefill)`: the `(start_s, end_s)` of every execution of the
    engine's decode program (the most-executed `jit_fn`) and of its other
    programs, as `serve_programs.split` tells them apart."""
    groups = {}
    for name, s, e in trace.devices[device].modules:
        if serve_programs.ENGINE_PROGRAM.match(name):
            groups.setdefault(name, []).append((s, e))
    if not groups:
        return [], []
    decode = max(groups, key=lambda k: len(groups[k]))
    return groups[decode], [iv for k, v in groups.items() if k != decode
                            for iv in v]


def _seconds(ops, pattern, executions):
    """Device seconds of the operations matching `pattern`, kept to the
    given executions; overlapping events (a `while` and its body) count
    once."""
    rx = re.compile(pattern)
    hit = trace_reduce.merge((s, e) for text, s, e in ops if rx.search(text))
    inside = trace_reduce.merge(executions)
    return trace_reduce.total(hit) - trace_reduce.total(
        trace_reduce.subtract(hit, inside))


def _state_dims(config):
    return (config["mamba_n_heads"], config["mamba_d_head"],
            config["mamba_d_state"])


def state_update_seconds(obs, run):
    """`(seconds, decode executions)` of the state-update operations inside
    the decode program's executions of the traced window."""
    path = run.tracer.xplane_path()
    if path is None or "mamba_n_heads" not in run.config:
        return None
    ops = _device_ops(path)
    decode, _ = engine_programs(obs["trace"])
    if not ops or not decode:
        return None
    h, p, n = _state_dims(run.config)
    slots = run.traffic["engine"]["max_slots"]
    seconds = _seconds(ops, rf"f32\[{slots},(?:\d+,)?{h},{p},{n}\]", decode)
    return (seconds, len(decode)) if seconds > 0 else None


def ssd_scan_seconds(obs, run):
    """`(seconds, prefill executions)` of the chunked-scan operations
    inside the prefill programs' executions of the traced window."""
    path = run.tracer.xplane_path()
    if path is None or "mamba_chunk_size" not in run.config:
        return None
    ops = _device_ops(path)
    _, prefill = engine_programs(obs["trace"])
    if not ops or not prefill:
        return None
    h, p, n = _state_dims(run.config)
    q = run.config["mamba_chunk_size"]
    seconds = _seconds(
        ops, rf" while\(|f32\[(?:\d+,)?{q},{q},{h}\]"
             rf"|f32\[(?:\d+,)?{h},{p},{n}\]", prefill)
    return (seconds, len(prefill)) if seconds > 0 else None


def decodes_in_window(obs):
    """Decode dispatches of the measured window and the mean number of
    live state slots each advanced, from the engine's counters."""
    tele = obs.get("telemetry")
    if not tele or not tele.get("tick_slots") \
            or not tele.get("state_slots_live"):
        return None
    decodes = tele["tick_slots"] / obs["max_slots"]
    return decodes, tele["state_slots_live"] / decodes
