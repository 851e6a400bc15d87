#!/usr/bin/env python3
"""Device time by the program's own scopes.

The program opens a `jax.named_scope` where it issues work (`Convolution:
stage1_unit1_conv1` in a Module step, `<block>/Convolution` in a gluon
capture, `mamba.state_update`, `mlp`, `head` ... in the serving models), and
jax writes the open scopes into every instruction's `op_name`. This module
charges every `XLA Ops` event of device 0 that lies inside an `XLA Modules`
execution to one scope, so a trace says how long the convolutions, the
normalisations or the dense products of a step or a tick took — by the name
the program gave them, not by `fusion.1433` or by an array's shape.

Where the names come from: **the file itself**. Beside the timeline the
profiler writes, for every program that ran, its compiled module (the plane
`/host:metadata`: one entry a program, keyed by the `program_id` that is the
fingerprint in `jit_step(9485870588864213460)`, holding the stat `Hlo Proto`).
`jax.profiler.ProfileData` does not show it, so the plane is read with a few
lines of protobuf wire format and each `HloInstructionProto` gives its name,
opcode, `metadata.op_name` and the computations it calls — for a fusion, the
instructions inside it, so that a fusion that carries a convolution or a dot
is charged to that one's scope and the time of fusions that span several
scopes is counted (`mixed`). An event's program is the execution it lies in,
its key `(program_id, instruction name)`: exact, no compile, nothing asked of
the running program, the one-op programs of eager glue (`jit__lambda_`)
included, and the same from a file on disk as at the end of a run.

The charging rules: a fusion goes to the scope of the convolution or dot it
carries, else to its own `op_name`'s; an event that contains others (a
`while`, a call) is charged only what its children leave; `copy-start/done`
and `slice-start/done` go to `async-copy`; `jvp(...)` / `transpose(jvp(...))`
wrappers become a `fwd` | `bwd` tag; `jit(...)` / `pjit(...)` components, the
trailing primitive and jax's own structural components (`while`, `body`,
`cond`, `closed_call` ...) are dropped; what is left empty is `unscoped`.

    python3 benchmark/program_scopes.py <xplane.pb>

prints the table of a trace on disk. The readers in `layer_metrics/` call
`for_run`, which builds the table once a process and logs it on `[scopes]`
lines. A trace in which no instruction carries a scope (a program from
before PR 37, or executables out of a compile cache such a program filled:
jax's cache key leaves the names out) gives None, and the readers report
nothing.
"""
import bisect
import functools
import math
import os
import re
import sys
import time

if __name__ == "__main__":      # run as a script: find the sibling modules
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import serve_programs
import trace_reduce
from harness import log

UNSCOPED = "unscoped"
ASYNC_COPY = "async-copy"
PREFILL_SPAN = "mx:generation.prefill"
PROGRAMS_PLANE = b"/host:metadata"
DECODE, PREFILL = "decode", "prefill"
# XLA's own asynchronous copies (weights and activations fetched ahead of
# their use): by opcode, or an `async-start` / `async-done` named after what
# it wraps (`%slice-start.60 = ... async-start(...)`)
ASYNC_OPCODES = {"copy-start", "copy-done", "slice-start", "slice-done"}
ASYNC_NAME = re.compile(
    r"^(?:copy|slice|dynamic-slice|dynamic-update-slice)-(?:start|done)\b")
CARRIED = ("convolution", "dot")
# components jax itself adds to a name stack when it lowers control flow or
# a call: not scopes of the program
STRUCTURAL = {"while", "body", "cond", "closed_call", "core_call", "remat",
              "checkpoint", "custom_jvp_call", "custom_vjp_call",
              "custom_vjp_call_jaxpr", "custom_lin", "shard_map", "scan"}
WRAPPER = re.compile(r"^(\w+)\((.*)\)$")
BRANCH = re.compile(r"^branch_\d+_fun$")
OPCODE = re.compile(r" = .*? ([a-z][\w\-]*)\(")
FINGERPRINT = re.compile(r"\((\d+)\)$")
# the device's clock runs about a millisecond ahead of the host's in the
# file (trace_reduce.py): how far outside its admission's span a prefill
# execution may seem to lie
CLOCK_SLACK_S = 3e-3


# ---------------------------------------------------------------------------
# op_name -> (scope path, direction)
# ---------------------------------------------------------------------------

def _split(op_name):
    """`op_name` cut at the `/` outside parentheses."""
    parts, depth, cur = [], 0, []
    for ch in op_name:
        if ch == "/" and depth == 0:
            parts.append("".join(cur))
            cur = []
            continue
        depth += (ch == "(") - (ch == ")")
        cur.append(ch)
    parts.append("".join(cur))
    return parts


@functools.lru_cache(maxsize=None)
def scope_of(op_name):
    """`(path, direction)` of an instruction's `op_name`: the scopes the
    program had open, outermost first and joined by `/`, and `fwd` | `bwd`
    (`bwd`: under a `transpose(...)`, the pullback of the scope).
    `jit(step)/jit(main)/transpose(jvp(Convolution:conv0))/conv_general_
    dilated` -> `("Convolution:conv0", "bwd")`; no scope -> `unscoped`."""
    # the profiler's `<op_name>:<op_type>`; an instruction XLA merged from
    # several carries their op_names joined by `;`: the first stands for it
    op_name = op_name.split(";", 1)[0]
    if op_name.endswith(":"):
        op_name = op_name[:-1]
    kept, direction = [], "fwd"
    for part in _split(op_name)[:-1]:       # the last is the primitive
        while True:
            m = WRAPPER.match(part)
            if m is None:
                break
            if m.group(1) in ("jit", "pjit"):
                part = ""
                break
            if m.group(1) == "transpose":
                direction = "bwd"
            part = m.group(2)
        # (an einsum opens a scope of its own, named by its subscripts)
        # and nested gluon blocks of one prefix repeat their name
        if part and part not in STRUCTURAL and not BRANCH.match(part) \
                and "->" not in part and part not in kept[-1:]:
            kept.append(part)
    return ("/".join(kept) or UNSCOPED), direction


def kinds_of(path):
    """The kind of each component of a scope path: `Convolution` of
    `Convolution:stage1_unit1_conv1`, the component itself elsewhere."""
    return [part.split(":", 1)[0] for part in path.split("/")]


def outermost(path):
    """What a serving model's scopes are read by: where they nest
    (`mla.project/norm`), the outermost names the work."""
    return kinds_of(path)[0]


def innermost(path):
    """What a training step's scopes are read by: the operator, under its
    node's name (`Convolution:conv0`) or inside its blocks
    (`resnetv10_stage1_conv0/Convolution`)."""
    return kinds_of(path)[-1]


def is_named(path):
    return path not in (UNSCOPED, ASYNC_COPY)


# ---------------------------------------------------------------------------
# the file's bytes: the compiled module of every program that ran
# ---------------------------------------------------------------------------

def _varint(buf, i):
    value = shift = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if b < 0x80:
            return value, i
        shift += 7


def _fields(buf, start, end):
    """`(field number, wire type, value)` of one message: an int for a
    varint, `(start, end)` of the payload for a length-delimited field;
    fixed-width fields are skipped."""
    i = start
    while i < end:
        tag, i = _varint(buf, i)
        number, wire = tag >> 3, tag & 7
        if wire == 0:
            value, i = _varint(buf, i)
            yield number, wire, value
        elif wire == 2:
            n, i = _varint(buf, i)
            yield number, wire, (i, i + n)
            i += n
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        else:
            raise ValueError(f"wire type {wire} at byte {i}")


def _text(buf, span):
    return bytes(buf[span[0]:span[1]]).decode("utf-8", "replace")


def _submessages(buf, span, *path):
    """The payloads reached from message `span` by the length-delimited
    fields `path`, in turn."""
    if not path:
        yield span
        return
    for number, wire, value in _fields(buf, *span):
        if number == path[0] and wire == 2:
            yield from _submessages(buf, value, *path[1:])


def _instruction(buf, span):
    """`(name, opcode, op_name, called computation ids)` of one
    HloInstructionProto (name 1, opcode 2, metadata 7 {op_name 2},
    called_computation_ids 38)."""
    name = opcode = op_name = ""
    called = []
    for number, wire, value in _fields(buf, *span):
        if wire == 2:
            if number == 1:
                name = _text(buf, value)
            elif number == 2:
                opcode = _text(buf, value)
            elif number == 7:
                for n, w, v in _fields(buf, *value):
                    if n == 2 and w == 2:
                        op_name = _text(buf, v)
            elif number == 38:                      # packed
                i = value[0]
                while i < value[1]:
                    one, i = _varint(buf, i)
                    called.append(one)
        elif number == 38:
            called.append(value)
    return name, opcode, op_name, called


def module_rows(buf, span):
    """`{instruction name: (opcode, op_name, inner)}` over every
    computation of one HloModuleProto (computations 3 {instructions 2,
    id 5}). `op_name` is the instruction's `metadata.op_name` — jax's name
    stack at the call that issued it, so the `jax.named_scope` path — or
    `""`; `inner`, for an instruction that calls computations (a fusion, a
    `while`, a call, a conditional), the `(opcode, op_name)` of their
    instructions less the parameters, else `()`."""
    computations = {}       # id -> [(name, opcode, op_name, called)]
    for comp in _submessages(buf, span, 3):
        ident, instructions = None, []
        for number, wire, value in _fields(buf, *comp):
            if number == 5 and wire == 0:
                ident = value
            elif number == 2 and wire == 2:
                instructions.append(_instruction(buf, value))
        computations[ident] = instructions
    return {
        name: (opcode, op_name, tuple(
            (o, n) for c in called for _, o, n, _ in computations.get(c, ())
            if o != "parameter"))
        for instructions in computations.values()
        for name, opcode, op_name, called in instructions}


def hlo_programs(path):
    """`{program_id: module_rows}` of the programs whose compiled module the
    profiler wrote into the file (XSpace.planes 1 -> the XPlane named
    `/host:metadata`, its event_metadata 4 -> XEventMetadata 2 {id 1, stats
    5 {bytes_value 6: an HloProto, whose hlo_module is field 1}}), or {}
    where the plane is not there. The timeline's `lines` are not parsed."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out = {}
    for plane in _submessages(buf, (0, len(buf)), 1):
        name, entries = None, []
        for number, wire, value in _fields(buf, *plane):
            if number == 2 and wire == 2:
                name = bytes(buf[value[0]:value[1]])
                if name != PROGRAMS_PLANE:
                    break
            elif number == 4 and wire == 2:
                entries.append(value)
        if name != PROGRAMS_PLANE:
            continue
        for entry in entries:
            for meta in _submessages(buf, entry, 2):
                program_id = None
                for number, wire, value in _fields(buf, *meta):
                    if number == 1 and wire == 0:
                        program_id = value
                for module in _submessages(buf, meta, 5, 6, 1):
                    out[program_id] = module_rows(buf, module)
    return out


def ops_and_spans(path, device=0):
    """One reading of the file: `(ops, spans)`. `ops`: `(instruction text,
    start_s, end_s)` of every `XLA Ops` event of `device`, as
    `ssm_ops._device_ops` gives them ([] without that plane). `spans`:
    `(start_s, end_s, bucket, tokens)` of every `mx:generation.prefill`
    span of the host plane, sorted (`tokens` None from a program that does
    not say it)."""
    from jax.profiler import ProfileData

    ops, spans = [], []
    for plane in ProfileData.from_file(path).planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(1)) == device:
            for line in plane.lines:
                if line.name == trace_reduce.OP_LINE:
                    ops = trace_reduce._events(line)
        elif plane.name == trace_reduce.HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name != PREFILL_SPAN:
                        continue
                    stats = dict(ev.stats)
                    if "bucket" in stats:
                        spans.append((
                            ev.start_ns * 1e-9,
                            (ev.start_ns + ev.duration_ns) * 1e-9,
                            int(stats["bucket"]),
                            int(stats["tokens"]) if "tokens" in stats
                            else None))
    return ops, sorted(spans)


# ---------------------------------------------------------------------------
# charging
# ---------------------------------------------------------------------------

class ProgramTimes:
    """One program's device time inside its executions of the trace."""

    def __init__(self, label):
        self.label = label
        self.modules = set()        # module names with their fingerprints
        self.executions = []        # (start_s, end_s)
        self.per_execution = []     # {(path, direction): seconds} of each
        self.seconds = {}           # (path, direction) -> device seconds
        self.opcodes = {}           # (path, direction) -> {opcode}
        self.total_s = 0.0
        self.fusion_s = 0.0         # of fusions whose inside the file shows
        self.mixed_s = 0.0          # ... of those spanning several scopes
        self.known = False          # the file holds the program's module

    def begin(self, start_s, end_s):
        self.executions.append((start_s, end_s))
        self.per_execution.append({})

    def charge(self, key, opcode, seconds):
        mine = self.per_execution[-1]
        mine[key] = mine.get(key, 0.0) + seconds
        self.seconds[key] = self.seconds.get(key, 0.0) + seconds
        self.opcodes.setdefault(key, set()).add(opcode)
        self.total_s += seconds

    def select(self, match):
        """Device seconds of the scopes whose path `match` accepts."""
        return sum(v for (path, _), v in self.seconds.items() if match(path))

    @property
    def named_s(self):
        return self.select(is_named)

    @property
    def unscoped_s(self):
        return self.select(lambda path: path == UNSCOPED)

    def ms_per_execution(self, match):
        return self.select(match) / len(self.executions) * 1e3


def issued_pct(programs):
    """Of the operation time of `programs`, the share whose issuer is known:
    under a scope the program named, or XLA's own asynchronous copies, which
    no scope could name. What is left is `unscoped`. None of no time."""
    total = sum(p.total_s for p in programs)
    if not total:
        return None
    return 100.0 * (1.0 - sum(p.unscoped_s for p in programs) / total)


def _self_times(events):
    """`events` `(start, end, payload)` of one execution -> `(payload, self
    seconds)`: what an event's children (the events inside it: a `while`'s
    body) leave of it."""
    out, stack = [], []         # stack rows: [end, payload, start, child_s]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, payload, start, child_s = stack.pop()
            out.append((payload, max(0.0, end - start - child_s)))

    for start, end, payload in sorted(events, key=lambda ev: (ev[0], -ev[1])):
        close(start)
        if stack:
            end = min(end, stack[-1][0])
            stack[-1][3] += end - start
        stack.append([end, payload, start, 0.0])
    close(float("inf"))
    return out


def _carried(inner):
    """The `op_name` of the convolution or dot among a fusion's
    instructions (the first), or None."""
    for opcode, op_name in inner:
        if opcode in CARRIED and op_name:
            return op_name
    return None


def _first_named(inner):
    """The first `op_name` among the instructions an instruction calls:
    what stands for one that carries none of its own."""
    return next((op_name for _, op_name in inner if op_name), "")


def _is_mixed(inner):
    scopes = {scope_of(op_name)[0] for opcode, op_name in inner
              if op_name and opcode not in ("parameter", "constant")}
    scopes.discard(UNSCOPED)
    return len(scopes) > 1


def _whole(modules):
    """`modules` (sorted by start) less the last execution where the end of
    the trace cut it: shorter than 0.95 of the median of its program's other
    executions (three or more). The profiler shows an execution that began
    before the trace not at all, and one that was running at its end up to
    there: counted as an execution, it would make every "ms an execution"
    of a 16-step window 3% too small."""
    if not modules:
        return modules
    name, s, e = modules[-1]
    others = sorted(b - a for n, a, b in modules[:-1] if n == name)
    if len(others) >= 3 and e - s < 0.95 * others[len(others) // 2]:
        return modules[:-1]
    return modules


def charge(ops, modules, programs):
    """`{label: ProgramTimes}`. `ops`: `(instruction text, start_s, end_s)`
    of device 0's `XLA Ops` line; `modules`: `(name, start_s, end_s)` of its
    `XLA Modules` line; `programs`: `hlo_programs`' `{program_id: rows}`.
    An execution's program is the fingerprint in its module's name. The
    engine's programs are labelled `decode` (the most-executed `jit_fn`) and
    `prefill` (its others), every other program by its module name less the
    fingerprint."""
    modules = _whole(sorted(modules, key=lambda m: m[1]))
    starts = [m[1] for m in modules]
    per_execution = [[] for _ in modules]
    for text, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        if i < 0 or s >= modules[i][2]:
            continue                    # outside every execution
        per_execution[i].append((s, min(e, modules[i][2]), text))

    engine = {}
    for name, _, _ in modules:
        if serve_programs.ENGINE_PROGRAM.match(name):
            engine[name] = engine.get(name, 0) + 1
    decode = max(engine, key=engine.get) if engine else None

    def label_of(name):
        if name in engine:
            return DECODE if name == decode else PREFILL
        return name.split("(", 1)[0]

    times = {}
    resolved = {}       # (fingerprint, instruction) -> its charge, found once
    for (name, ms, me), events in zip(modules, per_execution):
        prog = times.setdefault(label_of(name), ProgramTimes(label_of(name)))
        prog.modules.add(name)
        prog.begin(ms, me)
        fp = FINGERPRINT.search(name)
        fp = int(fp.group(1)) if fp else None
        rows = programs.get(fp, {})
        prog.known = prog.known or bool(rows)
        for text, self_s in _self_times(events):
            if self_s <= 0:
                continue
            instr = trace_reduce._op_name(text)
            found = resolved.get((fp, instr))
            if found is None:
                found = resolved[fp, instr] = _resolve(rows, instr, text)
            key, opcode, fused, mixed = found
            if fused:
                prog.fusion_s += self_s
                if mixed:
                    prog.mixed_s += self_s
            prog.charge(key, opcode, self_s)
    return times


def _resolve(rows, instr, text):
    """`((path, direction), opcode, a fusion whose inside the file shows,
    ... that spans several scopes)` of one instruction of a program whose
    rows are `rows` ({} where the file does not hold the program)."""
    m = OPCODE.search(text)
    opcode, op_name, inner = rows.get(
        instr, (m.group(1) if m else "?", "", ()))
    op_name = op_name or _first_named(inner)
    fused = opcode == "fusion" and bool(inner)
    if fused:
        op_name = _carried(inner) or op_name
    if opcode in ASYNC_OPCODES or ASYNC_NAME.match(instr):
        key = (ASYNC_COPY, "fwd")
    else:
        key = scope_of(op_name) if op_name else (UNSCOPED, "fwd")
    return key, opcode, fused, fused and _is_mixed(inner)


class ScopeTimes:
    def __init__(self, programs, spans=()):
        self.programs = programs
        self.spans = list(spans)

    @property
    def step(self):
        """The training step's program: of those that are not the
        engine's, the one with most device time."""
        rest = [p for p in self.programs.values()
                if p.label not in (DECODE, PREFILL)]
        return max(rest, key=lambda p: p.total_s) if rest else None

    def engine(self):
        return [self.programs[k] for k in (DECODE, PREFILL)
                if k in self.programs]

    def named_anything(self):
        return any(p.named_s > 0 for p in self.programs.values())

    def _admissions(self):
        """`(bucket, tokens, indices of its prefill executions)` of each
        admission whose prefill execution the trace holds: a
        `mx:generation.prefill` span takes the prefill executions that lie
        inside it (it ends with the fetch of the prefill's token), give or
        take the clocks' offset. A span may start before the device's first
        event: where the engine idles, the trace's device window opens with
        the prefill itself."""
        prefill = self.programs.get(PREFILL)
        for s, e, bucket, tokens in self.spans if prefill else ():
            inside = [i for i, (a, b) in enumerate(prefill.executions)
                      if s - CLOCK_SLACK_S <= 0.5 * (a + b)
                      <= e + CLOCK_SLACK_S]
            if inside:
                yield bucket, tokens, inside

    def prefill_per_bucket(self):
        """`(device seconds, bucket tokens, admissions)` of the prefill
        executions of the admissions the trace holds, or None without
        one."""
        seconds = buckets = n = 0
        for bucket, _, inside in self._admissions():
            executions = self.programs[PREFILL].executions
            seconds += sum(executions[i][1] - executions[i][0]
                           for i in inside)
            buckets += bucket
            n += 1
        return (seconds, buckets, n) if n else None

    def prefill_per_token(self, match):
        """`(device seconds under the scopes `match` accepts, prompt
        tokens)` over the admissions the trace holds: the span's own
        `tokens` stat, so the time and the tokens are those of the same
        prefills. None without an admission, or from a program whose spans
        do not say their tokens."""
        seconds = tokens = 0
        for _, said, inside in self._admissions():
            if said is None:
                return None
            charged = self.programs[PREFILL].per_execution
            seconds += sum(v for i in inside
                           for (path, _), v in charged[i].items()
                           if match(path))
            tokens += said
        return (seconds, tokens) if tokens else None

    def table(self, top=15):
        lines = []
        order = sorted(self.programs.values(), key=lambda p: -p.total_s)
        whole = sum(p.total_s for p in order) or 1.0
        for p in order:
            if p.total_s < 0.002 * whole:
                continue
            n = len(p.executions)
            spent = sorted(e - s for s, e in p.executions)
            head = (f"program {p.label} ({', '.join(sorted(p.modules))}): "
                    f"{n} executions of {spent[n // 2] * 1e3:.3f} ms at the "
                    f"median, {p.total_s / n * 1e3:.3f} ms of ops an "
                    f"execution: {100 * p.named_s / p.total_s:.1f}% under "
                    f"a scope the program named, "
                    f"{100 * p.select(lambda q: q == ASYNC_COPY) / p.total_s:.1f}"
                    f"% XLA's own asynchronous copies, "
                    f"{100 * p.unscoped_s / p.total_s:.1f}% unscoped")
            if p.known:
                head += (f"; fusions {100 * p.fusion_s / p.total_s:.1f}% of "
                         f"it, mixed (several scopes in one fusion) "
                         f"{100 * p.mixed_s / p.total_s:.1f}%")
            else:
                head += "; the file does not hold the program's module: " \
                        "no name read"
            if p.named_s == 0 and p.total_s >= 0.05 * whole:
                head += ("; NO instruction carries a scope: a program from "
                         "before PR 37, or executables out of a compile "
                         "cache such a program filled")
            lines.append(head)
            by_kind = {}
            kind_of = outermost if p.label in (DECODE, PREFILL) \
                else innermost
            for (path, _), v in p.seconds.items():
                by_kind[kind_of(path)] = by_kind.get(kind_of(path), 0.0) + v
            if len(by_kind) < len(p.seconds):
                lines.append("  by kind, ms an execution: " + ", ".join(
                    f"{k} {v / n * 1e3:.3f}" for k, v in sorted(
                        by_kind.items(), key=lambda kv: -kv[1])[:top]))
            for (path, direction), v in sorted(
                    p.seconds.items(), key=lambda kv: -kv[1])[:top]:
                lines.append(
                    f"  {path} {direction}: {v / n * 1e3:.3f} ms an "
                    f"execution ({100 * v / p.total_s:.1f}%), "
                    f"{'/'.join(sorted(p.opcodes[path, direction]))}")
        lines.append("every program, executions and device ms in all: "
                     + "; ".join(
                         f"{p.label} {len(p.executions)}, "
                         f"{p.total_s * 1e3:.2f}" for p in order))
        if self.spans:
            lines.append(
                f"{len(self.spans)} {PREFILL_SPAN} spans: bucket tokens "
                f"{sum(s[2] for s in self.spans)}, prompt tokens "
                f"{sum(s[3] or 0 for s in self.spans)}")
        return lines


# ---------------------------------------------------------------------------
# loading
# ---------------------------------------------------------------------------

def load(path, trace):
    """The ScopeTimes of an `.xplane.pb` whose reduction is `trace`, or
    None where the file holds no device operations."""
    ops, spans = ops_and_spans(path)
    if not ops:
        return None
    return ScopeTimes(charge(ops, trace.devices[0].modules,
                             hlo_programs(path)), spans)


_loaded = {}    # xplane path -> ScopeTimes or None, once a process


def for_run(obs, run):
    """The ScopeTimes of this run's profiler trace (None without one, or
    where no operation carries a scope). The first call reads the file and
    logs the table; whatever that raises is logged and costs the by-scope
    metrics, not the run's result line."""
    path = run.tracer.xplane_path()
    if path is None or "trace" not in obs:
        return None
    if path not in _loaded:
        t0 = time.perf_counter()
        try:
            times = load(path, obs["trace"])
            lines = times.table() if times is not None else []
            if times is not None and not times.named_anything():
                lines.append("no operation of the trace carries a scope: "
                             "the new readers report nothing")
                times = None
        except Exception as exc:    # a file this reader cannot take apart
            # (a profiler that lays the programs' modules out otherwise)
            log(f"[scopes] the file was not read: {exc!r}; the by-scope "
                f"readers report nothing")
            times, lines = None, []
        log(f"[scopes] the file read and charged in "
            f"{time.perf_counter() - t0:.1f}s")
        for line in lines:
            log("[scopes] " + line)
        _loaded[path] = times
    return _loaded[path]


def reader(read):
    """A by-scope reader's `read(obs, run)`, held to the promise above
    beyond the file's reading: where it raises, or computes no finite
    number (a trace the rules above did not foresee), it says so on a
    `[scopes]` line and reports nothing, and `run.py` prints the result
    line without the metric."""
    @functools.wraps(read)
    def guarded(obs, run):
        try:
            value = read(obs, run)
            if value is None or math.isfinite(value):
                return value
            said = f"read {value!r}"
        except Exception as exc:
            said = f"raised {exc!r}"
        log(f"[scopes] {read.__module__.rpartition('.')[2]} {said}: "
            f"not reported")
        return None
    return guarded


def step_ms(obs, run, match):
    """Device ms a step-program execution under the scopes `match`
    accepts; None without scopes or without a step program."""
    times = for_run(obs, run)
    if times is None or times.step is None:
        return None
    return times.step.ms_per_execution(match)


def decode_ms(obs, run, kinds):
    """Device ms a decode execution under the scopes whose outermost
    component is one of `kinds`; None without scopes, without a decode
    program, or where none of them is there."""
    times = for_run(obs, run)
    if times is None or DECODE not in times.programs:
        return None
    ms = times.programs[DECODE].ms_per_execution(
        lambda path: outermost(path) in kinds)
    return ms if ms > 0 else None


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    args = ap.parse_args(argv)
    trace = trace_reduce.load(args.xplane)
    times = load(args.xplane, trace)
    if times is None:
        print("the trace holds no device operation")
        return 1
    for line in times.table():
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
