#!/usr/bin/env python3
"""The program's own spans in a `jax.profiler` trace, and what they say about
the device's idle time.

`mxnet_tpu.tracing.span` writes every span it opens into the profiler's trace
as a host event `mx:<name>` (plane `/host:CPU`), on the device trace's clock.
This module loads them, gives each span name its count, total and self time,
and attributes every idle gap of device 0 to the **innermost** `mx:` span open
while it lasts — a gap that outlasts a span is cut at the span's boundary, and
what no span covers is `uncovered`. The gaps, the window and the shortest gap
that counts are `trace_reduce`'s (imported, not changed), so the seconds here
add up to `device_idle_pct`'s.

(ISSUE 23 asked for the whole gap to go to the innermost span covering half of
it. On the chip every gap of the serving cell straddles two spans — the token
fetch of one tick and the decode dispatch of the next — so that rule left 22%
of the idle time `uncovered` and 38% on the tick as a whole; cut at the
boundary, 99% lies under a leaf span. PERF.md, section 6, has both readings of
the same trace.)

    python3 benchmark/program_spans.py <xplane.pb> [--host-label gluon-loop]

prints the table for a trace on disk: each label the benchmark's own `bench:`
annotations give the idle time (`record_forward`, `fit-loop`, `engine-thread`,
...), split by the program's span. The per-layer readers
`dispatch_exposed_ms_per_step`, `loop_exposed_ms_per_step` and
`tick_host_exposed_ms` call `for_run`, which loads the run's trace once a
process and logs the same table on `[spans]` lines. A trace without `mx:`
events (a program from before PR 23) gives `None`, and the readers then
report nothing.
"""
import os
import sys

if __name__ == "__main__":      # run as a script: find the sibling modules
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import trace_reduce
from harness import log
from trace_reduce import HOST_PLANE, MIN_GAP_S

PREFIX = "mx:"
UNCOVERED = "uncovered"
BETWEEN_OPS = "between-ops"


class ProgramSpans:
    def __init__(self, spans):
        """`spans`: `(name, start_s, end_s, line)` with the prefix stripped;
        `line` tells host threads apart (nesting is per thread)."""
        self.spans = sorted(spans, key=lambda sp: (sp[3], sp[1], -sp[2]))

    def count(self, name, lo=float("-inf"), hi=float("inf")):
        """Spans called `name` that start inside `[lo, hi]`."""
        return sum(1 for n, s, _, _ in self.spans if n == name
                   and lo <= s <= hi)

    def seconds(self):
        """`{name: (count, total_s, self_s)}`; self time is a span's length
        less that of the spans nested directly in it on the same line."""
        out = {}
        stack = []      # open spans of the current line: [name, s, e, child_s]

        def close(upto, line_changed=False):
            while stack and (line_changed or stack[-1][2] <= upto):
                name, s, e, child_s = stack.pop()
                n, tot, own = out.get(name, (0, 0.0, 0.0))
                out[name] = (n + 1, tot + (e - s),
                             own + max(0.0, (e - s) - child_s))

        line = None
        for name, s, e, ln in self.spans:
            close(s, line_changed=ln != line)
            line = ln
            if stack and e <= stack[-1][2]:         # nested in the open span
                stack[-1][3] += e - s
            elif stack:     # overlaps without nesting (two threads, one line)
                close(float("inf"))
            stack.append([name, s, e, 0.0])
        close(float("inf"))
        return out

    def split_gap(self, s, e):
        """`{span name: seconds}` of `[s, e]`, cut at every span boundary
        inside it: each piece goes to the innermost span open then — of
        nested spans the inner one is the shorter, so the shortest span that
        covers the piece — else to `uncovered`."""
        over = [(a, b, name) for name, a, b, _ in self.spans
                if a < e and b > s]
        cuts = sorted({s, e, *(min(max(x, s), e)
                               for a, b, _ in over for x in (a, b))})
        out = {}
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            _, name = min(((y - x, n) for x, y, n in over if x <= mid < y),
                          default=(0.0, UNCOVERED))
            out[name] = out.get(name, 0.0) + (b - a)
        return out

    def idle_by_span(self, trace, device=0):
        """Device `device`'s idle seconds inside the trace's window as
        `{(bench label, span name): seconds}`. The bench label is
        `trace_reduce`'s for the whole gap (what `breakdown.idle_gaps`
        shows); a gap shorter than `MIN_GAP_S` is `between-ops` on both
        sides."""
        out = {}
        for s, e in trace_reduce.gaps(trace.devices[device].busy(),
                                      trace.t0, trace.t1):
            if e - s < MIN_GAP_S:
                parts, label = {BETWEEN_OPS: e - s}, BETWEEN_OPS
            else:
                parts, label = self.split_gap(s, e), trace.label_gap(s, e)
            for name, v in parts.items():
                out[label, name] = out.get((label, name), 0.0) + v
        return out

    def exposed_s(self, trace, prefixes, device=0):
        """Idle seconds attributed to spans whose name starts with one of
        `prefixes`."""
        return sum(v for (_, name), v in self.idle_by_span(trace,
                                                           device).items()
                   if name.startswith(tuple(prefixes)))

    def table(self, trace, device=0):
        """The lines of the `[spans]` table (parts under 50 us left out)."""
        def parts_of(d):
            return ", ".join(f"{k} {v:.4f}" for k, v in sorted(
                d.items(), key=lambda kv: -kv[1]) if v >= 5e-5)

        idle = self.idle_by_span(trace, device)
        by_span, by_label = {}, {}
        for (label, name), v in idle.items():
            by_span[name] = by_span.get(name, 0.0) + v
            by_label.setdefault(label, {})[name] = v
        lines = [f"device {device} idle {sum(idle.values()):.4f}s of "
                 f"{trace.window_s:.4f}s window; by the program's innermost "
                 f"span: {parts_of(by_span)}"]
        for label, parts in sorted(by_label.items(),
                                   key=lambda kv: -sum(kv[1].values())):
            tot = sum(parts.values())
            if label == BETWEEN_OPS or tot < 5e-5:
                continue
            uncovered = parts.get(UNCOVERED, 0.0)
            lines.append(
                f"bench label {label} {tot:.4f}s: "
                f"{100 * (tot - uncovered) / tot:.1f}% under a named span, "
                f"uncovered {uncovered:.4f}s; {parts_of(parts)}")
        lines.append("span: count, total s, self s — " + "; ".join(
            f"{name} {n}, {tot:.4f}, {own:.4f}" for name, (n, tot, own)
            in sorted(self.seconds().items(), key=lambda kv: -kv[1][2])))
        return lines


def load(path):
    """The ProgramSpans of an `.xplane.pb`, or None where it holds no `mx:`
    event."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != HOST_PLANE:
            continue
        for i, line in enumerate(plane.lines):
            spans += [(ev.name[len(PREFIX):], ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9, i)
                      for ev in line.events if ev.name.startswith(PREFIX)]
    return ProgramSpans(spans) if spans else None


_loaded = {}    # xplane path -> ProgramSpans or None, once a process


def queue_wait_line():
    """The engine's `serving.generation.queue_wait_us` histogram (submit to
    the start of the admission; whole process, lead-in included), or None
    where the program records none. Logged, not entered as a metric: like
    TTFT it moves neither end-to-end metric (PERF.md, section 7)."""
    try:
        from mxnet_tpu import telemetry
    except ImportError:
        return None
    hist = telemetry.get("serving.generation.queue_wait_us")
    snap = hist.snapshot() if hist is not None else None
    if not snap or not snap.get("count"):
        return None
    q = hist.quantiles(50, 90)
    return (f"queue wait ms (submit to admission, n={snap['count']}): p50 "
            f"{q[0] / 1e3:.1f} p90 {q[1] / 1e3:.1f} mean "
            f"{snap['sum'] / snap['count'] / 1e3:.1f}")


def for_run(obs, run):
    """The ProgramSpans of this run's profiler trace (None without one or
    without `mx:` events). The first call loads the file and logs the
    table."""
    path = run.tracer.xplane_path()
    if path is None or "trace" not in obs:
        return None
    if path not in _loaded:
        _loaded[path] = spans = load(path)
        if spans is None:
            log("[spans] the trace holds no mx: span (a program from before "
                "PR 23)")
        else:
            for line in spans.table(obs["trace"]):
                log("[spans] " + line)
            wait = queue_wait_line()
            if wait:
                log("[spans] " + wait)
    return _loaded[path]


def exposed_ms_per_step(obs, run, prefixes):
    """Device-0 idle under the spans named by `prefixes`, in ms a step of
    the traced window; None without `mx:` spans or without two traced
    steps."""
    spans = for_run(obs, run)
    if spans is None or obs.get("traced_step_s") is None:
        return None
    tr = obs["trace"]
    return spans.exposed_s(tr, prefixes) / (tr.window_s
                                            / obs["traced_step_s"]) * 1e3


def main(argv=None):
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("xplane")
    ap.add_argument("--host-label", default=None,
                    help="what trace_reduce calls a gap no bench: annotation "
                         "covers (gluon-loop, fit-loop, engine-thread)")
    ap.add_argument("--devices", type=int, default=None)
    args = ap.parse_args(argv)
    spans = load(args.xplane)
    if spans is None:
        print("the trace holds no mx: span")
        return 1
    trace = trace_reduce.load(args.xplane, n_devices=args.devices,
                              host_label=args.host_label)
    for line in spans.table(trace):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
