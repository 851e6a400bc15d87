"""Reduction of a `jax.profiler` trace (`.xplane.pb`) to what the per-layer
readers need: when an operation ran on each device, which XLA program each
belonged to, which were collectives, and what the benchmark's own host
annotations say the host was doing in each idle gap.

Read with nothing but jax (`jax.profiler.ProfileData`). The arithmetic works on
plain lists of `(name, start_s, end_s)` so benchmark/tests can check it on
hand-made intervals as well as on the recorded trace beside them.

What a TPU trace looks like (seen on the v5e, PR 22): one plane per chip named
`/device:TPU:<i>` with the lines `XLA Modules` (one event per execution of a
compiled program, named `<jit name>(<fingerprint>)`) and `XLA Ops` (one event
per HLO operation, named by the whole HLO instruction, `%fusion.7 = bf16[...]
fusion(...)`, of which only `fusion.7` is kept); host threads are lines of the
plane `/host:CPU`, where a `TraceAnnotation` is an event under its own name.
The device's clock runs about a millisecond ahead of the host's in the file.
"""
import re

from harness import ANNOTATION_PREFIX

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^%?(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")
# an idle gap shorter than this is the device's own turn-around between two
# operations, not time the host kept it waiting
MIN_GAP_S = 20e-6


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def merge(intervals):
    """Union of `(start, end)` intervals as a sorted list of disjoint ones."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def total(merged):
    return sum(e - s for s, e in merged)


def subtract(a, b):
    """The part of merged intervals `a` that no interval of merged `b`
    covers, as a merged list."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(merged, lo, hi):
    """The idle intervals of `[lo, hi]` between merged busy intervals."""
    return subtract([(lo, hi)], merged)


# ---------------------------------------------------------------------------
# the reduced trace
# ---------------------------------------------------------------------------

class DeviceTrace:
    """One chip: `ops` and `modules` are lists of `(name, start_s, end_s)`;
    an op's name is its HLO name, a module's the jit name with its
    fingerprint, `jit_step(9485870588864213460)`."""

    def __init__(self, index, ops, modules):
        self.index = index
        self.ops = ops
        self.modules = modules
        self._busy = None

    def busy(self):
        """When an operation ran, as merged intervals, kept to the time a
        program was executing: an asynchronous copy that one execution starts
        and the next one ends shows as one long event on the op line."""
        if self._busy is None:
            ops = merge((s, e) for _, s, e in self.ops)
            if self.modules:
                running = merge((s, e) for _, s, e in self.modules)
                ops = subtract(ops, gaps(running, float("-inf"),
                                         float("inf")))
            self._busy = ops
        return self._busy


class ReducedTrace:
    def __init__(self, devices, annotations, host_label=None):
        """`annotations`: the benchmark's own host spans, `(name, start_s,
        end_s)` with the prefix stripped. `host_label`: what an idle gap
        that no annotation covers is called (the runner knows which of the
        program's threads drives the device)."""
        if not devices:
            raise ValueError("the trace holds no device plane")
        self.devices = sorted(devices, key=lambda d: d.index)
        self.annotations = annotations
        self.host_label = host_label or "unannotated-host"
        starts = [s for d in self.devices for s, _ in d.busy()[:1]]
        ends = [e for d in self.devices for _, e in d.busy()[-1:]]
        # the window is the steady part the trace saw: first operation's
        # start to last operation's end, over the chips used
        self.t0 = min(starts) if starts else 0.0
        self.t1 = max(ends) if ends else 0.0
        self.window_s = self.t1 - self.t0
        per_dev = [total(d.busy()) for d in self.devices]
        self.busy_s = sum(per_dev) / len(per_dev)

    def idle_s(self, device=0):
        return self.window_s - total(self.devices[device].busy())

    def launches(self, device=0):
        """Executions of compiled programs inside the window."""
        return [m for m in self.devices[device].modules
                if m[2] > self.t0 and m[1] <= self.t1]

    def module_durations(self, pattern, device=0):
        """Device seconds of each execution of the programs whose name
        matches `pattern` (a regex, searched)."""
        rx = re.compile(pattern)
        return [e - s for name, s, e in self.devices[device].modules
                if rx.search(name)]

    def collective_intervals(self, device=0):
        return merge((s, e) for name, s, e in self.devices[device].ops
                     if COLLECTIVE.match(name))

    def compute_intervals(self, device=0):
        return merge((s, e) for name, s, e in self.devices[device].ops
                     if not COLLECTIVE.match(name))

    def collective_s(self, device=0):
        return total(self.collective_intervals(device))

    def exposed_collective_s(self, device=0):
        """Collective time during which no other operation ran there."""
        return total(subtract(self.collective_intervals(device),
                              self.compute_intervals(device)))

    def label_gap(self, s, e):
        best, best_cover = None, 0.0
        for name, a, b in self.annotations:
            cover = max(0.0, min(e, b) - max(s, a))
            if cover > best_cover:
                best, best_cover = name, cover
        return best if best is not None and best_cover >= 0.5 * (e - s) \
            else self.host_label

    def idle_by_label(self, device=0):
        """Idle seconds of the device by what the host was doing, longest
        first."""
        by = {}
        for s, e in gaps(self.devices[device].busy(), self.t0, self.t1):
            if e - s < MIN_GAP_S:
                label = "between-ops"
            else:
                label = self.label_gap(s, e)
            by[label] = by.get(label, 0.0) + (e - s)
        return sorted(by.items(), key=lambda kv: -kv[1])

    def op_seconds(self, device=0):
        by = {}
        for name, s, e in self.devices[device].ops:
            by[name] = by.get(name, 0.0) + (e - s)
        return sorted(by.items(), key=lambda kv: -kv[1])

    def breakdown(self, n=10):
        return {"device_ops": [[k, v] for k, v in self.op_seconds()[:n]],
                "idle_gaps": [[k, v] for k, v in self.idle_by_label()[:n]]}


# ---------------------------------------------------------------------------
# reading the file
# ---------------------------------------------------------------------------

def _events(line):
    return [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for ev in line.events]


def _op_name(text):
    """`fusion.7` of `%fusion.7 = bf16[...] fusion(...)`."""
    return text.split(" = ", 1)[0].lstrip("%")


def load(path, n_devices=None, host_label=None):
    """The ReducedTrace of an `.xplane.pb`. `n_devices`: keep the first n
    chips (a one-chip cell on a four-chip host traces all four planes)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, annotations = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OP_LINE:
                    ops = [(_op_name(n), s, e) for n, s, e in _events(line)]
                elif line.name == MODULE_LINE:
                    modules = _events(line)
            devices.append(DeviceTrace(int(m.group(1)), ops, modules))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                annotations += [(n[len(ANNOTATION_PREFIX):], s, e)
                                for n, s, e in _events(line)
                                if n.startswith(ANNOTATION_PREFIX)]
    devices.sort(key=lambda d: d.index)
    if n_devices is not None:
        devices = devices[:n_devices]
    return ReducedTrace(devices, annotations, host_label)
