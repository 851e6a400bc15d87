"""Runtime concurrency analysis: the lock-order recorder (MXNET_DEBUG_SYNC).

The static half of the framework's analysis gate lives in
``tools/tpulint`` (AST checkers over the source tree); this module is the
*runtime* half: a lock acquisition-order recorder that turns the repo's
hardest concurrency rules into machine-checked facts instead of reviewer
folklore. Two deadlock classes have already been paid for by hand — the
cross-graph flush deadlock (PR 10) and the assist-vs-worker delivery race
(PR 12) — and both would have been a one-line report under this recorder.

What it checks, when ``MXNET_DEBUG_SYNC=1``:

* **Lock-order inversions.** Every tracked lock acquisition while another
  tracked lock is held records a directed edge ``held -> acquired`` in a
  process-global order graph. An acquisition that closes a cycle (the
  classic ABBA: thread 1 takes A then B, thread 2 takes B then A) is
  reported with BOTH stacks — the stack that first established the
  opposite ordering and the stack that just inverted it — so the report
  reads like the postmortem you would otherwise reconstruct from a hung
  fleet.
* **Blocking hazards.** Holding any tracked lock while entering an
  operation that can block on *other threads or hosts* — a lazy-segment
  flush (which compiles + runs a whole XLA program), a blocking
  collective barrier, or an engine drain — is a deadlock-in-waiting even
  when today's interleaving happens to work. Call sites mark such
  regions with :func:`check_blocking`; a non-empty held set is reported
  with the held-acquisition stacks and the blocking-entry stack.

Reports surface three ways: ``analysis.*`` telemetry counters (recorded
unconditionally once the gate is on, same discipline as ``compile.*``),
a structured health-journal event when the health layer is live, and the
:func:`report` / :func:`assert_clean` API the concurrency test suites
assert on (``ci/run.sh`` re-runs the serving/generation/lazy/elastic
suites under ``MXNET_DEBUG_SYNC=1`` and fails on any inversion).

Overhead discipline (the PR 7/11 rule: gates cost one attribute read when
off): the gate is evaluated when a lock is *created* — :func:`make_lock`
/ :func:`make_rlock` / :func:`make_condition` return plain
``threading`` primitives when the gate is off, so steady-state code pays
literally nothing, not even a flag check per acquire (pinned by
``test_tpulint.py`` in a fresh subprocess). :func:`check_blocking` call
sites gate on ``analysis._enabled`` (one attribute read) themselves.

Second runtime-analysis half (PR 15): **compiled-program summaries** —
:func:`program_summary` AOT-lowers a cached executable from its recorded
aval skeleton and parses the lowered StableHLO + post-optimization HLO
into a structured record: collective inventory (all-reduce / all-gather /
reduce-scatter / collective-permute counts and byte volumes), donation
audit (which ``tf.aliasing_output``-declared arguments actually got
``input_output_alias`` entries in the compiled module), and per-input
residency (global vs per-device local bytes from the compiled input
shardings). ``tools/hlolint`` enforces per-cache contracts over these
summaries (the blocking CI gate); ``CompileCache`` dumps them at exit
when ``MXNET_HLOLINT_DUMP`` is set. The parsers are pure text analysis —
no jax needed to *read* a summary, only to produce one.
"""
from __future__ import annotations

import sys
import threading

from . import telemetry
from .base import MXNetError, getenv, register_env

__all__ = ["enabled", "enable", "make_lock", "make_rlock", "make_condition",
           "check_blocking", "report", "assert_clean", "reset",
           "format_report",
           # compiled-program summaries (the hlolint substrate)
           "program_summary", "summarize_hlo_text", "parse_donated_args",
           "parse_io_aliases", "parse_collectives", "parse_num_partitions",
           "cache_inventory"]

register_env("MXNET_DEBUG_SYNC", False,
             "record lock acquisition order + blocking hazards; zero cost "
             "when off (locks are plain threading primitives)")

# THE gate — read at lock creation time (and by check_blocking call
# sites). Flipping it at runtime via enable() affects locks created
# afterwards; the CI reruns set the env var so every lock in the process
# is tracked from import.
_enabled = bool(getenv("MXNET_DEBUG_SYNC"))

_STACK_LIMIT = 16

# recorder state — one process-global order graph. _state_lock is a plain
# lock and is never itself tracked; the per-thread `busy` flag keeps the
# recorder's own bookkeeping (telemetry increments, journal writes) from
# re-entering the recorder.
_state_lock = threading.Lock()
_edges = {}        # (a, b) -> {count, held_stack, acquire_stack}
_order = {}        # a -> set of b (a held when b acquired)
_inversions = []   # deduped by unordered lock pair
_inv_seen = set()
_hazards = []      # deduped by (kind, held-name tuple)
_haz_seen = set()
_locks_seen = set()

_tls = threading.local()


def enabled():
    return _enabled


def enable(on=True):
    """Flip the gate at runtime. Only locks created AFTER the flip are
    tracked (module-level locks made at import stay plain) — tests use
    this; production runs set ``MXNET_DEBUG_SYNC=1`` in the environment."""
    global _enabled
    _enabled = bool(on)


def _thread_state():
    st = getattr(_tls, "state", None)
    if st is None:
        st = _tls.state = {"held": [], "busy": False}
    return st


def _stack(skip=2):
    """Lightweight stack capture: (file:line func) strings via a raw frame
    walk — no source-line reads, cheap enough for every tracked acquire."""
    try:
        f = sys._getframe(skip)
    except ValueError:  # pragma: no cover — shallow stack
        return []
    out = []
    while f is not None and len(out) < _STACK_LIMIT:
        code = f.f_code
        out.append(f"{code.co_filename}:{f.f_lineno} {code.co_name}")
        f = f.f_back
    return out


def _reaches(src, dst):
    """True when ``dst`` is reachable from ``src`` in the order graph
    (iterative DFS; called under _state_lock)."""
    stack, seen = [src], set()
    while stack:
        n = stack.pop()
        if n == dst:
            return True
        if n in seen:
            continue
        seen.add(n)
        stack.extend(_order.get(n, ()))
    return False


def _journal(event_kind, **detail):
    """Best-effort health-journal event (lazy import: health imports this
    module for its own locks)."""
    try:
        from . import health

        if health._enabled:
            health.event(event_kind, **detail)
    except Exception:  # noqa: BLE001 — the journal is additive
        pass


def _record_edge(a_name, a_stack, b_name, b_stack):
    """Called under the caller thread's busy guard; takes _state_lock."""
    if a_name == b_name:
        # two DISTINCT instances sharing a name (every Beacon is
        # "health.beacon", every prefix cache "generation.prefix_cache"):
        # order within a name class cannot be validated by name, and a
        # self-edge would instantly read as a bogus cycle — skip, the
        # same trade lockdep makes for same-class nesting
        return None
    key = (a_name, b_name)
    with _state_lock:
        rec = _edges.get(key)
        if rec is not None:
            rec["count"] += 1
            return None
        _edges[key] = {"count": 1, "held_stack": list(a_stack),
                       "acquire_stack": list(b_stack)}
        _order.setdefault(a_name, set()).add(b_name)
        telemetry.gauge("analysis.lock_edges").set(len(_edges))
        if not _reaches(b_name, a_name):
            return None
        # the new edge closes a cycle: the opposite ordering was already
        # observed. Report once per unordered pair, with both stacks —
        # the first-seen opposite edge's and this acquisition's.
        pair = frozenset((a_name, b_name))
        if pair in _inv_seen:
            return None
        _inv_seen.add(pair)
        rev = _edges.get((b_name, a_name))
        inv = {"first": b_name, "then": a_name,
               "held": a_name, "acquiring": b_name,
               "held_stack": list(a_stack),
               "acquire_stack": list(b_stack),
               "opposite_stack": (list(rev["acquire_stack"])
                                  if rev else []),
               "thread": threading.current_thread().name}
        _inversions.append(inv)
    telemetry.counter("analysis.lock_inversions").inc()
    return inv


def _note_acquire(lock):
    st = _thread_state()
    if st["busy"]:
        return
    st["busy"] = True
    try:
        held = st["held"]
        for entry in held:
            if entry[0] is lock:   # reentrant re-acquire: bump, no edge
                entry[2] += 1
                return
        stack = _stack(skip=3)
        inv = None
        if held:
            for other, other_stack, _n in held:
                got = _record_edge(other.name, other_stack, lock.name,
                                   stack)
                inv = inv or got
        else:
            with _state_lock:
                _locks_seen.add(lock.name)
        held.append([lock, stack, 1])
        if inv is not None:
            _journal("lock_inversion", held=inv["held"],
                     acquiring=inv["acquiring"], thread=inv["thread"])
    finally:
        st["busy"] = False


def _note_release(lock):
    st = _thread_state()
    if st["busy"]:
        return
    held = st["held"]
    for i in range(len(held) - 1, -1, -1):
        if held[i][0] is lock:
            held[i][2] -= 1
            if held[i][2] == 0:
                del held[i]
            return
    # release of a lock acquired before tracking began — ignore


class _TrackedLock:
    """``threading.Lock``/``RLock`` wrapper that feeds the order graph.
    Implements the Condition lock protocol (``_is_owned`` /
    ``_release_save`` / ``_acquire_restore``) so
    ``threading.Condition(_TrackedLock(...))`` keeps bookkeeping balanced
    across ``wait()``."""

    __slots__ = ("name", "_lock", "_reentrant")

    def __init__(self, name, reentrant=False):
        self.name = name
        self._reentrant = reentrant
        self._lock = threading.RLock() if reentrant else threading.Lock()
        with _state_lock:
            _locks_seen.add(name)

    def acquire(self, blocking=True, timeout=-1):
        ok = self._lock.acquire(blocking, timeout)
        if ok:
            _note_acquire(self)
        return ok

    def release(self):
        _note_release(self)
        self._lock.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()

    def locked(self):
        inner = getattr(self._lock, "locked", None)
        if inner is not None:
            return inner()
        # threading.RLock grows locked() only in 3.13 — probe instead so
        # the tracked wrapper stays drop-in on 3.10 (an owned-by-us RLock
        # reports False, same blind spot the acquire-probe always had)
        if self._lock.acquire(False):
            self._lock.release()
            return False
        return True

    # -- Condition lock protocol -------------------------------------------

    def _is_owned(self):
        if self._reentrant:
            return self._lock._is_owned()
        # plain-Lock fallback (what Condition would do itself)
        if self._lock.acquire(False):
            self._lock.release()
            return False
        return True

    def _release_save(self):
        if not self._reentrant:
            _note_release(self)
            self._lock.release()
            return None
        # fully drop a possibly-recursive hold; remember our bookkeeping
        # count so _acquire_restore can rebuild it
        st = _thread_state()
        count = 0
        for i in range(len(st["held"]) - 1, -1, -1):
            if st["held"][i][0] is self:
                count = st["held"][i][2]
                del st["held"][i]
                break
        return (self._lock._release_save(), count)

    def _acquire_restore(self, state):
        if not self._reentrant:
            self._lock.acquire()
            _note_acquire(self)
            return
        inner, count = state
        self._lock._acquire_restore(inner)
        _note_acquire(self)
        if count > 1:
            st = _thread_state()
            for entry in st["held"]:
                if entry[0] is self:
                    entry[2] = count
                    break

    def __repr__(self):
        return f"<TrackedLock {self.name!r} reentrant={self._reentrant}>"


# ---------------------------------------------------------------------------
# factories — THE api instrumented modules use
# ---------------------------------------------------------------------------


def make_lock(name):
    """A mutex for subsystem ``name`` ("generation.tick"): plain
    ``threading.Lock`` when the gate is off, tracked when on."""
    if _enabled:
        return _TrackedLock(name)
    return threading.Lock()


def make_rlock(name):
    """Reentrant variant; only the outermost acquire records an edge."""
    if _enabled:
        return _TrackedLock(name, reentrant=True)
    return threading.RLock()


def make_condition(name):
    """``threading.Condition`` whose underlying lock is tracked; ``wait``
    releases/re-acquires through the recorder so held-state stays exact."""
    if _enabled:
        return threading.Condition(_TrackedLock(name, reentrant=True))
    return threading.Condition()


def check_blocking(kind, exempt=()):
    """Record a blocking hazard if this thread holds any tracked lock
    while entering blocking region ``kind`` ("lazy.flush",
    "collective.barrier", "engine.wait_all"). ``exempt`` lists lock
    objects that are legitimately held (e.g. the lazy graph's own lock
    around its flush). Call sites gate on ``analysis._enabled`` first."""
    if not _enabled:
        return None
    st = _thread_state()
    if st["busy"]:
        return None
    held = [e for e in st["held"] if e[0] not in exempt]
    if not held:
        return None
    st["busy"] = True
    try:
        names = tuple(e[0].name for e in held)
        stack = _stack(skip=2)
        with _state_lock:
            key = (kind, names)
            if key in _haz_seen:
                for h in _hazards:
                    if h["kind"] == kind and tuple(h["held"]) == names:
                        h["count"] += 1
                        break
                return None
            _haz_seen.add(key)
            haz = {"kind": kind, "held": list(names), "count": 1,
                   "held_stacks": [list(e[1]) for e in held],
                   "blocking_stack": stack,
                   "thread": threading.current_thread().name}
            _hazards.append(haz)
        telemetry.counter("analysis.blocking_hazards").inc()
        _journal("lock_blocking_hazard", kind=kind, held=list(names))
        return haz
    finally:
        st["busy"] = False


# ---------------------------------------------------------------------------
# reports
# ---------------------------------------------------------------------------


def report():
    """Snapshot: {enabled, locks, edges, inversions, hazards}. ``edges``
    is the observed acquisition-order list (a, b, count); ``inversions``
    and ``hazards`` carry both stacks each (see module docstring)."""
    with _state_lock:
        return {
            "enabled": _enabled,
            "locks": sorted(_locks_seen),
            "edges": sorted((a, b, rec["count"])
                            for (a, b), rec in _edges.items()),
            "inversions": [dict(i) for i in _inversions],
            "hazards": [dict(h) for h in _hazards],
        }


def clean():
    """True when no inversion or blocking hazard has been recorded."""
    with _state_lock:
        return not _inversions and not _hazards


def format_report(rep=None):
    """Human-readable rendering of :func:`report` — what the CI rerun
    prints on failure and what `tools/telemetry_report.py` summarizes."""
    rep = rep or report()
    lines = [f"lock-order analysis: {len(rep['locks'])} locks, "
             f"{len(rep['edges'])} order edges, "
             f"{len(rep['inversions'])} inversions, "
             f"{len(rep['hazards'])} blocking hazards"]
    for inv in rep["inversions"]:
        lines.append(f"\nINVERSION: held {inv['held']!r} while acquiring "
                     f"{inv['acquiring']!r} (thread {inv['thread']}), but "
                     f"the opposite order {inv['acquiring']!r} -> "
                     f"{inv['held']!r} was already established")
        lines.append("  stack holding %r:" % inv["held"])
        lines.extend("    " + s for s in inv["held_stack"][:8])
        lines.append("  stack acquiring %r:" % inv["acquiring"])
        lines.extend("    " + s for s in inv["acquire_stack"][:8])
        if inv["opposite_stack"]:
            lines.append("  stack that established the opposite order:")
            lines.extend("    " + s for s in inv["opposite_stack"][:8])
    for haz in rep["hazards"]:
        lines.append(f"\nBLOCKING HAZARD: {haz['held']} held entering "
                     f"{haz['kind']!r} (thread {haz['thread']}, "
                     f"seen {haz['count']}x)")
        lines.append("  blocking-entry stack:")
        lines.extend("    " + s for s in haz["blocking_stack"][:8])
        for name, st in zip(haz["held"], haz["held_stacks"]):
            lines.append(f"  stack holding {name!r}:")
            lines.extend("    " + s for s in st[:8])
    return "\n".join(lines)


def assert_clean():
    """Raise :class:`MXNetError` with the full report when any inversion
    or hazard was recorded — the concurrency suites' session-end check."""
    if not clean():
        raise MXNetError("lock-order analysis found violations:\n"
                         + format_report())


def reset():
    """Clear the order graph and reports (tests; the per-thread held
    stacks are left alone — live locks stay balanced)."""
    with _state_lock:
        _edges.clear()
        _order.clear()
        _inversions.clear()
        _inv_seen.clear()
        _hazards.clear()
        _haz_seen.clear()
        _locks_seen.clear()


# ===========================================================================
# Compiled-program summaries — the hlolint substrate (PR 15)
# ===========================================================================
#
# tpulint checks what we WROTE; these helpers check what XLA actually
# COMPILED. The repo's two worst recent bugs (the jax-0.4.37
# mixed-sharded-concat miscompile and the pipeline grad-scaling bug)
# lived exclusively in the lowered program, and every 1/N-bytes claim in
# ROADMAP is asserted by measuring buffers — a program summary makes the
# same contracts checkable from the executable itself.

import re as _re

# dtype token -> bytes per element, the HLO shape-token vocabulary
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

_COLLECTIVE_KINDS = ("all-reduce", "all-gather", "reduce-scatter",
                     "collective-permute", "all-to-all")

# `%x = f32[64,8]{1,0} all-gather(...)` or a tuple-shaped result
# `%x = (f32[64,8]{1,0}, f32[4]{0}) all-reduce-start(...)`. The optional
# -start suffix counts the async form once; -done deliberately does not
# match (it would double-count).
_COLL_RE = _re.compile(
    r"=\s*(\([^)]*\)|\S+)\s+"
    r"(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)"
    r"(?:-start)?\(")

_SHAPE_RE = _re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")

# one `{out_index}: (param, {param_index}, kind)` pair in the HloModule
# header's input_output_alias map
_ALIAS_RE = _re.compile(
    r"\{([0-9, ]*)\}:\s*\(([0-9]+),\s*\{[0-9, ]*\},\s*([a-z-]+)\)")

# `%arg3: tensor<8x4xf32> {tf.aliasing_output = 0 : i32}` in the
# lowered StableHLO @main signature (the tensor type is captured so the
# donation audit can size each declared argument WITHOUT trusting any
# aval alignment — jax drops unused args from the lowering, which shifts
# every later index). The attr-dict matcher must cross braces inside
# QUOTED values: a donated arg with an explicit layout lowers as
# `{mhlo.sharding = "{devices=[4,1]<=[4]}", tf.aliasing_output = 0 :
# i32}`, and a naive [^{}]* group would drop the donation marker of
# exactly the sharded programs the audit exists to protect.
# One char or one quoted string. No nested quantifier, so a dict the
# pattern cannot close fails in linear time (`(?:[^{}"]+|...)*` took 2^n
# on the first unquoted nested brace and hung the audit).
_ATTR_ATOM = r"[^{}\"]|\"[^\"]*\""
_STABLEHLO_ARG_RE = _re.compile(
    r"%arg(\d+):\s*tensor<([^>]*)>\s*"
    # the attr dict, with ONE level of nested braces: Shardy spells a
    # sharding `#sdy.sharding<@mesh, [{"dp"}]>` inside it
    r"(\{(?:" + _ATTR_ATOM + r"|\{(?:" + _ATTR_ATOM + r")*\})*\})?")

_MLIR_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "f8E4M3FN": 1, "f8E5M2": 1,
}


def _mlir_tensor_bytes(type_str):
    """Byte size of one MLIR tensor type string (``8x4xf32`` -> 128;
    scalar ``f32`` -> 4; unknown/dynamic dims count large so a failed
    parse is never silently excused)."""
    parts = type_str.strip().split("x")
    dtype = parts[-1]
    n = 1
    for d in parts[:-1]:
        if not d.isdigit():
            return 1 << 62
        n *= int(d)
    return n * _MLIR_DTYPE_BYTES.get(dtype, 4)


def _shape_token_bytes(token):
    """Byte size of one HLO shape token (``f32[64,8]{1,0}`` -> 2048;
    tuples sum their components; unknown dtypes count 4)."""
    total = 0
    for dt, dims in _SHAPE_RE.findall(token):
        n = 1
        if dims:
            for d in dims.split(","):
                if d.strip():
                    n *= int(d)
        total += n * _HLO_DTYPE_BYTES.get(dt, 4)
    return total


def parse_collectives(hlo_text, max_lines=24):
    """Collective inventory of one post-optimization HLO module:
    ``{kind: {"count": n, "bytes": total}}`` plus up to ``max_lines``
    trimmed op lines (the ``--explain`` evidence). Bytes are the op's
    RESULT shape — the per-participant payload the collective moves."""
    kinds = {}
    lines = []
    for line in hlo_text.splitlines():
        m = _COLL_RE.search(line)
        if m is None:
            continue
        shape_tok, kind = m.group(1), m.group(2)
        ent = kinds.setdefault(kind, {"count": 0, "bytes": 0})
        ent["count"] += 1
        ent["bytes"] += _shape_token_bytes(shape_tok)
        if len(lines) < max_lines:
            lines.append(line.strip()[:240])
    return kinds, lines


def parse_io_aliases(hlo_text):
    """The compiled module's ``input_output_alias`` entries from the
    HloModule header line: ``[{"output": "0", "param": 2, "kind":
    "may-alias"}, ...]`` — the ground truth of which donations actually
    aliased."""
    for line in hlo_text.splitlines():
        if "input_output_alias=" not in line:
            continue
        start = line.index("input_output_alias=")
        return [{"output": out.strip(), "param": int(param), "kind": kind}
                for out, param, kind in _ALIAS_RE.findall(line[start:])]
    return []


def parse_donated_args(stablehlo_text):
    """Declared donations in the lowered StableHLO ``@main`` signature:
    ``{arg_index: {"output": aliased_output_or_None, "bytes": n}}``.
    ``tf.aliasing_output`` marks an argument jax pre-matched to an
    output; ``jax.buffer_donor`` marks a donated buffer left for XLA to
    alias at compile time. A donation that produced NEITHER marker was
    dropped at lowering (the silent 2x-memory case); whether a marked one
    actually aliased is answered by the compiled module's
    ``input_output_alias`` header (:func:`parse_io_aliases`)."""
    start = stablehlo_text.find("@main(")
    if start < 0:
        return {}
    end = stablehlo_text.find(" {\n", start)
    region = stablehlo_text[start:end if end > 0 else len(stablehlo_text)]
    out = {}
    for idx, type_str, attrs in _STABLEHLO_ARG_RE.findall(region):
        if not attrs:
            continue
        m = _re.search(r"tf\.aliasing_output\s*=\s*(\d+)", attrs)
        if m is not None:
            out[int(idx)] = {"output": int(m.group(1)),
                             "bytes": _mlir_tensor_bytes(type_str)}
        elif "jax.buffer_donor" in attrs:
            out[int(idx)] = {"output": None,
                             "bytes": _mlir_tensor_bytes(type_str)}
    return out


def summarize_hlo_text(stablehlo_text, hlo_text):
    """Structured summary of one lowered+compiled program (pure text
    parsing — callable on dumped artifacts without jax)."""
    collectives, lines = parse_collectives(hlo_text)
    declared = parse_donated_args(stablehlo_text)
    aliased = parse_io_aliases(hlo_text)
    aliased_params = {a["param"] for a in aliased}
    unaliased = sorted(i for i in declared if i not in aliased_params)
    return {
        "collectives": collectives,
        "collective_bytes": sum(v["bytes"] for v in collectives.values()),
        "collective_lines": lines,
        "donation": {
            "declared": sorted(declared),
            # JSON object keys are strings — keep them so a dumped
            # summary and a live one read identically
            "declared_bytes": {str(i): d["bytes"]
                               for i, d in declared.items()},
            "aliased": aliased,
            "unaliased": unaliased,
        },
    }


def _input_rows(avals, shardings):
    """Per-input residency rows: global bytes from the recorded aval
    skeleton, replication + per-device local bytes from the compiled
    input shardings (aligned leaf-by-leaf over the SAME tree structure;
    an UNSPECIFIED sharding is ``None``, which is a pytree-empty value —
    it must be kept as a leaf or every later input's sharding shifts).
    A residual mismatch degrades to global-only rows."""
    import jax

    def keep(x):
        # None (unspecified sharding / empty state slot) stays positional
        return x is None or not isinstance(x, (list, tuple, dict))

    aval_all = jax.tree_util.tree_leaves(avals, is_leaf=keep)
    shard_leaves = []
    if shardings is not None:
        try:
            shard_leaves = jax.tree_util.tree_leaves(shardings,
                                                     is_leaf=keep)
        except Exception:  # noqa: BLE001 — residency rows are best-effort
            shard_leaves = []
    if len(shard_leaves) != len(aval_all):
        shard_leaves = [None] * len(aval_all)
    pairs = [(a, s) for a, s in zip(aval_all, shard_leaves)
             if hasattr(a, "shape") and hasattr(a, "dtype")]
    rows = []
    for a, s in pairs:
        n = 1
        for d in a.shape:
            n *= int(d)
        nbytes = n * a.dtype.itemsize
        row = {"shape": tuple(int(d) for d in a.shape),
               "dtype": str(a.dtype), "bytes": int(nbytes)}
        if s is not None and hasattr(s, "device_set"):
            try:
                row["replicated"] = bool(s.is_fully_replicated)
                local = s.shard_shape(a.shape)
                ln = 1
                for d in local:
                    ln *= int(d)
                row["local_bytes"] = int(ln * a.dtype.itemsize)
                row["devices"] = len(s.device_set)
            except Exception:  # noqa: BLE001 — exotic sharding types
                pass
        rows.append(row)
    return rows


_NUM_PARTITIONS_RE = _re.compile(r"num_partitions\s*=\s*(\d+)")


def parse_num_partitions(stablehlo_text):
    """The SPMD partition count from the lowered module's
    ``mhlo.num_partitions`` attribute (1 when absent) — the authoritative
    device count of the compiled program, independent of input-sharding
    introspection."""
    m = _NUM_PARTITIONS_RE.search(stablehlo_text)
    return int(m.group(1)) if m else 1


def program_summary(fn, avals):
    """AOT-lower one cached executable from its recorded aval skeleton
    and summarize the compiled program: collective inventory, donation
    audit, per-input residency, device count. ``fn`` may be the
    ``CompileCache`` first-call wrapper (its ``_fn`` is unwrapped) or a
    bare ``jax.jit`` callable; ``avals`` is ``(args, kwargs)`` of
    ``ShapeDtypeStruct``\\ s.

    NOTE the lowering can be a FULL recompile, so this never
    runs on a step path: only the ``MXNET_HLOLINT_DUMP`` exit hook, the
    bench inventory stamp, and tests call it."""
    from . import compile_cache as _cc

    target = getattr(fn, "_fn", fn)
    if not hasattr(target, "lower"):
        return {"error": "unlowerable (no .lower on target)"}
    args, kwargs = avals
    with _cc.donation_warnings_suppressed():
        lowered = target.lower(*args, **kwargs)
        stablehlo_text = lowered.as_text()
        compiled = lowered.compile()
        hlo_text = compiled.as_text()
    summary = summarize_hlo_text(stablehlo_text, hlo_text)
    shardings = None
    try:
        shardings = compiled.input_shardings
    except Exception:  # noqa: BLE001 — residency degrades, audit survives
        pass
    summary["inputs"] = _input_rows((args, kwargs), shardings)
    summary["num_devices"] = max(
        [parse_num_partitions(stablehlo_text)]
        + [r.get("devices", 1) for r in summary["inputs"]])
    return summary


def cache_inventory(name):
    """Aggregate collective inventory over every LIVE
    :class:`~mxnet_tpu.compile_cache.CompileCache` named ``name``, from
    each entry's recorded first-call avals (``track_memory=True`` caches
    only). Re-lowers (and for donated entries recompiles) each program —
    bench/report tooling, never a step path. Returns ``{"entries": n,
    "collective_bytes": total, "collectives": {kind: {count, bytes}},
    "errors": n}``."""
    from . import compile_cache as _cc

    agg, total, entries, errors = {}, 0, 0, 0
    for cache in _cc.all_caches():
        if cache.name != name:
            continue
        for key in list(cache._entry_stats):
            st = cache._entry_stats.get(key)
            fn = cache._entries.get(key)
            if st is None or fn is None:
                continue
            try:
                summary = program_summary(fn, st["avals"])
            except Exception:  # noqa: BLE001 — inventory is best-effort
                errors += 1
                continue
            if "error" in summary:
                errors += 1
                continue
            entries += 1
            total += summary["collective_bytes"]
            for kind, v in summary["collectives"].items():
                ent = agg.setdefault(kind, {"count": 0, "bytes": 0})
                ent["count"] += v["count"]
                ent["bytes"] += v["bytes"]
    return {"entries": entries, "collective_bytes": total,
            "collectives": agg, "errors": errors}
