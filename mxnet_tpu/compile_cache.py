"""CompileCache — the explicit, observable jit-executable cache.

The reference amortizes graph setup through CachedOp's signature-keyed
graph cache (`src/imperative/cached_op.cc` `SetForwardGraph`:295 — shape/
dtype of every input is the key). Here the executables are `jax.jit`
callables, and before this module they were held in anonymous
`functools.lru_cache`s: a bucketing run or a partial last batch that
churned shapes recompiled *silently*, which is exactly the failure mode
BENCH_r05 could not attribute. Every compiled-callable cache in the
framework (symbol executors, CachedOp, the fused train step, the fused
optimizer update) now lives in a named :class:`CompileCache`, so the
registry answers the three questions a perf round asks:

* how many distinct programs exist (``compile.cache_entries`` gauge),
* how often a step re-used one (``compile.cache_hits`` /
  ``compile.cache_misses`` counters),
* how long the misses cost (``compile.seconds`` counter — the first
  invocation of a cached callable is timed: jax traces + XLA-compiles
  synchronously on first call, so first-call time ≈ compile time).

Counters are recorded unconditionally (one lock-protected increment per
step — noise next to a dispatch) so cache accounting works even when the
wider telemetry plane is off.

What jax compiles on its own — a re-trace of a cached callable whose
argument changed layout, commitment or weak type, an eager op's program —
never passes through ``get_or_build``, so the ledger above cannot see it
(PR 21: 40.7 s at step 2, unseen). ``jax.monitoring`` duration listeners,
registered once at import, count those too: ``compile.jax_traces`` (every
jaxpr trace, including a re-trace that then hits jax's executable cache),
``compile.jax_backend_compiles`` (XLA compiles and persistent-cache loads)
and ``compile.jax_seconds``; :func:`jax_events` keeps the last few
thousand with their end times, so a stall can be matched to a re-trace.

Persistent on-disk XLA cache: placed from OUTSIDE the program. When
``JAX_COMPILATION_CACHE_DIR`` is set jax itself reads it and nothing here
sets a directory; otherwise the cache lives at one fixed path inside the
checkout (``.jax_cache/`` beside the package — the path is part of a cache
key, so a directory that moves never hits). A program compiled once is
deserialized, not re-built, by every later process; donated programs
included.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
import warnings
import weakref

from . import telemetry
from .base import getenv, register_env

__all__ = ["CompileCache", "persistent_cache_dir", "stats", "named_stats",
           "name_totals", "all_caches", "donation_warnings_suppressed",
           "dump_audit", "audit_ledger", "jax_events"]

register_env("MXNET_FUSED_STEP", True,
             "fuse forward+backward+optimizer update into one jitted XLA "
             "computation per step (0 falls back to the eager per-op path)")
register_env("MXNET_HLOLINT_DUMP", "",
             "directory for compiled-program audit dumps: at process exit "
             "every audited cache entry's program summary (collective "
             "inventory, donation aliasing, residency) is written as JSON "
             "for the tools/hlolint contract gate")
register_env("MXNET_HLOLINT_CACHES", "spmd,zero1,pipeline,serving,"
             "generation,lazy",
             "comma-separated audit tags recorded for the hlolint dump "
             "(a cache entry's tag is its get_or_build audit= label, "
             "defaulting to the cache name)")
register_env("MXNET_HLOLINT_MAX_ENTRIES", 16,
             "per-tag cap on audited entries in one process (each dump "
             "entry re-lowers — and for donated programs recompiles — "
             "the executable at exit)")

_caches = weakref.WeakSet()
_caches_lock = threading.Lock()

# hlolint audit ledger (MXNET_HLOLINT_DUMP): strong refs to the first
# MXNET_HLOLINT_MAX_ENTRIES executables per audit tag, recorded at first
# call so the exit hook can AOT-lower them after the suites that warmed
# them have let their per-context caches die. Empty (and never appended
# to) when the env var is unset — steady state pays one getenv per MISS.
_audit_lock = threading.Lock()
_audit_ledger = {}   # (tag, repr(key)) -> {cache, tag, key, fn, avals}
_audit_hooked = [False]

# monotonic per-NAME hit/miss/compile-time totals, surviving cache GC —
# `named_stats("serving")` must answer "did steady state compile anything?"
# with a counter that can only grow, not a sum over whatever instances
# happen to still be alive (a collected Predictor would silently subtract
# its history and break delta-based zero-compile assertions)
_name_totals = {}


# jax's own trace/compile events (jax.monitoring), newest last
_JAX_EVENT_KINDS = {
    "/jax/core/compile/jaxpr_trace_duration":
        ("jaxpr_trace", "compile.jax_traces"),
    "/jax/core/compile/backend_compile_duration":
        ("backend_compile", "compile.jax_backend_compiles"),
}
_jax_events = collections.deque(maxlen=16384)


def _on_jax_duration(event, duration, **_):
    kind = _JAX_EVENT_KINDS.get(event)
    if kind is None:
        return
    telemetry.counter(kind[1]).inc()
    telemetry.counter("compile.jax_seconds").inc(duration)
    _jax_events.append((time.perf_counter(), kind[0], duration))


def jax_events():
    """``(perf_counter at end, kind, seconds)`` of jax's own recent traces
    (``"jaxpr_trace"``) and XLA compiles or persistent-cache loads
    (``"backend_compile"``), oldest first, bounded."""
    return list(_jax_events)


def _totals(name):
    with _caches_lock:
        t = _name_totals.get(name)
        if t is None:
            t = _name_totals[name] = {"hits": 0, "misses": 0,
                                      "compile_seconds": 0.0}
        return t


@contextlib.contextmanager
def donation_warnings_suppressed():
    """jax warns when donated buffers cannot be consumed (the CPU backend
    ignores donation). The fused paths donate unconditionally — on TPU
    donation is the point (in-place weight updates), on CPU a harmless
    no-op — so their call sites wrap invocations in this scope instead of
    installing a process-global filter that would also silence the signal
    for a user's own jax code."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def persistent_cache_dir():
    """Place jax's persistent compilation cache (idempotent; called at
    import) and return its directory. ``JAX_COMPILATION_CACHE_DIR`` set:
    jax has already read it and this sets nothing. Unset: the one fixed
    in-checkout path."""
    external = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if external:
        return external
    import jax

    os.makedirs(_CHECKOUT_CACHE_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", _CHECKOUT_CACHE_DIR)
    return _CHECKOUT_CACHE_DIR


def _avals_of(args, kwargs):
    """Shape/dtype/sharding skeleton of one call's arguments — enough to
    AOT-lower the SAME program again (an array's sharding decides how the
    program is partitioned and where it runs) without keeping a buffer
    alive."""
    import jax

    def aval(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=x.sharding)
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(aval, (tuple(args), dict(kwargs)))


def _entries_gauge():
    """Recompute the live-entry gauge over every live cache."""
    with _caches_lock:
        total = sum(len(c) for c in _caches)
    telemetry.gauge("compile.cache_entries").set(total)


class CompileCache:
    """A named map ``key -> compiled callable`` with hit/miss/compile-time
    accounting. ``key`` is any hashable — by convention the full shape
    signature (shape+dtype of every input) plus whatever static
    configuration the builder closes over (train flag, optimizer
    fingerprint), the CachedOp signature-match model."""

    def __init__(self, name, maxsize=None, track_memory=True):
        self.name = name
        self.maxsize = maxsize
        # track_memory=False skips first-call aval recording, keeping this
        # cache OUT of executable_stats()/the /memory scrape — the per-op
        # caches hold hundreds of tiny one-op programs whose per-entry AOT
        # memory analysis would cost a recompile each for no insight
        self.track_memory = track_memory
        self.hits = 0
        self.misses = 0
        self.compile_seconds = 0.0
        self._name_totals = _totals(name)
        self._entries = {}
        # key -> seconds of the entry's first call (trace + XLA compile, or
        # a persistent-cache read): what a cold run pays per program
        self.first_call_seconds = {}
        # key -> {"avals": first-call abstract shapes, "memory": analysis}
        # (shape/dtype skeletons only — never holds buffers alive)
        self._entry_stats = {}
        self._lock = threading.Lock()
        with _caches_lock:
            _caches.add(self)

    def __len__(self):
        return len(self._entries)

    def keys(self):
        return list(self._entries.keys())

    def get_or_build(self, key, build, audit=None):
        """The cached callable for ``key``; on miss, ``build()`` makes one
        (typically a ``jax.jit`` closure) and its first invocation is timed
        into ``compile.seconds``.

        ``audit`` names the hlolint contract row this entry is audited
        under (``MXNET_HLOLINT_DUMP`` / ``tools/hlolint``); it defaults to
        the cache name. The fused train step passes the composition that
        actually built the program ("spmd"/"pipeline"/"zero1"/
        "fused_step") since those share the executor-side caches.
        """
        fn = self._entries.get(key)
        if fn is not None:
            self.hits += 1
            self._name_totals["hits"] += 1
            telemetry.counter("compile.cache_hits").inc()
            if self.maxsize is not None:
                # LRU, not FIFO: refresh position so overflow evicts a COLD
                # entry, never the per-step executable hit every iteration
                with self._lock:
                    if key in self._entries:
                        self._entries[key] = self._entries.pop(key)
            return fn
        with self._lock:
            fn = self._entries.get(key)
            if fn is not None:
                self.hits += 1
                self._name_totals["hits"] += 1
                telemetry.counter("compile.cache_hits").inc()
                return fn
            self.misses += 1
            self._name_totals["misses"] += 1
            telemetry.counter("compile.cache_misses").inc()
            if self.hits > 0 and self._entries:
                # a STEADY-STATE miss: this cache has already served hits,
                # so a new key means something about the workload changed —
                # blame the axis instead of burning the budget silently
                _blame_miss(self.name, key, self._entries)
            fn = self._wrap_first_call(build(), key, audit)
            if self.maxsize is not None and len(self._entries) >= self.maxsize:
                # drop the least-recently-used entry — executables are
                # re-buildable, never precious
                evicted = next(iter(self._entries))
                self._entries.pop(evicted)
                self._entry_stats.pop(evicted, None)
                self.first_call_seconds.pop(evicted, None)
                try:
                    from . import health

                    if health._enabled:
                        # an eviction at steady state means the next use
                        # of that key RECOMPILES — exactly the sequence a
                        # postmortem wants in the journal
                        health.event("compile_cache_evict",
                                     cache=self.name,
                                     entries=len(self._entries))
                except Exception:  # noqa: BLE001 — journal is additive
                    pass
            self._entries[key] = fn
        _entries_gauge()
        return fn

    def _record_avals(self, key, args, kwargs):
        """Shape/dtype skeleton of the first call — enough to re-lower the
        program for XLA memory analysis (`memory_stats`) without keeping a
        single buffer alive."""
        try:
            self._entry_stats[key] = {
                "avals": _avals_of(args, kwargs),
                "memory": None, "collectives": None}
        except Exception:  # noqa: BLE001 — stats are additive, never fatal
            pass

    def entry_memory(self, key, _want_collectives=False):
        """XLA compiled-memory analysis for one entry: {argument_bytes,
        output_bytes, temp_bytes, peak_bytes} or None. Computed LAZILY via
        an AOT `lower().compile()` pass over the recorded avals and
        memoized (failures too); never runs on the step path. NOTE the
        first computation can be a FULL recompile, not just a re-trace:
        the AOT path bypasses jax's jit dispatch cache — budget seconds
        per entry on the first scrape of a big cache."""
        st = self._entry_stats.get(key)
        if st is None:
            return None
        if st["memory"] is not None and not (
                _want_collectives and st.get("collectives") is None):
            return st["memory"] or None  # False = memoized FAILED analysis
        fn = self._entries.get(key)
        target = getattr(fn, "_fn", fn)
        if not hasattr(target, "lower"):
            return None
        try:
            args, kwargs = st["avals"]
            with donation_warnings_suppressed():
                compiled = target.lower(*args, **kwargs).compile()
            ma = compiled.memory_analysis()
            # the collective inventory needs the full post-optimization
            # HLO TEXT, which is expensive to serialise and parse for big
            # programs, so it is extracted only when entry_collectives
            # asked for it (the /memory scrape sweeps every entry and must
            # stay as cheap as plain memory_analysis)
            if _want_collectives:
                try:
                    from . import analysis

                    kinds, _ = analysis.parse_collectives(compiled.as_text())
                    st["collectives"] = {k: dict(v)
                                         for k, v in kinds.items()}
                except Exception:  # noqa: BLE001 — inventory best-effort
                    st["collectives"] = False
            st["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
                # resident working set while the program runs: inputs +
                # outputs + temporaries, minus buffers aliased in place
                # (donation) — the per-executable peak-HBM estimate
                "peak_bytes": int(ma.argument_size_in_bytes
                                  + ma.output_size_in_bytes
                                  + ma.temp_size_in_bytes
                                  - ma.alias_size_in_bytes)}
        except Exception:  # noqa: BLE001 — analysis is best-effort
            st["memory"] = False  # memoize the failure: the AOT lowering
            st["collectives"] = st.get("collectives") or False
            return None           # is expensive and will not get better
        return st["memory"]

    def entry_collectives(self, key):
        """Collective inventory of one entry's COMPILED program
        (``{kind: {count, bytes}}``, bytes per participant) or None —
        recorded by the shared AOT pass on demand (an entry first scanned
        by a plain memory scrape pays one extra lowering here); same
        parser as the hlolint audit."""
        st = self._entry_stats.get(key)
        if st is None:
            return None
        if st.get("collectives") is None:
            self.entry_memory(key, _want_collectives=True)
        coll = st.get("collectives")
        return coll if coll not in (None, False) else None

    def memory_stats(self, compute=False):
        """Per-entry memory rows for this cache: entries whose analysis
        has been computed (``compute=True`` forces the lazy analysis for
        every entry first). Rows: {key, argument_bytes, ...}."""
        rows = []
        for key in list(self._entry_stats):
            st = self._entry_stats.get(key)
            if st is None:
                continue
            mem = self.entry_memory(key) if compute else st["memory"]
            if mem:  # None = not computed, False = memoized failure
                rows.append(dict(mem, key=repr(key)))
        return rows

    def _wrap_first_call(self, fn, key=None, audit=None):
        cache = self

        class _Timed:
            """First call runs under a timer (trace + XLA compile happen
            synchronously there) with the jax donation warning suppressed;
            later calls go straight through."""

            __slots__ = ("_fn", "_first")

            def __init__(self):
                self._fn = fn
                self._first = True

            def __call__(self, *args, **kwargs):
                if self._first:
                    t0 = time.perf_counter()
                    with donation_warnings_suppressed():
                        out = self._fn(*args, **kwargs)
                    # only now: a FAILED first call must retry with the
                    # accounting intact (another caller can hit this
                    # shared entry after one caller's trace error)
                    self._first = False
                    if key is not None and cache.track_memory:
                        cache._record_avals(key, args, kwargs)
                    if key is not None and getenv("MXNET_HLOLINT_DUMP"):
                        _audit_record(cache, audit or cache.name, key,
                                      self, args, kwargs)
                    dt = time.perf_counter() - t0
                    if key is not None:
                        cache.first_call_seconds[key] = dt
                    cache.compile_seconds += dt
                    cache._name_totals["compile_seconds"] += dt
                    telemetry.counter("compile.seconds").inc(dt)
                    telemetry.histogram("compile.first_call_us").record(dt * 1e6)
                    return out
                return self._fn(*args, **kwargs)

        return _Timed()

    def clear(self):
        with self._lock:
            self._entries.clear()
        _entries_gauge()

    def snapshot(self):
        return {"name": self.name, "entries": len(self._entries),
                "hits": self.hits, "misses": self.misses,
                "compile_seconds": self.compile_seconds}


def all_caches():
    """Live :class:`CompileCache` instances."""
    with _caches_lock:
        return list(_caches)


def stats():
    """Aggregate {entries, hits, misses, compile_seconds} over live caches
    plus a per-cache breakdown (`tools/telemetry_report.py` prints this)."""
    per = [c.snapshot() for c in all_caches()]
    return {"entries": sum(p["entries"] for p in per),
            "hits": sum(p["hits"] for p in per),
            "misses": sum(p["misses"] for p in per),
            "compile_seconds": sum(p["compile_seconds"] for p in per),
            "caches": sorted(per, key=lambda p: p["name"])}


def name_totals():
    """{name: {hits, misses, compile_seconds, entries}} for EVERY cache
    name ever seen — the monotonic per-name ledger behind
    :func:`named_stats`, in one map. ``entries`` counts currently-live
    executables. `telemetry.snapshot()` embeds this as the
    ``compile_caches`` section so op-level (``op_eager``/``op_vjp``),
    segment-level (``lazy``) and subsystem caches all read the same way in
    ``tools/telemetry_report.py``."""
    with _caches_lock:
        totals = {n: dict(t) for n, t in _name_totals.items()}
        live = list(_caches)
    for t in totals.values():
        t["entries"] = 0
    for c in live:
        t = totals.get(c.name)
        if t is not None:
            t["entries"] += len(c)
    return totals


def named_stats(name):
    """The per-subsystem view of :func:`stats` for every cache ever named
    ``name`` (e.g. ``named_stats("serving")`` answers "did steady-state
    traffic compile anything?" without counting the training-side
    executors that share the process). ``hits``/``misses``/
    ``compile_seconds`` are MONOTONIC process-lifetime totals — a
    garbage-collected cache keeps its contribution, so deltas are safe to
    assert on; ``entries``/``caches`` describe the currently-live ones."""
    per = [c.snapshot() for c in all_caches() if c.name == name]
    totals = _totals(name)
    return {"entries": sum(p["entries"] for p in per),
            "hits": totals["hits"],
            "misses": totals["misses"],
            "compile_seconds": totals["compile_seconds"],
            "caches": len(per)}


# ---------------------------------------------------------------------------
# steady-state recompile blamer
# ---------------------------------------------------------------------------
#
# The zero-steady-compile SLO (PR 11: compile.cache_misses rate <= 0 after
# the warmup grace) can only say THAT a warmed cache missed, not WHY. The
# blamer structurally diffs the missing key against its nearest existing
# neighbor and names the axis that changed — shape (batch vs inner dim),
# dtype, optimizer hyperparam, sharding plan, or attr — as a
# `compile_blame` health-journal event and `compile.blamed_misses` /
# `compile.blame_axis.*` counters. "Why did steady state recompile?"
# becomes a named diagnosis instead of folklore debugging.

_BLAME_NEIGHBORS = 64      # newest keys considered as nearest-neighbor
_BLAME_AXES_MAX = 4        # axes reported per event

_DTYPE_NAMES = frozenset(
    "float16 float32 float64 bfloat16 int8 int16 int32 int64 uint8 uint16 "
    "uint32 uint64 bool complex64 complex128".split())

_SHARD_SPEC_RE = None  # compiled lazily (re import stays off the hot path)


def _is_dtype_leaf(v):
    if hasattr(v, "itemsize") and hasattr(v, "name"):     # np.dtype
        return True
    if isinstance(v, type) and getattr(v, "__name__", "") in _DTYPE_NAMES:
        return True
    return isinstance(v, str) and v in _DTYPE_NAMES


def _is_shard_leaf(v, parent):
    """A sharding-plan component: a spec string (`tp=2,fsdp=4`) or any
    leaf of a tuple tagged by its subsystem ("zero1"/"spmd"/"mesh"...)."""
    global _SHARD_SPEC_RE
    if isinstance(parent, tuple) and parent and isinstance(parent[0], str) \
            and parent[0] in ("zero1", "spmd", "mesh", "pipeline"):
        return True
    if not isinstance(v, str):
        return False
    if _SHARD_SPEC_RE is None:
        import re as _re

        _SHARD_SPEC_RE = _re.compile(r"(^|[,(])\s*(tp|fsdp|dp|pp|sp|ep)=")
    return bool(_SHARD_SPEC_RE.search(v))


def _flatten_key(k, path=(), parent=None, out=None):
    """Leaf list [(path, parent_container, value)] of one cache key —
    keys are nested tuples by convention (shape signatures, static
    config), so tuple/list are the only containers walked."""
    if out is None:
        out = []
    if isinstance(k, (tuple, list)):
        for i, v in enumerate(k):
            _flatten_key(v, path + (i,), k, out)
        if not k:
            out.append((path, parent, k))
    else:
        out.append((path, parent, k))
    return out


def _axis_of(path, parent, old, new):
    """Name the key axis a differing leaf belongs to."""
    if _is_dtype_leaf(old) or _is_dtype_leaf(new):
        return "dtype"
    if _is_shard_leaf(old, parent) or _is_shard_leaf(new, parent):
        return "sharding"
    if isinstance(old, bool) or isinstance(new, bool):
        return "attr"
    if isinstance(old, int) and isinstance(new, int):
        if isinstance(parent, (tuple, list)) and parent and all(
                isinstance(x, int) and not isinstance(x, bool)
                for x in parent):
            # an all-int tuple in a cache key is a shape by convention
            # (executor._sig, serving bucket sigs, slab geometry)
            dim = path[-1] if path else 0
            return "shape(batch)" if dim == 0 else f"shape(dim{dim})"
        return "attr"
    if isinstance(old, float) and isinstance(new, float):
        return "hyperparam"
    return "attr"


def _key_distance(a_flat, b_map):
    """(score, diffs): structural mismatches weigh 1000, each differing
    leaf 1, with a <1 numeric-closeness tiebreak so batch 9 blames the
    size-8 bucket, not the size-4 one."""
    diffs = []
    score = 0.0
    seen = set()
    for path, parent, v in a_flat:
        seen.add(path)
        if path not in b_map:
            score += 1000.0
            continue
        bparent, bv = b_map[path]
        eq = False
        try:
            eq = bool(v == bv) and type(v) is type(bv)
        except Exception:  # noqa: BLE001 — exotic leaf comparisons
            eq = v is bv
        if eq:
            continue
        score += 1.0
        if isinstance(v, (int, float)) and isinstance(bv, (int, float)) \
                and not isinstance(v, bool) and not isinstance(bv, bool):
            denom = abs(float(v)) + abs(float(bv)) + 1e-9
            score += min(1.0, abs(float(v) - float(bv)) / denom) * 0.5
        diffs.append((path, parent, bv, v))  # (path, parent, old, new)
    score += 1000.0 * sum(1 for p in b_map if p not in seen)
    return score, diffs


def _blame_miss(cache_name, key, entries):
    """Diff ``key`` against its nearest neighbor among ``entries`` and
    publish the diagnosis. Called under the cache lock on a steady-state
    miss — rare by contract, and cheap next to the compile that follows."""
    try:
        new_flat = _flatten_key(key)
        best = None
        for old_key in list(entries)[-_BLAME_NEIGHBORS:]:
            b_map = {p: (parent, v)
                     for p, parent, v in _flatten_key(old_key)}
            score, diffs = _key_distance(new_flat, b_map)
            if best is None or score < best[0]:
                best = (score, old_key, diffs)
        if best is None:
            return
        _, nearest, diffs = best
        axes = []
        for path, parent, old, new in diffs[:_BLAME_AXES_MAX]:
            axes.append({"axis": _axis_of(path, parent, old, new),
                         "path": "/".join(str(p) for p in path),
                         "old": repr(old)[:80], "new": repr(new)[:80]})
        if not axes:
            # same leaves, different structure (rank change, extra input)
            axes.append({"axis": "structure", "path": "",
                         "old": repr(nearest)[:120],
                         "new": repr(key)[:120]})
        primary = axes[0]["axis"]
        telemetry.counter("compile.blamed_misses").inc()
        safe = primary.replace("(", "_").replace(")", "")
        telemetry.counter(f"compile.blame_axis.{safe}").inc()
        try:
            from . import health

            if health._enabled:
                health.event("compile_blame", cache=cache_name,
                             axis=primary, axes=axes,
                             key=repr(key)[:240],
                             nearest=repr(nearest)[:240])
        except Exception:  # noqa: BLE001 — the journal is additive
            pass
    except Exception:  # noqa: BLE001 — diagnosis must never break a build
        pass


# ---------------------------------------------------------------------------
# hlolint audit ledger (MXNET_HLOLINT_DUMP)
# ---------------------------------------------------------------------------


def _audit_tags():
    raw = str(getenv("MXNET_HLOLINT_CACHES") or "")
    return {s.strip() for s in raw.split(",") if s.strip()}


def _audit_record(cache, tag, key, timed, args, kwargs):
    """Retain one first-called executable (strong ref + aval skeleton)
    for the exit dump. Per-tag capped; dedupes by (tag, repr(key)) so the
    same program warmed by many per-context caches is lowered once."""
    try:
        tags = _audit_tags()
        if tags and tag not in tags:
            return
        avals = _avals_of(args, kwargs)
        cap = int(getenv("MXNET_HLOLINT_MAX_ENTRIES"))
        with _audit_lock:
            lk = (tag, repr(key))
            if lk in _audit_ledger:
                return
            if sum(1 for t, _ in _audit_ledger if t == tag) >= cap:
                return
            _audit_ledger[lk] = {"cache": cache.name, "tag": tag,
                                 "key": repr(key), "fn": timed,
                                 "avals": avals}
            if not _audit_hooked[0]:
                _audit_hooked[0] = True
                import atexit

                atexit.register(_dump_audit_atexit)
    except Exception:  # noqa: BLE001 — auditing must never break a step
        pass


def audit_ledger():
    """The recorded (tag, key) pairs — test/tooling introspection."""
    with _audit_lock:
        return sorted(_audit_ledger)


def dump_audit(dirpath):
    """Summarize every ledger entry (AOT lower + compile — seconds per
    donated entry) and write one JSON dump into ``dirpath`` for
    ``python -m tools.hlolint check``. Returns the file path or None when
    the ledger is empty."""
    from . import analysis

    with _audit_lock:
        recs = list(_audit_ledger.values())
    if not recs:
        return None
    entries = []
    for r in recs:
        try:
            summary = analysis.program_summary(r["fn"], r["avals"])
        except Exception as e:  # noqa: BLE001 — one bad entry can't
            summary = {"error": repr(e)[:240]}   # lose the whole dump
        entries.append({"cache": r["cache"], "tag": r["tag"],
                        "key": r["key"], "summary": summary})
    import json

    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(
        dirpath, f"hlolint-{os.getpid()}-{time.time_ns() % 10**9}.json")
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump({"pid": os.getpid(), "entries": entries}, f, indent=1)
    os.replace(tmp, path)
    return path


def _dump_audit_atexit():
    try:
        d = getenv("MXNET_HLOLINT_DUMP")
        if d:
            dump_audit(d)
    except Exception:  # noqa: BLE001 — exit hooks never raise
        pass


def _listen_to_jax():
    from jax import monitoring

    monitoring.register_event_duration_secs_listener(_on_jax_duration)


persistent_cache_dir()
_listen_to_jax()
