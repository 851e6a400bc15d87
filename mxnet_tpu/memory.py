"""Device-memory accounting: a live buffer census by category.

The framework makes memory CLAIMS — ZeRO-1 allocates optimizer state at
1/N bytes per replica (`parallel/zero1.py`), serving pins one padded batch
buffer set per bucket, the fused step donates weights so no second copy
exists — and before this module nothing in a live process could verify
them. This module is the truth plane:

* **categories** — every long-lived device buffer the framework owns is
  registered under one of ``weights`` / ``optimizer_state`` /
  ``gradients`` / ``serving_batches`` / ``kv_cache`` (the generation
  engines' preallocated KV slabs — registered as live-view providers
  because the slab arrays are REPLACED by every donated decode step;
  prefix-cache entries and their forked session copies are ROWS of that
  same slab, so the buffer-pointer dedup below attributes them once, at
  the slab's allocation size, never double — only a speculative draft
  model's own slab adds bytes, through its own provider);
  everything else live on the backend (feeds in flight, temporaries the
  GC has not collected) shows up as ``other``. Registration is by WEAK reference — a provider
  (executor, updater, ZeRO-1 context, predictor) that dies drops out of
  the census automatically, and tracking never extends a buffer's
  lifetime.
* **census** — :func:`census` walks the live registrations, reads each
  buffer's *physical* per-device residency (``addressable_shards`` — a
  dp-sharded ZeRO-1 state bucket counts 1/N per device, a replicated
  weight counts fully on every device) and publishes ``memory.*``
  gauges: per category, ``memory.<cat>_bytes`` is the max bytes any one
  device holds (the HBM-pressure number) and ``memory.<cat>_bytes_total``
  the sum across local devices.
* **per-executable peak HBM** — :meth:`CompileCache.entry_memory
  <mxnet_tpu.compile_cache.CompileCache.entry_memory>` feeds
  :func:`executable_stats`: XLA's compiled-program memory analysis
  (argument/output/temp bytes) per cache entry, so "which program's
  working set blew the HBM budget" is answerable per compiled executable.

Census cost is O(live buffers) with device reads only on shard metadata —
it runs on demand (telemetry HTTP ``/memory``, ``prom_text()``, tests),
never on the step path.
"""
from __future__ import annotations

import threading
import weakref

from . import analysis
from . import telemetry
from .base import getenv, register_env

__all__ = ["CATEGORIES", "track", "track_transient", "register_provider",
           "census", "update_gauges", "executable_stats", "clear",
           "device_capacity_bytes"]

register_env("MXNET_DEVICE_HBM_BYTES", 0,
             "per-device memory capacity override in bytes for the "
             "memory.headroom_bytes gauge; 0 = use the backend's "
             "reported bytes_limit (none on CPU: headroom unpublished)")

CATEGORIES = ("weights", "optimizer_state", "gradients", "serving_batches",
              "kv_cache")

_lock = analysis.make_lock("memory.census")
# category -> list of weakref.ref to NDArray / jax array (long-lived)
_tracked = {c: [] for c in CATEGORIES}
# category -> list of (weakref to owner, getter(owner) -> iterable of arrays)
_providers = {c: [] for c in CATEGORIES}
_SWEEP_FLOOR = 4096
# category -> list length that triggers the next inline dead-ref sweep.
# Doubles past the live count after a sweep that freed little, so a
# category that legitimately holds >4096 LIVE buffers pays O(n) per
# geometric growth step, not per track() call
_sweep_at = {c: _SWEEP_FLOOR for c in CATEGORIES}


def clear():
    """Drop every registration (tests)."""
    with _lock:
        for c in CATEGORIES:
            _tracked[c] = []
            _providers[c] = []
            _sweep_at[c] = _SWEEP_FLOOR


def track(category, arrays):
    """Register long-lived buffers under ``category`` (NDArray, jax array,
    or an iterable of either). Weakly referenced — dead entries are swept
    at census time."""
    if category not in _tracked:
        raise ValueError(f"unknown memory category {category!r} "
                         f"(one of {CATEGORIES})")
    if not isinstance(arrays, (list, tuple, set)):
        arrays = [arrays]
    refs = []
    for a in arrays:
        try:
            refs.append(weakref.ref(a))
        except TypeError:
            pass  # unweakrefable leaf (python scalar riding a state tuple)
    with _lock:
        cur = _tracked[category]
        cur.extend(refs)
        if len(cur) > _sweep_at[category]:
            # bound the list between censuses: drop dead refs inline so a
            # long serving run that never scrapes /memory stays O(live)
            kept = [r for r in cur if r() is not None]
            _tracked[category] = kept
            _sweep_at[category] = max(_SWEEP_FLOOR, 2 * len(kept))


# transient buffers (a serving batch in flight) use the same list — the
# weakref dies with the buffer, and the periodic sweep keeps the list
# bounded. The distinct name keeps call sites honest about lifetime.
track_transient = track


def register_provider(category, owner, getter):
    """Register a LIVE view: ``getter(owner)`` is called at census time to
    enumerate the category's current buffers (for state that is replaced
    every step, e.g. ZeRO-1's donated flat state arrays — a snapshot
    weakref would die on the first update). ``owner`` is weakly held."""
    if category not in _providers:
        raise ValueError(f"unknown memory category {category!r} "
                         f"(one of {CATEGORIES})")
    with _lock:
        _providers[category].append((weakref.ref(owner), getter))


def _unwrap(obj):
    """NDArray -> its jax buffer; jax arrays pass through."""
    data = getattr(obj, "_data", None)
    return data if data is not None else obj


def _per_device_nbytes(arr):
    """{device_key: physical bytes} for one buffer. Sharded arrays report
    each shard on its device (the 1/N truth); replicated-on-mesh arrays
    report the full size on EVERY device they occupy."""
    try:
        shards = arr.addressable_shards
    except Exception:  # noqa: BLE001 — not a jax array (numpy fallback)
        nb = int(getattr(arr, "nbytes", 0))
        return {"host": nb} if nb else {}
    out = {}
    for s in shards:
        out[str(s.device)] = out.get(str(s.device), 0) + int(s.data.nbytes)
    return out


def _buffer_key(arr):
    """Identity for dedup: two NDArrays sharing one jax buffer (shared
    serving weights bound into several bucket executors) count once."""
    try:
        return arr.unsafe_buffer_pointer()
    except Exception:  # noqa: BLE001
        return id(arr)


def _iter_category(category):
    """Live buffers of one category: swept tracked refs + provider views.

    The dead-ref sweeps run entirely under ``_lock`` — dereferencing a
    weakref is cheap and census is off the step path, and holding the
    lock means a concurrent :func:`track` (which may REPLACE the list
    when the 4096 bound trips) can never interleave with the sweep's
    rewrite. Only the provider ``getter`` calls (arbitrary user code)
    run outside the lock."""
    live = []
    with _lock:
        cur = _tracked[category]
        kept = []
        for r in cur:
            o = r()
            if o is not None:
                live.append(o)
                kept.append(r)
        if len(kept) != len(cur):
            _tracked[category] = kept
            _sweep_at[category] = max(_SWEEP_FLOOR, 2 * len(kept))
        cur_p = _providers[category]
        kept_p = [(ref, getter) for ref, getter in cur_p
                  if ref() is not None]
        if len(kept_p) != len(cur_p):
            _providers[category] = kept_p
    for ref, getter in kept_p:
        owner = ref()
        if owner is None:  # died since the sweep
            continue
        try:
            live.extend(getter(owner) or [])
        except Exception:  # noqa: BLE001 — a dying provider must not kill
            pass           # the census
    return live


def census(update=True):
    """One coherent memory snapshot::

        {"categories": {cat: {"total", "per_device_max", "buffers"}},
         "per_device": {device: bytes (categorized)},
         "live_total": <all live backend arrays>,
         "other": live_total - categorized,
         "device_count": N}

    ``update=True`` (default) also publishes the ``memory.*`` gauges so
    the next telemetry snapshot / ``prom_text()`` carries them."""
    seen = set()
    cats = {}
    per_device = {}
    categorized = 0
    for cat in CATEGORIES:
        total = 0
        dev = {}
        n = 0
        for obj in _iter_category(cat):
            arr = _unwrap(obj)
            if arr is None:
                continue
            key = _buffer_key(arr)
            if key in seen:
                continue
            seen.add(key)
            by_dev = _per_device_nbytes(arr)
            if not by_dev:
                continue
            n += 1
            for d, nb in by_dev.items():
                dev[d] = dev.get(d, 0) + nb
                per_device[d] = per_device.get(d, 0) + nb
                total += nb
        categorized += total
        cats[cat] = {"total": total,
                     "per_device_max": max(dev.values()) if dev else 0,
                     "buffers": n}
    live_total = 0
    try:
        import jax

        live_seen = set()
        for a in jax.live_arrays():
            k = _buffer_key(a)
            if k in live_seen:
                continue
            live_seen.add(k)
            live_total += sum(_per_device_nbytes(a).values())
    except Exception:  # noqa: BLE001 — census must degrade, not raise
        live_total = categorized
    out = {"categories": cats,
           "per_device": per_device,
           "live_total": live_total,
           "other": max(0, live_total - categorized),
           "device_count": len(per_device)}
    cap = device_capacity_bytes()
    if cap:
        # peak-HBM headroom PROJECTED to the worst already-analyzed
        # executable: capacity − (busiest device's categorized bytes +
        # unattributed live bytes + the largest temp working set any
        # warmed program needs while it runs). Negative means the next
        # dispatch of that program is an OOM waiting to happen even
        # though the resident census still fits — the SLO default row
        # memory.headroom_bytes:value>=0 burns on exactly that.
        used = max(per_device.values()) if per_device else 0
        out["capacity_bytes"] = cap
        out["worst_executable_temp_bytes"] = _worst_temp_bytes()
        out["headroom_bytes"] = (cap - used - out["other"]
                                 - out["worst_executable_temp_bytes"])
    if update:
        _publish(out)
    return out


def device_capacity_bytes():
    """Per-device memory capacity in bytes: the backend's reported
    ``bytes_limit`` where available (TPU/GPU), else the
    ``MXNET_DEVICE_HBM_BYTES`` override, else 0 (unknown — headroom is
    not published)."""
    cap = int(getenv("MXNET_DEVICE_HBM_BYTES"))
    if cap:
        return cap
    try:
        import jax

        ms = jax.devices()[0].memory_stats()
        if ms:
            return int(ms.get("bytes_limit") or 0)
    except Exception:  # noqa: BLE001 — CPU backends have no stats
        pass
    return 0


def _worst_temp_bytes():
    """Largest temp working set among executables whose lazy memory
    analysis has ALREADY run (compute=False — the census never triggers
    an AOT pass; /memory's executable_stats(compute=True) is what fills
    this in)."""
    from . import compile_cache

    worst = 0
    for c in compile_cache.all_caches():
        for row in c.memory_stats(compute=False):
            worst = max(worst, int(row.get("temp_bytes") or 0))
    return worst


def _publish(snap):
    """The gauges. Unconditional (like compile.* counters): memory truth
    must be visible even when the wider telemetry plane is off."""
    for cat, v in snap["categories"].items():
        telemetry.gauge(f"memory.{cat}_bytes").set(v["per_device_max"])
        telemetry.gauge(f"memory.{cat}_bytes_total").set(v["total"])
    telemetry.gauge("memory.other_bytes").set(snap["other"])
    telemetry.gauge("memory.live_bytes_total").set(snap["live_total"])
    if "headroom_bytes" in snap:
        telemetry.gauge("memory.headroom_bytes").set(snap["headroom_bytes"])
        telemetry.gauge("memory.capacity_bytes").set(snap["capacity_bytes"])


def update_gauges():
    """Refresh ``memory.*`` gauges from a fresh census (prom_text / the
    HTTP endpoint call this right before rendering)."""
    return census(update=True)


def executable_stats():
    """Per-executable peak-HBM from XLA's compiled-program memory
    analysis, for every :class:`~mxnet_tpu.compile_cache.CompileCache`
    entry: ``{cache_name: [{key, argument_bytes, output_bytes, temp_bytes,
    peak_bytes}]}``. Lazy and memoized per entry, never on the step path —
    but the FIRST call after new compiles pays an AOT lowering pass per
    new entry, which can be a full
    recompile: expect the first ``/memory`` scrape of a freshly-warmed
    process to take seconds."""
    from . import compile_cache

    out = {}
    for c in compile_cache.all_caches():
        # compute=True: this is the on-demand read — without it the lazy
        # analysis would never run anywhere. Memoized per entry (failures
        # too), so repeat scrapes pay nothing
        rows = c.memory_stats(compute=True)
        if rows:
            out.setdefault(c.name, []).extend(rows)
    return out
