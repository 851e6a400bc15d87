"""Compile-ahead warmup: pay every bucket's compile before traffic exists.

A serving process that compiles lazily pays its XLA compile on the first
unlucky *user* request of each bucket shape — seconds of p99 latency that
look like an outage. Warmup runs a zeros batch through every bucket at
startup, so the ``"serving"`` compile cache is fully populated before the
first real request and steady state pays ZERO compiles (pinned by
test_serving.py the way the fused-step PR pinned its padded-batch miss
count). Later processes on the same persistent compile cache
(``compile_cache.persistent_cache_dir``) deserialize these programs instead
of rebuilding them — warmup then costs disk reads, not compiles.
"""
from __future__ import annotations

import time

from .. import telemetry
from ..log import get_logger

__all__ = ["warmup"]


def warmup(target, buckets=None):
    """Compile every executable of ``target`` ahead of traffic.

    ``target`` is a ``Predictor`` or ``DynamicBatcher`` (one forward
    program per batch bucket), or a ``GenerationEngine`` /
    ``GenerationRouter`` (one prefill program per prompt-length bucket
    plus THE decode program, per replica).

    Returns ``{"buckets", "compiles", "seconds", "cache_entries"}`` —
    ``compiles`` is the exact number of new programs built (cache-miss
    delta), so a second call reports 0. ``serving.warmup_compiles`` /
    ``serving.generation.warmup_compiles`` ride the telemetry registry
    when enabled.
    """
    if hasattr(target, "prefill_buckets") or (
            hasattr(target, "engines")
            and any(hasattr(e, "prefill_buckets")
                    for e in getattr(target, "engines", []))):
        # generation plane: the engine/router owns the exact-count warm
        # (prefill ladder + decode, free-slot safe) — see
        # GenerationEngine.warm
        return target.warm(buckets)
    pred = getattr(target, "predictor", target)
    buckets = (pred.buckets if buckets is None
               else tuple(sorted({int(b) for b in buckets})))
    cache = pred.cache
    misses0 = cache.misses
    t0 = time.perf_counter()
    for b in buckets:
        pred.warm_bucket(b)
    compiles = cache.misses - misses0
    seconds = time.perf_counter() - t0
    pred._warmed = True           # readiness: warmup complete (/readyz)
    if telemetry._enabled:
        telemetry.counter("serving.warmup_compiles").inc(compiles)
    get_logger("mxnet_tpu.serving").info(
        "serving warmup: %d bucket(s) -> %d compile(s) in %.2fs "
        "(cache %r now holds %d executables)",
        len(buckets), compiles, seconds, cache.name, len(cache))
    return {"buckets": list(buckets), "compiles": compiles,
            "seconds": seconds, "cache_entries": len(cache)}
