"""Double-buffered device staging — the host-overlap half of the async
dispatch pipeline.

The reference's L2 dependency engine (`include/mxnet/engine.h`) exists to
hide host work behind device compute; on the TPU-native port every
lockstep step pays batch pad/cast/``device_put`` and metric reads on the
critical path while the device sits idle.  :class:`DeviceStager` closes
the input half of that gap: while step *t* executes, a staging thread
pads/casts/places batch *t+1* into a bounded ring of pre-placed buffers,
so the consuming step finds device-resident arrays instead of host
numpy.  The consumer side (``Module.fit``'s dispatch-then-prepare order,
the serving batcher's stage-ahead) lives with each loop, and the metric's
one step of lag with ``mxnet_tpu.metric``; this module owns only the
buffer discipline.

Correctness invariants, in order of importance:

* **Donation safety** — a staged slot's arrays stay strongly referenced
  from :meth:`DeviceStager.stage` until :meth:`DeviceStager.retire`, and
  ``stage`` refuses new work while every slot is staged or in flight.
  Feeds are never donated by the fused program (see
  ``Executor.fused_step``'s donate tuple), but the ring discipline is
  what guarantees a buffer is not recycled by the allocator while the
  step consuming it is still in flight.
* **Identity hand-off** — :meth:`DeviceStager.take` matches on the batch
  *object*, not its contents; a consumer that shows up with a different
  batch (reordered iterator, bucketing switch) simply misses and falls
  back to the lockstep path.  Staging is an optimisation, never a
  semantic.
* **Lock coverage** — the ring's condition comes from
  ``analysis.make_condition``, so ``MXNET_DEBUG_SYNC=1`` folds the
  staging thread into the lock-order/blocking-hazard analysis like every
  other subsystem.

The ring holds two slots (classic double buffering: one in flight, one
staging).
"""
from __future__ import annotations

import threading
import time as _time

from .. import telemetry


class _Slot:
    """One ring entry: the batch it was staged for, the prepared feed,
    and its lifecycle bits (ready -> in_flight -> retired)."""

    __slots__ = ("batch", "prep", "guard", "feed", "pad", "error",
                 "ready", "in_flight")

    def __init__(self, batch, prep, guard):
        self.batch = batch
        self.prep = prep
        self.guard = guard
        self.feed = None
        self.pad = 0
        self.error = None
        self.ready = False
        self.in_flight = False


class DeviceStager:
    """Bounded ring of device-staged input batches fed by one thread.

    Protocol (all methods are main-thread unless noted)::

        staged = stager.stage(batch, prep)   # enqueue; thread runs prep()
        ...dispatch step t...
        hit = stager.take(batch)             # (feed, pad) or None
        ...step consuming the feed completes (metric applied)...
        stager.retire()                      # oldest in-flight slot freed

    ``prep`` runs on the staging thread and returns ``(feed_dict, pad)``
    where the feed values are already cast + device-placed (honoring the
    caller's SPMD input shardings).  ``guard`` (optional) is re-checked at
    ``take`` time on the main thread; returning False discards the slot —
    the consumer's placement context changed between stage and consume.
    """

    def __init__(self, name="io.stager", depth=2):
        self._depth = max(2, int(depth))
        # analysis-tracked so MXNET_DEBUG_SYNC sees the staging thread
        from .. import analysis
        self._cv = analysis.make_condition(name)
        self._name = name
        self._slots = []          # FIFO: staged + in-flight, oldest first
        self._queue = []          # staged-but-unprepared, thread input
        self._thread = None
        self._closed = False

    # -- introspection (tests pin the donation-safety discipline on these)
    @property
    def depth(self):
        return self._depth

    def occupancy(self):
        """(staged_or_preparing, in_flight) slot counts."""
        with self._cv:
            live = [s for s in self._slots]
            return (len([s for s in live if not s.in_flight]),
                    len([s for s in live if s.in_flight]))

    # -- producer side -----------------------------------------------------
    def stage(self, batch, prep, guard=None, block=False):
        """Enqueue ``batch`` for staging; returns True if accepted.

        When the ring is full (every slot staged or in flight — i.e. the
        consumer is behind by ``depth`` steps), the batch is NOT staged
        and False is returned unless ``block``: dropping to lockstep for
        one step is always safe, silently reusing a live buffer never is.
        """
        if self._closed:
            return False
        with self._cv:
            if block:
                while len(self._slots) >= self._depth and not self._closed:
                    self._cv.wait(timeout=0.05)
            if self._closed or len(self._slots) >= self._depth:
                telemetry.counter("io.stage_ring_full").inc()
                return False
            slot = _Slot(batch, prep, guard)
            self._slots.append(slot)
            self._queue.append(slot)
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._run, name=self._name, daemon=True)
                self._thread.start()
            self._cv.notify_all()
        return True

    # -- staging thread ----------------------------------------------------
    def _run(self):
        while True:
            with self._cv:
                while not self._queue and not self._closed:
                    self._cv.wait(timeout=0.2)
                if self._closed and not self._queue:
                    return
                slot = self._queue.pop(0)
            t0 = _time.perf_counter()
            try:
                slot_feed, slot_pad = slot.prep()
            except Exception as e:  # consumer falls back to lockstep
                slot.error = e
                slot_feed, slot_pad = None, 0
            dt_us = (_time.perf_counter() - t0) * 1e6
            telemetry.counter("io.stage_prep_us_total").inc(int(dt_us))
            with self._cv:
                slot.feed, slot.pad = slot_feed, slot_pad
                slot.ready = True
                self._cv.notify_all()

    # -- consumer side -----------------------------------------------------
    def take(self, batch):
        """The staged ``(feed, pad)`` for this exact batch object, or None.

        Blocks (counted into ``io.stage_wait_us_total``) if the staging
        thread has not finished preparing it yet; a miss, a prep error, or
        a failed ``guard`` re-check all return None and drop the slot so
        the caller runs its lockstep path.
        """
        with self._cv:
            slot = None
            for s in self._slots:
                if not s.in_flight and s.batch is batch:
                    slot = s
                    break
            if slot is None:
                return None
            t0 = _time.perf_counter()
            waited = False
            while not slot.ready:
                waited = True
                self._cv.wait(timeout=0.2)
            if waited:
                telemetry.counter("io.stage_wait_us_total").inc(
                    int((_time.perf_counter() - t0) * 1e6))
            if slot.error is not None or slot.feed is None or \
                    (slot.guard is not None and not slot.guard()):
                self._slots.remove(slot)
                self._cv.notify_all()
                telemetry.counter("overlap.fallback_batches").inc()
                return None
            slot.in_flight = True
            telemetry.counter("overlap.staged_batches").inc()
            return slot.feed, slot.pad

    def retire(self):
        """Free the oldest in-flight slot — call once the step that
        consumed it can no longer be touching its buffers (its outputs
        were read, or a later step completed).  Idempotent when nothing
        is in flight."""
        with self._cv:
            for i, s in enumerate(self._slots):
                if s.in_flight:
                    del self._slots[i]
                    self._cv.notify_all()
                    return True
            return False

    def close(self):
        """Drop every slot and stop the staging thread (fit teardown)."""
        with self._cv:
            self._closed = True
            self._queue = []
            self._slots = []
            self._cv.notify_all()
            thread = self._thread
            self._thread = None
        if thread is not None:
            thread.join(timeout=2.0)
