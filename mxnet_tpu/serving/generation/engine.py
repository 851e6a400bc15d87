"""GenerationEngine — token-level continuous batching over a slot-major
serving cache that the MODEL defines (a KV slot slab; a recurrent model's
per-slot state beside it).

PR 5's :class:`~mxnet_tpu.serving.batcher.DynamicBatcher` schedules at
REQUEST granularity: a batch forms, computes once, and every member leaves
together. Autoregressive generation breaks that shape — sessions are
hundreds of sequential single-token steps of wildly different counts, so
request-level batching would hold every finished sequence hostage to the
longest one (and re-running the full forward per token would cost O(T) per
token, O(T²) per sequence). This engine is the token-level scheduler:

* **slot-based session store** — a preallocated cache the model defines
  (``model.init_cache``: a tuple of arrays, each slot-major; for
  :class:`TransformerLM` the K and V slabs ``[max_slots, layers, heads,
  max_len, head_dim]``, for :class:`HybridLM` those of its attention
  layers plus a recurrent and a convolution state per slot). The engine
  carries it as one value — donated whole to every program, forked and
  parked one slot of every member at a time — and asks the model what it
  must know (``cache_traits``; docs/faq/perf.md, "The cache protocol").
  Its shape NEVER changes: admitting
  a session is a prefill write into a free slot index, evicting is
  clearing host-side metadata — continuous batching without a recompile,
  ever (the arXiv:2603.09555 compile-once O(1)-cache discipline).
* **continuous scheduling** — every engine tick runs ONE fused
  ``decode_step`` over the slab: every live session advances one token
  together, each writing ONE K/V row in place and attending only its
  own rows ``[0, length]``; a slot without a live session rides along
  at position -1, neither read nor written
  (:meth:`TransformerLM.decode_step`; on one TPU chip the slab kernel of
  ``ops/pallas_decode.py`` reads just the live blocks, elsewhere the XLA
  formulation masks the whole page). Where no live session can end on a
  token's value the NEXT decode is dispatched before the current one's
  tokens are fetched (:meth:`_dispatch_ahead`), so the device runs decodes
  back to back and the host's share of a tick hides behind them. The tick
  then evicts finished/EOS/deadline-expired sessions, and
  admits queued prefills into the freed slots mid-stream. The intake is
  PR 5's :class:`~mxnet_tpu.serving.admission.AdmissionQueue`
  (``QueueFullError`` backpressure, ``ServerClosedError`` after close,
  per-session deadlines swept per tick via ``expire()``), prompts pad up
  a prefill-length bucket ladder, and a blocking stream iterator assists
  caller-runs style.
* **prefix cache + in-slab KV forking** — with
  ``MXNET_GENERATION_PREFIX_CACHE=1`` a refcounted radix trie
  (:mod:`.prefix_cache`) maps prompt prefixes to slab slots holding their
  K/V. Admission of a prompt whose prefix is cached runs ONE traced fork
  executable (``dynamic_slice`` + ``dynamic_update_slice`` copying the
  source slot's rows) and prefills only the unmatched suffix
  (:meth:`TransformerLM.prefill_at`) — a fleet-shared system prompt
  prefills once, then every later session pays O(suffix). Sessions
  always outrank cached entries for slots (LRU eviction of refcount-zero
  entries on admission pressure, journaled through the health ring).
* **speculative decoding** — with ``MXNET_GENERATION_SPEC_K=k`` a draft
  (:mod:`.speculative`: ``MXNET_GENERATION_DRAFT`` checkpoint or the
  n-gram fallback) proposes k tokens per live slot per tick and ONE
  fixed-shape slab-wide verify executable
  (:meth:`TransformerLM.verify_step` — k+1 unrolled decode graphs, so
  greedy output is BIT-EXACT with the plain path) checks them all;
  the engine commits the longest agreeing draft prefix plus the target's
  own next token (1 to k+1 tokens per tick) and rolls the rest back by
  simply not advancing the slot's position — rejected rows beyond the
  frontier are never attended and are overwritten before they could be.
* **compile discipline** — one ``CompileCache("generation")`` entry per
  prefill bucket plus exactly ONE decode (or verify) executable — and,
  per enabled feature, one fork entry, one suffix-prefill entry per
  bucket and the draft's own pinned set — all with the slab buffers
  donated. ``serving.warmup`` pins the
  exact count ahead of traffic; steady state compiles nothing.

Telemetry rides ``serving.generation.*`` (live-slot gauge, tokens/s,
TTFT/tick histograms, per-reason eviction counters, derived
``slot_fill_ratio``, ``slab_blocks_live``/``slab_blocks_total`` — the
share of the slab's blocks each dispatch had to read —
``state_slots_live``/``state_bytes_touched``/``state_bytes_resident`` for a
model with per-slot state that is not rows, ``prefill_tokens``, plus ``prefix.{hits,misses,forks,inserts,
evictions}``/``prefix.cached_tokens`` and ``spec.{proposed,accepted,
rolled_back,committed}`` with derived ``spec.acceptance_ratio``).
``queue_wait_us`` is submit → start of the admission (host events at both
ends). ``prefill_us`` is prefill dispatch → first token on the HOST: the
tick dispatches its decode first and the prefill queues behind it on the
device, so it reads one decode plus the prefill — the prefill's own
device time is only in a device trace.
Tracing: every tick is one live span tree (``generation.tick`` → sweep /
decode / admit → prefill → prefill.fetch / commit → commit.fetch, each
also ``mx:<name>`` in any ``jax.profiler`` trace), nothing per session per
tick; with ``MXNET_TRACING=1`` each session also gets a root
(``generation.session``, ``tokens=<n>`` at finish) with queued / prefill /
evict children. The slab (and the checkpoint draft's slab) registers
under the ``kv_cache`` memory-census category — forked rows live inside
the same slab buffers, so the census never double-counts them.
"""
from __future__ import annotations

import contextlib
import threading
import time

import numpy as np

from ... import analysis
from ... import health
from ... import memory
from ... import telemetry
from ... import tracing
from ...base import MXNetError, getenv, register_env
from ...compile_cache import CompileCache
from ...log import get_logger
from .. import qos
from ..admission import AdmissionQueue, DeadlineExceededError, Request
from ..health import attach_engine, queue_ready
from . import speculative
from .prefix_cache import RadixPrefixCache
from .session import GenerationStream

__all__ = ["GenerationEngine", "prefill_ladder"]

register_env("MXNET_GENERATION_SLOTS", 8,
             "KV-slab slot count per generation engine: the max number of "
             "concurrently-decoding sessions (one fused decode_step covers "
             "the whole slab each tick)")
register_env("MXNET_GENERATION_MAX_LEN", 256,
             "KV-slab sequence capacity per slot (prompt + generated "
             "tokens); bounds per-slot HBM at "
             "2*layers*heads*max_len*head_dim*dtype bytes")
register_env("MXNET_GENERATION_PREFILL_BUCKETS", "",
             "prefill-length bucket ladder (comma-separated ints, each a "
             "compiled prefill program); empty = powers of two from 8 up "
             "to MXNET_GENERATION_MAX_LEN")
register_env("MXNET_GENERATION_TICK_BUDGET_MS", 10.0,
             "max milliseconds one scheduler tick spends admitting queued "
             "prefills before the fused decode runs again (>= 1 admission "
             "per tick when slots are free, so queues always drain)")
register_env("MXNET_GENERATION_PREFIX_CACHE", False,
             "cache prompt-prefix KV in free slab slots (refcounted radix "
             "trie): admission of a prompt with a cached prefix runs one "
             "traced slot-to-slot fork + a suffix-only prefill instead of "
             "a full-prompt prefill")
register_env("MXNET_GENERATION_PREFIX_MIN_TOKENS", 8,
             "shortest prompt prefix worth forking from (or inserting "
             "into) the prefix cache — below this a full prefill is "
             "cheaper than the fork dispatch")


def prefill_ladder(buckets, max_len):
    """Normalize a prefill bucket spec (None ->
    ``MXNET_GENERATION_PREFILL_BUCKETS``; empty -> powers of two up to
    ``max_len``) into an ascending tuple capped at ``max_len`` —
    spec parsing/validation shared with the predictor's
    :func:`~mxnet_tpu.serving.predictor.bucket_ladder`."""
    from ..predictor import bucket_ladder

    if buckets is None:
        buckets = getenv("MXNET_GENERATION_PREFILL_BUCKETS")
    if not (buckets.strip() if isinstance(buckets, str) else buckets):
        b, buckets = 8, []
        while b < max_len:
            buckets.append(b)
            b *= 2
        buckets.append(max_len)
    out = bucket_ladder(buckets, env_var="MXNET_GENERATION_PREFILL_BUCKETS")
    return tuple(sorted({min(int(b), int(max_len)) for b in out}))


class _Session:
    """Engine-side state of one admitted (or queued) generation."""

    __slots__ = ("prompt", "max_new_tokens", "eos_id", "deadline", "stream",
                 "span", "slot", "generated", "prefix_len", "version",
                 "tenant", "qos_rank", "admit_seq")

    def __init__(self, prompt, max_new_tokens, eos_id, deadline, stream,
                 tenant=None):
        self.prompt = prompt            # np.int32 [n]
        self.max_new_tokens = int(max_new_tokens)
        self.eos_id = eos_id
        self.deadline = deadline
        self.stream = stream
        self.span = None                # tracing root (MXNET_TRACING=1)
        self.slot = None
        self.generated = 0
        self.prefix_len = 0             # cached tokens forked at admission
        self.version = 0                # weights version pinned at admission
        #                                 (rollout: the session finishes
        #                                 bit-exact on these weights even
        #                                 after a swap)
        self.tenant = tenant            # QoS tenant (None = default class)
        self.qos_rank = None            # class rank stamped at admission
        self.admit_seq = 0              # admission order: the preemptor
        #                                 parks the YOUNGEST batch session


class GenerationEngine:
    """Continuous-batching autoregressive server over one model replica.

    Parameters
    ----------
    model : a model offering the cache protocol
        (:class:`TransformerLM`, :class:`HybridLM`): ``cfg.max_len``,
        ``mesh``, ``param_specs``, ``init_cache`` / ``cache_traits`` /
        ``prefill`` / ``decode_step`` (pure, jit-able, cache-donating),
        and ``prefill_at`` / ``verify_step`` when its cache is rewindable.
    params : dict[str, jax.Array]
        The model's parameters (``init_params`` placement).
    max_slots / max_len / buckets / tick_budget_ms :
        Overrides of the ``MXNET_GENERATION_*`` knobs.
    max_queue : int, optional
        Intake bound (default ``MXNET_SERVING_MAX_QUEUE``).
    eos_id : int, optional
        Default end-of-sequence token for sessions that don't pass one.
    start : bool
        Spin the scheduler worker thread (tests drive ticks manually with
        ``False``).
    prefix_cache / prefix_min_tokens :
        Overrides of ``MXNET_GENERATION_PREFIX_CACHE`` /
        ``_PREFIX_MIN_TOKENS`` — cache prompt-prefix KV in free slab
        slots and admit matching prompts via fork + suffix prefill.
        Refused (``MXNetError``) for a model whose cache is not
        rewindable (a recurrent state), as is ``spec_k > 0``.
    spec_k : int, optional
        Override of ``MXNET_GENERATION_SPEC_K`` — draft length for the
        speculative verify lane (0 = plain one-token decode). The slab
        grows ``spec_k`` scratch rows so a near-capacity slot's verify
        writes stay in bounds, which costs ``spec_k`` positions of the
        model's range: ``max_len`` is clamped to ``cfg.max_len - spec_k``.
    draft : Draft, optional
        The draft model for the speculative lane (default: a
        ``CheckpointDraft`` from ``MXNET_GENERATION_DRAFT``, else the
        n-gram fallback).
    """

    def __init__(self, model, params, max_slots=None, max_len=None,
                 buckets=None, max_queue=None, tick_budget_ms=None,
                 eos_id=None, start=True, prefix_cache=None,
                 prefix_min_tokens=None, spec_k=None, draft=None):
        self._model = model
        self._params = params
        self._slots = int(getenv("MXNET_GENERATION_SLOTS")
                          if max_slots is None else max_slots)
        self._spec_k = int(getenv("MXNET_GENERATION_SPEC_K")
                           if spec_k is None else spec_k)
        if self._spec_k < 0:
            raise MXNetError(f"spec_k must be >= 0, got {self._spec_k}")
        self._max_len = int(getenv("MXNET_GENERATION_MAX_LEN")
                            if max_len is None else max_len)
        self._max_len = min(self._max_len, model.cfg.max_len - self._spec_k)
        if self._max_len < 2:
            raise MXNetError(
                f"max_len {self._max_len} after reserving {self._spec_k} "
                f"speculative scratch rows from the model's positional "
                f"range {model.cfg.max_len} — lower MXNET_GENERATION_SPEC_K")
        # the slab carries spec_k scratch rows past session capacity: a
        # verify block starting at the last legal position writes k rows
        # past it, and those writes must land somewhere no session owns
        self._slab_len = self._max_len + self._spec_k
        if self._slots < 1:
            raise MXNetError(f"need >= 1 slot, got {self._slots}")
        self._buckets = prefill_ladder(buckets, self._max_len)
        budget_ms = (getenv("MXNET_GENERATION_TICK_BUDGET_MS")
                     if tick_budget_ms is None else tick_budget_ms)
        self._tick_budget_s = float(budget_ms) / 1e3
        self._eos_id = eos_id
        self._logger = get_logger("mxnet_tpu.serving.generation")

        self._cache = CompileCache("generation")
        # weight rollout state: _param_sets pins every weights version a
        # live session may still decode under — {version: (params, ws)}
        # where ws is the publishing WeightSet (None for construction
        # params). swap_weights() flips _params/_weights_version between
        # ticks; _gc_param_sets() releases a version once no session
        # pins it
        self._weights_version = 0
        self._param_sets = {0: (params, None)}
        # multi-tenant QoS (default-off): with a registry active the slab
        # grows MXNET_QOS_PARK_SLOTS park rows past session capacity —
        # preemption forks a batch session's KV rows into the park region
        # and resumes it later, bit-exact, through the SAME fork
        # executable. With QoS off _total_slots == _slots, so every
        # executable key (and the compile accounting) is bit-identical
        self._qos = qos.active()
        self._park = (int(getenv("MXNET_QOS_PARK_SLOTS"))
                      if self._qos is not None else 0)
        if self._park < 0:
            raise MXNetError(
                f"MXNET_QOS_PARK_SLOTS must be >= 0, got {self._park}")
        self._total_slots = self._slots + self._park
        self._parked = {}            # park slot -> {sess, length, last_tok,
        #                              parked_at}
        self._park_free = list(range(self._slots, self._total_slots))
        self._admit_seq = 0
        # the cache belongs to the model: a tuple of arrays, slot-major,
        # that the engine carries whole — donated to every program, forked
        # and parked one slot of every member at a time — and otherwise
        # only asks the model about (cache_traits)
        self._kv = tuple(model.init_cache(self._total_slots,
                                          self._slab_len))
        traits = model.cache_traits(self._kv)
        # the decode kernel's block over the slab's rows, None when the
        # model keeps the XLA formulation (telemetry only)
        self._slab_block = traits["block"]
        # per-slot bytes of state that is not a range of rows (a recurrent
        # layer's): read and written whole by every tick a slot is live
        self._state_bytes = int(traits["state_bytes_per_slot"])
        # counters the MODEL defines and computes on the device from what a
        # decode step left in the cache (an expert model's routing): names
        # under "serving.generation.", or () for a model that has none
        self._tick_counter_names = tuple(traits.get("tick_counters", ()))
        # host-side slot metadata — only the tick loop (under _tick_lock)
        # mutates these
        self._sessions = [None] * self._total_slots
        # a decode dispatched one tick AHEAD of its commit (_dispatch_ahead):
        # the pending state the next tick commits, None when none is out
        self._ahead = None
        # where the decode's token argument lives: placed like the token
        # OUTPUT of the decode before it, which _dispatch_ahead feeds back
        # — a host array left to jax's default placement would make the
        # same program compile a second time for the fed-back kind
        from jax.sharding import NamedSharding, PartitionSpec
        self._token_sharding = NamedSharding(model.mesh, PartitionSpec())
        self._lengths = np.zeros(self._total_slots, np.int32)
        self._last_tok = np.zeros(self._total_slots, np.int32)
        self._live = 0

        self._queue = AdmissionQueue(max_queue,
                                     metric_prefix="serving.generation")
        self._tick_lock = analysis.make_lock("generation.tick")
        self._work = analysis.make_condition("generation.work")
        self._closed = False
        self._tokens_window = 0
        self._rate_t0 = time.monotonic()
        self.sessions_submitted = 0   # per-replica intake (router balance)
        # fleet-health wiring: liveness/readiness probes (/healthz,
        # /readyz, router drain) + the scheduler-tick progress beacon the
        # stall watchdog monitors. Registration is construction-time;
        # the tick path pays one health._enabled read when the layer is
        # off (pinned by test_health.py)
        self._warmed = False          # set by warm(); ready() also
        #                               accepts traffic-compiled engines
        self.health_name, self._beacon = attach_engine(self)
        if self._qos is not None and health._enabled:
            # per-tenant TTFT burn rows join the SLO tracker once per
            # registry (idempotent across replicas)
            qos.attach_slo(self._qos)

        use_prefix = (bool(getenv("MXNET_GENERATION_PREFIX_CACHE"))
                      if prefix_cache is None else bool(prefix_cache))
        if (use_prefix or self._spec_k) and not traits["rewindable"]:
            # prefix reuse extends a slot from a row offset and speculation
            # rolls it back by not advancing a position: both need every
            # member of the cache to be a range of rows
            raise MXNetError(
                f"{'prefix cache' if use_prefix else 'speculative decoding'}"
                f" (prefix_cache={use_prefix}, spec_k={self._spec_k}) cannot"
                f" serve this model: {traits['why_not_rewindable']}")
        if use_prefix and getattr(model.cfg, "moe_experts", 0) > 0:
            # MoE expert capacity is computed over the forward's input
            # length, so a suffix-only prefill can capacity-drop
            # DIFFERENT tokens than the full-prompt prefill would — the
            # fork path would then diverge beyond the documented ulp
            # level depending on what the cache happened to hold. Until
            # prefill_at routes with full-prompt capacity semantics the
            # cache stays off for MoE models
            self._logger.warning(
                "prefix cache disabled: MoE capacity is length-dependent"
                " and a suffix prefill would route differently than the"
                " full prefill")
            use_prefix = False
        self._prefix_min = int(
            getenv("MXNET_GENERATION_PREFIX_MIN_TOKENS")
            if prefix_min_tokens is None else prefix_min_tokens)
        self._prefix = (RadixPrefixCache(owner=self.health_name)
                        if use_prefix else None)
        self._draft = None
        if self._spec_k:
            self._draft = (speculative.default_draft(model.mesh)
                           if draft is None else draft)
            self._draft.attach(self)

        # the slab is device state the engine REPLACES every tick, so the
        # census needs a live view, not a snapshot weakref
        memory.register_provider("kv_cache", self, lambda e: list(e._kv))

        self._worker = None
        if start:
            self._worker = threading.Thread(
                target=self._loop, daemon=True,
                name="mxnet_tpu.serving.generation.engine")
            self._worker.start()

    # -- properties ----------------------------------------------------------

    @property
    def max_slots(self):
        """Session capacity (park slots excluded — they are preemption
        headroom, never admittable)."""
        return self._slots

    @property
    def total_slots(self):
        """Slab slot count including the QoS park region — the dimension
        every slab-shaped executable and the draft's slab use."""
        return self._total_slots

    @property
    def parked_count(self):
        """Preempted sessions currently parked in the slab's park region."""
        return len(self._parked)

    @property
    def batch_live(self):
        """Live batch-class sessions — the router's class-aware placement
        signal (interactive avoids batch-heavy replicas, batch packs onto
        them). Always 0 while QoS is off."""
        if self._qos is None:
            return 0
        return sum(1 for s in self._sessions
                   if s is not None and s.qos_rank == qos.BATCH_RANK)

    @property
    def max_len(self):
        return self._max_len

    @property
    def prefill_buckets(self):
        return self._buckets

    @property
    def spec_k(self):
        """Draft length of the speculative lane (0 = plain decode)."""
        return self._spec_k

    @property
    def draft(self):
        return self._draft

    @property
    def prefix_cache(self):
        """The engine's :class:`RadixPrefixCache` (None when disabled)."""
        return self._prefix

    @property
    def weights_version(self):
        """Version of the CURRENT weight set (new admissions use it; live
        sessions keep the version they were admitted under)."""
        return self._weights_version

    @property
    def live_weight_versions(self):
        """Sorted versions some live session still decodes under plus the
        current one — >1 entry only while an old version drains after a
        swap."""
        versions = {s.version for s in self._sessions if s is not None}
        versions.add(self._weights_version)
        return sorted(versions)

    def prefix_match_len(self, prompt):
        """Longest USABLE cached prefix of ``prompt`` on this engine (0
        when below the fork threshold or the cache is off) — the router's
        affinity probe; cheap host trie walk, no device work. Only
        current-version entries count (admission forks filter the same
        way)."""
        if self._prefix is None:
            return 0
        m = self._prefix.match_len(
            np.asarray(prompt, dtype=np.int32).reshape(-1),
            version=self._weights_version)
        return m if m >= self._prefix_min else 0

    @property
    def cache(self):
        """The engine's ``"generation"`` :class:`CompileCache` — ``.misses``
        is the exact number of programs compiled so far."""
        return self._cache

    @property
    def live_slots(self):
        return self._live

    @property
    def queue_depth(self):
        return len(self._queue)

    @property
    def load(self):
        """Occupancy the router balances on: (live + queued) / slots."""
        return (self._live + len(self._queue)) / float(self._slots)

    @property
    def closed(self):
        return self._closed

    # -- health --------------------------------------------------------------

    def healthy(self):
        """Liveness: (ok, detail). False only when the scheduler worker
        thread died while the engine still owes work (a closed engine's
        joined worker is fine, and manually-ticked engines have none)."""
        if (self._worker is not None and not self._worker.is_alive()
                and not self._closed):
            return False, "scheduler worker thread died"
        return True, "ok"

    def ready(self):
        """Readiness: (ok, reason) — the router's placement gate and the
        ``/readyz`` probe. Not ready while draining (closed), while the
        tick beacon is marked stalled by the watchdog, before any
        executable exists (warm() not run AND no traffic compiled one),
        or with the intake queue above the watermark."""
        if self._closed:
            return False, "closed (draining)"
        if self._beacon.stalled:
            return False, "scheduler stalled (watchdog)"
        if not self._warmed and not len(self._cache):
            return False, "warmup not run"
        return queue_ready(self._queue)

    def kv_slab_bytes(self):
        """Total device bytes the serving cache pins (every member: key and
        value slabs, and a recurrent model's state) — the number
        ``docs/faq/perf.md`` "Sizing the KV slab" budgets."""
        return sum(int(leaf.nbytes) for leaf in self._kv)

    def slot_snapshot(self, slot):
        """Host copies of one slot of every member of the cache, in the
        cache's order, taken between ticks — for tests and for comparing
        what a session left (its K/V rows, a recurrent model's state) with
        a reference. A slot keeps a finished session's contents until the
        next occupant's prefill."""
        with self._tick_lock:
            return tuple(np.asarray(leaf[int(slot)]) for leaf in self._kv)

    # the K and V slabs of a cache that leads with them (both models'
    # do): what tests and debuggers index; the engine itself does not
    @property
    def _ck(self):
        return self._kv[0]

    @property
    def _cv(self):
        return self._kv[1]

    def bucket_for(self, n):
        for b in self._buckets:
            if b >= n:
                return b
        return None

    # -- client API ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens=64, eos_id=None, timeout=None,
               tenant=None):
        """Admit one prompt; returns a :class:`GenerationStream`
        immediately. ``timeout`` (seconds) is the SESSION deadline —
        checked every scheduler tick, in queue and mid-generation; expiry
        evicts the slot and fails the stream with
        :class:`DeadlineExceededError`. ``tenant`` names the QoS tenant
        (class/quota/weight per ``MXNET_QOS_SPEC``; ignored while QoS is
        off). Raises ``QueueFullError`` / ``ServerClosedError`` (and,
        QoS active, ``QuotaExceededError``) synchronously (backpressure
        is a signal, not a stall)."""
        prompt = np.asarray(prompt, dtype=np.int32).reshape(-1)
        if prompt.size < 1:
            raise MXNetError("empty prompt")
        if prompt.size > self._buckets[-1]:
            raise MXNetError(
                f"prompt of {prompt.size} tokens exceeds the largest "
                f"prefill bucket {self._buckets[-1]}")
        if max_new_tokens < 1:
            raise MXNetError("max_new_tokens must be >= 1")
        if prompt.size + int(max_new_tokens) > self._max_len:
            raise MXNetError(
                f"prompt ({prompt.size}) + max_new_tokens "
                f"({max_new_tokens}) exceeds the slab capacity "
                f"{self._max_len} (MXNET_GENERATION_MAX_LEN)")
        deadline = (time.monotonic() + float(timeout)
                    if timeout is not None else None)
        stream = GenerationStream(self, prompt.size, max_new_tokens,
                                  deadline, tenant=tenant)
        sess = _Session(prompt, max_new_tokens,
                        self._eos_id if eos_id is None else eos_id,
                        deadline, stream, tenant=tenant)
        if tracing._enabled:
            sess.span = tracing.begin("generation.session", cat="generation",
                                      prompt_tokens=int(prompt.size),
                                      max_new_tokens=int(max_new_tokens))
        req = Request([prompt], 1, stream._future, deadline=deadline,
                      payload=sess, tenant=tenant)
        try:
            self._queue.put(req)
        except Exception as e:
            if sess.span is not None:
                sess.span.set(error=repr(e)).finish()
            raise
        if telemetry._enabled:
            telemetry.counter("serving.generation.sessions").inc()
        if health._enabled:
            # work is pending: the tick beacon's silence now counts as a
            # stall until the slab drains again
            self._beacon.arm()
        with self._work:
            # under the condition lock: concurrent submitters would lose
            # increments of a bare +=
            self.sessions_submitted += 1
            self._work.notify_all()
        return stream

    def generate(self, prompt, **kwargs):
        """Blocking convenience: submit and collect the full token list
        (the iterator's caller-runs assist drives ticks inline when the
        worker is idle)."""
        return list(self.submit(prompt, **kwargs))

    def warm(self, buckets=None):
        """Compile-ahead every generation executable the enabled features
        will run, counted exactly (``cache.misses`` delta): one prefill
        program per bucket, plus — prefix cache on — one suffix-prefill
        program per bucket and THE fork program, plus THE decode program
        (plain) or THE verify program and the draft's own pinned set
        (speculative). Prefill/suffix warms write garbage into a FREE
        slot (skipped, with a log, for buckets that cannot get one on an
        already-full slab — they were compiled by real traffic anyway);
        the decode/verify warm runs only while no session is live, with
        every slot dead (:meth:`_tick_positions`: nothing is written), so
        warming a serving engine never perturbs a session or a cached
        prefix entry. Returns
        ``{"buckets", "compiles", "seconds", "cache_entries"}``."""
        import jax.numpy as jnp

        buckets = (self._buckets if buckets is None
                   else tuple(sorted({int(b) for b in buckets})))
        t0 = time.perf_counter()
        misses0 = self._cache.misses
        with self._tick_lock:
            free_list = self._free_slots()
            free = free_list[0] if free_list else None
            for b in buckets:
                if b not in self._buckets:
                    raise MXNetError(f"bucket {b} not in ladder "
                                     f"{self._buckets}")
                if free is None:
                    self._logger.warning(
                        "generation warmup: slab full, skipping prefill "
                        "warm for bucket %d", b)
                    continue
                fn = self._prefill_fn(b)
                _, self._kv = fn(
                    self._params, self._kv,
                    jnp.zeros((b,), jnp.int32), jnp.asarray(1, jnp.int32),
                    jnp.asarray(free, jnp.int32))
                if self._prefix is not None:
                    fn = self._suffix_prefill_fn(b)
                    _, self._kv = fn(
                        self._params, self._kv,
                        jnp.zeros((b,), jnp.int32),
                        jnp.asarray(1, jnp.int32),
                        jnp.asarray(free, jnp.int32),
                        jnp.asarray(0, jnp.int32))
            if (self._prefix is not None or self._park) and free is not None:
                # self-copy: compiles the fork without disturbing anything
                # (the prefix cache's admission fork AND the QoS
                # preempt/park/resume path share this one executable —
                # warming it here is what keeps preemption compile-free)
                self._fork(free, free)
            idle = self._live == 0
            if self._spec_k:
                if idle:
                    fn = self._verify_fn()
                    _, self._kv = fn(
                        self._params, self._kv,
                        jnp.zeros((self._total_slots, self._spec_k + 1),
                                  jnp.int32),
                        jnp.asarray(self._tick_positions()))
                    self._draft.warm()
                else:
                    self._logger.warning(
                        "generation warmup: engine busy, skipping "
                        "verify/draft warm")
            elif idle:
                fn = self._decode_fn()
                toks, self._kv = fn(
                    self._params, self._kv,
                    self._tokens_on_device(self._last_tok),
                    jnp.asarray(self._tick_positions()))
                # the token select of _dispatch_ahead (an eager op: jax's
                # own cache holds it, not this engine's)
                jnp.where(self._tokens_on_device(
                    np.ones(self._total_slots, bool)),
                    self._tokens_on_device(self._last_tok), toks)
                if self._tick_counter_names:
                    self._tick_counters_fn()(
                        self._kv, jnp.asarray(self._tick_positions()))
        compiles = self._cache.misses - misses0
        seconds = time.perf_counter() - t0
        self._warmed = True           # readiness: warmup complete
        if telemetry._enabled:
            telemetry.counter("serving.generation.warmup_compiles").inc(
                compiles)
        self._logger.info(
            "generation warmup: %d bucket(s) + %s -> %d compile(s) in "
            "%.2fs (cache %r holds %d executables)", len(buckets),
            "verify" if self._spec_k else "decode", compiles,
            seconds, self._cache.name, len(self._cache))
        return {"buckets": list(buckets), "compiles": compiles,
                "seconds": seconds, "cache_entries": len(self._cache)}

    # -- weight rollout ------------------------------------------------------

    def _place_params(self, new):
        """Validate and device-place one incoming host weight dict against
        the CURRENT params: same key set, same shapes, values cast to the
        current dtypes and placed with the model's partition specs — the
        guarantees that make the swap a pure buffer substitution (every
        executable key is shape-only, params are non-donated arguments,
        so the warmed decode/verify/prefill programs are reused
        untouched)."""
        import jax

        cur = self._params
        if set(new) != set(cur):
            missing = sorted(set(cur) - set(new))
            extra = sorted(set(new) - set(cur))
            raise MXNetError(
                f"swap_weights: parameter names differ from the bound set "
                f"(missing {missing}, unexpected {extra}) — a hot swap "
                "must cover exactly the bound parameters")
        specs = self._model.param_specs()
        placed = {}
        for name, v in new.items():
            old = cur[name]
            arr = np.asarray(v)
            if tuple(arr.shape) != tuple(old.shape):
                raise MXNetError(
                    f"swap_weights: parameter {name!r} has shape "
                    f"{tuple(arr.shape)} but the warmed executables "
                    f"expect {tuple(old.shape)} — identical shapes/dtypes "
                    "are what make the swap compile-free")
            placed[name] = jax.device_put(
                arr.astype(old.dtype, copy=False), specs[name])
        return placed

    def swap_weights(self, weights, draft_params=None, version=None):
        """Atomic zero-downtime weight flip, BETWEEN ticks (takes the
        tick lock): new admissions prefill and decode under the new
        weights; sessions already live keep decoding — bit-exact — under
        the version they were admitted with until they finish (the tick
        runs one executable dispatch per live version, same programs,
        the slots of other cohorts marked dead). The
        KV slab, the radix prefix cache structure and the speculative
        draft slab all survive the flip; prefix entries stamped with
        other versions are evicted (their KV would splice old-weight
        rows under new-weight logits), and a checkpoint draft's params
        flip immediately for every slot — stale draft slab rows only
        cost acceptance ratio, never correctness (the verify is the
        ground truth).

        ``weights`` is a :class:`~..rollout.WeightSet` or a plain host
        param dict. Returns the new version, or None when ``version``
        equals the current one (idempotent double-publish no-op).
        Rolling BACK to a still-pinned older version reuses its placed
        params directly."""
        ws = None
        if hasattr(weights, "arg_params") and hasattr(weights, "version"):
            ws = weights
            version = ws.version if version is None else version
            new = dict(ws.arg_params)
            new.update(ws.aux_params)
            if draft_params is None and ws.draft_params:
                draft_params = ws.draft_params
        else:
            new = dict(weights)
        with self._tick_lock:
            if version is None:
                version = self._weights_version + 1
            version = int(version)
            if version == self._weights_version:
                if telemetry._enabled:
                    telemetry.counter(
                        "serving.generation.weight_swap_noops").inc()
                return None
            held = self._param_sets.get(version)
            if held is not None:
                # rollback to a version still pinned by draining sessions:
                # its placed buffers are right there
                placed = held[0]
            else:
                placed = self._place_params(new)
                self._param_sets[version] = (
                    placed, ws.acquire() if ws is not None else None)
            self._params = placed
            self._weights_version = version
            if draft_params and self._draft is not None:
                self._draft.swap_params(draft_params)
            if self._prefix is not None:
                self._prefix.evict_other_versions(version)
            self._gc_param_sets()
        if telemetry._enabled:
            telemetry.counter("serving.generation.weight_swaps").inc()
            telemetry.gauge("serving.generation.weights_version").set(
                version)
        if health._enabled:
            health.event("rollout_swap", engine=self.health_name,
                         version=version,
                         draining=len(self._param_sets) - 1)
        self._logger.info(
            "weights swapped to version %d (%d older version(s) still "
            "draining)", version, len(self._param_sets) - 1)
        return version

    def weights_snapshot(self):
        """Replicated host copy of the CURRENT weights (+ draft) and
        their version — the router pins this before a fleet's first
        rolling swap so automatic rollback always has a target, even
        when the construction params were never published."""
        with self._tick_lock:
            params = {k: np.asarray(v) for k, v in self._params.items()}
            draft = None
            if self._draft is not None and hasattr(self._draft, "_params"):
                draft = {k: np.asarray(v)
                         for k, v in self._draft._params.items()}
            return self._weights_version, params, draft

    def _version_params(self, version):
        """The placed param dict pinned for ``version`` (the cohort
        dispatch in _decode_dispatch/_spec_dispatch)."""
        return self._param_sets[version][0]

    def _cohorts(self):
        """Live slots grouped by pinned weights version — one entry in
        steady state; more only while old versions drain after swaps."""
        out = {}
        for slot, sess in enumerate(self._sessions):
            if sess is not None:
                out.setdefault(sess.version, []).append(slot)
        return out

    def _gc_param_sets(self):
        """Release weight versions no live session pins anymore (tick
        lock held). The current version always stays; a released
        version's WeightSet drops its engine reference and the drain is
        journaled — 'both WeightSets stay alive until the old one
        drains' is exactly this refcount."""
        if len(self._param_sets) <= 1:
            return
        pinned = {s.version for s in self._sessions if s is not None}
        pinned.add(self._weights_version)
        for v in [v for v in self._param_sets if v not in pinned]:
            _, ws = self._param_sets.pop(v)
            if ws is not None:
                ws.release()
            if health._enabled:
                health.event("rollout_drained", engine=self.health_name,
                             version=v, current=self._weights_version)
        if telemetry._enabled:
            telemetry.gauge(
                "serving.generation.weight_versions_live").set(
                len(self._param_sets))

    def close(self, timeout=None):
        """Graceful drain: stop admission (``ServerClosedError`` for new
        submits), keep ticking until every admitted AND queued session
        completes, join the worker. Idempotent. Deregisters the health
        probes — a deliberately closed engine must not pin ``/readyz``."""
        self._queue.close()
        self._closed = True
        with self._work:
            self._work.notify_all()
        if self._worker is not None and self._worker.is_alive():
            self._worker.join(timeout)
        health.unregister(self.health_name)
        self._beacon.idle()
        # a closed engine pins no published weights: drop every WeightSet
        # reference (the placed current params stay usable for reopen-free
        # introspection)
        for _, ws in self._param_sets.values():
            if ws is not None:
                ws.release()
        self._param_sets = {self._weights_version: (self._params, None)}

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        self.close()
        return False

    def stats(self):
        out = {"cache": self._cache.snapshot(),
               "buckets": list(self._buckets),
               "slots": self._slots, "live": self._live,
               "queued": len(self._queue),
               "sessions": self.sessions_submitted,
               "max_len": self._max_len,
               "spec_k": self._spec_k,
               "kv_slab_bytes": self.kv_slab_bytes(),
               "weights_version": self._weights_version,
               "weight_versions_live": self.live_weight_versions}
        if self._prefix is not None:
            out["prefix"] = self._prefix.stats()
        if self._draft is not None and hasattr(self._draft, "slab_bytes"):
            out["draft_slab_bytes"] = self._draft.slab_bytes()
        if self._qos is not None:
            out["qos"] = {"park_slots": self._park,
                          "parked": len(self._parked),
                          "weighted_demand": self.qos_demand()}
        return out

    # -- compiled programs ---------------------------------------------------

    def _prefill_fn(self, bucket):
        """The bucket's prefill executable: prompt forward + slab write +
        greedy next token, slab buffers donated."""
        model, cache = self._model, self._cache

        def build():
            import jax
            import jax.numpy as jnp

            def fn(params, cache, toks, length, slot):
                logits, *cache = model.prefill(params, *cache, toks,
                                               length, slot)
                return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

            return jax.jit(fn, donate_argnums=(1,))

        key = ("prefill", bucket, self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    def _decode_fn(self):
        """THE decode executable — one fused step over the whole slab,
        greedy sampling inside, slab buffers donated. Its key never
        changes, so continuous admission/eviction is hit-only."""
        model, cache = self._model, self._cache

        def build():
            import jax
            import jax.numpy as jnp

            def fn(params, cache, tokens, positions):
                logits, *cache = model.decode_step(params, *cache, tokens,
                                                   positions)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        tuple(cache))

            return jax.jit(fn, donate_argnums=(1,))

        key = ("decode", self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    def _tick_counters_fn(self):
        """The model's own counters of one decode step
        (``cache_traits(...)["tick_counters"]``): a few integers computed on
        the device from the cache the decode just returned (not donated) and
        its positions. Dispatched behind a decode only while telemetry is
        on, fetched at that decode's commit; not named ``fn``, so a trace
        tells it from the decode and prefill programs."""
        model, cache = self._model, self._cache

        def build():
            import jax

            def tick_counters(cache, positions):
                return model.tick_counters(*cache, positions)

            return jax.jit(tick_counters)

        key = ("tick_counters", self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    def _fork_fn(self):
        """THE fork executable: copy one slot of EVERY member of the cache
        (K and V rows of all layers; a recurrent model's state too) onto
        another slot, src/dst traced — one program serves every (cached
        entry, session slot) pair and the QoS park/resume. Cache donated;
        a prefix hit costs one dispatch plus the suffix prefill."""
        cache = self._cache

        def build():
            import jax
            from jax import lax

            def fn(kv, src, dst):
                def copy(leaf):
                    rest = (0,) * (leaf.ndim - 1)
                    row = lax.dynamic_slice(leaf, (src,) + rest,
                                            (1,) + leaf.shape[1:])
                    return lax.dynamic_update_slice(leaf, row, (dst,) + rest)

                return tuple(copy(leaf) for leaf in kv)

            return jax.jit(fn, donate_argnums=(0,))

        key = ("fork", self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    def _tokens_on_device(self, host):
        """A per-slot host array as the decode's token argument."""
        import jax

        return jax.device_put(host, self._token_sharding)

    def _fork(self, src, dst):
        """Run the fork executable: slot ``src`` of the cache onto slot
        ``dst`` (prefix insert and hit, QoS park and resume)."""
        import jax.numpy as jnp

        with tracing.span("generation.fork", cat="generation", src=int(src),
                          dst=int(dst)):
            self._kv = self._fork_fn()(self._kv,
                                       jnp.asarray(src, jnp.int32),
                                       jnp.asarray(dst, jnp.int32))

    def _suffix_prefill_fn(self, bucket):
        """The bucket's suffix-prefill executable: the prompt tail after
        a fork, writing rows [offset, offset+bucket) and attending the
        forked prefix — offset traced, one program per bucket."""
        model, cache = self._model, self._cache

        def build():
            import jax
            import jax.numpy as jnp

            def fn(params, cache, toks, length, slot, offset):
                logits, *cache = model.prefill_at(params, *cache, toks,
                                                  length, slot, offset)
                return jnp.argmax(logits).astype(jnp.int32), tuple(cache)

            return jax.jit(fn, donate_argnums=(1,))

        key = ("suffix_prefill", bucket, self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    def _verify_fn(self):
        """THE speculative verify executable — k+1 unrolled decode graphs
        over the whole slab in one program (greedy argmax per position
        inside), slab donated. Like the decode key, it never changes:
        every draft/accept pattern is a hit."""
        model, cache = self._model, self._cache

        def build():
            import jax
            import jax.numpy as jnp

            def fn(params, cache, tokens, positions):
                logits, *cache = model.verify_step(params, *cache, tokens,
                                                   positions)
                return (jnp.argmax(logits, axis=-1).astype(jnp.int32),
                        tuple(cache))

            return jax.jit(fn, donate_argnums=(1,))

        key = ("verify", self._spec_k, self._total_slots, self._slab_len)
        return cache.get_or_build(key, build)

    # -- scheduler -----------------------------------------------------------

    def _has_work(self):
        return (self._live > 0 or len(self._queue) > 0
                or len(self._parked) > 0 or self._ahead is not None)

    def _loop(self):
        while True:
            with self._work:
                while not self._closed and not self._has_work():
                    self._work.wait()
                if self._closed and not self._has_work():
                    return
            self._tick_once()

    def _assist_once(self):
        """Caller-runs assist (stream iterators call this while waiting):
        run one tick inline if the tick lock is free. Returns True when a
        tick ran (or there was nothing to do), False when the worker (or
        another assistant) holds the lock — the caller should briefly
        park instead of spinning."""
        if not self._tick_lock.acquire(blocking=False):
            return False
        try:
            if self._has_work():
                self._tick()
            return True
        finally:
            self._tick_lock.release()

    def _tick_once(self):
        with self._tick_lock:
            if self._has_work():
                self._tick()

    def _tick(self):
        """One scheduler tick (tick lock held): dispatch ONE fused decode
        over the slab, sweep deadlines and admit prefills into free slots
        while it runs, then commit its tokens and evict finished sessions.
        A tick never raises — an executable
        failure fails the live sessions (never-strand, the batcher's
        guard) and reallocates the possibly-donated slab."""
        tele = telemetry._enabled
        t0 = time.perf_counter()
        # the tick's own span tree (sweep / decode / admit > prefill >
        # prefill.fetch / commit > commit.fetch, children via the context
        # var; per-SESSION spans keep their explicit session parents) —
        # `mx:generation.*` in any jax.profiler trace, and observed into
        # tracing.tick_recorder, the generation analog of the slow-step
        # flight recorder (/trace serves it as worst_tick)
        tick_span = tracing.span("generation.tick", cat="generation",
                                 live=self._live, queued=len(self._queue))
        with tick_span:
            try:
                # dispatch the decode FIRST, do the host bookkeeping
                # (queue expiry, deadline sweep, admission scan) while the
                # executable runs, THEN block and commit — the tick's host
                # work hides behind device time instead of serializing
                # ahead of it. Sessions evicted or replaced inside that
                # window are identity-guarded at commit (their tokens are
                # discarded; the stale slab rows are masked garbage the
                # next occupant's prefill overwrites). Admitted prefills
                # chain on the still-lazy decode cache outputs, so they
                # join the NEXT tick's decode — per-session token streams
                # are those of one request generated alone.
                # the decode this tick commits is the one the last
                # tick dispatched AHEAD, else one dispatched now; where
                # it may (_lookahead_ok) the decode after it goes out
                # before the commit, so the device has its next program
                # queued while the host fetches, commits and dispatches
                pending, self._ahead = self._ahead, None
                first = pending is None
                if first:
                    pending = self._decode_dispatch()
                if pending is not None and self._lookahead_ok(pending):
                    # (a tick that dispatched its own decode above has
                    # its span already: a tick shows one decode child)
                    self._ahead = self._dispatch_ahead(pending,
                                                       span=not first)
                self._sweep()
                # a live slot is parked only BETWEEN decodes: the one in
                # flight advances its slot, and a recurrent state is
                # not advanced twice by the same token the way a K/V
                # row is rewritten (the parked copy would hold a token
                # that the identity guard then discards at commit)
                self._admit(preempt=pending is None)
                if pending is not None:
                    self._decode_commit(pending)
                    if self._qos is not None:
                        self._admit()
                if len(self._param_sets) > 1:
                    # a swap transition is draining: release versions
                    # whose last session just finished
                    self._gc_param_sets()
            except Exception as e:  # noqa: BLE001 — never-strand + serve on
                self._logger.error("generation tick failed: %r", e)
                tick_span.set(error=repr(e))
                for slot, sess in enumerate(self._sessions):
                    if sess is not None:
                        self._evict(slot, "error", e)
                # parked sessions died with the slab too (their KV rows
                # lived in the same donated buffers) — never-strand
                for park, rec in list(self._parked.items()):
                    self._fail_parked(park, rec, e)
                self._ahead = None
                # the failed executable may have consumed the donated slab
                self._kv = tuple(self._model.init_cache(
                    self._total_slots, self._slab_len))
                if self._prefix is not None:
                    # the cached rows died with the donated buffers
                    self._prefix.clear("slab_reset")
                if self._draft is not None:
                    self._draft.reset()
                # every session died with the slab: stale weight versions
                # have nothing left to drain for
                self._gc_param_sets()
        if self._has_work():
            # close an assist-vs-worker race: an assist tick pops the
            # queue BEFORE publishing the session as live, and a parked
            # worker re-checking _has_work() inside that window goes back
            # to sleep with nobody left to wake it once the assisting
            # client stops iterating (e.g. takes its first token, then
            # blocks in result()). Any tick that leaves work pending
            # re-notifies, so the worker always resumes the schedule.
            with self._work:
                self._work.notify_all()
        if tracing._enabled:
            tracing.tick_recorder.observe(tick_span.tree())
        if health._enabled:
            # progress beacon: the tick RAN (even a failed one evicted and
            # reallocated — that is progress, not a stall); an empty slab
            # parks the scheduler, so silence while idle is not a stall
            self._beacon.touch()
            if not self._has_work():
                self._beacon.idle()
        if tele:
            dt = time.perf_counter() - t0
            telemetry.counter("serving.generation.ticks").inc()
            telemetry.histogram("serving.generation.tick_us").record(dt * 1e6)
            telemetry.gauge("serving.generation.live_slots").set(self._live)
            now = time.monotonic()
            if not self._has_work():
                # going idle: an un-reset gauge would report the last
                # active window's rate forever (the parked scheduler
                # never recomputes it)
                telemetry.gauge("serving.generation.tokens_per_s").set(0.0)
                self._tokens_window = 0
                self._rate_t0 = now
            elif now - self._rate_t0 >= 0.5:
                telemetry.gauge("serving.generation.tokens_per_s").set(
                    self._tokens_window / (now - self._rate_t0))
                self._tokens_window = 0
                self._rate_t0 = now

    def _sweep(self):
        """The tick's deadline sweep: queued requests past their deadline,
        live sessions past theirs, and the park region."""
        with tracing.span("generation.sweep", cat="generation"):
            now = time.monotonic()
            for req in self._queue.expire(now):
                self._fail_queued(req.payload, now)
            for slot, sess in enumerate(self._sessions):
                if (sess is not None and sess.deadline is not None
                        and now >= sess.deadline):
                    self._evict(
                        slot, "deadline", DeadlineExceededError(
                            f"session deadline passed after "
                            f"{sess.generated} generated token(s)"))
            self._sweep_parked(now)

    def _free_slots(self):
        """Session slots holding neither a live session nor a cached
        prefix (park-region slots are preemption headroom, never
        admission targets)."""
        held = self._prefix.slots() if self._prefix is not None else ()
        return [i for i in range(self._slots)
                if self._sessions[i] is None and i not in held]

    def _tick_positions(self, active=None):
        """Positions for the fixed-shape decode/verify executables: a live
        slot's length, and -1 — dead, by ``decode_step``'s contract — for
        every other slot: free, parked, or holding a cached prefix. A dead
        slot is neither written nor read, so the rows a cached entry or a
        parked session owns stay exactly as they were, and the tick reads
        the slab's live part only.

        ``active`` (an iterable of slot indices) also marks every LIVE
        slot outside it dead — the per-version cohort dispatch during a
        weight-swap transition: each cohort's executable call advances
        only its own slots."""
        pos = self._lengths.copy()
        act = None if active is None else set(active)
        for i, s in enumerate(self._sessions):
            if s is None or (act is not None and i not in act):
                pos[i] = -1
        return pos

    def _count_dispatch(self, positions):
        """Telemetry of one plain decode dispatch: the slab's blocks it
        reads and the state it advances (host arithmetic), and — for a model
        that defines counters of its own — their program, dispatched behind
        the decode on the cache it returned. Returns that program's output,
        still on the device (the commit fetches it with the tokens), or
        None."""
        import jax.numpy as jnp

        self._count_slab_blocks(positions)
        if not self._tick_counter_names:
            return None
        return self._tick_counters_fn()(self._kv, jnp.asarray(positions))

    def _count_slab_blocks(self, positions, steps=1):
        """Telemetry: how much of the slab this dispatch reads.
        ``slab_blocks_live`` over ``slab_blocks_total`` is the share of
        the slab's blocks the decode program had to read (1 when the model
        keeps the XLA formulation, which reads every row). With the slab
        kernel ``slab_blocks_live`` IS its grid: `decode_update_attend`
        takes one step a live block a layer (`pallas_decode.live_steps`)
        and none for the rest — and so does `pallas_window.
        kv_update_attend` on a FULL member, whose block the models that use
        it give as the trait (a ring's grid is its own rows' blocks, which
        nothing here counts). Host arithmetic on positions the tick
        already holds."""
        from ...ops.pallas_decode import live_blocks

        block = self._slab_block
        total = steps * self._total_slots * (
            self._slab_len // block if block else 1)
        if block:
            live = sum(int(live_blocks(
                np.where(positions >= 0, positions + i, -1), block).sum())
                for i in range(steps))
        else:
            live = total
        telemetry.counter("serving.generation.slab_blocks_live").inc(live)
        telemetry.counter("serving.generation.slab_blocks_total").inc(total)
        if self._state_bytes:
            self._count_state(steps * int((positions >= 0).sum()))

    def _count_state(self, live):
        """Telemetry of a model whose cache holds per-slot state that is not
        rows: the live slots whose state this dispatch advances, the bytes
        that moves at the least (each live slot's state read once and
        written once), and what the state pins. Host arithmetic."""
        telemetry.counter("serving.generation.state_slots_live").inc(live)
        telemetry.counter("serving.generation.state_bytes_touched").inc(
            2 * live * self._state_bytes)
        telemetry.gauge("serving.generation.state_bytes_resident").set(
            self._total_slots * self._state_bytes)

    def _prefix_claimable(self):
        """Cache entries session pressure may evict: everything above the
        retention floor. The floor (one entry, zero on a single-slot
        engine) keeps the hottest prefix alive through full occupancy —
        without it a saturated slab would evict the shared system prompt
        and every later admission would cold-miss, exactly the fleet
        pathology the cache exists to prevent."""
        if self._prefix is None:
            return 0
        keep = min(1, max(self._slots - 1, 0))
        return max(len(self._prefix) - keep, 0)

    def _claim_slot(self, free):
        """Pop a slot for a session: from the free list, else by evicting
        the LRU refcount-zero prefix entry above the retention floor —
        live sessions outrank cached prefixes. None when the slab is
        truly full."""
        if free:
            return free.pop(0)
        if self._prefix_claimable() and len(self._queue):
            return self._prefix.evict_lru("slot_pressure")
        return None

    def _admit(self, preempt=True):
        """Move queued sessions into free slots (prefill), oldest first
        (QoS active: class/deadline order), until the slab is full, the
        queue is empty, or the tick budget is spent — at least one
        admission per tick when a slot is free (or freeable by evicting
        a cached prefix), so backlog always drains even under a tiny
        budget. Under QoS, a full slab with a higher-class request at
        the queue head first PARKS the youngest batch session (one per
        tick — bounded churn) to free its slot; ``preempt`` False (a
        decode is in flight) leaves that to the pass after its commit."""
        free = self._free_slots()
        if self._qos is not None and not free and preempt:
            freed = self._preempt_for_priority()
            if freed is not None:
                free = [freed]
        if not free and not (self._prefix_claimable()
                             and len(self._queue)):
            return
        t0 = time.perf_counter()
        tele = telemetry._enabled
        with tracing.span("generation.admit", cat="generation",
                          free=len(free)):
            self._admit_into(free, t0, tele)

    def _admit_into(self, free, t0, tele):
        import jax.numpy as jnp

        while True:
            slot = self._claim_slot(free)
            if slot is None:
                return
            if (self._qos is not None and self._parked
                    and self._should_resume()):
                # no queued request outranks the parked batch work: un-park
                # the oldest preempted session into this slot instead of
                # admitting (anti-starvation — parked work drains the
                # moment pressure lifts)
                if self._resume_into(slot):
                    if time.perf_counter() - t0 > self._tick_budget_s:
                        return
                    continue
            batch, _ = self._queue.get_batch_nowait(1)
            if not batch:
                free.append(slot)
                return
            sess = batch[0].payload
            sess.qos_rank = batch[0].qos_rank
            now = time.monotonic()
            if sess.deadline is not None and now >= sess.deadline:
                self._fail_queued(sess, now)
                free.append(slot)
                continue
            n = int(sess.prompt.size)
            # prefix-cache lane: fork the longest usable cached prefix
            # slot-to-slot, then prefill only the unmatched suffix
            node = None
            if self._prefix is not None:
                node, m = self._prefix.match(
                    sess.prompt, version=self._weights_version)
                if node is None or m < self._prefix_min:
                    node = None
                elif m + self.bucket_for(n - m) > self._slab_len:
                    # the suffix BUCKET (not just the suffix) must fit
                    # past the split point — dynamic_update_slice CLAMPS
                    # an overhanging block start, which would smear the
                    # padded suffix over the forked prefix rows. Near-
                    # capacity prompts fall back to the always-in-bounds
                    # full prefill instead
                    node = None
            # queue wait: submit -> start of this admission. Both ends are
            # host events, so it is sound under the overlap order (the
            # prefill_us histogram below is not: see its record site)
            waited_us = (now - sess.stream.submitted_at) * 1e6
            # the prompt tokens this admission prefills, and the bucket
            # they are padded to: both are stats of the span below, so a
            # profiler trace says its own prefill sizes
            suffix = n - m if node is not None else n
            bucket = self.bucket_for(suffix)
            t_pf = time.perf_counter()
            trc = tracing._enabled and sess.span is not None
            if trc:
                # queue-wait child reconstructed from the submit instant
                t_pf_us = tracing.now_us()
                tracing.emit_span("generation.queued", sess.span.t0,
                                  t_pf_us - sess.span.t0,
                                  cat="generation", parent=sess.span)
            try:
                with tracing.span("generation.prefill", cat="generation",
                                  bucket=bucket, tokens=suffix, slot=slot,
                                  waited_us=int(waited_us)):
                    if node is not None:
                        tok = self._fork_admit(sess, slot, node, m, bucket)
                    else:
                        padded = np.zeros(bucket, np.int32)
                        padded[:n] = sess.prompt
                        fn = self._prefill_fn(bucket)
                        tok, self._kv = fn(
                            self._params, self._kv,
                            jnp.asarray(padded), jnp.asarray(n, jnp.int32),
                            jnp.asarray(slot, jnp.int32))
                        if tele and self._prefix is not None:
                            telemetry.counter(
                                "serving.generation.prefix.misses").inc()
                    with tracing.span("generation.prefill.fetch",
                                      cat="generation"):
                        # blocks until the prefill ran: under the overlap
                        # order that is behind the decode this tick
                        # dispatched first
                        tok = int(tok)
            except Exception as e:
                # the popped session is in neither the queue nor a slot —
                # the tick handler only evicts ADMITTED sessions, so fail
                # its stream here or it is stranded forever (never-strand,
                # the batcher's guard); re-raise for the slab reallocation
                if tele:
                    telemetry.counter("serving.generation.evictions").inc()
                    telemetry.counter("serving.generation.evict_error").inc()
                sess.stream._fail(e)
                if sess.span is not None:
                    sess.span.set(error=repr(e), reason="error").finish()
                raise
            if trc:
                # the same instants as the live span, under the session root
                tracing.emit_span("generation.prefill", t_pf_us,
                                  tracing.now_us() - t_pf_us,
                                  cat="generation", parent=sess.span,
                                  bucket=bucket, tokens=suffix, slot=slot,
                                  cached_prefix=sess.prefix_len)
            sess.slot = sess.stream.slot = slot
            # pinned for the session's whole life: after a swap the tick
            # keeps decoding this session under these exact weights
            sess.version = self._weights_version
            self._admit_seq += 1
            sess.admit_seq = self._admit_seq
            self._sessions[slot] = sess
            self._lengths[slot] = n
            self._last_tok[slot] = tok
            self._live += 1
            if self._draft is not None:
                self._draft.on_admit(slot, sess.prompt, tok)
            self._deliver(sess, tok, first=True)
            if tele:
                telemetry.counter("serving.generation.prefills").inc()
                telemetry.counter("serving.generation.prefill_tokens").inc(
                    n - sess.prefix_len)
                telemetry.histogram(
                    "serving.generation.queue_wait_us").record(waited_us)
                # prefill dispatch -> first token on the host. The tick
                # dispatched its decode first and the prefill queues
                # behind it on the device, so this reads ONE DECODE PLUS
                # the prefill; the prefill's own device time is the
                # trace's (the benchmark's prefill_ms_p50)
                telemetry.histogram("serving.generation.prefill_us").record(
                    (time.perf_counter() - t_pf) * 1e6)
            # cache the full prompt's KV for future sessions while a free
            # slot exists (never evict FOR an insert: only live sessions
            # force evictions) — the slot's rows [0, n) are exactly the
            # prompt's K/V right after prefill, so one fork snapshots them
            if (self._prefix is not None and n >= self._prefix_min
                    and free):
                cslot = free[0]
                if self._prefix.insert(sess.prompt, cslot,
                                       version=sess.version) is not None:
                    free.pop(0)
                    self._fork(slot, cslot)
            # the prompt's last token may already end the session; a slot
            # freed that way goes straight back on the free list so a
            # burst of first-token-EOS sessions drains within the tick
            self._maybe_finish(slot)
            if self._sessions[slot] is None:
                free.append(slot)
            if time.perf_counter() - t0 > self._tick_budget_s:
                return

    def _fork_admit(self, sess, slot, node, m, bucket):
        """Cache-hit admission: pin the entry, fork its slot onto the
        session's, suffix-prefill the unmatched tail (padded to
        ``bucket``) at offset ``m``. Returns the first sampled token,
        still on the device (the caller fetches it)."""
        import jax.numpy as jnp

        suffix = sess.prompt[m:]
        ns = int(suffix.size)
        padded = np.zeros(bucket, np.int32)
        padded[:ns] = suffix
        self._prefix.acquire(node)
        try:
            self._fork(node.slot, slot)
            fn = self._suffix_prefill_fn(bucket)
            tok, self._kv = fn(
                self._params, self._kv, jnp.asarray(padded),
                jnp.asarray(ns, jnp.int32), jnp.asarray(slot, jnp.int32),
                jnp.asarray(m, jnp.int32))
        finally:
            self._prefix.release(node)
        sess.prefix_len = m
        sess.stream.cached_prefix_len = m
        if telemetry._enabled:
            telemetry.counter("serving.generation.prefix.hits").inc()
            telemetry.counter("serving.generation.prefix.forks").inc()
            telemetry.counter(
                "serving.generation.prefix.cached_tokens_served").inc(m)
        return tok

    def _decode_dispatch(self):
        """Dispatch ONE fused step over the whole slab WITHOUT
        materializing the token output; :meth:`_decode_commit` blocks and
        delivers. Every live session advances one token (plain) or up to
        ``spec_k + 1`` (speculative verify); dead slots ride along unread
        and unwritten — that fixed shape is what makes mid-stream
        admit/evict free.

        During a weight-swap transition (live sessions pinned to more
        than one version) the SAME executable runs once per version
        cohort with that cohort's pinned params, other cohorts' slots
        marked dead — N dispatches, zero new programs. A later cohort's
        call only reads the earlier ones' cache outputs (pure lazy
        dataflow) and every non-member slot is dead to it, so every
        session's output stays bit-exact with an unswapped engine on its
        own weights. Returns the pending state for :meth:`_decode_commit`,
        or None when no slot is live."""
        import jax.numpy as jnp

        if self._live == 0:
            return None
        if self._spec_k:
            return self._spec_dispatch()
        fn = self._decode_fn()
        cohorts = self._cohorts()
        mixed = len(cohorts) > 1
        pending = []
        for version in sorted(cohorts):
            slots = cohorts[version]
            positions = self._tick_positions(slots if mixed else None)
            with tracing.span("generation.decode", cat="generation",
                              live=len(slots), version=version):
                toks, self._kv = fn(
                    self._version_params(version), self._kv,
                    self._tokens_on_device(self._last_tok),
                    jnp.asarray(positions))
            counted = None
            if telemetry._enabled:
                counted = self._count_dispatch(positions)
            # snapshot the cohort's sessions: a slot evicted or re-
            # admitted between dispatch and commit fails the identity
            # check and its token is discarded
            pending.append((slots, [self._sessions[s] for s in slots],
                            toks, counted))
        return ("plain", pending)

    def _lookahead_ok(self, pending):
        """Whether the decode AFTER ``pending`` may be dispatched before
        ``pending`` is committed: it then takes its tokens from
        ``pending``'s device output, and which slots it advances must be
        known without them — so no session may end on a token's value (an
        ``eos_id``), and the plain one-cohort path only (no speculation, no
        QoS parking, one weights version)."""
        return (pending[0] == "plain" and len(pending[1]) == 1
                and self._qos is None and len(self._param_sets) == 1
                and all(s is None or s.eos_id is None
                        for s in self._sessions))

    def _dispatch_ahead(self, pending, span=True):
        """Dispatch the decode that follows ``pending`` (dispatched, not yet
        committed): a session ``pending`` advances and that does not end
        with it (by its token count or the slab's capacity — what
        :meth:`_maybe_finish` will find at the commit) goes on one position
        further with the token ``pending`` leaves on the device; a session
        admitted since starts from its prefill's token on the host; every
        other slot is dead. Returns the new pending state, None when no
        slot goes on."""
        import jax.numpy as jnp

        (slots, snap, toks, _), = pending[1]
        positions = np.full(self._total_slots, -1, np.int32)
        from_host = np.ones(self._total_slots, bool)
        out = set()                 # slots whose session ``pending`` holds
        for slot, sess in zip(slots, snap):
            if self._sessions[slot] is not sess:
                continue            # evicted or replaced since the dispatch
            out.add(slot)
            if (sess.generated + 1 < sess.max_new_tokens
                    and self._lengths[slot] + 2 <= self._max_len):
                positions[slot] = self._lengths[slot] + 1
                from_host[slot] = False
        for slot, sess in enumerate(self._sessions):
            if sess is not None and slot not in out:
                positions[slot] = self._lengths[slot]
        live = [int(s) for s in np.nonzero(positions >= 0)[0]]
        if not live:
            return None
        with (tracing.span("generation.decode", cat="generation",
                           live=len(live), version=self._weights_version,
                           ahead=True) if span else contextlib.nullcontext()):
            tokens = jnp.where(self._tokens_on_device(from_host),
                               self._tokens_on_device(self._last_tok), toks)
            toks, self._kv = self._decode_fn()(
                self._params, self._kv, tokens, jnp.asarray(positions))
        counted = None
        if telemetry._enabled:
            counted = self._count_dispatch(positions)
        return ("plain", [(live, [self._sessions[s] for s in live], toks,
                           counted)])

    def _decode_commit(self, state):
        """Block on the dispatched token outputs and commit them:
        deliver one token per still-live slot, advance lengths, evict
        terminal sessions. A slot whose session changed since dispatch
        (overlap-window evict/re-admit) is skipped — its slab write is
        masked garbage the next prefill overwrites."""
        kind, pending = state
        with tracing.span("generation.commit", cat="generation", kind=kind):
            if kind == "spec":
                self._spec_commit(pending)
            else:
                self._plain_commit(pending)

    def _plain_commit(self, pending):
        live = 0
        for slots, snap, toks, counted in pending:
            with tracing.span("generation.commit.fetch", cat="generation"):
                toks = np.asarray(toks)     # blocks until the decode ran
            if counted is not None:
                for name, n in zip(self._tick_counter_names,
                                   np.asarray(counted)):
                    telemetry.counter("serving.generation." + name).inc(
                        int(n))
            for slot, dispatched in zip(slots, snap):
                sess = self._sessions[slot]
                if sess is None or sess is not dispatched:
                    continue
                live += 1
                # the token we fed now occupies position lengths[slot]
                self._lengths[slot] += 1
                tok = int(toks[slot])
                self._last_tok[slot] = tok
                self._deliver(sess, tok)
                self._maybe_finish(slot)
            if telemetry._enabled:
                telemetry.counter("serving.generation.tick_slots").inc(
                    self._slots)
        if telemetry._enabled:
            telemetry.counter("serving.generation.decode_tokens").inc(live)

    def _spec_dispatch(self):
        """Speculative half of :meth:`_decode_dispatch`: draft proposes,
        the verify executable is dispatched per cohort, tokens stay
        lazy. Returns the pending state for :meth:`_spec_commit`."""
        import jax.numpy as jnp

        k = self._spec_k
        # the draft proposes ONCE for all slots with its current (post-
        # swap) params — proposals are free to be "wrong" for an old-
        # version cohort, its own verify corrects them bit-exactly; a
        # bad acceptance ratio during the drain is the whole cost
        props = np.asarray(
            self._draft.propose(k, self._sessions), np.int32)   # [S, k]
        tokens = np.concatenate([self._last_tok[:, None], props], axis=1)
        fn = self._verify_fn()
        cohorts = self._cohorts()
        mixed = len(cohorts) > 1
        pending = []
        for version in sorted(cohorts):
            slots = cohorts[version]
            positions = self._tick_positions(slots if mixed else None)
            with tracing.span("generation.verify", cat="generation",
                              live=len(slots), k=k, version=version):
                toks, self._kv = fn(
                    self._version_params(version), self._kv,
                    jnp.asarray(tokens), jnp.asarray(positions))
            if telemetry._enabled:
                self._count_slab_blocks(positions, steps=k + 1)
            pending.append((slots, [self._sessions[s] for s in slots],
                            toks))
        return ("spec", (props, pending))

    def _spec_commit(self, state):
        """Block on the dispatched verify outputs and commit: each
        still-live slot takes the longest agreeing draft prefix plus the
        target's next token (1..k+1 tokens), rolling the rest back by
        NOT advancing its position past the last commit — the rejected
        rows beyond the new frontier are never attended and the next
        tick overwrites them in order before they could be."""
        props, pending = state
        k = self._spec_k
        tele = telemetry._enabled
        live = accepted = committed_total = 0
        for slots, snap, toks in pending:
            with tracing.span("generation.commit.fetch", cat="generation"):
                toks = np.asarray(toks)                         # [S, k+1]
            for slot, dispatched in zip(slots, snap):
                sess = self._sessions[slot]
                if sess is None or sess is not dispatched:
                    continue
                live += 1
                t = toks[slot]
                d = props[slot]
                a = 0
                while a < k and d[a] == t[a]:
                    a += 1
                committed = []
                for j in range(a + 1):
                    # same bookkeeping as one plain decode step: the token
                    # we fed at position lengths[slot] is now in the slab,
                    # t[j] is the sampled-but-not-yet-fed continuation
                    self._lengths[slot] += 1
                    tok = int(t[j])
                    self._last_tok[slot] = tok
                    committed.append(tok)
                    self._deliver(sess, tok)
                    self._maybe_finish(slot)
                    if self._sessions[slot] is None:
                        break
                if (self._sessions[slot] is not None
                        and self._draft is not None):
                    self._draft.on_commit(slot, committed)
                # accepted = draft proposals that actually became committed
                # tokens. On a full commit that is `a` (the bonus token is
                # not a draft); when the loop broke early on a terminal
                # state every committed token so far WAS a matching draft —
                # counting the unreachable tail of `a` would inflate the
                # acceptance_ratio operators tune k against
                accepted += min(len(committed), a)
                committed_total += len(committed)
            if tele:
                telemetry.counter("serving.generation.tick_slots").inc(
                    self._slots)
        if tele:
            telemetry.counter("serving.generation.decode_tokens").inc(live)
            telemetry.counter("serving.generation.spec.ticks").inc()
            telemetry.counter("serving.generation.spec.verified_slots").inc(
                live)
            telemetry.counter("serving.generation.spec.proposed").inc(
                live * k)
            telemetry.counter("serving.generation.spec.accepted").inc(
                accepted)
            telemetry.counter("serving.generation.spec.rolled_back").inc(
                live * k - accepted)
            telemetry.counter("serving.generation.spec.committed").inc(
                committed_total)

    # -- delivery / eviction -------------------------------------------------

    def _deliver(self, sess, tok, first=False):
        sess.generated += 1
        sess.stream._push(tok)
        self._tokens_window += 1
        if self._qos is not None:
            # token-rate quota burn-down — may push the tenant's bucket
            # negative, which blocks its NEXT admission (generation length
            # is unknowable at admit time, so charging at delivery is the
            # only honest accounting)
            self._qos.charge_tokens(sess.tenant, 1)
        if telemetry._enabled:
            telemetry.counter("serving.generation.tokens").inc()
            spec = (self._qos.spec_for(sess.tenant)
                    if self._qos is not None else None)
            if spec is not None:
                telemetry.counter(
                    qos.labeled_metric("qos.tokens", spec)).inc()
            # generated == 1 guards the adopt path: a migrated session's
            # re-prefill redelivers into an old stream whose TTFT already
            # happened on the source replica — recording it again would
            # double-count (and flatter: the adopting engine only re-ran
            # the prefill, not the queue wait)
            if first and sess.generated == 1:
                ttft = (time.monotonic() - sess.stream.submitted_at) * 1e6
                telemetry.histogram("serving.generation.ttft_us").record(
                    ttft)
                if spec is not None:
                    # the per-tenant histogram the SLO burn rows
                    # (qos.attach_slo) and the worst-tenant report line read
                    telemetry.histogram(
                        qos.labeled_metric("qos.ttft_us", spec)).record(ttft)
                if sess.prefix_len:
                    # hit-path TTFT separately: the fork+suffix admission
                    # vs the full-prefill population above
                    telemetry.histogram(
                        "serving.generation.prefix.ttft_us").record(ttft)

    def _maybe_finish(self, slot):
        """Evict the slot if its session just reached a terminal state."""
        sess = self._sessions[slot]
        if sess.eos_id is not None and self._last_tok[slot] == sess.eos_id:
            self._evict(slot, "eos")
        elif sess.generated >= sess.max_new_tokens:
            self._evict(slot, "finished")
        elif self._lengths[slot] + 1 > self._max_len:
            # no room to write the next token's K/V — the slab, not the
            # request, is the binding constraint here
            self._evict(slot, "max_len")

    def _evict(self, slot, reason, exc=None):
        """Free the slot: host metadata only — the KV rows stay as masked
        garbage until the next occupant's prefill rewrites them."""
        sess = self._sessions[slot]
        self._sessions[slot] = None
        self._lengths[slot] = 0
        self._last_tok[slot] = 0
        self._live -= 1
        if self._draft is not None:
            self._draft.on_evict(slot)
        if telemetry._enabled:
            telemetry.counter("serving.generation.evictions").inc()
            telemetry.counter(f"serving.generation.evict_{reason}").inc()
        if health._enabled and reason not in ("eos", "finished"):
            # journal only the ABNORMAL evictions (deadline/max_len/error)
            # — normal completions would drown the ring
            health.event("generation_evict", engine=self.health_name,
                         slot=slot, reason=reason,
                         tokens=sess.generated)
        if exc is not None:
            sess.stream._fail(exc)
        else:
            sess.stream._finish()
        if sess.span is not None:
            t_us = tracing.now_us()
            tracing.emit_span("generation.evict", t_us, 0.0,
                              cat="generation", parent=sess.span,
                              reason=reason)
            sess.span.set(reason=reason, tokens=sess.generated,
                          **({"error": repr(exc)} if exc is not None else {}))
            sess.span.finish()

    def _fail_queued(self, sess, now):
        """Deadline death while still queued: no slot to free, just the
        stream to unblock (and the span tree to close)."""
        exc = DeadlineExceededError(
            f"session waited {now - sess.stream.submitted_at:.3f}s in "
            "queue, past its deadline")
        if telemetry._enabled:
            telemetry.counter("serving.generation.evict_deadline").inc()
            telemetry.counter("serving.generation.evictions").inc()
        if health._enabled:
            health.event("generation_evict", engine=self.health_name,
                         reason="deadline", queued=True)
        sess.stream._fail(exc)
        if sess.span is not None:
            sess.span.set(error=repr(exc), reason="deadline").finish()

    # -- QoS park region (preemption / resume / migration) -------------------

    def _sweep_parked(self, now):
        """Deadline sweep over the park region — parking a session does
        not stop its clock (the client's deadline is wall time, and a
        parked batch session under sustained interactive pressure may
        never get its slot back)."""
        if not self._parked:
            return
        for park, rec in list(self._parked.items()):
            sess = rec["sess"]
            if sess.deadline is not None and now >= sess.deadline:
                self._fail_parked(
                    park, rec, DeadlineExceededError(
                        f"session deadline passed while parked after "
                        f"{sess.generated} generated token(s)"),
                    reason="deadline")

    def _fail_parked(self, park, rec, exc, reason="error"):
        """Terminal failure for a PARKED session: free the park slot and
        fail the stream in-band (never-strand — a parked session is in
        neither the queue nor a live slot, so nobody else will)."""
        del self._parked[park]
        self._park_free.append(park)
        sess = rec["sess"]
        if telemetry._enabled:
            telemetry.counter("serving.generation.evictions").inc()
            telemetry.counter(f"serving.generation.evict_{reason}").inc()
        if health._enabled:
            health.event("generation_evict", engine=self.health_name,
                         reason=reason, parked=True, tokens=sess.generated)
        sess.stream._fail(exc)
        if sess.span is not None:
            sess.span.set(error=repr(exc), reason=reason,
                          parked=True).finish()

    def _preempt_for_priority(self):
        """Park the YOUNGEST live batch-class session (fewest sunk tokens
        by admission order) when a higher-class request heads the queue
        and the slab is full: one traced fork copies its KV rows into a
        free park slot, host metadata moves aside, and the slot frees for
        the interactive admission. One victim per call (the tick calls
        once) bounds preemption churn. Returns the freed slot, or None
        when preemption is impossible (no park headroom, no batch victim)
        or unwarranted (the queue head is itself batch — an AGED batch
        request never preempts, aging only reorders the queue).

        Zero new executables: the fork program is the prefix cache's /
        warm()'s, keyed ``("fork", total_slots, slab_len)``."""
        if not self._park_free:
            return None
        head = self._queue.peek()
        if (head is None or head.qos_rank is None
                or head.qos_rank >= qos.BATCH_RANK):
            return None
        victim = None
        for slot in range(self._slots):
            sess = self._sessions[slot]
            if sess is None or sess.qos_rank != qos.BATCH_RANK:
                continue
            if (victim is None
                    or sess.admit_seq > self._sessions[victim].admit_seq):
                victim = slot
        if victim is None:
            return None
        sess = self._sessions[victim]
        park = self._park_free.pop()
        try:
            self._fork(victim, park)
        except Exception:
            # the victim is still live in its slot; the tick handler's
            # sweep will fail it with everyone else
            self._park_free.append(park)
            raise
        self._parked[park] = {"sess": sess,
                              "length": int(self._lengths[victim]),
                              "last_tok": int(self._last_tok[victim]),
                              "parked_at": time.monotonic()}
        # host metadata moves aside WITHOUT failing the stream — the
        # session is paused, not dead; its slot goes dead to the decode
        # (_tick_positions), so nothing touches its rows
        self._sessions[victim] = None
        self._lengths[victim] = 0
        self._last_tok[victim] = 0
        self._live -= 1
        if self._draft is not None:
            self._draft.on_evict(victim)
        spec = self._qos.spec_for(sess.tenant)
        if telemetry._enabled:
            telemetry.counter("serving.generation.preemptions").inc()
            telemetry.counter(qos.labeled_metric("qos.preempted", spec)).inc()
        if health._enabled:
            health.event("qos_preempt", engine=self.health_name,
                         slot=victim, park=park, tenant=spec.name,
                         tokens=sess.generated)
        if sess.span is not None:
            tracing.emit_span("generation.preempt", tracing.now_us(), 0.0,
                              cat="generation", parent=sess.span,
                              slot=victim, park=park)
        return victim

    def _should_resume(self):
        """A free slot goes to a parked session unless a HIGHER-class
        request heads the queue (batch-vs-batch: the parked session wins
        — it has sunk prefill + decode work the queued one hasn't)."""
        head = self._queue.peek()
        return (head is None or head.qos_rank is None
                or head.qos_rank >= qos.BATCH_RANK)

    def _resume_into(self, slot):
        """Un-park the OLDEST parked session into the free slot: one
        traced fork copies its KV rows back, host metadata is restored,
        and greedy decode continues bit-exact with an uninterrupted run
        (fork is a bitwise row copy; decode is slot-index-independent).
        Returns True when a session was resumed."""
        park = min(self._parked,
                   key=lambda p: self._parked[p]["parked_at"])
        rec = self._parked.pop(park)
        sess = rec["sess"]
        try:
            self._fork(park, slot)
        except Exception as e:
            # never-strand: the session is now in neither _parked nor a
            # slot — fail its stream here, then let the tick handler
            # reallocate the slab
            self._park_free.append(park)
            sess.stream._fail(e)
            if sess.span is not None:
                sess.span.set(error=repr(e), reason="error").finish()
            raise
        self._park_free.append(park)
        sess.slot = sess.stream.slot = slot
        self._sessions[slot] = sess
        self._lengths[slot] = rec["length"]
        self._last_tok[slot] = rec["last_tok"]
        self._live += 1
        if self._draft is not None:
            # rebuild the draft's context: prompt + all delivered tokens
            # except the pending last (exactly what on_admit saw at the
            # original admission, extended by the generated prefix)
            ctx = np.concatenate([
                sess.prompt,
                np.asarray(sess.stream.tokens[:-1], np.int32)])
            self._draft.on_admit(slot, ctx, rec["last_tok"])
        spec = self._qos.spec_for(sess.tenant)
        if telemetry._enabled:
            telemetry.counter(qos.labeled_metric("qos.resumed", spec)).inc()
        if health._enabled:
            health.event("qos_resume", engine=self.health_name, slot=slot,
                         tenant=spec.name,
                         parked_s=round(
                             time.monotonic() - rec["parked_at"], 3))
        if sess.span is not None:
            tracing.emit_span("generation.resume", tracing.now_us(), 0.0,
                              cat="generation", parent=sess.span, slot=slot,
                              park=park)
        return True

    def qos_demand(self):
        """Fairness-weighted demand for the autoscaler: every live and
        parked session plus every queued request, each weighted by its
        tenant's QoS weight (interactive work votes harder for replicas
        than batch). None while QoS is off — callers fall back to the
        raw ``live_slots + queue_depth`` count."""
        if self._qos is None:
            return None
        d = 0.0
        for sess in self._sessions:
            if sess is not None:
                d += self._qos.weight(sess.tenant)
        for rec in self._parked.values():
            d += self._qos.weight(rec["sess"].tenant)
        return d + self._queue.weighted_depth()

    def eject_parked(self, max_n=None):
        """Pop up to ``max_n`` parked sessions (oldest first) OUT of this
        engine as host-side migration records — the router's
        ``rebalance_parked`` hands them to a less-loaded peer replica's
        :meth:`adopt`. Each record carries everything needed to continue
        the generation elsewhere: prompt, tokens generated so far,
        remaining budget, tenant, and the LIVE stream (the client keeps
        iterating the same object; only its engine changes). The park
        slots free immediately — the KV rows become masked garbage."""
        out = []
        with self._tick_lock:
            parks = sorted(self._parked,
                           key=lambda p: self._parked[p]["parked_at"])
            if max_n is not None:
                parks = parks[:max_n]
            for park in parks:
                rec = self._parked.pop(park)
                self._park_free.append(park)
                sess = rec["sess"]
                out.append({"prompt": sess.prompt,
                            "tokens": list(sess.stream.tokens),
                            "max_new_tokens": sess.max_new_tokens,
                            "eos_id": sess.eos_id,
                            "deadline": sess.deadline,
                            "tenant": sess.tenant,
                            "stream": sess.stream,
                            "span": sess.span})
        if out and telemetry._enabled:
            telemetry.counter("serving.generation.qos.ejected").inc(len(out))
        return out

    def adopt(self, record):
        """Admit a migrated session ejected from a peer replica:
        re-prefill the FULL context (prompt + every token generated so
        far) through the normal admission path and keep delivering the
        remaining budget into the ORIGINAL stream. Greedy continuation
        is bit-exact with a fresh submit of that context — it IS one
        (same prefill executable, same greedy argmax). The request rides
        ``qos_exempt`` (its quota was charged at original admission;
        double-charging would punish the tenant for the fleet's
        rebalancing). Returns False when the context cannot fit this
        engine (caller keeps the record and tries elsewhere)."""
        toks = [int(t) for t in record["tokens"]]
        ctx = np.concatenate([np.asarray(record["prompt"], np.int32).ravel(),
                              np.asarray(toks, np.int32)])
        n = int(ctx.size)
        remaining = int(record["max_new_tokens"]) - len(toks)
        if (remaining < 1 or n > self._buckets[-1]
                or n + remaining > self._max_len or self._closed):
            return False
        stream = record["stream"]
        sess = _Session(ctx, record["max_new_tokens"], record["eos_id"],
                        record["deadline"], stream,
                        tenant=record["tenant"])
        sess.generated = len(toks)
        sess.span = record.get("span")
        # the stream's caller-runs assist must drive THIS engine's ticks
        # from now on
        stream._engine = self
        req = Request([ctx], 1, stream._future, deadline=record["deadline"],
                      payload=sess, tenant=record["tenant"])
        req.qos_exempt = True
        try:
            self._queue.put(req)
        except Exception:
            return False
        if telemetry._enabled:
            telemetry.counter("serving.generation.qos.adopted").inc()
        if health._enabled:
            self._beacon.arm()
        with self._work:
            self.sessions_submitted += 1
            self._work.notify_all()
        return True
