"""Speculative decoding drafts — propose k tokens per tick, let the slab
verify them.

Plain continuous batching advances every session ONE token per fused tick;
the engine's speculative lane advances up to ``k + 1``: a cheap *draft*
proposes k tokens per live slot, the target model checks all of them in
ONE fixed-shape verify executable (:meth:`TransformerLM.verify_step` — k+1
unrolled decode graphs, so greedy output stays BIT-EXACT with the plain
path), and the engine commits the longest agreeing prefix plus the
target's own next token. The draft never affects WHAT is generated — only
how many verify positions pay off — so any draft is safe; a good one
turns the acceptance ratio into tokens-per-tick.

Two drafts:

* :class:`NgramDraft` (the default) — host-side prompt-lookup: propose
  the continuation of the most recent earlier occurrence of the session's
  own trailing n-gram. Zero device state, zero compiles, surprisingly
  effective on templated/repetitive output (the self-speculation trick).
* :class:`CheckpointDraft` — a small :class:`TransformerLM` loaded from
  ``MXNET_GENERATION_DRAFT`` (:func:`save_draft` / :func:`load_draft`
  ``.npz`` checkpoints). It keeps its OWN fixed-shape KV slab mirroring
  the engine's slots and obeys the same compile-once discipline: one
  prefill entry per bucket (admission) plus ONE fused ``draft_step``
  that ingests the tick's committed tokens (variable count per slot,
  handled by write-masking-free frontier sequencing — invalid rows are
  overwritten before they could ever be attended) and then rolls k
  greedy proposal steps, all in a single executable with the draft slab
  donated.

The engine calls drafts through a small lifecycle protocol
(:class:`Draft`): ``attach`` (engine shape/cache wiring), ``warm``
(compile-ahead, counted), ``on_admit``/``on_evict``/``on_commit``
(per-slot state), ``reset`` (slab reallocation after a failed tick) and
``propose`` (the per-tick [S, k] block).
"""
from __future__ import annotations

import json

import numpy as np

from ... import memory
from ...base import MXNetError, getenv, register_env

__all__ = ["Draft", "NgramDraft", "CheckpointDraft", "save_draft",
           "load_draft", "default_draft"]

register_env("MXNET_GENERATION_SPEC_K", 0,
             "speculative decoding draft length k (tokens proposed per "
             "tick; the verify advances each slot by up to k+1). 0 "
             "disables the speculative lane (plain one-token decode)")
register_env("MXNET_GENERATION_DRAFT", "",
             "path to a save_draft() .npz checkpoint for the speculative "
             "draft model; empty = the host-side n-gram (prompt-lookup) "
             "fallback draft")

# bound the n-gram scan window: proposals are free to be wrong (the verify
# corrects), so an O(history) scan per slot per tick buys nothing past the
# recent context
_NGRAM_WINDOW = 256


class Draft:
    """Draft lifecycle protocol (no-op defaults; subclass what you need)."""

    def attach(self, engine):
        """Called once from the engine constructor with the owning engine
        (slots, max_len, buckets, compile cache, model mesh)."""

    def warm(self):
        """Compile-ahead every draft executable; return the number of
        entries this draft pins (0 for host-side drafts)."""
        return 0

    def on_admit(self, slot, prompt, first_tok):
        """A session entered ``slot`` with ``prompt`` and its prefill
        produced ``first_tok`` (committed but not yet fed anywhere)."""

    def on_commit(self, slot, committed):
        """The verify tick committed ``committed`` (list of ints, length
        1..k+1) for ``slot``."""

    def on_evict(self, slot):
        """The session in ``slot`` ended."""

    def reset(self):
        """The engine reallocated its slab after a failed tick; drop any
        per-slot device state the same way."""

    def swap_params(self, params):
        """A weight rollout published new draft parameters; flip to them
        (host dict, same names/shapes). Returns True when the draft has
        parameters to swap (False for host-side drafts — a no-op)."""
        return False

    def propose(self, k, sessions):
        """Return an int32 [S, k] proposal block (rows of dead slots are
        ignored). ``sessions`` is the engine's slot list (None = dead);
        each live session exposes ``.prompt`` and ``.stream.tokens``."""
        raise NotImplementedError


class NgramDraft(Draft):
    """Prompt-lookup draft: continue the most recent earlier occurrence
    of the session's trailing n-gram (n = 3, 2, 1), falling back to
    repeating the last token. Pure host work on metadata the engine
    already keeps — the zero-dependency default draft."""

    def __init__(self, max_ngram=3):
        self._n = int(max_ngram)

    def _propose_one(self, hist, k):
        n = hist.size
        lo = max(n - _NGRAM_WINDOW, 0)
        for g in range(min(self._n, n - 1), 0, -1):
            pat = hist[n - g:]
            # most recent earlier occurrence with at least one
            # continuation token
            for i in range(n - g - 1, lo - 1, -1):
                if np.array_equal(hist[i:i + g], pat):
                    cont = hist[i + g:i + g + k]
                    if cont.size:
                        out = list(cont)
                        while len(out) < k:
                            out.append(out[-1])
                        return out
        return [int(hist[-1])] * k

    def propose(self, k, sessions):
        out = np.zeros((len(sessions), k), np.int32)
        for s, sess in enumerate(sessions):
            if sess is None:
                continue
            hist = np.concatenate(
                [sess.prompt, np.asarray(sess.stream.tokens, np.int32)])
            out[s] = self._propose_one(hist, k)
        return out


class CheckpointDraft(Draft):
    """A small TransformerLM draft with its own fixed-shape KV slab.

    Per engine tick it runs ONE fused ``draft_step``: unrolled ingest of
    the tick's committed block (per-slot valid counts — invalid trailing
    rows land beyond the slot's frontier and are overwritten by the next
    ingest before anything attends them) followed by k unrolled greedy
    proposal steps whose K/V writes are speculative in the same
    frontier-safe way. The draft slab therefore needs ``max_len + 2k``
    rows of positional headroom; :meth:`attach` raises when the draft
    model's ``max_len`` cannot cover it.
    """

    def __init__(self, model, params):
        self._model = model
        self._params = params
        self._eng = None
        self._dk = self._dv = None
        self._len = None          # per-slot draft frontier
        self._pending = None      # per-slot committed-not-ingested tokens

    def attach(self, engine):
        self._eng = engine
        k = engine.spec_k
        need = engine.max_len + 2 * k
        if need > self._model.cfg.max_len:
            raise MXNetError(
                f"draft model positional range {self._model.cfg.max_len} < "
                f"engine max_len {engine.max_len} + 2*k ({need} rows needed "
                "for speculative scratch); lower MXNET_GENERATION_MAX_LEN / "
                "MXNET_GENERATION_SPEC_K or train a longer draft")
        self._slab_len = need
        self._alloc()
        # total_slots (not max_slots): the draft slab mirrors the engine's
        # slab row-for-row, INCLUDING the QoS park region — a preempted
        # session's resume re-prefills the draft row anyway, but every
        # propose/ingest runs fixed-shape over the whole slab, so the
        # shapes (and executable keys) must match. QoS off: identical.
        self._len = np.zeros(engine.total_slots, np.int32)
        self._pending = [[] for _ in range(engine.total_slots)]
        # the draft slab is replaced by every donated draft_step — a live
        # view, like the engine's own slab; distinct buffers, so the
        # census adds it to kv_cache without double-counting the target's
        memory.register_provider("kv_cache", self,
                                 lambda d: [d._dk, d._dv])

    def _alloc(self):
        self._dk, self._dv = self._model.init_cache(
            self._eng.total_slots, self._slab_len)

    def slab_bytes(self):
        return int(self._dk.nbytes) + int(self._dv.nbytes)

    # -- compiled programs ---------------------------------------------------

    def _prefill_fn(self, bucket):
        model, cache = self._model, self._eng.cache

        def build():
            import jax

            def fn(params, dk, dv, toks, length, slot):
                _, dk, dv = model.prefill(params, dk, dv, toks, length, slot)
                return dk, dv

            return jax.jit(fn, donate_argnums=(1, 2))

        key = ("draft_prefill", bucket, self._eng.total_slots,
               self._slab_len)
        # audit="generation": the draft slab programs live in the engine's
        # "generation" cache (passed in) — same hlolint contract row
        return cache.get_or_build(key, build, audit="generation")

    def _step_fn(self, k):
        model, cache = self._model, self._eng.cache

        def build():
            import jax
            import jax.numpy as jnp

            def fn(params, dk, dv, tokens, counts, positions):
                # phase 1: ingest the committed block (k+1 unrolled decode
                # graphs); stash each step's greedy argmax
                nxt = []
                for i in range(k + 1):
                    lg, dk, dv = model.decode_step(params, dk, dv,
                                                   tokens[:, i],
                                                   positions + i)
                    nxt.append(jnp.argmax(lg, axis=-1).astype(jnp.int32))
                nxt = jnp.stack(nxt, axis=1)                     # [S, k+1]
                # the first proposal continues the LAST VALID ingested
                # token (index counts-1; dead slots clamp to 0 — garbage
                # the engine discards)
                idx = jnp.maximum(counts - 1, 0)[:, None]
                cur = jnp.take_along_axis(nxt, idx, axis=1)[:, 0]
                props = [cur]
                # phase 2: k-1 more greedy steps feeding our own
                # proposals; their writes start at the post-ingest
                # frontier (positions + counts) and are speculative
                for j in range(k - 1):
                    lg, dk, dv = model.decode_step(params, dk, dv, cur,
                                                   positions + counts + j)
                    cur = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                    props.append(cur)
                return jnp.stack(props, axis=1), dk, dv

            return jax.jit(fn, donate_argnums=(1, 2))

        key = ("draft_step", k, self._eng.total_slots, self._slab_len)
        return cache.get_or_build(key, build, audit="generation")

    # -- lifecycle -----------------------------------------------------------

    def warm(self):
        import jax.numpy as jnp

        eng = self._eng
        misses0 = eng.cache.misses
        for b in eng.prefill_buckets:
            fn = self._prefill_fn(b)
            self._dk, self._dv = fn(
                self._params, self._dk, self._dv,
                jnp.zeros((b,), jnp.int32), jnp.asarray(1, jnp.int32),
                jnp.asarray(0, jnp.int32))
        k = eng.spec_k
        fn = self._step_fn(k)
        _, self._dk, self._dv = fn(
            self._params, self._dk, self._dv,
            jnp.zeros((eng.total_slots, k + 1), jnp.int32),
            jnp.zeros(eng.total_slots, jnp.int32),
            jnp.zeros(eng.total_slots, jnp.int32))
        # warm garbage lands in rows the next real prefill/ingest
        # overwrites before attending (the frontier argument); lengths
        # were never advanced, so no state to undo
        return eng.cache.misses - misses0

    def on_admit(self, slot, prompt, first_tok):
        import jax.numpy as jnp

        eng = self._eng
        n = int(prompt.size)
        bucket = eng.bucket_for(n)
        padded = np.zeros(bucket, np.int32)
        padded[:n] = prompt
        fn = self._prefill_fn(bucket)
        self._dk, self._dv = fn(
            self._params, self._dk, self._dv, jnp.asarray(padded),
            jnp.asarray(n, jnp.int32), jnp.asarray(slot, jnp.int32))
        self._len[slot] = n
        self._pending[slot] = [int(first_tok)]

    def on_commit(self, slot, committed):
        self._pending[slot] = [int(t) for t in committed]

    def on_evict(self, slot):
        self._len[slot] = 0
        self._pending[slot] = []

    def reset(self):
        self._alloc()
        self._len[:] = 0
        self._pending = [[] for _ in range(self._eng.total_slots)]

    def swap_params(self, params):
        """Flip the draft to new weights immediately — the slab survives
        untouched. Rows ingested under the old weights only degrade the
        acceptance ratio until overwritten (the target's verify is the
        ground truth, so output never changes); shapes/dtypes must match
        so the pinned draft executables are reused compile-free."""
        import jax

        cur = self._params
        new = {str(k): v for k, v in dict(params).items()}
        if set(new) != set(cur):
            raise MXNetError(
                f"draft swap_params: parameter names differ (have "
                f"{sorted(cur)}, got {sorted(new)})")
        specs = self._model.param_specs()
        placed = {}
        for name, v in new.items():
            arr = np.asarray(v)
            old = cur[name]
            if tuple(arr.shape) != tuple(old.shape):
                raise MXNetError(
                    f"draft swap_params: {name!r} shape "
                    f"{tuple(arr.shape)} != bound {tuple(old.shape)}")
            placed[name] = jax.device_put(
                arr.astype(old.dtype, copy=False), specs[name])
        self._params = placed
        return True

    def propose(self, k, sessions):
        import jax.numpy as jnp

        S = len(sessions)
        tokens = np.zeros((S, k + 1), np.int32)
        counts = np.zeros(S, np.int32)
        for s, sess in enumerate(sessions):
            if sess is None:
                continue
            pend = self._pending[s]
            tokens[s, :len(pend)] = pend
            counts[s] = len(pend)
        fn = self._step_fn(k)
        props, self._dk, self._dv = fn(
            self._params, self._dk, self._dv, jnp.asarray(tokens),
            jnp.asarray(counts), jnp.asarray(self._len))
        self._len += counts
        for s, sess in enumerate(sessions):
            if sess is not None:
                self._pending[s] = []
        return np.asarray(props)


# ---------------------------------------------------------------------------
# Checkpoint format
# ---------------------------------------------------------------------------


def save_draft(path, model, params):
    """Persist a TransformerLM draft as one ``.npz``: the config as an
    embedded JSON field plus every parameter array (dict keys survive as
    npz member names)."""
    import dataclasses

    arrays = {name: np.asarray(v) for name, v in params.items()}
    np.savez(path, __config__=json.dumps(dataclasses.asdict(model.cfg)),
             **arrays)


def load_draft(path, mesh=None):
    """Load a :func:`save_draft` checkpoint: returns ``(model, params)``
    with every parameter placed per the model's partition specs."""
    import jax

    from ...models import TransformerLM, TransformerLMConfig

    with np.load(path, allow_pickle=False) as z:
        cfg = TransformerLMConfig(**json.loads(str(z["__config__"])))
        model = TransformerLM(cfg, mesh)
        specs = model.param_specs()
        params = {name: jax.device_put(z[name], specs[name])
                  for name in z.files if name != "__config__"}
    return model, params


def default_draft(mesh=None):
    """The draft the engine uses when none is passed: a
    :class:`CheckpointDraft` from ``MXNET_GENERATION_DRAFT`` when set,
    else the :class:`NgramDraft` fallback."""
    path = str(getenv("MXNET_GENERATION_DRAFT")).strip()
    if path:
        model, params = load_draft(path, mesh)
        return CheckpointDraft(model, params)
    return NgramDraft()
