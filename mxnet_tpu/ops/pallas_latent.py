"""One decode tick's attention over a LATENT slab as a Pallas kernel (TPU).

A latent-attention model (`models/latent_moe.py`) keeps of every position
one row for all heads: the normalised latent `c` (`kv_lora_rank` numbers)
and the rotated shared key `k_r` (`qk_rope_head_dim`). With the keys'
up-projection absorbed into the query, a decode tick of slot ``s`` is

    scores[h, l] = (qc[s, h] . c[s, l] + qr[s, h] . k_r[s, l]) * scale
    out[s, h]    = softmax_l(scores[h, :pos + 1]) @ c[s, :pos + 1]

over the rows ``[0, positions[s]]`` of its own page: every row is read once
and serves all heads, 2 * (R + rope) + 2 * R FLOPs a head a row — at 64
heads about 120 FLOPs a cache byte, between a copy and a matmul. The XLA
formulation reads all ``L`` rows of all slots and round-trips a ``[S, H,
L]`` score array through HBM; this kernel reads the live blocks only (the
live slots are taken first in the grid ``(slot, L-block)``, the block index
is clamped to the slot's last live block — an index that does not change
costs no DMA — and ``pl.when`` skips what lies past it) and keeps scores,
the running softmax and the ``[H, R]`` accumulator in VMEM.

A prefill attends in the published form — keys and values up-projected per
head — over one whole sequence, and no kernel of `pallas_attention.py`
takes it: they keep a head's whole K and V in VMEM (12 MB; 16,384 positions
of 192 + 128 wide heads are 10 MB before double buffering) and want one
head size. :func:`prefill_attend` streams key blocks through the grid
instead and takes the key in its two parts, a head's own `k_nope` and the
`k_r` all heads share, so the shared part is never copied 64 times.

Layout. Two slabs, both free of lane padding: ``c`` as ``[S, layers, L,
R]`` (``R`` on lanes, a multiple of 128) and ``k_r`` as ``[S, layers, rope,
L]`` (positions on lanes), so the scores are two plain matmuls, ``qc @
c^T`` and ``qr @ k_r``, and the output a third, ``p @ c``. The new row is
written before the call (a ``dynamic_update_slice`` a slot, which XLA
performs in place on the donated slab: `models/latent_moe.py`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _NEG_INF, _LANES, _divisor_block

__all__ = ["latent_block", "latent_attend", "prefill_block",
           "prefill_attend"]

# the c block, double-buffered by the pipeline, beside the fp32 scores and
# accumulator: well under Mosaic's 16 MiB scoped-VMEM limit on a v5e
_BLOCK_BUDGET_BYTES = 2 * 2 ** 20


def latent_block(c_shape, dtype, target=1024):
    """The shape test for :func:`latent_attend`: the block over the ``L``
    axis of a ``[S, layers, L, R]`` latent slab, None when the caller keeps
    the XLA formulation (``R`` not whole lane rows; no lane-aligned block
    divides ``L``)."""
    _, _, length, rank = c_shape
    if rank % _LANES:
        return None
    block = _divisor_block(length, target, multiple=_LANES)
    if block is None or block % _LANES:
        return None
    while block * rank * jnp.dtype(dtype).itemsize > _BLOCK_BUDGET_BYTES:
        if block % (2 * _LANES):
            return None
        block //= 2
    return block


def _kernel(n_ref, slot_ref, pos_ref, layer_ref, qc_ref, qr_ref, c_ref,
            kr_ref, o_ref, m_sc, l_sc, acc_sc, *, scale, block):
    """One (slot, L-block) grid step: the block's ``[block, R]`` latent rows
    and ``[rope, block]`` shared keys against the slot's ``H`` absorbed
    queries, streamed into a running softmax (fp32)."""
    del layer_ref                               # the index maps read it
    j, b = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot_ref[j]]
    live = j < n_ref[0]

    @pl.when(jnp.logical_and(live, b == 0))
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(whole):
        """``whole``: every row of the block is at or below the position;
        else the rows past it are selected away — they may hold anything a
        previous occupant left, inf and nan included, which a zero weight
        would not stop."""
        c = c_ref[0, 0]                                       # [block, R]
        s = lax.dot_general(qc_ref[0], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s + jnp.dot(qr_ref[0], kr_ref[0, 0],
                        preferred_element_type=jnp.float32)
        s = s * scale                                         # [H, block]
        if not whole:
            at = b * block + lax.broadcasted_iota(jnp.int32, (1, block), 1)
            s = jnp.where(at <= pos, s, _NEG_INF)
            rows = b * block + lax.broadcasted_iota(
                jnp.int32, (block, 1), 0)
            c = jnp.where(rows <= pos, c, jnp.zeros_like(c))
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(jnp.logical_and(live, (b + 1) * block <= pos + 1))
    def _():
        attend(True)

    @pl.when(jnp.logical_and(live, jnp.logical_and(
        b * block <= pos, pos + 1 < (b + 1) * block)))
    def _():
        attend(False)

    @pl.when(jnp.logical_and(live, b == pl.num_programs(1) - 1))
    def _():
        o_ref[0] = acc_sc[...] / l_sc[...]


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def latent_attend(qc, qr, slab_c, slab_kr, layer, positions, *, block, scale,
                  interpret=False):
    """Layer ``layer``'s decode attention on the latent slabs ``slab_c``
    ``[S, layers, L, R]`` and ``slab_kr`` ``[S, layers, rope, L]``: every
    slot with ``positions[s] >= 0`` attends its absorbed queries ``qc[s]``
    ``[H, R]`` and rotary queries ``qr[s]`` ``[H, rope]`` over the rows
    ``[0, positions[s]]`` of its page (the row at the position is already
    written); a slot with a negative position is dead — nothing of it is
    read and its result is 0. Returns the weighted sums of latent rows
    ``[S, H, R]`` fp32. ``block`` comes from :func:`latent_block`;
    positions lie below ``L``. ``layer`` is an int32 scalar and TRACED, so
    every layer's call shares one trace and lowering."""
    n_slots, _, length, rank = slab_c.shape
    rope = slab_kr.shape[2]
    heads = qc.shape[1]
    if length % block or block % _LANES:
        raise ValueError(f"latent_attend: block {block} does not tile "
                         f"L={length} by whole lane rows")
    positions = positions.astype(jnp.int32)
    alive = positions >= 0
    # live slots first, in slot order; the steps past them stay on the last
    # live slot's last block (no DMA, no compute)
    n_live = jnp.sum(alive, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(jnp.int32)
    slot_of = order[jnp.minimum(jnp.arange(n_slots, dtype=jnp.int32),
                                jnp.maximum(n_live - 1, 0))]

    def row(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        return (slot_ref[j], 0, 0)

    def last_live(j, b, n_ref, slot_ref, pos_ref):
        last = jnp.maximum(pos_ref[slot_ref[j]], 0) // block
        return jnp.where(j < n_ref[0], jnp.minimum(b, last), last)

    def c_page(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        return (slot_ref[j], layer_ref[0],
                last_live(j, b, n_ref, slot_ref, pos_ref), 0)

    def kr_page(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        return (slot_ref[j], layer_ref[0], 0,
                last_live(j, b, n_ref, slot_ref, pos_ref))

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_slots, length // block),
            in_specs=[
                pl.BlockSpec((1, heads, rank), row),
                pl.BlockSpec((1, heads, rope), row),
                pl.BlockSpec((1, 1, block, rank), c_page),
                pl.BlockSpec((1, 1, rope, block), kr_page),
            ],
            out_specs=pl.BlockSpec((1, heads, rank), row),
            scratch_shapes=[
                pltpu.VMEM((heads, 1), jnp.float32),        # running max
                pltpu.VMEM((heads, 1), jnp.float32),        # running sum
                pltpu.VMEM((heads, rank), jnp.float32),     # p @ c
            ]),
        out_shape=jax.ShapeDtypeStruct((n_slots, heads, rank), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        name="latent_attend",
        interpret=interpret,
    )(n_live[None], slot_of, positions,
      jnp.asarray(layer, jnp.int32).reshape(1), qc.astype(slab_c.dtype),
      qr.astype(slab_kr.dtype), slab_c, slab_kr)
    return jnp.where(alive[:, None, None], out, 0.0)


def prefill_block(length, target=1024):
    """The shape test for :func:`prefill_attend`: the block over a sequence
    of ``length`` positions (queries and keys alike), None when the caller
    keeps the XLA formulation (no lane-aligned block divides it)."""
    block = _divisor_block(length, target, multiple=_LANES)
    if block is None or block % _LANES:
        return None
    return block


def _prefill_kernel(qn_ref, qr_ref, kn_ref, kr_ref, v_ref, o_ref, m_sc, l_sc,
                    acc_sc, *, scale, block):
    """One (head, query block, key block) grid step of causal attention
    with a running softmax (fp32); key blocks past the query block are
    skipped (their index is clamped, so they cost no DMA either)."""
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(diagonal):
        nt = (((1,), (1,)), ((), ()))
        s = lax.dot_general(qn_ref[0], kn_ref[0], nt,
                            preferred_element_type=jnp.float32)
        s = s + lax.dot_general(qr_ref[0], kr_ref[...], nt,
                                preferred_element_type=jnp.float32)
        s = s * scale                                       # [block, block]
        if diagonal:
            rows = lax.broadcasted_iota(jnp.int32, (block, block), 0)
            cols = lax.broadcasted_iota(jnp.int32, (block, block), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        v = v_ref[0]
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    @pl.when(j < i)
    def _():
        attend(False)

    @pl.when(j == i)
    def _():
        attend(True)
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def prefill_attend(q_nope, q_rope, k_nope, k_rope, v, *, block, scale,
                   interpret=False):
    """Causal softmax attention of one sequence whose keys come in two
    parts: ``q_nope``, ``k_nope`` ``[H, L, dn]`` and ``v`` ``[H, L, dv]``
    per head, ``q_rope`` ``[H, L, dr]`` against ``k_rope`` ``[L, dr]``,
    one for all heads; scores ``(q_nope . k_nope + q_rope . k_rope) *
    scale``. Returns ``[H, L, dv]`` in ``v``'s dtype. ``block`` comes from
    :func:`prefill_block`."""
    heads, length, dn = q_nope.shape
    dr, dv = q_rope.shape[2], v.shape[2]
    if length % block:
        raise ValueError(f"prefill_attend: block {block} does not tile "
                         f"L={length}")
    n = length // block

    def query(h, i, j):
        return (h, i, 0)

    def key(h, i, j):
        return (h, jnp.minimum(j, i), 0)

    return pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, block=block),
        grid=(heads, n, n),
        in_specs=[
            pl.BlockSpec((1, block, dn), query),
            pl.BlockSpec((1, block, dr), query),
            pl.BlockSpec((1, block, dn), key),
            pl.BlockSpec((block, dr), lambda h, i, j: (jnp.minimum(j, i), 0)),
            pl.BlockSpec((1, block, dv), key),
        ],
        out_specs=pl.BlockSpec((1, block, dv), query),
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),            # running max
            pltpu.VMEM((block, 1), jnp.float32),            # running sum
            pltpu.VMEM((block, dv), jnp.float32),           # p @ v
        ],
        out_shape=jax.ShapeDtypeStruct((heads, length, dv), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="latent_prefill_attend",
        interpret=interpret,
    )(q_nope, q_rope, k_nope, k_rope, v)
