"""Attention for a K/V cache whose head size IS the lane width (128), with
members of two lengths: Pallas kernels (TPU) for the decode tick and for
the prefill of `models/window_moe.py`.

**The decode** (:func:`kv_update_attend`, kernel `kv128_attend`). A member
of the cache is a slab ``[S, layers, H, R, 128]``: ``R`` rows a slot a
layer a K/V head. A slab whose ``hd`` is a multiple of 128 lies
``hd``-minor on the chip, as XLA lays it, so a block of rows ``[block,
128]`` is one DMA and needs no view (`ops/pallas_decode.py` is the kernel of
the slabs that lie ``L``-minor, ``hd`` < 128). One rule covers both kinds of
member — the token at position ``p`` lives at row ``p mod R`` and the slot
attends its first ``min(p + 1, R)`` rows:

* a FULL member has ``R = max_len`` rows, so the row is ``p`` and the live
  rows are ``[0, p]``;
* a WINDOW member is a ring of ``R = sliding_window`` rows: once ``p >= R -
  1`` every row is live and holds one of the last ``R`` positions (the new
  row overwrites position ``p - R``, which has just left the window);
  before that the rows past ``p`` are whatever the slot's previous occupant
  left, and are selected away.

Softmax does not care in which order the rows come, so the ring is never
unrolled. The grid is `pallas_decode.py`'s: ONE axis with a step for every
LIVE block of the tick and no other — the live slots in slot order, each
from block 0 to its last live block ``(min(p + 1, R) - 1) // block``, the
full member's ``p // block`` and a wrapped ring's last (`pallas_decode.
live_steps` of the positions clamped to ``R - 1``; the bound is dynamic,
and the step's slot and block ride as scalar-prefetch operands beside the
positions). A dead slot and a block past a position have no step: stepped
over under ``pl.when`` they cost no DMA but 0.45-0.49 us each on a v5e,
half of the 2,048 steps a tick of the Olmo block's four full layers (PR
44 measured, PR 45 took them out). A tick with no live slot takes one
step, which sends its write-back block back as it came. As there, the new
row is merged into its block in VMEM and goes back to the slab through an
output aliased to the input — 16 rows, one packed bfloat16 tile: no XLA
scatter or ``dynamic-update-slice``. The
``G`` query heads of a K/V head are scored as ONE ``[G, 128] x [128,
block]`` product on the MXU and summed as one ``[G, block] x [block, 128]``:
``G`` FLOPs a cache byte (8 at mellum's 32:4 heads, 6 at Trinity's 48:8; any
``G``: Mosaic takes a ``[6, 128]`` tile as it takes ``[8, 128]``), far under
the chip's ridge (240), so the rows' bytes bind. The block over the rows
follows the heads (:func:`kv_block`: 1,024 rows at 4 K/V heads, 512 at 8).

At ONE query a K/V head (``G = 1``: Olmo-Hybrid's full layers, 30 heads, a
block of 128 rows) that body is a softmax chain a HEAD, and it left a live
step at 4.65 us where its 1.97 MB of tiles take 2.4 (v5e, PR 44) — not for
the MXU's sake (a tile is 8 pushes of 16 rows whether it is loaded for one
row or streamed past the queries, and the four MXUs already share them) but
for the chains': 30 of them on ``[1, 128]`` vectors, one sublane of a vreg's
eight, each with two cross-lane reductions and three read-modify-writes of
one-row scratch. :func:`_kernel_one_query` keeps the two products a head and
lets the step's score rows meet in a ``[H, block]`` scratch: ONE chain on 4
vregs, one update of the accumulator, 644 bundles where there were 1,357,
and a live step of 2.75 us against 2.71 for the kernel's DMA alone. The
group is static in the trace; every ``G > 1`` lowers to what it lowered to.

**The prefill** (:func:`band_prefill_attend`, kernel `swa_prefill_attend`):
causal attention of one whole sequence with an optional window, grouped
queries, key blocks streamed through the grid as `pallas_latent.
prefill_attend` streams them. A query block meets only the key blocks of
its BAND — ``ceil((window - 1) / block) + 1`` of them with a window, those
at or before it without — the others are neither fetched nor computed;
only the band's two edge blocks are masked.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import telemetry
from .pallas_attention import _NEG_INF, _LANES, _divisor_block
from .pallas_decode import live_steps

__all__ = ["kv_block", "kv_update_attend", "count_body", "band_block",
           "band_steps", "band_prefill_attend"]

# the K and V blocks of every head, double-buffered by the pipeline: a
# quarter of Mosaic's 16 MiB scoped-VMEM limit on a v5e
_BLOCK_BUDGET_BYTES = 4 * 2 ** 20
# rows the write-back block holds: one packed bfloat16 tile (two of float32)
_WRITE_ROWS = 16


def kv_block(slab_shape, dtype, target=1024):
    """The shape test for :func:`kv_update_attend`: the block over the row
    axis of a ``[S, layers, H, R, hd]`` slab, None when the caller keeps the
    XLA formulation (``hd`` is not the lane width; no lane-aligned block
    divides ``R``; one block of all heads exceeds the budget)."""
    _, _, h, rows, hd = slab_shape
    if hd != _LANES:
        return None
    block = _divisor_block(rows, target, multiple=_LANES)
    if block is None or block % _LANES:
        return None
    while 4 * h * hd * block * jnp.dtype(dtype).itemsize > _BLOCK_BUDGET_BYTES:
        if block % (2 * _LANES):
            return None
        block //= 2
    return block


def _kernel(slot_ref, block_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref,
            k_ref, v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, *, scale,
            heads, rows, block):
    """One grid step, which is one LIVE block ``block_ref[t]`` of slot
    ``slot_ref[t]`` (`pallas_decode.live_steps`: a slot's steps follow one
    another, block 0 first): every K/V head's ``[block, 128]`` K and V
    tiles against the slot's ``G`` queries of that head, streamed into a
    running softmax (fp32); the block that holds the slot's row takes the
    new row, and the 16 rows around it go back to the slab."""
    del layer_ref                               # the index maps read it
    t = pl.program_id(0)
    b = block_ref[t]
    pos = pos_ref[slot_ref[t]]
    at = pos % rows                             # the new row
    n_live = jnp.minimum(pos + 1, rows)         # rows the slot attends
    # only the one step of a tick with no live slot has a dead one
    live = pos >= 0
    nt = (((1,), (1,)), ((), ()))

    @pl.when(jnp.logical_and(live, b == 0))
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(h, k, v, seen):
        """K/V head ``h``'s tiles into the running softmax of its ``G``
        queries; ``seen`` [1, block] masks the live rows, None when every
        row of the block is."""
        s = lax.dot_general(q_ref[0, h], k, nt,
                            preferred_element_type=jnp.float32) * scale
        if seen is not None:
            s = jnp.where(seen, s, _NEG_INF)                    # [G, block]
        m_prev = m_sc[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[h] = alpha * l_sc[h] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[h] = m_new
        acc_sc[h] = alpha * acc_sc[h] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    first = b * block
    holds_new = jnp.logical_and(first <= at, at < first + block)

    # every row of the block is live and the new row lies elsewhere
    @pl.when(jnp.logical_and(live, jnp.logical_and(
        first + block <= n_live, jnp.logical_not(holds_new))))
    def _():
        for h in range(heads):
            attend(h, k_ref[0, 0, h], v_ref[0, 0, h], None)

    @pl.when(jnp.logical_and(live, holds_new))
    def _():
        row = first + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        seen = first + lax.broadcasted_iota(jnp.int32, (1, block), 1) \
            < n_live
        # the 16 rows around the new one, as they go back to the slab
        group = pl.multiple_of((at - first) // _WRITE_ROWS * _WRITE_ROWS,
                               _WRITE_ROWS)
        near = first + group + lax.broadcasted_iota(
            jnp.int32, (_WRITE_ROWS, 1), 0)
        for h in range(heads):
            kn, vn = kn_ref[0, h], vn_ref[0, h]                 # [1, 128]
            k = jnp.where(row == at, kn, k_ref[0, 0, h])
            # rows past the live ones may hold anything a previous occupant
            # left, inf and nan included: selected away (a zero weight
            # would not stop them)
            v = jnp.where(row == at, vn,
                          jnp.where(row < n_live, v_ref[0, 0, h],
                                    jnp.zeros_like(vn)))
            ko_ref[0, 0, h] = jnp.where(
                near == at, kn, k_ref[0, 0, h, pl.ds(group, _WRITE_ROWS), :])
            vo_ref[0, 0, h] = jnp.where(
                near == at, vn, v_ref[0, 0, h, pl.ds(group, _WRITE_ROWS), :])
            attend(h, k, v, seen)

    # the slot's last live block (in a wrapped ring not the new row's)
    @pl.when(jnp.logical_and(live, b == (n_live - 1) // block))
    def _():
        for h in range(heads):
            o_ref[0, h] = acc_sc[h] / l_sc[h]

    # no live slot at all: the write-back block still goes back, unchanged
    @pl.when(jnp.logical_not(live))
    def _():
        ko_ref[0, 0] = k_ref[0, 0, :, pl.ds(0, _WRITE_ROWS), :]
        vo_ref[0, 0] = v_ref[0, 0, :, pl.ds(0, _WRITE_ROWS), :]


def _kernel_one_query(slot_ref, block_ref, pos_ref, layer_ref, q_ref, kn_ref,
                      vn_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc,
                      acc_sc, s_sc, pv_sc, *, scale, heads, rows, block):
    """:func:`_kernel`'s grid step when a K/V head has ONE query: the same
    two products a head, but the step's ``heads`` score rows meet in
    ``s_sc`` and run through ONE softmax chain ``[heads, block]``, and the
    ``heads`` rows of ``p @ v`` meet in ``pv_sc`` for one update of the
    accumulator (module docstring: what a chain a head cost). A function
    of its own, so that :func:`_kernel` traces what it traced."""
    del layer_ref                               # the index maps read it
    t = pl.program_id(0)
    b = block_ref[t]
    pos = pos_ref[slot_ref[t]]
    at = pos % rows                             # the new row
    n_live = jnp.minimum(pos + 1, rows)         # rows the slot attends
    # only the one step of a tick with no live slot has a dead one
    live = pos >= 0
    nt = (((1,), (1,)), ((), ()))

    @pl.when(jnp.logical_and(live, b == 0))
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(k_of, v_of, seen):
        """Every head's tiles (``k_of(h)``, ``v_of(h)`` [block, 128]) into
        the running softmax of the step's ``heads`` queries; ``seen`` as in
        :func:`_kernel`."""
        for h in range(heads):
            s_sc[h:h + 1] = lax.dot_general(
                q_ref[0, h], k_of(h), nt, preferred_element_type=jnp.float32)
        s = s_sc[...] * scale                               # [heads, block]
        if seen is not None:
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        s_sc[...] = p = jnp.exp(s - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        for h in range(heads):
            v = v_of(h)
            pv_sc[h:h + 1] = jnp.dot(s_sc[h:h + 1].astype(v.dtype), v,
                                     preferred_element_type=jnp.float32)
        acc_sc[...] = alpha * acc_sc[...] + pv_sc[...]

    first = b * block
    holds_new = jnp.logical_and(first <= at, at < first + block)

    # every row of the block is live and the new row lies elsewhere
    @pl.when(jnp.logical_and(live, jnp.logical_and(
        first + block <= n_live, jnp.logical_not(holds_new))))
    def _():
        attend(lambda h: k_ref[0, 0, h], lambda h: v_ref[0, 0, h], None)

    @pl.when(jnp.logical_and(live, holds_new))
    def _():
        row = first + lax.broadcasted_iota(jnp.int32, (block, 1), 0)
        seen = first + lax.broadcasted_iota(jnp.int32, (1, block), 1) \
            < n_live
        # the 16 rows around the new one, as they go back to the slab
        group = pl.multiple_of((at - first) // _WRITE_ROWS * _WRITE_ROWS,
                               _WRITE_ROWS)
        near = first + group + lax.broadcasted_iota(
            jnp.int32, (_WRITE_ROWS, 1), 0)
        for h in range(heads):
            ko_ref[0, 0, h] = jnp.where(
                near == at, kn_ref[0, h],
                k_ref[0, 0, h, pl.ds(group, _WRITE_ROWS), :])
            vo_ref[0, 0, h] = jnp.where(
                near == at, vn_ref[0, h],
                v_ref[0, 0, h, pl.ds(group, _WRITE_ROWS), :])

        def v_of(h):
            # rows past the live ones: selected away, as in `_kernel`
            vn = vn_ref[0, h]
            return jnp.where(row == at, vn,
                             jnp.where(row < n_live, v_ref[0, 0, h],
                                       jnp.zeros_like(vn)))

        attend(lambda h: jnp.where(row == at, kn_ref[0, h], k_ref[0, 0, h]),
               v_of, seen)

    # the slot's last live block (in a wrapped ring not the new row's)
    @pl.when(jnp.logical_and(live, b == (n_live - 1) // block))
    def _():
        o_ref[0, 0] = acc_sc[...] / l_sc[...]

    # no live slot at all: the write-back block still goes back, unchanged
    @pl.when(jnp.logical_not(live))
    def _():
        ko_ref[0, 0] = k_ref[0, 0, :, pl.ds(0, _WRITE_ROWS), :]
        vo_ref[0, 0] = v_ref[0, 0, :, pl.ds(0, _WRITE_ROWS), :]


def count_body(q, slab_k):
    """With telemetry on, count which body :func:`kv_update_attend` takes
    for these operands: `attn.decode.kv128.one_query` or `.grouped`. For
    the caller's trace, once a layer: the kernel's own is shared by every
    layer of a member and may be older than the caller's."""
    if telemetry._enabled:
        telemetry.counter("attn.decode.kv128." + (
            "one_query" if q.shape[1] == slab_k.shape[2] else "grouped")).inc()


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def kv_update_attend(q, k_new, v_new, slab_k, slab_v, layer, positions, *,
                     block, scale=None, interpret=False):
    """One decode tick of layer ``layer`` on a member of the cache (slabs
    ``[S, layers, H, R, 128]``, donated): for every slot with
    ``positions[s] >= 0`` store ``k_new[s]``/``v_new[s]`` ([S, H, 128]) at
    row ``positions[s] mod R`` and attend ``q[s]`` over the slot's first
    ``min(positions[s] + 1, R)`` rows (module docstring: ``R = max_len`` is
    a full member, ``R = sliding_window`` a ring); a slot with a negative
    position is dead — nothing of it is read or written, and its attention
    is 0. ``q`` is ``[S, Hq, 128]`` with ``Hq`` a multiple of ``H``: query
    head ``i`` reads K/V head ``i // (Hq // H)``; ``Hq == H`` takes
    :func:`_kernel_one_query`'s body, any other :func:`_kernel`'s (the
    same mathematics at the same precision). ``scale`` multiplies the
    scores (None: ``1/sqrt(hd)``). Returns ``(attention [S, Hq, 128] fp32,
    slab_k, slab_v)``. ``block`` comes from :func:`kv_block`. ``layer`` is
    an int32 scalar and TRACED: every layer's call on a member shares one
    trace and lowering. The grid is the tick's live blocks (module
    docstring): the work list is a function of ``positions``, ``block`` and
    ``R`` alone, so XLA computes it once for the layers of a member."""
    n_slots, _, heads, rows, hd = slab_k.shape
    if hd != _LANES or rows % block or block % _LANES:
        raise ValueError(f"kv_update_attend: block {block} does not tile "
                         f"{rows} rows of {hd} by whole lane rows")
    q_heads = q.shape[1]
    if q_heads % heads:
        raise ValueError(f"kv_update_attend: {q_heads} query heads do not "
                         f"group over {heads} K/V heads")
    group = q_heads // heads
    positions = positions.astype(jnp.int32)
    alive = positions >= 0
    # a step a live block: min(p + 1, R) rows are `min(p, R - 1) // block + 1`
    # blocks, the full member's `p // block + 1` and a wrapped ring's all
    n_steps, slot_of, block_of = live_steps(
        jnp.where(alive, jnp.minimum(positions, rows - 1), -1), block,
        rows // block)

    def row(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], 0, 0, 0)

    def page(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], layer_ref[0], 0, block_ref[t], 0)

    def written(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], layer_ref[0], 0,
                jnp.maximum(pos_ref[slot_ref[t]], 0) % rows // _WRITE_ROWS, 0)

    # one query a head (a static fact of the trace): the body that runs the
    # step's heads as one chain, its state a row a head
    one = group == 1
    kernel = functools.partial(
        _kernel_one_query if one else _kernel,
        scale=1.0 / math.sqrt(hd) if scale is None else scale,
        heads=heads, rows=rows, block=block)
    state = (heads,) if one else (heads, group)
    attended = (1, heads, hd) if one else (heads, group, hd)
    dt = slab_k.dtype
    out, slab_k, slab_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((1, heads, group, hd), row),
                pl.BlockSpec((1, heads, 1, hd), row),
                pl.BlockSpec((1, heads, 1, hd), row),
                pl.BlockSpec((1, 1, heads, block, hd), page),
                pl.BlockSpec((1, 1, heads, block, hd), page),
            ],
            out_specs=[
                pl.BlockSpec((1,) + attended, row),
                pl.BlockSpec((1, 1, heads, _WRITE_ROWS, hd), written),
                pl.BlockSpec((1, 1, heads, _WRITE_ROWS, hd), written),
            ],
            scratch_shapes=[
                pltpu.VMEM(state + (1,), jnp.float32),          # running max
                pltpu.VMEM(state + (1,), jnp.float32),          # running sum
                pltpu.VMEM(state + (hd,), jnp.float32),         # p @ v
            ] + ([
                pltpu.VMEM((heads, block), jnp.float32),    # scores, then p
                pltpu.VMEM((heads, hd), jnp.float32),       # the step's p @ v
            ] if one else [])),
        out_shape=[
            jax.ShapeDtypeStruct((n_slots,) + attended, jnp.float32),
            jax.ShapeDtypeStruct(slab_k.shape, slab_k.dtype),
            jax.ShapeDtypeStruct(slab_v.shape, slab_v.dtype),
        ],
        # operands count the scalar-prefetch ones: the slabs are 7 and 8
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="kv128_attend",
        interpret=interpret,
    )(slot_of, block_of, positions,
      jnp.asarray(layer, jnp.int32).reshape(1),
      q.astype(dt).reshape(n_slots, heads, group, hd),
      k_new.astype(dt)[:, :, None, :], v_new.astype(dt)[:, :, None, :],
      slab_k, slab_v)
    attn = out.reshape(n_slots, q_heads, hd)
    return jnp.where(alive[:, None, None], attn, 0.0), slab_k, slab_v


def band_block(length, window=None, target=1024):
    """The shape test for :func:`band_prefill_attend`: the block over a
    sequence of ``length`` positions (queries and keys alike), None when
    the caller keeps the XLA formulation (no lane-aligned block divides
    it). With a window the block is at most half of it, so that the band's
    masked edge blocks stay a small part of the band."""
    if window is not None:
        target = min(target, max(_LANES, window // 2 // _LANES * _LANES))
    block = _divisor_block(length, target, multiple=_LANES)
    if block is None or block % _LANES:
        return None
    return block


def band_steps(length, block, window=None):
    """Key blocks a query block of the band meets (the grid's last axis):
    all at or before it without a window; with one, the ``ceil((window -
    1) / block) + 1`` that hold the keys ``(q - window, q]`` of its
    queries."""
    n = length // block
    return n if window is None else min(n, -(-(window - 1) // block) + 1)


def _band_kernel(q_ref, k_ref, v_ref, o_ref, m_sc, l_sc, acc_sc, *, scale,
                 block, steps, window):
    """One (head, query block, band step) grid step of causal attention
    with a running softmax (fp32). Band step ``j`` of query block ``i`` is
    key block ``i - (steps - 1) + j``; the steps before block 0 are skipped
    (their index is clamped, so they cost no DMA either)."""
    i, j = pl.program_id(1), pl.program_id(2)
    kb = i - (steps - 1) + j

    @pl.when(j == 0)
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def attend(edge):
        s = lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
        if edge:
            # key position minus query position
            ahead = (kb - i) * block \
                + lax.broadcasted_iota(jnp.int32, (block, block), 1) \
                - lax.broadcasted_iota(jnp.int32, (block, block), 0)
            seen = ahead <= 0
            if window is not None:
                seen = jnp.logical_and(seen, ahead > -window)
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        v = v_ref[0]
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)

    # inside the band every (query, key) pair of the block is admitted
    inside = kb < i
    if window is not None:
        inside = jnp.logical_and(inside, (kb - i - 1) * block + 1 > -window)

    @pl.when(jnp.logical_and(kb >= 0, inside))
    def _():
        attend(False)

    @pl.when(jnp.logical_and(kb >= 0, jnp.logical_not(inside)))
    def _():
        attend(True)

    # the band's last step is the diagonal block: never skipped
    @pl.when(j == steps - 1)
    def _():
        o_ref[0] = (acc_sc[...] / l_sc[...]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block", "scale", "window",
                                             "interpret"))
def band_prefill_attend(q, k, v, *, block, scale, window=None,
                        interpret=False):
    """Causal softmax attention of one sequence, grouped queries, an
    optional window: ``q`` ``[Hq, L, hd]``, ``k`` and ``v`` ``[H, L, hd]``
    with ``Hq`` a multiple of ``H`` (query head ``i`` reads K/V head ``i //
    (Hq // H)``); a query at position ``p`` sees the keys at ``(p - window,
    p]`` (all at or before it when ``window`` is None); scores ``q . k *
    scale``. Returns ``[Hq, L, hd]`` in ``v``'s dtype. ``block`` comes from
    :func:`band_block`. The first position attends itself, so no row of
    the softmax is empty."""
    q_heads, length, hd = q.shape
    heads = k.shape[0]
    if length % block or q_heads % heads:
        raise ValueError(f"band_prefill_attend: block {block} does not tile "
                         f"L={length}, or {q_heads} query heads do not "
                         f"group over {heads} K/V heads")
    group = q_heads // heads
    steps = band_steps(length, block, window)

    def query(h, i, j):
        return (h, i, 0)

    def key(h, i, j):
        return (h // group, jnp.maximum(i - (steps - 1) + j, 0), 0)

    return pl.pallas_call(
        functools.partial(_band_kernel, scale=scale, block=block,
                          steps=steps, window=window),
        grid=(q_heads, length // block, steps),
        in_specs=[
            pl.BlockSpec((1, block, hd), query),
            pl.BlockSpec((1, block, hd), key),
            pl.BlockSpec((1, block, hd), key),
        ],
        out_specs=pl.BlockSpec((1, block, hd), query),
        scratch_shapes=[
            pltpu.VMEM((block, 1), jnp.float32),            # running max
            pltpu.VMEM((block, 1), jnp.float32),            # running sum
            pltpu.VMEM((block, hd), jnp.float32),           # p @ v
        ],
        out_shape=jax.ShapeDtypeStruct((q_heads, length, hd), v.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        name="swa_prefill_attend",
        interpret=interpret,
    )(q, k, v)
