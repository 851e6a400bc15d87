"""One decode tick's slab access as a Pallas kernel (TPU): write one K/V row
per live slot in place, attend over the live part of the slab only.

A decode tick extends every live slot of the serving slab
``[S, n_layers, H, L, hd]`` by one token: slot ``s`` stores the token's K
and V at row ``positions[s]`` and attends rows ``[0, positions[s]]`` of its
own page. The XLA formulation reads all ``L`` rows of all ``S`` slots
whatever is live. This kernel touches the slab where it lies and only where
it must: its grid is ONE axis with a step for every LIVE block of the tick
and no other — the live slots in slot order, each from block 0 to the block
that holds its position (`live_steps`: the bound, `live_blocks(positions,
block).sum()`, is dynamic, and the step's slot and block ride as
scalar-prefetch operands beside the positions). A dead slot and a block
past a position have no step: stepped over under ``pl.when`` they cost no
DMA but 0.3 us each on a v5e, 6,000 of them a tick of GPT-2 XL's 48
layers. The softmax streams in fp32. The new row is merged into the slot's
last block as it passes through VMEM, and the 128 positions around it go
back to the slab through an output aliased to the input: no XLA scatter or
``dynamic-update-slice`` on the slab at all. A tick with no live slot takes
one step, which sends its write-back block back as it came.

Layout. XLA's TPU layout for a slab whose ``hd`` is not a multiple of the
128 lanes puts ``L`` minor-most (``{3,4,2,1,0}``: no lane padding), so the
kernel takes the slab as ``[S, n_layers, H, hd, L]`` — `swapaxes` of the
logical array, a bitcast of the bytes — and never makes XLA copy it (an XLA
scatter wants ``hd`` minor and answers with two copies of the whole slab).
K and V arrive as ``[hd, block]`` tiles, ``hd`` on sublanes, positions on
lanes. Scores reduce over sublanes (vreg adds); the softmax streams per lane
(128 running maxima, sums and PV columns a head, all elementwise) and the
lanes are folded once a slot: two reductions a head and one matmul with ones
that also turns the result lane-dense. Query and new rows arrive as ``[hd, H]``
so that a head's column broadcasts along lanes (once a slot, into scratch). A slab with ``hd % 128 ==
0`` lies ``hd``-minor and is the XLA path's (`decode_block` says so before
the call).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _NEG_INF, _LANES, _divisor_block

__all__ = ["decode_block", "decode_update_attend", "live_blocks",
           "live_steps"]

# K and V blocks of every head, double-buffered by the pipeline, beside the
# write-back blocks and the fp32 accumulator: half of Mosaic's 16 MiB
# scoped-VMEM limit on a v5e
_BLOCK_BUDGET_BYTES = 8 * 2 ** 20


def decode_block(slab_shape, dtype, target=256):
    """The shape test for :func:`decode_update_attend`: the block over the
    slab's ``L`` axis when the kernel takes a ``[S, n_layers, H, L, hd]``
    slab of this shape, None when the caller keeps the XLA formulation
    (``hd`` a multiple of 128 lies hd-minor on the chip; no lane-aligned
    block divides ``L``; one block of all heads exceeds the budget)."""
    _, _, h, length, hd = slab_shape
    if hd % _LANES == 0 or hd % 8:
        return None
    block = _divisor_block(length, target, multiple=_LANES)
    if block is None or block % _LANES:
        return None
    while 4 * h * hd * block * jnp.dtype(dtype).itemsize > _BLOCK_BUDGET_BYTES:
        if block % (2 * _LANES):
            return None
        block //= 2
    return block


def live_blocks(positions, block):
    """Blocks of the slab one tick reads with this block size: a slot at
    position ``p >= 0`` reads ``p // block + 1`` of them, a dead slot
    (negative position) none. Numpy or jax integers; the engine counts with
    it on the host."""
    return (positions // block + 1) * (positions >= 0)


def live_steps(positions, block, blocks_a_slot):
    """The grid of one tick of :func:`decode_update_attend`, from the
    positions alone: ``(n_steps, slot_of_step, block_of_step)``. A step is
    one live block — the live slots in slot order, each slot's blocks from 0
    to the one that holds its position — so ``n_steps`` is
    ``live_blocks(positions, block).sum()``; a tick with no live slot has
    one step (on the last slot's block 0, which goes back as it came). The
    lists are ``[S * blocks_a_slot]`` int32, in range past ``n_steps``
    too."""
    units = live_blocks(positions.astype(jnp.int32), block)
    ends = jnp.cumsum(units)                    # steps up to each slot's last
    step = jnp.arange(units.shape[0] * blocks_a_slot, dtype=jnp.int32)
    done = ends[None, :] <= step[:, None]       # [steps, S]: slot is behind
    slot_of = jnp.sum(done, axis=1, dtype=jnp.int32)
    block_of = step - jnp.max(jnp.where(done, ends[None, :], 0), axis=1)
    return (jnp.maximum(ends[-1], 1),
            jnp.minimum(slot_of, units.shape[0] - 1),
            jnp.minimum(block_of, blocks_a_slot - 1))


def _padded_heads(h, hd):
    """Heads rounded up so that ``heads * hd`` fills whole lane rows."""
    step = _LANES // math.gcd(hd, _LANES)
    return -(-h // step) * step


def _kernel(slot_ref, block_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref,
            k_ref, v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, q_sc,
            kn_sc, vn_sc, *, scale, heads, group, hd, block):
    """One grid step, which is one LIVE block ``block_ref[t]`` of slot
    ``slot_ref[t]``: every slab head's ``[hd, block]`` K and V tiles
    against the slot's ``group`` queries of that head (grouped-query
    attention: query head ``h * group + g`` reads slab head ``h``;
    ``group`` 1 is one query a head), in groups of 128 positions; the group
    that holds the slot's position takes the new row and is written back.
    A slot's steps follow one another, block 0 first (`live_steps`).
    Heads run in a ``fori_loop`` that is unrolled when LOWERED: the body is
    traced once (unrolled in Python it cost seconds of tracing at every
    process start) and the compiler still schedules across heads (rolled,
    a live slot cost twice the time on the chip). What a head needs by its
    index lies head-major in scratch."""
    del layer_ref                               # the index maps read it
    t = pl.program_id(0)
    b = block_ref[t]
    pos = pos_ref[slot_ref[t]]

    def per_head(body, n=heads):
        lax.fori_loop(0, n, lambda h, carry: body(h) or carry, 0,
                      unroll=True)

    @pl.when(b == 0)                            # the slot's first step
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)
        # a head's column of the query and of the new rows, along lanes
        for h in range(heads * group):
            q_sc[h] = jnp.broadcast_to(q_ref[0][:, h:h + 1], (hd, _LANES))
        for h in range(heads):
            for src, dst in ((kn_ref, kn_sc), (vn_ref, vn_sc)):
                dst[h] = jnp.broadcast_to(src[0][:, h:h + 1], (hd, _LANES))

    def attend(h, k, v, seen):
        """Slab head ``h``'s ``[hd, 128]`` K and V tiles (fp32) into the
        running softmax of each of its queries."""
        for g in range(group):
            attend_query(h if group == 1 else h * group + g, k, v, seen)

    def attend_query(h, k, v, seen):
        """Query head ``h``'s running softmax takes one ``[hd, 128]`` K and
        V tile. It streams PER LANE: 128 running maxima, sums and PV
        columns a head, all elementwise — no reduction across lanes until
        the slot's last step. ``seen`` masks the lanes at or below the
        position; None when the whole group is."""
        rows = pl.ds(pl.multiple_of(h * hd, hd), hd)
        sc = jnp.sum(q_sc[h] * k, axis=0, keepdims=True) * scale
        if seen is not None:
            sc = jnp.where(seen, sc, _NEG_INF)                  # [1, 128]
        m_prev = m_sc[h]
        m_new = jnp.maximum(m_prev, sc)
        p = jnp.exp(sc - m_new)
        alpha = jnp.exp(m_prev - m_new)
        pv = p * v
        if seen is not None:
            # rows past the position may hold anything, inf and nan
            # included: selected away (a zero weight would not stop them)
            p, pv = jnp.where(seen, p, 0.0), jnp.where(seen, pv, 0.0)
        l_sc[h] = alpha * l_sc[h] + p
        m_sc[h] = m_new
        acc_sc[rows, :] = alpha * acc_sc[rows, :] + pv

    @pl.when(pos >= 0)
    def _():
        for g in range(block // _LANES):
            start = b * block + g * _LANES
            lanes = pl.ds(g * _LANES, _LANES)

            @pl.when(start + _LANES <= pos)     # every row of it is live
            def _(lanes=lanes):
                per_head(lambda h: attend(
                    h, k_ref[0, 0, h, :, lanes].astype(jnp.float32),
                    v_ref[0, 0, h, :, lanes].astype(jnp.float32), None))

            # the group that holds the position takes the new row, and goes
            # back to the slab
            @pl.when(jnp.logical_and(start <= pos, pos < start + _LANES))
            def _(lanes=lanes, start=start):
                at = start + lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
                seen, cur = at <= pos, at == pos

                def merge(h):
                    k = jnp.where(
                        cur, kn_sc[h],
                        k_ref[0, 0, h, :, lanes].astype(jnp.float32))
                    v = jnp.where(
                        cur, vn_sc[h],
                        v_ref[0, 0, h, :, lanes].astype(jnp.float32))
                    ko_ref[0, 0, h] = k.astype(ko_ref.dtype)
                    vo_ref[0, 0, h] = v.astype(vo_ref.dtype)
                    attend(h, k, v, seen)

                per_head(merge)

    @pl.when(jnp.logical_and(pos >= 0, b == pos // block))   # its last
    def _():
        def fold(h):
            # the 128 streams' weights: exp(m - max m), 0 for a lane that
            # never saw a live position
            rows = pl.ds(pl.multiple_of(h * hd, hd), hd)
            m = m_sc[h]
            w = jnp.exp(m - jnp.max(m, axis=-1, keepdims=True))
            total = jnp.sum(l_sc[h] * w, axis=-1, keepdims=True)
            acc_sc[rows, :] = acc_sc[rows, :] * (w / total)

        per_head(fold, heads * group)
        # the sum over lanes of every row, as one lane-dense row
        ones = jnp.ones((8, _LANES), jnp.float32)
        o = lax.dot_general(ones, acc_sc[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        o_ref[0] = o[0:1]

    # no live slot at all (only then has a step a dead slot): the
    # write-back block still goes back, unchanged
    @pl.when(pos < 0)
    def _():
        ko_ref[0, 0] = k_ref[0, 0, :, :, pl.ds(0, _LANES)]
        vo_ref[0, 0] = v_ref[0, 0, :, :, pl.ds(0, _LANES)]


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def decode_update_attend(q, k_new, v_new, slab_k, slab_v, layer, positions,
                         *, block, scale=None, interpret=False):
    """One decode tick of layer ``layer`` on the slab
    (``[S, n_layers, H, L, hd]``, donated): for every slot with
    ``positions[s] >= 0`` store ``k_new[s]``/``v_new[s]`` ([S, H, hd]) at
    row ``positions[s]`` and attend ``q[s]`` over rows ``[0, positions[s]]``;
    a slot with a negative position is dead — nothing of it is read or
    written, and its attention is 0. ``q`` is ``[S, Hq, hd]`` with ``Hq`` a
    multiple of ``H``: query head ``i`` reads slab head ``i // (Hq // H)``
    (grouped-query attention; ``Hq == H`` is one query a head), decided from
    the shapes. ``scale`` multiplies the scores (None: ``1/sqrt(hd)``).
    Returns ``(attention [S, Hq, hd] fp32, slab_k, slab_v)``. ``block``
    comes from :func:`decode_block`; positions lie below ``L``.

    ``layer`` is an int32 scalar and TRACED, and the function is jitted: a
    model calls it once a layer inside its own program, and every call
    after the first reuses the first one's trace and lowering (traced once
    a layer, and unrolled over heads, the kernel cost a 48-layer model a
    minute of Python at every process start, compile cache or not)."""
    n_slots, _, heads, length, hd = slab_k.shape
    if length % block or block % _LANES:
        raise ValueError(f"decode_update_attend: block {block} does not "
                         f"tile L={length} by whole lane rows")
    q_heads = q.shape[1]
    if q_heads % heads:
        raise ValueError(f"decode_update_attend: {q_heads} query heads do "
                         f"not group over {heads} slab heads")
    padded = _padded_heads(q_heads, hd)
    positions = positions.astype(jnp.int32)
    alive = positions >= 0
    n_steps, slot_of, block_of = live_steps(positions, block,
                                            length // block)

    def row(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], 0, 0)

    def page(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], layer_ref[0], 0, 0, block_ref[t])

    def written(t, slot_ref, block_ref, pos_ref, layer_ref):
        return (slot_ref[t], layer_ref[0], 0, 0,
                jnp.maximum(pos_ref[slot_ref[t]], 0) // _LANES)

    def columns(x):                               # [S, H, hd] -> [S, hd, H]
        return jnp.swapaxes(x.astype(jnp.float32), 1, 2)

    kernel = functools.partial(
        _kernel, scale=1.0 / math.sqrt(hd) if scale is None else scale,
        heads=heads, group=q_heads // heads, hd=hd, block=block)
    view = (n_slots, slab_k.shape[1], heads, hd, length)
    out, slab_k, slab_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),
            in_specs=[
                pl.BlockSpec((1, hd, q_heads), row),
                pl.BlockSpec((1, hd, heads), row),
                pl.BlockSpec((1, hd, heads), row),
                pl.BlockSpec((1, 1, heads, hd, block), page),
                pl.BlockSpec((1, 1, heads, hd, block), page),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, padded * hd), row),
                pl.BlockSpec((1, 1, heads, hd, _LANES), written),
                pl.BlockSpec((1, 1, heads, hd, _LANES), written),
            ],
            scratch_shapes=[
                pltpu.VMEM((q_heads, 1, _LANES), jnp.float32),   # max by lane
                pltpu.VMEM((q_heads, 1, _LANES), jnp.float32),   # sum-exp
                pltpu.VMEM((padded * hd, _LANES), jnp.float32),  # PV
                pltpu.VMEM((q_heads, hd, _LANES), jnp.float32),  # q by lane
                pltpu.VMEM((heads, hd, _LANES), jnp.float32),    # new K row
                pltpu.VMEM((heads, hd, _LANES), jnp.float32),    # new V row
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, 1, padded * hd), jnp.float32),
            jax.ShapeDtypeStruct(view, slab_k.dtype),
            jax.ShapeDtypeStruct(view, slab_v.dtype),
        ],
        # operands count the scalar-prefetch ones: the slabs are 7 and 8
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot_of, block_of, positions,
      jnp.asarray(layer, jnp.int32).reshape(1), columns(q), columns(k_new),
      columns(v_new), jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
    attn = out[:, 0, :q_heads * hd].reshape(n_slots, q_heads, hd)
    return (jnp.where(alive[:, None, None], attn, 0.0),
            jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
