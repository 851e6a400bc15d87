"""One decode tick's access to a LATENT slab as a Pallas kernel (TPU): take
the tick's new row, attend the live rows, nothing else.

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
L]`` score array through HBM; this kernel reads the live rows only and
keeps scores, the running softmax and the ``[H, R]`` accumulator in VMEM.

**A grid step a live slot, and in it the slot's live rows.** The grid is
ONE axis whose bound is the tick's live slots (their numbers are a
scalar-prefetch operand, as the positions are); a dead slot has no step.
The slabs stay in HBM (``pl.ANY``) and the step fetches the slot's rows by
hand, ``block`` rows a unit into one of two buffers a slab, the next unit —
or the next slot's first — in flight while the current one is scored.
Nothing is stepped over and nothing dead is fetched: a grid ``(slot,
L-block)`` paid a step for every block past a position (about half of them
at the lengths a long-context cell holds) whether or not ``pl.when`` skipped
it, and a ``BlockSpec`` fetches the block that holds the position whole.
Here that last unit is fetched a quarter of a unit a copy, as far as the
position, and scored over that many rows (one ``pl.when`` a size), so the
block can be large — few units, few softmax updates — and the last one
still costs what is live of it.

**The kernel owns the tick's row.** The new latent row and shared key
arrive as operands. In the slot's last unit the row is merged in VMEM (a
select on an iota), the merged rows are attended, and one packed tile
around the position goes back to each slab by a copy of its own through an
output aliased to it — ``[16, R]`` rows of ``c`` and ``[rope, 128]`` lanes
of ``k_r`` — so the decode program holds no XLA scatter,
``dynamic-update-slice`` or ``dynamic-slice`` on a slab (`pallas_window.py`
and `pallas_decode.py`, whose grid is likewise the live part alone, are the
precedents). Nothing of a dead slot's page is read or written; a tick with no
live slot at all takes one step that does nothing.

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
c^T`` and ``qr @ k_r``, and the output a third, ``p @ c``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _NEG_INF, _LANES, _divisor_block
from .pallas_decode import live_blocks

__all__ = ["latent_block", "latent_attend", "prefill_block",
           "prefill_attend"]

# one buffer of `c` rows (there are two, beside two of `k_r`, the last
# unit's merged copy, the fp32 scores and the accumulator): with them well
# under Mosaic's 16 MiB scoped-VMEM limit on a v5e
_BLOCK_BUDGET_BYTES = 2 * 2 ** 20
# rows of `c` the written tile holds: one packed bfloat16 tile (two of
# float32); of `k_r` it holds one lane row of positions
_WRITE_ROWS = 16


def latent_block(c_shape, dtype, target=2048):
    """The shape test for :func:`latent_attend`: the rows of a ``[S,
    layers, L, R]`` latent slab it fetches as one unit, None when the caller
    keeps the XLA formulation (``R`` not whole lane rows; no lane-aligned
    block divides ``L``)."""
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


def _kernel(n_ref, slot_ref, base_ref, pos_ref, layer_ref, qc_ref, qr_ref,
            lat_ref, krn_ref, c_hbm, kr_hbm, o_ref, co_hbm, kro_hbm, cbuf,
            krbuf, ctile, krtile, sem, wsem, m_sc, l_sc, acc_sc, *, scale,
            block, piece):
    """One live slot: its units of ``block`` rows — ``[block, R]`` latent
    rows and ``[rope, block]`` shared keys — against the slot's ``H``
    absorbed queries, streamed into a running softmax (fp32). The last unit
    holds the position: it is fetched and scored as far as the position,
    takes the new row and sends the tile around it back to the slabs."""
    del c_hbm, kr_hbm                   # aliased: read through the outputs
    j = pl.program_id(0)
    n = n_ref[0]
    layer = layer_ref[0]
    pieces = block // piece

    def unit(verb, s, p, u, buf):
        """Start, or wait for, the copies of unit ``u`` of slot ``s`` at
        position ``p`` into buffer ``buf``, ``piece`` rows each: all of a
        unit below the position's, of that one as many as reach it."""
        def body(i, carry):
            rows = pl.ds(pl.multiple_of(u * block + i * piece, piece), piece)
            to = pl.ds(pl.multiple_of(i * piece, piece), piece)
            getattr(pltpu.make_async_copy(
                co_hbm.at[s, layer, rows, :], cbuf.at[buf, to, :],
                sem.at[0, buf]), verb)()
            getattr(pltpu.make_async_copy(
                kro_hbm.at[s, layer, :, rows], krbuf.at[buf, :, to],
                sem.at[1, buf]), verb)()
            return carry
        lax.fori_loop(0, jnp.where(u < p // block, pieces,
                                   p % block // piece + 1), body, 0)

    def tiles(s, row, lane):
        return (pltpu.make_async_copy(
                    ctile, co_hbm.at[s, layer, pl.ds(row, _WRITE_ROWS), :],
                    wsem.at[0]),
                pltpu.make_async_copy(
                    krtile, kro_hbm.at[s, layer, :, pl.ds(lane, _LANES)],
                    wsem.at[1]))

    def attend(c, kr, seen):
        """``seen`` [1, rows] masks the positions at or below the slot's,
        None when every row is."""
        s = lax.dot_general(qc_ref[0], c, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = s + jnp.dot(qr_ref[0], kr, preferred_element_type=jnp.float32)
        s = s * scale                                          # [H, rows]
        if seen is not None:
            s = jnp.where(seen, s, _NEG_INF)
        m_prev = m_sc[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[...] = alpha * l_sc[...] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[...] = m_new
        acc_sc[...] = alpha * acc_sc[...] + jnp.dot(
            p.astype(c.dtype), c, preferred_element_type=jnp.float32)

    @pl.when(n > 0)                     # a tick with no live slot: nothing
    def _():
        slot = slot_ref[j]
        pos = pos_ref[slot]
        last = pos // block
        base = base_ref[j]              # units before this slot's: parity

        @pl.when(j == 0)
        def _():
            unit("start", slot, pos, 0, 0)

        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

        def whole(u, carry):
            buf = (base + u) % 2
            unit("start", slot, pos, u + 1, 1 - buf)
            unit("wait", slot, pos, u, buf)
            attend(cbuf[buf], krbuf[buf], None)
            return carry

        lax.fori_loop(0, last, whole, 0)
        buf = (base + last) % 2

        @pl.when(j + 1 < n)
        def _():
            after = slot_ref[j + 1]
            unit("start", after, pos_ref[after], 0, 1 - buf)

        unit("wait", slot, pos, last, buf)
        first = last * block
        at = pos - first                # the position's row in the unit
        lat, krn = lat_ref[0], krn_ref[0]               # [1, R], [rope, 1]

        @pl.when(j > 0)                 # the tiles of the slot before left
        def _():
            for cp in tiles(slot, 0, 0):
                cp.wait()

        # the tiles around the new row, as they go back to the slabs
        row0 = pl.multiple_of(at // _WRITE_ROWS * _WRITE_ROWS, _WRITE_ROWS)
        ctile[...] = jnp.where(
            first + row0 + lax.broadcasted_iota(
                jnp.int32, (_WRITE_ROWS, 1), 0) == pos,
            lat, cbuf[buf, pl.ds(row0, _WRITE_ROWS), :])
        lane0 = pl.multiple_of(at // _LANES * _LANES, _LANES)
        krtile[...] = jnp.where(
            first + lane0 + lax.broadcasted_iota(
                jnp.int32, (1, _LANES), 1) == pos,
            krn, krbuf[buf, :, pl.ds(lane0, _LANES)])
        written = tiles(slot, pl.multiple_of(first + row0, _WRITE_ROWS),
                        pl.multiple_of(first + lane0, _LANES))
        for cp in written:
            cp.start()
        # rows past the position hold what a previous occupant or an older
        # unit left, inf and nan included: selected away (a zero weight
        # would not stop them)
        for k in range(1, pieces + 1):
            @pl.when(at // piece + 1 == k)
            def _(size=k * piece):
                rows = first + lax.broadcasted_iota(jnp.int32, (size, 1), 0)
                lanes = first + lax.broadcasted_iota(jnp.int32, (1, size), 1)
                c = jnp.where(rows == pos, lat,
                              jnp.where(rows < pos, cbuf[buf, :size, :],
                                        jnp.zeros_like(lat)))
                attend(c, jnp.where(lanes == pos, krn,
                                    krbuf[buf, :, :size]), lanes <= pos)
        o_ref[0] = acc_sc[...] / l_sc[...]

        @pl.when(j == n - 1)
        def _():
            for cp in written:
                cp.wait()


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def latent_attend(qc, qr, lat, k_r, slab_c, slab_kr, layer, positions, *,
                  block, scale, interpret=False):
    """One decode tick of layer ``layer`` on the latent slabs ``slab_c``
    ``[S, layers, L, R]`` and ``slab_kr`` ``[S, layers, rope, L]``
    (donated): every slot with ``positions[s] >= 0`` stores its new latent
    row ``lat[s]`` ``[R]`` and shared key ``k_r[s]`` ``[rope]`` at
    ``positions[s]`` and attends its absorbed queries ``qc[s]`` ``[H, R]``
    and rotary queries ``qr[s]`` ``[H, rope]`` over the rows ``[0,
    positions[s]]`` of its page; a slot with a negative position is dead —
    nothing of it is read or written, and its result is 0. Returns ``(the
    weighted sums of latent rows [S, H, R] fp32, slab_c, slab_kr)``.
    ``block`` comes from :func:`latent_block`; positions lie below ``L``.
    ``layer`` is an int32 scalar and TRACED, so every layer's call
    shares one trace and lowering."""
    n_slots, _, length, rank = slab_c.shape
    rope = slab_kr.shape[2]
    heads = qc.shape[1]
    if length % block or block % _LANES:
        raise ValueError(f"latent_attend: block {block} does not tile "
                         f"L={length} by whole lane rows")
    # rows a copy of a slot's last unit, and the step of the sizes it is
    # scored at: a quarter of the unit where that is whole lane rows
    piece = block // 4 if block % (4 * _LANES) == 0 else block
    positions = positions.astype(jnp.int32)
    alive = positions >= 0
    # the live slots first, in slot order (the dead ones after them have no
    # step); a tick with no live slot has one step, which does nothing
    n_live = jnp.sum(alive, dtype=jnp.int32)
    slot_of = jnp.argsort(jnp.logical_not(alive), stable=True) \
        .astype(jnp.int32)
    units = live_blocks(positions, block)[slot_of]
    base = (jnp.cumsum(units) - units).astype(jnp.int32)

    def row(j, n_ref, slot_ref, base_ref, pos_ref, layer_ref):
        return (slot_ref[j], 0, 0)

    in_hbm = pl.BlockSpec(memory_space=pl.ANY)
    out, slab_c, slab_kr = pl.pallas_call(
        functools.partial(_kernel, scale=scale, block=block, piece=piece),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(jnp.maximum(n_live, 1),),
            in_specs=[
                pl.BlockSpec((1, heads, rank), row),
                pl.BlockSpec((1, heads, rope), row),
                pl.BlockSpec((1, 1, rank), row),
                pl.BlockSpec((1, rope, 1), row),
                in_hbm, in_hbm,
            ],
            out_specs=[pl.BlockSpec((1, heads, rank), row), in_hbm, in_hbm],
            scratch_shapes=[
                pltpu.VMEM((2, block, rank), slab_c.dtype),
                pltpu.VMEM((2, rope, block), slab_kr.dtype),
                pltpu.VMEM((_WRITE_ROWS, rank), slab_c.dtype),
                pltpu.VMEM((rope, _LANES), slab_kr.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),            # [slab, buffer]
                pltpu.SemaphoreType.DMA((2,)),              # the two tiles
                pltpu.VMEM((heads, 1), jnp.float32),        # running max
                pltpu.VMEM((heads, 1), jnp.float32),        # running sum
                pltpu.VMEM((heads, rank), jnp.float32),     # p @ c
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((n_slots, heads, rank), jnp.float32),
            jax.ShapeDtypeStruct(slab_c.shape, slab_c.dtype),
            jax.ShapeDtypeStruct(slab_kr.shape, slab_kr.dtype),
        ],
        # operands count the scalar-prefetch ones: the slabs are 9 and 10
        input_output_aliases={9: 1, 10: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        name="latent_attend",
        interpret=interpret,
    )(n_live[None], slot_of, base, positions,
      jnp.asarray(layer, jnp.int32).reshape(1), qc.astype(slab_c.dtype),
      qr.astype(slab_kr.dtype), lat.astype(slab_c.dtype)[:, None, :],
      k_r.astype(slab_kr.dtype)[:, :, None], slab_c, slab_kr)
    return jnp.where(alive[:, None, None], out, 0.0), slab_c, slab_kr


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
