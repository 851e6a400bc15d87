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
lanes. A slab with ``hd % 128 == 0`` lies ``hd``-minor and is the XLA
path's (`decode_block` says so before the call).

Two bodies, chosen from the operands' shapes (``Hq // H`` queries a slab
head; `count_body` counts which, once a layer of the caller's trace):

* **A group of queries** (``Hq // H > 1``: granite's and LFM2's 32 over 8
  heads of 64; :func:`_kernel`) goes through the MXU, as `pallas_window.
  kv_update_attend`'s does: the scores are ``q [G, hd] @ k [hd, block]``,
  the weighted sum is ``p [G, block]`` against ``v [hd, block]`` contracted
  over the lanes, and ONE running softmax a product holds ``[G, 1]`` maxima
  and sums and a ``[G, hd]`` fp32 accumulator. A ``[64, block]`` tile is
  half of what the MXU takes a push, and two heads' tiles are contiguous in
  the block, so heads go through in PAIRS (`_heads_a_product`: as many as
  fill 128 rows): ``[2G, 128]`` queries laid block-diagonally (head A's in
  columns 0-63, head B's in 64-127, zeros elsewhere) score both heads in
  one product with a full 8-sublane softmax chain, and of the ``[2G, 128]``
  weighted sum each head keeps its own 64 columns — four chains a step of
  LFM2's eight heads. The products take K and V as they are stored and the
  queries in the wider of their dtype and the slab's; maxima, exponentials,
  sums and the accumulator are fp32, and so are the weights: against a
  bfloat16 V they go as two bfloat16 terms (``p_hi + p_lo``) stacked as
  rows of one product, which pushes no tile twice. A slot's last step is
  traced once for each of the block's groups of 128 positions that can
  hold the new row: the groups before it are live and go through whole,
  the one that holds it is merged and masked in fp32 (the vector unit
  selects 32-bit lanes), the ones after it are left out of the products.
* **One query a head** (``Hq == H``: GPT-2's; :func:`_kernel_one_query`)
  has nothing to group, and the MXU was never its cost (`pallas_window.py`,
  PR 44): scores reduce over sublanes (vreg adds); the softmax streams per
  lane (128 running maxima, sums and PV columns a head, all elementwise)
  and the lanes are folded once a slot: two reductions a head and one
  matmul with ones that also turns the result lane-dense. Query and new
  rows arrive as ``[hd, H]`` so that a head's column broadcasts along lanes
  (once a slot, into scratch). This body ran every shape before ISSUE 48
  and traces what it traced.
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

__all__ = ["decode_block", "decode_update_attend", "count_body",
           "live_blocks", "live_steps"]

# K and V blocks of every head, double-buffered by the pipeline, beside the
# write-back blocks and the fp32 accumulator: half of Mosaic's 16 MiB
# scoped-VMEM limit on a v5e
_BLOCK_BUDGET_BYTES = 8 * 2 ** 20


def decode_block(slab_shape, dtype, target=None):
    """The shape test for :func:`decode_update_attend`: the block over the
    slab's ``L`` axis when the kernel takes a ``[S, n_layers, H, L, hd]``
    slab of this shape, None when the caller keeps the XLA formulation
    (``hd`` a multiple of 128 lies hd-minor on the chip; no lane-aligned
    block divides ``L``; one block of all heads exceeds the budget).
    ``target`` bounds the block; by default it follows the rows a slot,
    an eighth of them between 256 and 1,024: a grid step costs 0.2-0.4 us
    beside its DMA (0.64 us for 256 rows of eight heads of 64) and the
    grouped body hides under the DMA only from 1,024 rows on, while a
    slot's last block is fetched whole — half a block too many a slot a
    layer, which is what a slab of 1,024 rows a slot (GPT-2 XL's, 256)
    cannot spare and one of 8,192 (LFM2's, 1,024) hardly sees; granite's
    4,096 take 512 (v5e, `PERF.md` section 6, PR 48)."""
    _, _, h, length, hd = slab_shape
    if hd % _LANES == 0 or hd % 8:
        return None
    if target is None:
        target = min(max(length // 8, 256), 1024)
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


def _kernel_one_query(slot_ref, block_ref, pos_ref, layer_ref, q_ref, kn_ref,
                      vn_ref, k_ref, v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc,
                      acc_sc, q_sc, kn_sc, vn_sc, *, scale, heads, hd, block):
    """:func:`_kernel`'s grid step when a slab head has ONE query (GPT-2's
    heads): nothing to group, so nothing for the MXU — every head's
    ``[hd, block]`` K and V tiles against the head's query on the vector
    unit, in groups of 128 positions (module docstring); the group that
    holds the slot's position takes the new row and is written back. A
    function of its own, so that it traces what it traced before
    :func:`_kernel` went to the MXU.
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
        for h in range(heads):
            q_sc[h] = jnp.broadcast_to(q_ref[0][:, h:h + 1], (hd, _LANES))
        for h in range(heads):
            for src, dst in ((kn_ref, kn_sc), (vn_ref, vn_sc)):
                dst[h] = jnp.broadcast_to(src[0][:, h:h + 1], (hd, _LANES))

    def attend(h, k, v, seen):
        """Head ``h``'s running softmax takes one ``[hd, 128]`` K and V
        tile (fp32). It streams PER LANE: 128 running maxima, sums and PV
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

        per_head(fold)
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


def _heads_a_product(heads, hd):
    """Slab heads whose tiles one MXU product takes: as many as fill the
    128 rows of a weight tile (two heads of 64), when they pair off; else
    one."""
    n = _LANES // hd if _LANES % hd == 0 else 1
    return n if heads % n == 0 else 1


def _kernel(slot_ref, block_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref,
            k_ref, v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, *, scale,
            stack, group, hd, block):
    """One grid step, which is one LIVE block ``block_ref[t]`` of slot
    ``slot_ref[t]`` (a slot's steps follow one another, block 0 first:
    `live_steps`), when a slab head has ``group`` > 1 queries
    (grouped-query attention: query head ``h * group + g`` reads slab head
    ``h``). ``stack`` slab heads go through the MXU together
    (`_heads_a_product`): their ``[hd, block]`` tiles, contiguous in the
    block, are one ``[stack * hd, block]`` operand, and their ``stack *
    group`` queries lie block-diagonally in ``q_ref[0, c]`` (head ``j``'s in
    rows ``j * group ...``, columns ``j * hd ...``, zeros elsewhere), so ONE
    product scores them all and a second sums the weighted V rows — of its
    ``[rows, stack * hd]`` result a head keeps its own ``hd`` columns. One
    running softmax a product: ``[rows, 1]`` maxima and sums, a ``[rows,
    stack * hd]`` fp32 accumulator. The slot's last block takes the new row
    and sends the 128 positions around it back to the slab."""
    del layer_ref                               # the index maps read it
    t = pl.program_id(0)
    b = block_ref[t]
    pos = pos_ref[slot_ref[t]]
    first = b * block
    products = k_ref.shape[2] // stack
    rows = stack * group
    nt = (((1,), (1,)), ((), ()))
    # float32 operands stay float32 in the MXU (Mosaic's default takes them
    # through in one bfloat16 pass)
    exact = lax.Precision.HIGHEST

    @pl.when(b == 0)                            # the slot's first step
    def _():
        m_sc[...] = jnp.full(m_sc.shape, _NEG_INF, jnp.float32)
        l_sc[...] = jnp.zeros(l_sc.shape, jnp.float32)
        acc_sc[...] = jnp.zeros(acc_sc.shape, jnp.float32)

    def stacked(of):
        """``of(j)`` for the product's heads, one under the other."""
        parts = [of(j) for j in range(stack)]
        return parts[0] if stack == 1 else jnp.concatenate(parts, axis=0)

    def weighted(p, v):
        """``p @ v.T`` with the weights as they are, float32: a bfloat16
        slab takes them as two bfloat16 terms, one under the other — more
        rows past the same tiles, and no tile pushed twice."""
        if v.dtype == jnp.float32:
            return lax.dot_general(p, v, nt, precision=exact,
                                   preferred_element_type=jnp.float32)
        high = p.astype(v.dtype)
        low = (p - high.astype(jnp.float32)).astype(v.dtype)
        both = lax.dot_general(jnp.concatenate([high, low], axis=0), v, nt,
                               preferred_element_type=jnp.float32)
        return both[:rows] + both[rows:]

    def attend(c, tiles, seen):
        """Product ``c``'s K and V tiles — ``tiles`` lists them along the
        positions, ``(k, v)`` of ``[stack * hd, n * 128]`` each — into the
        running softmax of its queries; ``seen`` [1, positions] masks those
        at or below the slot's, None when every one is."""
        q = q_ref[0, c]
        s = [jnp.dot(q, k.astype(q.dtype), preferred_element_type=jnp.float32,
                     precision=exact if q.dtype == jnp.float32 else None)
             for k, _ in tiles]
        s = (s[0] if len(s) == 1 else jnp.concatenate(s, axis=1)) * scale
        if seen is not None:
            s = jnp.where(seen, s, _NEG_INF)            # [rows, positions]
        m_prev = m_sc[c]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_sc[c] = alpha * l_sc[c] + jnp.sum(p, axis=1, keepdims=True)
        m_sc[c] = m_new
        acc, at = alpha * acc_sc[c], 0
        for _, v in tiles:
            acc = acc + weighted(p[:, at:at + v.shape[1]], v)
            at += v.shape[1]
        acc_sc[c] = acc

    # every row of the block is live and the new row lies further on
    @pl.when(jnp.logical_and(pos >= 0, first + block <= pos))
    def _():
        for c in range(products):
            attend(c, [(stacked(lambda j: k_ref[0, 0, c * stack + j]),
                        stacked(lambda j: v_ref[0, 0, c * stack + j]))], None)

    def last(g):
        """The slot's last step, its position among the block's 128
        positions ``g``: they take the new row and go back to the slab; the
        positions before them are all live, those after them all dead and
        left out of the products."""
        lanes = pl.ds(g * _LANES, _LANES)
        at = first + lax.broadcasted_iota(jnp.int32, (1, (g + 1) * _LANES), 1)
        seen = at <= pos
        cur = at[:, g * _LANES:] == pos

        def merged(new_ref, ref, c):
            """The product's tiles of those 128 positions, the new row in
            its column; in float32, in which the vector unit selects."""
            return jnp.where(
                cur,
                stacked(lambda j: jnp.broadcast_to(
                    new_ref[0][:, c * stack + j:c * stack + j + 1],
                    (hd, _LANES))),
                stacked(lambda j: ref[0, 0, c * stack + j, :, lanes]).astype(
                    jnp.float32))

        def before(ref, c):
            return stacked(
                lambda j: ref[0, 0, c * stack + j, :, pl.ds(0, g * _LANES)])

        for c in range(products):
            k, v = merged(kn_ref, k_ref, c), merged(vn_ref, v_ref, c)
            for j in range(stack):
                rows_j = slice(j * hd, (j + 1) * hd)
                ko_ref[0, 0, c * stack + j] = k[rows_j].astype(ko_ref.dtype)
                vo_ref[0, 0, c * stack + j] = v[rows_j].astype(vo_ref.dtype)
            # rows past the position may hold anything, inf and nan
            # included: selected away (a zero weight would not stop them)
            v = jnp.where(seen[:, g * _LANES:], v, 0.0)
            near = (k.astype(k_ref.dtype), v.astype(v_ref.dtype))
            attend(c, [(before(k_ref, c), before(v_ref, c)), near] if g
                   else [near], seen)
            o = acc_sc[c] / l_sc[c]
            for j in range(stack):
                o_ref[0, pl.ds((c * stack + j) * group, group), :] = \
                    o[j * group:(j + 1) * group, j * hd:(j + 1) * hd]

    for g in range(block // _LANES):
        start = first + g * _LANES
        pl.when(jnp.logical_and(start <= pos, pos < start + _LANES))(
            functools.partial(last, g))

    # no live slot at all (only then has a step a dead slot): the
    # write-back block still goes back, unchanged
    @pl.when(pos < 0)
    def _():
        ko_ref[0, 0] = k_ref[0, 0, :, :, pl.ds(0, _LANES)]
        vo_ref[0, 0] = v_ref[0, 0, :, :, pl.ds(0, _LANES)]


def count_body(q, slab_k):
    """With telemetry on, count which body :func:`decode_update_attend`
    takes for these operands: `attn.decode.slab.one_query` or `.grouped`.
    For the caller's trace, once a layer: the kernel's own is shared by
    every layer and may be older than the caller's."""
    if telemetry._enabled:
        telemetry.counter("attn.decode.slab." + (
            "one_query" if q.shape[1] == slab_k.shape[2] else "grouped")).inc()


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
    group = q_heads // heads
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

    scale = 1.0 / math.sqrt(hd) if scale is None else scale
    if group == 1:
        # one query a head (a static fact of the trace): nothing to group
        padded = _padded_heads(q_heads, hd)
        kernel = functools.partial(_kernel_one_query, scale=scale,
                                   heads=heads, hd=hd, block=block)
        queries, q_spec = columns, pl.BlockSpec((1, hd, q_heads), row)
        attended = (1, padded * hd)
        scratch = [
            pltpu.VMEM((q_heads, 1, _LANES), jnp.float32),   # max by lane
            pltpu.VMEM((q_heads, 1, _LANES), jnp.float32),   # sum-exp
            pltpu.VMEM((padded * hd, _LANES), jnp.float32),  # PV
            pltpu.VMEM((q_heads, hd, _LANES), jnp.float32),  # q by lane
            pltpu.VMEM((heads, hd, _LANES), jnp.float32),    # new K row
            pltpu.VMEM((heads, hd, _LANES), jnp.float32),    # new V row
        ]
    else:
        stack = _heads_a_product(heads, hd)
        products, rows = heads // stack, stack * group
        kernel = functools.partial(_kernel, scale=scale, stack=stack,
                                   group=group, hd=hd, block=block)

        def queries(x):
            """The queries of each product's heads, block-diagonally:
            [S, Hq, hd] -> [S, products, stack * group, stack * hd]."""
            x = x.astype(jnp.promote_types(x.dtype, slab_k.dtype))
            own = jnp.eye(stack, dtype=bool)[:, None, :, None]
            return jnp.where(
                own, x.reshape(n_slots, products, stack, group, 1, hd),
                0).reshape(n_slots, products, rows, stack * hd)

        q_spec = pl.BlockSpec((1, products, rows, stack * hd),
                              lambda *refs: row(*refs) + (0,))
        attended = (q_heads, hd)
        scratch = [
            pltpu.VMEM((products, rows, 1), jnp.float32),    # running max
            pltpu.VMEM((products, rows, 1), jnp.float32),    # running sum
            pltpu.VMEM((products, rows, stack * hd), jnp.float32),  # p @ v
        ]
    view = (n_slots, slab_k.shape[1], heads, hd, length)
    out, slab_k, slab_v = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n_steps,),
            in_specs=[
                q_spec,
                pl.BlockSpec((1, hd, heads), row),
                pl.BlockSpec((1, hd, heads), row),
                pl.BlockSpec((1, 1, heads, hd, block), page),
                pl.BlockSpec((1, 1, heads, hd, block), page),
            ],
            out_specs=[
                pl.BlockSpec((1,) + attended, row),
                pl.BlockSpec((1, 1, heads, hd, _LANES), written),
                pl.BlockSpec((1, 1, heads, hd, _LANES), written),
            ],
            scratch_shapes=scratch),
        out_shape=[
            jax.ShapeDtypeStruct((n_slots,) + attended, jnp.float32),
            jax.ShapeDtypeStruct(view, slab_k.dtype),
            jax.ShapeDtypeStruct(view, slab_v.dtype),
        ],
        # operands count the scalar-prefetch ones: the slabs are 7 and 8
        input_output_aliases={7: 1, 8: 2},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot_of, block_of, positions,
      jnp.asarray(layer, jnp.int32).reshape(1), queries(q), columns(k_new),
      columns(v_new), jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
    if group == 1:
        out = out[:, 0, :q_heads * hd].reshape(n_slots, q_heads, hd)
    return (jnp.where(alive[:, None, None], out, 0.0),
            jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
