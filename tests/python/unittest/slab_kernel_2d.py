"""The slab kernel as it was before ISSUE 41 — grid ``(slot, L-block)``, the
live slots sorted first, the steps past a position and of the dead slots
taken and skipped under ``pl.when`` — kept as the reference that
`test_decode_slab.py` holds the live-only grid to, bit for bit: the two
take a slot's live blocks in the same order through the same body, so
nothing of the attention or of the slab may differ. Not the program's: the
package has one path (`mxnet_tpu/ops/pallas_decode.py`).
"""
import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from mxnet_tpu.ops.pallas_attention import _NEG_INF, _LANES
from mxnet_tpu.ops.pallas_decode import _padded_heads


def _kernel(n_ref, slot_ref, pos_ref, layer_ref, q_ref, kn_ref, vn_ref, k_ref,
            v_ref, o_ref, ko_ref, vo_ref, m_sc, l_sc, acc_sc, q_sc, kn_sc,
            vn_sc, *, scale, heads, group, hd, block):
    """One (slot, L-block) grid step; the body is the package's, under the
    ``live`` flag of a grid that steps over what is not live."""
    del layer_ref                               # the index maps read it
    j, b = pl.program_id(0), pl.program_id(1)
    pos = pos_ref[slot_ref[j]]
    live = j < n_ref[0]

    def per_head(body, n=heads):
        lax.fori_loop(0, n, lambda h, carry: body(h) or carry, 0,
                      unroll=True)

    @pl.when(jnp.logical_and(live, b == 0))
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

    @pl.when(jnp.logical_and(live, b * block <= pos))
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

    @pl.when(jnp.logical_and(live, b == pl.num_programs(1) - 1))
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

    # no live slot at all: the write-back block still goes back, unchanged
    @pl.when(jnp.logical_and(n_ref[0] == 0,
                             jnp.logical_and(j == 0, b == 0)))
    def _():
        ko_ref[0, 0] = k_ref[0, 0, :, :, pl.ds(0, _LANES)]
        vo_ref[0, 0] = v_ref[0, 0, :, :, pl.ds(0, _LANES)]


@functools.partial(jax.jit, static_argnames=("block", "scale", "interpret"))
def decode_update_attend(q, k_new, v_new, slab_k, slab_v, layer, positions,
                         *, block, scale=None, interpret=False):
    """Operands and results as `pallas_decode.decode_update_attend`."""
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
    # live slots first, in slot order; the steps past them stay on the last
    # live slot's last block (no DMA, no compute, nothing written)
    n_live = jnp.sum(alive, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(jnp.int32)
    slot_of = order[jnp.minimum(jnp.arange(n_slots, dtype=jnp.int32),
                                jnp.maximum(n_live - 1, 0))]

    def row(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        return (slot_ref[j], 0, 0)

    def page(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        last = jnp.maximum(pos_ref[slot_ref[j]], 0) // block
        return (slot_ref[j], layer_ref[0], 0, 0,
                jnp.where(j < n_ref[0], jnp.minimum(b, last), last))

    def written(j, b, n_ref, slot_ref, pos_ref, layer_ref):
        return (slot_ref[j], layer_ref[0], 0, 0,
                jnp.maximum(pos_ref[slot_ref[j]], 0) // _LANES)

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
            grid=(n_slots, length // block),
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
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(n_live[None], slot_of, positions,
      jnp.asarray(layer, jnp.int32).reshape(1), columns(q), columns(k_new),
      columns(v_new), jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
    attn = out[:, 0, :q_heads * hd].reshape(n_slots, q_heads, hd)
    return (jnp.where(alive[:, None, None], attn, 0.0),
            jnp.swapaxes(slab_k, 3, 4), jnp.swapaxes(slab_v, 3, 4))
