"""One decode tick's recurrent-state update as a Pallas kernel (TPU): every
live slot's state of one recurrent layer is read once, advanced one step where
it lies in the serving slab, and written back once. Two recurrences, a kernel
each: Mamba-2's (:func:`state_update`, below) and the gated delta rule's
(:func:`gdn_state_update`, at the end of the file).

The slab is ``[S, layers, H, P, N]`` float32 (slot-major, ``N`` the state
size on lanes). One step of layer ``page`` for slot ``s`` and head ``h`` is

    S[s, page, h] = decay[s, h] * S[s, page, h] + dtx[s, h, :, None] * B[s]
    y[s, h, :]    = S[s, page, h] @ C[s]

XLA writes the update in place (a dynamic-update-slice fusion) but cannot
also emit ``y`` from that fusion, so it reads the layer's page of every slot
twice; this kernel makes both in one pass, takes the live slots first in its
grid, and neither reads nor writes a dead one (the state goes back through an
output aliased to the input).

What bounds it is the copy of a page in and out, and that is the memory's
own rate, not the pipeline's: on the v5e a 2.1 MB page takes 2.93 us to read
and 3.21 us to write when nothing else runs (87% and 80% of 819 GB/s), 6.38 us
with both in flight however deep the queue, and a body that only rewrites
the state 6.40 (80% of the 4.19 MB at 819 GB/s; PERF.md section 5, PR 34).
This body hides under it. The state is advanced in float32 on the VPU. ``y``
is contracted on the MXU, which is otherwise idle: the new state of up to 128
rows is the stationary operand and ``C`` the moving one, at
float32-equivalent precision (``HIGHEST``: the compiler's multi-pass split of
both operands), so no lane reduction runs per vreg of state — summed over
lanes on the cross-lane unit, as before PR 34, the same page took 7.8 us.
The eight rows of the moving operand each keep an eighth of ``C``'s lanes and
are added at the end: eight short sums instead of one long one. The per-head
scalars and columns arrive head-minor (``[.., H]``: a head's column
broadcasts along lanes, which hides under the copy), ``B`` and ``C`` as rows.
`state_update_applies` is the shape test a caller makes before the call.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _LANES

__all__ = ["state_update", "state_update_applies", "gdn_state_update",
           "gdn_update_applies"]

# one slot's page in and out, double-buffered by the pipeline, must leave
# room in the limit the kernel asks for
_VMEM_LIMIT_BYTES = 32 * 2 ** 20
_BLOCK_BUDGET_BYTES = 24 * 2 ** 20


def state_update_applies(slab_shape, dtype):
    """Whether :func:`state_update` takes a ``[S, layers, H, P, N]`` slab of
    this shape: float32, the state size whole lane rows, the head size whole
    sublane rows, and one slot's page — in and out, double-buffered —
    inside the kernel's fast-memory budget."""
    _, _, h, p, n = slab_shape
    return (jnp.dtype(dtype) == jnp.float32 and n % _LANES == 0
            and p % 8 == 0 and 4 * h * p * n * 4 <= _BLOCK_BUDGET_BYTES)


def _live_first(alive):
    """The grid's work list: `(n_live [int32 scalar], slot_of [S])` — the
    live slots first, in slot order; the steps past them stay on the last
    live slot (no DMA, no compute, nothing written)."""
    n_slots = alive.shape[0]
    n_live = jnp.sum(alive, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(jnp.int32)
    return n_live, order[jnp.minimum(jnp.arange(n_slots, dtype=jnp.int32),
                                     jnp.maximum(n_live - 1, 0))]


def _rows_per_product(heads, hp):
    """Heads whose rows make one stationary operand: as many as fill the
    MXU's 128 rows, a whole number of times over the page."""
    return max(d for d in range(1, heads + 1)
               if heads % d == 0 and (d == 1 or d * hp <= _LANES))


def _kernel(n_ref, slot_ref, page_ref, decay_ref, dtx_ref, b_ref, c_ref,
            s_ref, y_ref, so_ref, *, tile):
    del page_ref                                # the index maps read it
    j = pl.program_id(0)
    heads, _, n = s_ref.shape[2:]

    @pl.when(j < n_ref[0])
    def _():
        b = b_ref[0]                                            # [1, N]
        # row r of the moving operand: the r-th eighth of C's lanes
        row = lax.broadcasted_iota(jnp.int32, (8, n), 0) * (n // 8)
        lane = lax.broadcasted_iota(jnp.int32, (8, n), 1)
        c8 = jnp.where(jnp.logical_and(lane >= row, lane < row + n // 8),
                       c_ref[0], 0.0)
        for t in range(heads // tile):
            new = []
            for h in range(t * tile, (t + 1) * tile):
                new.append(decay_ref[0][:, h:h + 1] * s_ref[0, 0, h]
                           + dtx_ref[0][:, h:h + 1] * b)        # [P, N]
                so_ref[0, 0, h] = new[-1]
            part = lax.dot_general(
                c8, jnp.concatenate(new, axis=0), (((1,), (1,)), ((), ())),
                precision=lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)             # [8, rows]
            y_ref[0, t:t + 1, :] = jnp.sum(part, axis=0, keepdims=True)

    # no live slot at all: the write-back block still goes back, unchanged
    @pl.when(jnp.logical_and(n_ref[0] == 0, j == 0))
    def _():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def state_update(slab, page, decay, dtx, b, c, alive, *, interpret=False):
    """Advance layer ``page`` of the state slab (``[S, layers, H, P, N]``
    float32, donated) by one step for every slot with ``alive[s]``:
    ``decay`` [S, H], ``dtx`` [S, H, P] (``dt * x``), ``b`` and ``c`` [S, N],
    all float32. Returns ``(y [S, H, P] float32, slab)``; a dead slot's
    state is neither read nor written and its ``y`` is 0.

    ``page`` is an int32 scalar and TRACED, and the function is jitted: a
    model calls it once a layer inside its own program, and every call after
    the first reuses the first one's trace and lowering (as
    `pallas_decode.decode_update_attend`)."""
    n_slots, _, heads, hp, n = slab.shape
    alive = alive.astype(bool)
    n_live, slot_of = _live_first(alive)

    def row(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], 0, 0)

    def state(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], page_ref[0], 0, 0, 0)

    f32 = jnp.float32
    tile = _rows_per_product(heads, hp)
    y, slab = pl.pallas_call(
        functools.partial(_kernel, tile=tile),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((1, 1, heads), row),               # decay
                pl.BlockSpec((1, hp, heads), row),              # dt * x
                pl.BlockSpec((1, 1, n), row),                   # B
                pl.BlockSpec((1, 1, n), row),                   # C
                pl.BlockSpec((1, 1, heads, hp, n), state),
            ],
            out_specs=[
                pl.BlockSpec((1, heads // tile, tile * hp), row),
                pl.BlockSpec((1, 1, heads, hp, n), state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((n_slots, heads // tile, tile * hp),
                                        f32),
                   jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operands count the scalar-prefetch ones: the slab is 7
        input_output_aliases={7: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="mamba_state_update",
        interpret=interpret,
    )(n_live[None], slot_of, jnp.asarray(page, jnp.int32).reshape(1),
      decay.astype(f32)[:, None, :], jnp.swapaxes(dtx.astype(f32), 1, 2),
      b.astype(f32)[:, None, :], c.astype(f32)[:, None, :], slab)
    return (jnp.where(alive[:, None, None],
                      y.reshape(n_slots, heads, hp), 0.0), slab)


# ---------------------------------------------------------------------------
# the gated delta rule (Gated DeltaNet, arXiv:2412.06464)
# ---------------------------------------------------------------------------
#
# The slab is ``[S, layers, dk, H * dv]`` float32: a head's matrix state ``[dk,
# dv]`` keeps its keys on the sublanes and lies beside the other heads' on the
# lanes, so a page of 30 heads of 96 x 192 is 96 rows of 45 whole lane rows
# and its bytes on the chip are its count (``[.., 96, 192]`` would be padded to
# 256 lanes). One step of layer ``page`` for slot ``s`` and head ``h``:
#
#     S' = alpha[s, h] * S;   u = v[s, h] - S'^T k[s, h]
#     S  = S' + beta[s, h] * k[s, h] u^T;   o[s, h] = S^T q[s, h]
#
# The update reads the state to form its own correction, so XLA reads the page
# three times (the two contractions and the update); the kernel reads it once
# and writes it once. Both contractions run over the SUBLANES (the ``dk`` keys
# of a head), the page being the large operand: on the MXU that operand would
# be the stationary one, loaded a 128 x 128 tile at a time for a handful of
# moving rows (45 tiles x 6 passes x 2 products a page), so they run on the
# VPU in float32 — a multiply and a running add a vreg of state, exact — and
# the copy of the page in and out is what is left to bound the kernel. A
# head's ``k`` and ``q`` arrive as columns (``[dk, H]``: a column broadcasts
# along the lanes of its head) and ``alpha``, ``beta``, ``v`` and ``o`` as
# lane rows ``[1, H * dv]``. A head of 192 lanes ends inside a lane row, so
# the heads are taken as many at a time as end on one (two: 384 lanes) and
# the columns of a group are selected by lane.


def _gdn_group(heads, dv):
    """Heads a group: the fewest whose lanes end on a lane row, None when
    no whole number of such groups covers the heads."""
    group = _LANES // math.gcd(dv, _LANES)
    return group if heads % group == 0 else None


def gdn_update_applies(slab_shape, dtype, heads):
    """Whether :func:`gdn_state_update` takes a ``[S, layers, dk, H * dv]``
    slab of this shape with ``heads`` heads: float32, the keys whole sublane
    rows, the heads in groups of whole lane rows, and one slot's page — in
    and out, double-buffered — inside the kernel's fast-memory budget."""
    _, _, dk, lanes = slab_shape
    return (jnp.dtype(dtype) == jnp.float32 and dk % 8 == 0
            and lanes % heads == 0
            and _gdn_group(heads, lanes // heads) is not None
            and 4 * dk * lanes * 4 <= _BLOCK_BUDGET_BYTES)


def _gdn_kernel(n_ref, slot_ref, page_ref, alpha_ref, beta_ref, v_ref, k_ref,
                q_ref, s_ref, o_ref, so_ref, *, heads, group):
    del page_ref                                # the index maps read it
    j = pl.program_id(0)
    dk, lanes = s_ref.shape[2:]
    dv = lanes // heads
    width = group * dv

    @pl.when(j < n_ref[0])
    def _():
        lane = lax.broadcasted_iota(jnp.int32, (dk, width), 1)

        def columns(ref, first):
            """[dk, width]: each head of the group's column along its own
            lanes."""
            out = ref[0][:, first:first + 1]
            for g in range(1, group):
                out = jnp.where(lane >= g * dv,
                                ref[0][:, first + g:first + g + 1], out)
            return out

        for t in range(heads // group):
            at = pl.ds(t * width, width)
            k, q = columns(k_ref, t * group), columns(q_ref, t * group)
            kept = alpha_ref[0, :, at] * s_ref[0, 0, :, at]     # [dk, width]
            u = v_ref[0, :, at] - jnp.sum(kept * k, axis=0, keepdims=True)
            new = kept + k * (beta_ref[0, :, at] * u)
            so_ref[0, 0, :, at] = new
            o_ref[0, :, at] = jnp.sum(new * q, axis=0, keepdims=True)

    # no live slot at all: the write-back block still goes back, unchanged
    @pl.when(jnp.logical_and(n_ref[0] == 0, j == 0))
    def _():
        so_ref[...] = s_ref[...]


@functools.partial(jax.jit, static_argnames=("interpret",))
def gdn_state_update(slab, page, alpha, beta, q, k, v, alive, *,
                     interpret=False):
    """Advance layer ``page`` of the state slab (``[S, layers, dk, H * dv]``
    float32, donated) by one step of the gated delta rule for every slot with
    ``alive[s]``: ``alpha`` and ``beta`` [S, H], ``q`` and ``k`` [S, H, dk],
    ``v`` [S, H, dv], all float32. Returns ``(o [S, H, dv] float32, slab)``;
    a dead slot's state is neither read nor written and its ``o`` is 0.
    ``page`` is an int32 scalar and TRACED, as :func:`state_update`'s."""
    n_slots, _, dk, lanes = slab.shape
    heads, dv = v.shape[1:]
    group = _gdn_group(heads, dv)
    alive = alive.astype(bool)
    n_live, slot_of = _live_first(alive)

    def row(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], 0, 0)

    def state(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], page_ref[0], 0, 0)

    f32 = jnp.float32

    def lane_row(x):            # [S, H] -> [S, 1, H * dv], a head's lanes
        return jnp.repeat(x.astype(f32), dv, axis=-1)[:, None, :]

    o, slab = pl.pallas_call(
        functools.partial(_gdn_kernel, heads=heads, group=group),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_slots,),
            in_specs=[
                pl.BlockSpec((1, 1, lanes), row),               # alpha
                pl.BlockSpec((1, 1, lanes), row),               # beta
                pl.BlockSpec((1, 1, lanes), row),               # v
                pl.BlockSpec((1, dk, heads), row),              # k
                pl.BlockSpec((1, dk, heads), row),              # q
                pl.BlockSpec((1, 1, dk, lanes), state),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, lanes), row),
                pl.BlockSpec((1, 1, dk, lanes), state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((n_slots, 1, lanes), f32),
                   jax.ShapeDtypeStruct(slab.shape, slab.dtype)],
        # operands count the scalar-prefetch ones: the slab is 8
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        name="gdn_state_update",
        interpret=interpret,
    )(n_live[None], slot_of, jnp.asarray(page, jnp.int32).reshape(1),
      lane_row(alpha), lane_row(beta),
      v.astype(f32).reshape(n_slots, 1, lanes),
      jnp.swapaxes(k.astype(f32), 1, 2), jnp.swapaxes(q.astype(f32), 1, 2),
      slab)
    return (jnp.where(alive[:, None, None],
                      o.reshape(n_slots, heads, dv), 0.0), slab)
