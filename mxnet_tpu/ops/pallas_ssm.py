"""One decode tick's recurrent-state update as a Pallas kernel (TPU): every
live slot's state of one Mamba-2 layer is read once, advanced one step where
it lies in the serving slab, and written back once.

The slab is ``[S, layers, H, P, N]`` float32 (slot-major, ``N`` the state
size on lanes). One step of layer ``page`` for slot ``s`` and head ``h`` is

    S[s, page, h] = decay[s, h] * S[s, page, h] + dtx[s, h, :, None] * B[s]
    y[s, h, :]    = S[s, page, h] @ C[s]

— pure bandwidth: 2 x the state's bytes a live slot, a few FLOPs a byte. XLA
writes the update in place (a dynamic-update-slice fusion) but cannot also
emit ``y`` from that fusion, so it reads the layer's page of every slot twice;
this kernel makes both in one pass, takes the live slots first in its grid,
and neither reads nor writes a dead one (the state goes back through an output
aliased to the input). The per-head scalars and columns arrive head-minor
(``[.., H]``: a head's column broadcasts along lanes), ``B`` and ``C`` as
rows. `state_update_applies` is the shape test a caller makes before the call.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas_attention import _LANES

__all__ = ["state_update", "state_update_applies"]

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


def _kernel(n_ref, slot_ref, page_ref, decay_ref, dtx_ref, b_ref, c_ref,
            s_ref, y_ref, so_ref, *, heads):
    del page_ref                                # the index maps read it
    j = pl.program_id(0)

    @pl.when(j < n_ref[0])
    def _():
        b, c = b_ref[0], c_ref[0]                               # [1, N]
        lane = lax.broadcasted_iota(jnp.int32, y_ref.shape[1:], 1)
        y = jnp.zeros(y_ref.shape[1:], jnp.float32)            # [P, H]
        for h in range(heads):
            new = (decay_ref[0][:, h:h + 1] * s_ref[0, 0, h]
                   + dtx_ref[0][:, h:h + 1] * b)                # [P, N]
            so_ref[0, 0, h] = new
            y = jnp.where(lane == h,
                          jnp.sum(new * c, axis=1, keepdims=True), y)
        y_ref[0] = y

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
    n_live = jnp.sum(alive, dtype=jnp.int32)
    order = jnp.argsort(jnp.logical_not(alive), stable=True).astype(jnp.int32)
    # live slots first, in slot order; the steps past them stay on the last
    # live slot (no DMA, no compute, nothing written)
    slot_of = order[jnp.minimum(jnp.arange(n_slots, dtype=jnp.int32),
                                jnp.maximum(n_live - 1, 0))]

    def row(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], 0, 0)

    def state(j, n_ref, slot_ref, page_ref):
        return (slot_ref[j], page_ref[0], 0, 0, 0)

    f32 = jnp.float32
    y, slab = pl.pallas_call(
        functools.partial(_kernel, heads=heads),
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
                pl.BlockSpec((1, hp, heads), row),
                pl.BlockSpec((1, 1, heads, hp, n), state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((n_slots, hp, heads), f32),
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
    return (jnp.where(alive[:, None, None], jnp.swapaxes(y, 1, 2), 0.0),
            slab)
