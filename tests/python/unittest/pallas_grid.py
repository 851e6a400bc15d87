"""What a live-only grid's `pallas_call` is handed (`pallas_decode.
decode_update_attend`, `pallas_window.kv_update_attend`): the tests of both
kernels evaluate the bound and the work list from the function's trace."""
import numpy as np

import jax


def grid_of_call(fn, *operands):
    """`fn(*operands)` holds one `pallas_call` whose grid is one axis under
    a dynamic bound, with four scalar-prefetch operands (the work list's two
    members, the positions, the layer): `(bound, slot_of_step,
    block_of_step, positions)` as the call receives them, evaluated (numpy)."""
    closed = jax.make_jaxpr(fn)(*operands)
    (at, call), = [(i, e) for i, e in enumerate(closed.jaxpr.eqns)
                   if e.primitive.name == "pallas_call"]
    grid = call.params["grid_mapping"]
    assert len(grid.grid) == grid.num_dynamic_grid_bounds == 1
    assert grid.num_index_operands == 4    # the lists, positions, the layer
    upto = closed.jaxpr.replace(outvars=call.invars[:4],
                                eqns=closed.jaxpr.eqns[:at])
    return tuple(map(np.asarray, jax.core.eval_jaxpr(
        upto, closed.consts, *operands)))
