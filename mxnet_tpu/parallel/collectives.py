"""Collective communication primitives.

The reference's collectives are NCCL calls (`kvstore_nccl.h`), hand-built
reduce trees (`comm.h:451`, `comm_tree.h:50`), and ps-lite RPC
(`kvstore_dist.h`). Here each primitive has two faces:

* **in-program** (inside `shard_map`/`jit`): thin wrappers over
  `jax.lax` collectives — XLA schedules them onto ICI.
* **eager** (NDArray level, outside jit): a tiny jitted program built on
  demand — the analogue of the reference pushing a reduction lambda onto
  the engine (`comm.h Reduce`).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax, shard_map  # noqa: F401 — shard_map is re-exported
from jax.sharding import NamedSharding, PartitionSpec as P

from ..compile_cache import CompileCache
from .mesh import default_mesh


# -- in-program (use inside shard_map) --------------------------------------

def all_reduce(x, axis_name, op="sum"):
    """AllReduce along a mesh axis (NCCL allreduce / `comm.h` Reduce+Bcast)."""
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op}")


def all_gather(x, axis_name, axis=0, tiled=True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def reduce_scatter(x, axis_name, axis=0):
    return lax.psum_scatter(x, axis_name, scatter_dimension=axis, tiled=True)


psum_scatter = reduce_scatter


def sharding_constraint(x, sharding):
    """`with_sharding_constraint` — the GSPMD annotation the
    sharded-weight-update paper (arXiv:2004.13336) is built on: a psum
    followed by a constraint to a sharded layout lowers to ReduceScatter,
    a constraint from sharded back to replicated lowers to AllGather."""
    return lax.with_sharding_constraint(x, sharding)


def ppermute(x, axis_name, perm):
    """Point-to-point ring shift; the building block of ring attention."""
    return lax.ppermute(x, axis_name, perm)


def ring_shift(x, axis_name, axis_size, shift=1):
    """Send this shard to rank+shift (mod n) — one ICI hop on a torus."""
    perm = [(i, (i + shift) % axis_size) for i in range(axis_size)]
    return lax.ppermute(x, axis_name, perm)


# -- eager (NDArray / host level) -------------------------------------------

# the eager-collective programs, named so `named_stats("collectives")`
# attributes wire recompiles (was an anonymous lru_cache — the class
# tpulint's executable-cache rule now flags); track_memory=False — tiny
# one-op reduce programs, no /memory insight worth an AOT recompile
_eager_cache = CompileCache("collectives", track_memory=False)


def _eager_allreduce_fn(mesh, axis, op):
    def build():
        spec = P(axis)

        def body(x):
            return all_reduce(x, axis, op)

        return jax.jit(shard_map(body, mesh=mesh, in_specs=(spec,),
                                 out_specs=spec))

    return _eager_cache.get_or_build((mesh, axis, op), build)


def _flat_collective_mesh(mesh):
    """1-D view of `mesh` for eager collectives (a multi-axis mesh would
    otherwise mis-shape the stacked leading dim)."""
    import numpy as _np
    from jax.sharding import Mesh

    if len(mesh.axis_names) == 1:
        return mesh, mesh.axis_names[0]
    flat = Mesh(_np.asarray(mesh.devices).reshape(-1), ("_all",))
    return flat, "_all"


def eager_all_reduce(value, axis=None, op="sum", mesh=None):
    """AllReduce a replicated-per-device stacked value eagerly.

    ``value``: array whose leading dim is the mesh-axis size (one slice per
    device) — HOST-LOCAL slices in a multi-process job. Returns the same
    (global) shape with every slice = the reduction.
    """
    mesh = mesh or default_mesh()
    if axis is None or axis not in mesh.axis_names:
        mesh, axis = _flat_collective_mesh(mesh)
    if jax.process_count() > 1 and not isinstance(value, jax.Array):
        # host-local stacked slices → global array (non-addressable shards
        # can't be fed from a host-local jnp array)
        from jax.experimental import multihost_utils

        value = multihost_utils.host_local_array_to_global_array(
            value, mesh, P(axis))
    return _eager_allreduce_fn(mesh, axis, op)(value)


def barrier(mesh=None):
    """Block until all devices reach this point (reference
    `KVStore::Barrier`, `kvstore_dist.h:105`): a tiny psum over the mesh."""
    import numpy as _np

    from .. import analysis

    if analysis._enabled:
        # a barrier parks this thread until every peer arrives: any
        # tracked lock held here can deadlock the whole fleet (the
        # assist-vs-worker class from PR 12)
        analysis.check_blocking("collective.barrier")

    mesh = mesh or default_mesh()
    mesh, axis = _flat_collective_mesh(mesh)
    local = _np.ones((jax.local_device_count() if jax.process_count() > 1
                      else mesh.shape[axis],), _np.int32)
    out = eager_all_reduce(local, axis=axis, mesh=mesh)
    jax.block_until_ready(out)
    return int(out.addressable_shards[0].data[0]) if jax.process_count() > 1 else int(out[0])
