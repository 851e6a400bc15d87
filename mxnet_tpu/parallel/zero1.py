"""ZeRO-1 cross-replica weight-update sharding (arXiv:2004.13336).

Data parallelism as shipped so far is fully redundant past the gradient
sum: every replica allreduces FULL gradients (PR 4's flat buckets) and
then runs the FULL optimizer update on a FULL copy of the optimizer state
(PR 3's fused step). "Automatic Cross-Replica Sharding of Weight Update in
Data-Parallel Training" observes that the update is element-wise, so it
can be sharded across the replicas for free:

    allreduce(grad); update(all params)          # replicated (before)
    reduce-scatter(grad) -> update(1/N shard of params + state)
        -> allgather(updated shard)              # ZeRO-1 (this module)

cutting optimizer memory and update FLOPs by the replica count N while
moving the same bytes (ring allreduce = 2(N-1)/N·B; reduce-scatter +
allgather = (N-1)/N·B each). This module is the sharding substrate:

* **Flat buckets** — the update operates on PR 4's per-dtype flat buckets
  (`grad_sync.bucket_assign`, same `MXNET_KVSTORE_BUCKET_MB` cap), each
  padded to a multiple of N (uneven-shard padding; padded elements carry
  zero grad/lr/wd so they stay zero through any supported optimizer).

* **GSPMD, not hand-rolled collectives** — exactly the paper's mechanism:
  the traced step annotates the packed gradient and parameter buckets with
  a `dp`-sharded layout (`collectives.sharding_constraint`) and the
  updated weights with a replicated one; XLA lowers the cross-replica sum
  + sharded constraint to ReduceScatter and the replicated constraint to
  AllGather, and the whole thing stays ONE donated-buffer XLA computation
  per bucket-layout key (`Executor.fused_step` / `Updater._fused_call`).

* **Sharded allocation** — optimizer state is *created* as `dp`-sharded
  flat arrays (`jit(..., out_shardings=shard)`), so each replica ever
  materializes only its 1/N slice; `nbytes_per_replica()` measures it.

* **Transparent checkpoints** — `export_to_updater` gathers the shards
  back into the per-parameter state trees the eager `Updater` owns (so
  `save_optimizer_states` / PR 1's CRC'd checkpoint path see ordinary
  states), and `ensure()` re-shards from those trees on resume.

Gate: `MXNET_ZERO1=1` (default off). The eager per-key update loop and the
replicated fused step remain the correctness references: sharding the
update is exact up to LLVM FMA-contraction differences between program
structures/partition counts (~1 ulp per step; bitwise for the layouts
`tests/python/unittest/test_zero1.py` pins — see docs/faq/perf.md).
"""
from __future__ import annotations

import logging

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from .. import tracing
from ..base import getenv, register_env
from . import mesh as mesh_mod
from .collectives import sharding_constraint
from .grad_sync import bucket_assign, bucket_cap_bytes
from .partition import flat_shard, nbytes_on_device, pad_to_shards, replicated

__all__ = ["Zero1Context", "zero1_enabled"]

register_env("MXNET_ZERO1", False,
             "shard the weight update across the dp mesh axis (ZeRO-1: "
             "reduce-scatter -> 1/N-shard optimizer step -> allgather); "
             "only the fused step paths shard — the eager per-key loop "
             "stays the replicated correctness reference")
register_env("MXNET_ZERO1_NDEV", 0,
             "device count of the ZeRO-1 update shard group (0 = the "
             "ambient mesh from use_mesh/MXNET_MESH_SHAPE, else every "
             "device)")


def zero1_enabled():
    return bool(getenv("MXNET_ZERO1"))


def _resolve_mesh(mesh):
    """The update shard group: an explicit mesh, else the ambient one,
    else a 1-D dp mesh over MXNET_ZERO1_NDEV (or all) devices."""
    if mesh is None:
        mesh = mesh_mod.current_mesh()
    if mesh is None:
        ndev = int(getenv("MXNET_ZERO1_NDEV") or 0)
        # default_mesh consults MXNET_MESH_SHAPE before falling back to a
        # 1-D dp mesh over every device
        mesh = mesh_mod.dp_mesh(ndev) if ndev else mesh_mod.default_mesh()
    axis = mesh_mod.AXIS_DP if mesh_mod.has_axis(mesh, mesh_mod.AXIS_DP) \
        else mesh.axis_names[0]
    return mesh, axis


class _BucketPlan:
    """Static layout of one flat update bucket: which entries it holds,
    their shapes/sizes in pack order, and the pad that makes the flat
    length divisible by the shard count."""

    __slots__ = ("keys", "dtype", "shapes", "sizes", "pad", "nelem")

    def __init__(self, keys, dtype, shapes, sizes, pad):
        self.keys = tuple(keys)
        self.dtype = jnp.dtype(dtype)
        self.shapes = tuple(tuple(s) for s in shapes)
        self.sizes = tuple(int(s) for s in sizes)
        self.pad = int(pad)
        self.nelem = sum(self.sizes) + self.pad

    def sig(self):
        return (self.keys, str(self.dtype), self.shapes, self.pad)


def _plan_buckets(entries, nshards, cap_bytes):
    """Flat per-dtype buckets over ``entries`` = [(shape, dtype), ...] —
    the PR 4 gradient-sync layout (same assignment walk, same cap), each
    padded up to a multiple of ``nshards``."""
    raw = bucket_assign([(tuple(s), d, -i)
                         for i, (s, d) in enumerate(entries)], cap_bytes)
    plans = []
    for b in raw:
        shapes = [tuple(entries[k][0]) for k in b.keys]
        sizes = [int(np.prod(s)) if s else 1 for s in shapes]
        pad = pad_to_shards(sum(sizes), nshards)
        plans.append(_BucketPlan(b.keys, b.dtype, shapes, sizes, pad))
    return tuple(plans)


def _pack_flat(arrs, plan):
    """Flatten+concat+pad one bucket (traceable; mirrors grad_sync's pack
    with the shard pad appended)."""
    parts = [a.reshape(-1).astype(plan.dtype) for a in arrs]
    flat = parts[0] if len(parts) == 1 else jnp.concatenate(parts)
    if plan.pad:
        flat = jnp.pad(flat, (0, plan.pad))
    return flat


_zero1_cache = None


def _cache():
    """Named CompileCache for the state-init/pack programs (the per-step
    update itself is cached by its caller — executor / updater cache)."""
    global _zero1_cache
    if _zero1_cache is None:
        from ..compile_cache import CompileCache

        _zero1_cache = CompileCache("zero1", maxsize=64)
    return _zero1_cache


class Zero1Context:
    """Sharded weight-update state + traced update for one parameter set.

    Owned by the caller that runs the fused update (`Module` for the
    symbolic fused step, `Updater` for the gluon/aggregated path) and
    registered on the `Updater` (``updater._zero1``) so checkpoint
    save/load stays transparent: `Updater.get_states` exports the shards
    back into per-parameter states before pickling, `Updater.set_states`
    invalidates this context so the next step re-shards the loaded states.
    """

    def __init__(self, mesh=None, bucket_mb=None):
        self.mesh, self.axis = _resolve_mesh(mesh)
        self.nshards = mesh_mod.axis_size(self.mesh, self.axis)
        self.repl = replicated(self.mesh)
        self.shard = flat_shard(self.mesh, self.axis)
        self._cap = bucket_cap_bytes(bucket_mb)
        self.plans = None
        self.flat_states = None   # list (per bucket) of state trees
        self.dirty = False        # sharded state not yet exported
        self._sig = None
        self._indices = ()
        if telemetry._enabled:
            telemetry.gauge("zero1.shards").set(self.nshards)
        # memory census: the sharded flat state IS the optimizer-state
        # residency claim (1/N per device) — a live view, because the
        # donated buffers are replaced every step
        from .. import memory
        from jax import tree_util as _jtu

        memory.register_provider(
            "optimizer_state", self,
            lambda s: [leaf for st in (s.flat_states or ())
                       for leaf in _jtu.tree_leaves(st)
                       if hasattr(leaf, "nbytes")])

    # -- identity ------------------------------------------------------------

    def key(self):
        """Compile-cache key component: everything that changes the traced
        update's layout (mesh devices/axis, bucket plan, cap)."""
        return ("zero1", self.axis, self.nshards, self._cap,
                mesh_mod.devices_key(self.mesh),
                tuple(p.sig() for p in self.plans) if self.plans else None)

    def invalidate(self):
        """Drop the sharded state so the next `ensure` re-imports from the
        updater's per-parameter states (called after `set_states`)."""
        self.flat_states = None
        self._sig = None
        self.dirty = False

    # -- state lifecycle -----------------------------------------------------

    def ensure(self, optimizer, updater, indices, weights):
        """(Re)build the bucket plan and make the sharded state exist for
        this parameter set: imported from ``updater.states`` when any
        index already has one (resume / mode transition; missing ones are
        created replicated first), else allocated sharded from scratch —
        full-size state arrays are never created on the fresh path."""
        entries = [(tuple(w.shape), jnp.dtype(w.dtype)) for w in weights]
        sig = (tuple((s, str(d)) for s, d in entries),
               optimizer._fused_static_key(), tuple(indices))
        if self._sig == sig and self.flat_states is not None:
            return
        with tracing.span("zero1.ensure", cat="train", shards=self.nshards,
                          params=len(indices)):
            self._ensure(optimizer, updater, indices, weights, entries, sig)

    def _ensure(self, optimizer, updater, indices, weights, entries, sig):
        if self.dirty and self.flat_states is not None and \
                updater is not None:
            # the parameter set changed mid-run (sig mismatch with live
            # dirty shards: a param added/dropped/reordered) — the shards
            # are the ONLY copy, so gather them per-parameter FIRST;
            # surviving indices re-import below instead of being
            # zero-reinitialized
            self.export_to_updater(updater)
        self.plans = _plan_buckets(entries, self.nshards, self._cap)
        self._sig = sig
        self._indices = tuple(indices)
        have_any = updater is not None and len(indices) > 0 and \
            any(idx in updater.states for idx in indices)
        if have_any:
            # partial coverage (a parameter added since the checkpoint, a
            # grad_req flipped to 'write'): create only the MISSING
            # per-parameter states — replicated `ensure_states` semantics —
            # then re-shard the full set; loaded state is never discarded
            for idx, w in zip(indices, weights):
                if idx not in updater.states:
                    updater.states[idx] = \
                        optimizer.create_state_multi_precision(idx, w)
                    updater.states_synced[idx] = True
            self.flat_states = self._import_states(updater, indices)
        else:
            self.flat_states = self._init_states(optimizer, weights)
        self.dirty = False
        if telemetry._enabled:
            telemetry.gauge("zero1.buckets").set(len(self.plans))
            telemetry.gauge("zero1.state_bytes_per_replica").set(
                self.state_nbytes_per_replica())

    def _init_states(self, optimizer, weights):
        """Allocate the optimizer state SHARDED: one jitted init program
        per bucket with `out_shardings=shard`, so each replica only ever
        materializes its 1/N slice (the ZeRO-1 memory claim)."""
        out = []
        for plan in self.plans:
            w_flat = self._pack_eager([weights[k] for k in plan.keys], plan)

            def build(plan=plan):
                dt = plan.dtype

                def init(wf):
                    return optimizer.fused_state_init(wf.astype(jnp.float32),
                                                      dt)

                return jax.jit(init, out_shardings=self.shard)

            fn = _cache().get_or_build(
                ("init", optimizer._fused_static_key(), str(plan.dtype),
                 plan.nelem, self.key()[:5]), build)
            out.append(fn(w_flat))
        return out

    def _pack_eager(self, nds, plan):
        """Jitted pack of NDArray buffers into one replicated flat bucket
        (state init / import only — the per-step pack is traced inline)."""
        def build(plan=plan):
            def pack(*arrs):
                return _pack_flat(arrs, plan)

            return jax.jit(pack, out_shardings=self.repl)

        fn = _cache().get_or_build(
            ("pack", plan.sig(), self.key()[:5]), build)
        return fn(*[nd._data for nd in nds])

    def _import_states(self, updater, indices):
        """Re-shard per-parameter state trees (a loaded checkpoint, or a
        preceding eager run) into flat sharded buckets."""
        from jax import tree_util as jtu

        out = []
        for plan in self.plans:
            per_param = [updater.states[indices[k]] for k in plan.keys]
            leaves0, treedef = jtu.tree_flatten(per_param[0])
            flat_leaves = []
            for li in range(len(leaves0)):
                leaf_nds = []
                for st in per_param:
                    leaves, td = jtu.tree_flatten(st)
                    if td != treedef:
                        raise ValueError(
                            "ZeRO-1 import: optimizer state structure "
                            "differs within one bucket")
                    leaf_nds.append(leaves[li])
                flat = self._pack_eager(leaf_nds, _BucketPlan(
                    plan.keys, leaf_nds[0].dtype,
                    [l.shape for l in leaf_nds],
                    [int(np.prod(l.shape)) if l.shape else 1
                     for l in leaf_nds], plan.pad))
                flat_leaves.append(jax.device_put(flat, self.shard))
            out.append(jtu.tree_unflatten(treedef, flat_leaves))
        return out

    def export_to_updater(self, updater):
        """Gather the sharded state back into per-parameter trees in
        ``updater.states`` (the structures `create_state_multi_precision`
        would have made), then invalidate: checkpoint saves and eager-path
        transitions both see ordinary replicated states, and the next
        sharded step re-imports. The gather is one slice per (leaf,
        parameter) — checkpoint-frequency work, not step work."""
        from jax import tree_util as jtu
        from ..ndarray import NDArray

        if self.flat_states is None:
            return
        for plan, st in zip(self.plans, self.flat_states):
            leaves, treedef = jtu.tree_flatten(st)
            gathered = [np.asarray(l) for l in leaves]
            off = 0
            for k, shape, size in zip(plan.keys, plan.shapes, plan.sizes):
                param_leaves = [
                    NDArray(jnp.asarray(g[off:off + size].reshape(shape)))
                    for g in gathered]
                idx = self._indices[k]
                updater.states[idx] = jtu.tree_unflatten(treedef,
                                                         param_leaves)
                updater.states_synced[idx] = True
                off += size
        self.invalidate()

    # -- accounting ----------------------------------------------------------

    def state_nbytes_per_replica(self):
        """Optimizer-state bytes resident on ONE replica — ≈ 1/N of the
        replicated footprint (+ pad slack), measured from the actual
        shard buffers."""
        from jax import tree_util as jtu

        if self.flat_states is None:
            return 0
        total = 0
        for st in self.flat_states:
            for leaf in jtu.tree_leaves(st):
                total += nbytes_on_device(leaf)
        return total

    def state_nbytes_total(self):
        from jax import tree_util as jtu

        if self.flat_states is None:
            return 0
        return sum(int(l.size) * l.dtype.itemsize
                   for st in self.flat_states for l in jtu.tree_leaves(st))

    # -- step ----------------------------------------------------------------

    def put_replicated(self, x):
        """Commit one input onto the mesh, replicated. Steady state is a
        no-op for weights/aux (they come back replicated from the previous
        step); per-step feeds broadcast once here."""
        arr = x if isinstance(x, jax.Array) or not hasattr(x, "_data") \
            else x._data
        try:
            if getattr(arr, "sharding", None) == self.repl:
                return arr
        except Exception:  # noqa: BLE001 — fall through to device_put
            pass
        return jax.device_put(arr, self.repl)

    def _seg_vec(self, vec, plan):
        """Per-element hyperparameter vector for one bucket: gather the
        per-parameter values (traced) and repeat them over each
        parameter's span — pad elements get 0, so padding is inert."""
        sel = vec[jnp.asarray(np.asarray(plan.keys, np.int32))]
        if plan.pad:
            sel = jnp.concatenate([sel, jnp.zeros((1,), sel.dtype)])
            reps = np.asarray(list(plan.sizes) + [plan.pad])
        else:
            reps = np.asarray(plan.sizes)
        return jnp.repeat(sel, reps, total_repeat_length=plan.nelem)

    def traced_update(self, optimizer, params, grads, flat_states,
                      lrs, wds, rescale, unpack_shardings=None):
        """The sharded weight update, traceable inside the fused step:
        per bucket, pack → constrain grads+weights to the dp-sharded
        layout (with an upstream cross-replica sum this lowers to
        ReduceScatter), run ``Optimizer.fused_update`` on the 1/N shard
        (the bucket is ONE 'parameter' with vector lr/wd — bit-identical
        element math to the replicated path), constrain updated weights
        back to replicated (AllGather), unpack. Returns
        ``(new_params_list, new_flat_states)``.

        ``unpack_shardings`` (aligned with ``params``, from the SPMD
        context when `MXNET_SPMD` composes with ZeRO-1): each unpacked
        parameter is constrained to ITS planned layout instead of
        replicated — the allgather only rebuilds what the tp/fsdp plan
        keeps on each device, and sharded weights persist at 1/N."""
        from jax import tree_util as jtu

        new_params = list(params)
        new_states = []

        def pack(arrs, plan):
            flat = _pack_flat(arrs, plan)
            # replicate-first on EVERY lane, for two audited reasons
            # (tools/hlolint dumps of the compiled programs):
            # * SPMD composition: the bucket concatenates MIXED-sharded
            #   operands (tp/fsdp params next to replicated biases).
            #   jax 0.4.x's SPMD partitioner miscompiled a concat of
            #   mixed-sharded operands partitioned straight to the flat
            #   dp layout — values interleaved by shard stride. The
            #   installed jax 0.9.0 gets it right (canary-pinned in
            #   test_hlolint.py), so this reason is history; the next
            #   one alone keeps replicate-first.
            # * plain lane: partitioning the concat of REPLICATED
            #   operands straight to the dp layout lowers as
            #   dynamic-update-slice + a FULL-BUCKET all-reduce per pack
            #   (hlolint found two full-bucket all-reduces per step) —
            #   replicate-first makes the shard constraint a local slice,
            #   no collective at all. The element math is unchanged (a
            #   layout pin on the same values).
            flat = sharding_constraint(flat, self.repl)
            return sharding_constraint(flat, self.shard)

        for bi, plan in enumerate(self.plans):
            w_flat = pack([params[k] for k in plan.keys], plan)
            g_flat = pack([grads[k] for k in plan.keys], plan)
            lr_vec = self._seg_vec(lrs, plan)
            wd_vec = self._seg_vec(wds, plan)
            new_w, new_s = optimizer.fused_update(
                [w_flat], [g_flat], [flat_states[bi]],
                [lr_vec], [wd_vec], rescale)
            # replicate-first on BOTH lanes: the unpack slices the flat
            # bucket into per-param pieces, and slicing the dp-sharded
            # flat straight into mixed target layouts trips the same
            # partitioner hazard as the pack-side concat
            full = sharding_constraint(new_w[0], self.repl)
            off = 0
            for k, shape, size in zip(plan.keys, plan.shapes, plan.sizes):
                new_p = full[off:off + size].reshape(shape).astype(
                    params[k].dtype)
                if unpack_shardings is not None:
                    new_p = sharding_constraint(new_p, unpack_shardings[k])
                new_params[k] = new_p
                off += size
            new_states.append(jtu.tree_map(
                lambda a: sharding_constraint(a, self.shard), new_s[0]))
        return new_params, new_states
