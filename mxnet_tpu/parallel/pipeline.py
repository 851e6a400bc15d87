"""Pipeline-parallel training over the 'pp' mesh axis (GPipe schedule).

The reference has none (SURVEY.md §2.4 row "Pipeline parallelism: ❌").
This module grows the original forward-only demo (`pipeline_step`, kept
below) into a real training subsystem:

* :func:`partition_stages` cuts the Symbol graph into ``S`` contiguous
  stages balanced by parameter + activation weight (the linear-partition
  DP), and derives the cut boundaries — every intermediate value that
  crosses a cut rides the inter-stage handoff buffer.
* :class:`PipelineContext` compiles the GPipe micro-batch schedule into
  the donated-buffer fused train step (`Executor.fused_step`): the batch
  is split into ``M`` micro-batches, ``M + S - 1`` `lax.scan` ticks run
  one stage per device of the 'pp' mesh axis (`lax.switch` on
  `axis_index` selects the stage subgraph), activations hand off with
  `lax.ppermute` (one ICI hop on a TPU torus), and `jax.vjp` through the
  schedule produces the reverse pipeline flow — gradients accumulate
  across micro-batches inside the ONE jitted computation, then feed the
  same grad-sync / ZeRO-1 / optimizer tail as the unpipelined step.

Bubble accounting: the schedule idles (S-1)/(M+S-1) of its device-ticks
(`pipeline.bubble_ratio` gauge) — raise `MXNET_PIPELINE_MICROBATCHES` to
amortize (docs/faq/perf.md "Choosing micro-batch count").

Numerics: micro-batching is exact for batch-separable graphs (per-row
losses; the SoftmaxOutput default). Graphs that mix rows across the batch
fall back to the unpipelined fused step: auxiliary (running-stat) states
(BatchNorm), `normalization='batch'/'valid'` loss heads, outputs without
a leading batch dim, and non-float cut boundaries are all detected at
plan time (`PipelineFallback`). A short trailing micro-batch is padded
with recycled rows and masked exactly through the output slice's vjp.

Gate: `MXNET_PIPELINE_STAGES` (0 = off) / `MXNET_PIPELINE_MICROBATCHES`
(0 = 2x stages).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry
from ..base import getenv, register_env
from . import mesh as mesh_mod
from .collectives import shard_map

__all__ = ["pipeline_step", "partition_stages", "PipelineContext",
           "PipelineFallback", "pipeline_enabled", "StagePlan"]

register_env("MXNET_PIPELINE_STAGES", 0,
             "pipeline-parallel stage count for the fused train step "
             "(GPipe micro-batch schedule over the 'pp' mesh axis); "
             "0 = off. Graphs the schedule cannot split exactly fall "
             "back to the unpipelined fused step")
register_env("MXNET_PIPELINE_MICROBATCHES", 0,
             "micro-batches per step for the pipeline schedule; 0 = "
             "2x MXNET_PIPELINE_STAGES. Bubble fraction is "
             "(S-1)/(M+S-1) — see docs/faq/perf.md")


def pipeline_enabled():
    return int(getenv("MXNET_PIPELINE_STAGES") or 0) >= 2


class PipelineFallback(Exception):
    """The graph (or environment) cannot run the pipeline schedule; the
    caller should use the unpipelined fused step. Carries the reason —
    Module logs it once."""


# ---------------------------------------------------------------------------
# Stage partition
# ---------------------------------------------------------------------------

class _BoundaryVal:
    """One tensor crossing a stage cut: (producer node, output index) plus
    its micro-batch-scale shape/dtype and flat span in the handoff buffer."""

    __slots__ = ("nid", "oi", "shape", "dtype", "size", "offset")

    def __init__(self, nid, oi, shape, dtype, offset):
        self.nid = nid
        self.oi = int(oi)
        self.shape = tuple(int(s) for s in shape)
        self.dtype = jnp.dtype(dtype)
        self.size = int(np.prod(self.shape)) if self.shape else 1
        self.offset = int(offset)

    def sig(self):
        return (self.shape, str(self.dtype), self.offset)


class StagePlan:
    """Static pipeline layout: topo-contiguous stage node lists, per-cut
    boundary layouts, micro-batch-scale output specs, and the balance
    telemetry the partition DP produced."""

    def __init__(self, stages, stage_costs, boundaries, out_specs,
                 node_index, var_ids, max_flat):
        self.stages = tuple(tuple(s) for s in stages)
        self.stage_costs = tuple(float(c) for c in stage_costs)
        self.boundaries = tuple(tuple(b) for b in boundaries)
        self.out_specs = tuple(out_specs)  # [(shape(mb,...), dtype)]
        self.node_index = node_index       # id(node) -> global topo index
        self.var_ids = var_ids             # arg name -> id(var node)
        self.max_flat = int(max_flat)

    @property
    def num_stages(self):
        return len(self.stages)

    def sig(self):
        """Hashable layout identity (compile-cache key component)."""
        return (tuple(len(s) for s in self.stages),
                tuple(tuple(v.sig() for v in b) for b in self.boundaries),
                tuple((s, str(d)) for s, d in self.out_specs),
                self.max_flat)


def _balanced_cuts(costs, num_stages):
    """Linear-partition DP: split ``costs`` into ``num_stages`` contiguous
    non-empty segments minimizing the max segment sum. Returns segment
    start indices (first is 0)."""
    n = len(costs)
    prefix = np.concatenate([[0.0], np.cumsum(np.asarray(costs, np.float64))])

    def seg(i, j):  # cost of items [i, j)
        return prefix[j] - prefix[i]

    INF = float("inf")
    # best[k][j]: minimal max-cost of splitting first j items into k parts
    best = [[INF] * (n + 1) for _ in range(num_stages + 1)]
    cut = [[0] * (n + 1) for _ in range(num_stages + 1)]
    best[0][0] = 0.0
    for k in range(1, num_stages + 1):
        for j in range(k, n - (num_stages - k) + 1):
            for i in range(k - 1, j):
                c = max(best[k - 1][i], seg(i, j))
                if c < best[k][j]:
                    best[k][j] = c
                    cut[k][j] = i
    starts = []
    j = n
    for k in range(num_stages, 0, -1):
        i = cut[k][j]
        starts.append(i)
        j = i
    return list(reversed(starts))


# cross-micro-batch loss normalizations: backward divides by the TRACED
# batch dim, which is the micro-batch under this schedule — not separable
_BATCH_NORMALIZATIONS = ("batch", "valid")


def partition_stages(symbol, num_stages, input_specs, batch_names=()):
    """Cut ``symbol`` into ``num_stages`` balanced contiguous stages.

    ``input_specs``: {arg name: (shape, dtype)} at MICRO-batch scale —
    batch inputs already sized to one micro-batch. ``batch_names``: the
    data/label inputs (excluded from the parameter-weight cost term).

    Raises :class:`PipelineFallback` for graphs the schedule cannot run
    exactly; see the module docstring for the trigger list.
    """
    from ..symbol.symbol import _topo_order

    S = int(num_stages)
    if S < 2:
        raise PipelineFallback(f"need >= 2 stages, got {S}")
    if symbol.list_auxiliary_states():
        raise PipelineFallback(
            "graph has auxiliary (running-stat) states; per-micro-batch "
            "aux chaining is not batch-separable")
    nodes = _topo_order([n for n, _ in symbol._outputs])
    compute = [n for n in nodes if not n.is_variable]
    if len(compute) < S:
        raise PipelineFallback(
            f"{len(compute)} compute nodes cannot fill {S} stages")
    for n in compute:
        if str(n.attrs.get("normalization", "null")) in _BATCH_NORMALIZATIONS:
            raise PipelineFallback(
                f"{n.op} normalization={n.attrs['normalization']!r} "
                "divides by the traced batch dim (not micro-batch "
                "separable)")
    node_index = {id(n): i for i, n in enumerate(nodes)}
    var_ids = {}
    for n in nodes:
        if n.is_variable:
            if n.name not in input_specs:
                raise PipelineFallback(f"no bound spec for input {n.name!r}")
            var_ids[n.name] = id(n)

    # abstract eval of every compute value at micro-batch scale: shapes
    # AND dtypes, without running math (jax.eval_shape over the same walk
    # the stage branches run)
    entries = []
    for n in compute:
        for i in range(n.num_outputs()):
            entries.append((n, i))

    names = list(input_specs)

    def probe(key, *args):
        env = {}
        for nm, a in zip(names, args):
            env[(var_ids[nm], 0)] = a
        _walk_nodes(compute, env, key, True, node_index)
        return tuple(env[(id(n), i)] for n, i in entries)

    arg_specs = [jax.ShapeDtypeStruct(tuple(s), jnp.dtype(d))
                 for s, d in (input_specs[nm] for nm in names)]
    try:
        out = jax.eval_shape(probe, jax.random.PRNGKey(0), *arg_specs)
    except Exception as e:  # noqa: BLE001 — any abstract-eval failure
        raise PipelineFallback(f"graph abstract eval failed: {e!r}") from e
    val_info = {(id(n), i): (tuple(sd.shape), jnp.dtype(sd.dtype))
                for (n, i), sd in zip(entries, out)}

    # cost model: parameter elements this node owns (its variable inputs
    # that are not data/label feeds) + its output activation elements —
    # the same weight/FLOP proxy the GPipe paper balances on
    batch_set = set(batch_names)
    costs = []
    for n in compute:
        c = 0.0
        for child, _oi in n.inputs:
            if child.is_variable and child.name not in batch_set:
                shape = input_specs[child.name][0]
                c += float(np.prod(shape)) if shape else 1.0
        for i in range(n.num_outputs()):
            c += float(np.prod(val_info[(id(n), i)][0]) or 1.0)
        costs.append(c)

    starts = _balanced_cuts(costs, S)
    bounds = starts[1:] + [len(compute)]
    stages = [compute[a:b] for a, b in zip(starts, bounds)]
    stage_costs = [sum(costs[a:b]) for a, b in zip(starts, bounds)]
    stage_of = {}
    for s, stg in enumerate(stages):
        for n in stg:
            stage_of[id(n)] = s

    # need_beyond[(nid, oi)]: the deepest stage that consumes this value
    # (graph outputs must reach the last stage)
    need_beyond = {}
    for s, stg in enumerate(stages):
        for n in stg:
            for child, oi in n.inputs:
                if not child.is_variable:
                    k = (id(child), oi)
                    need_beyond[k] = max(need_beyond.get(k, -1), s)
    for n, oi in symbol._outputs:
        if not n.is_variable:
            need_beyond[(id(n), oi)] = S - 1

    boundaries = []
    max_flat = 0
    for c in range(S - 1):
        layout = []
        off = 0
        for n, oi in entries:
            if stage_of[id(n)] <= c and need_beyond.get((id(n), oi), -1) > c:
                shape, dtype = val_info[(id(n), oi)]
                if not jnp.issubdtype(dtype, jnp.floating):
                    raise PipelineFallback(
                        f"cut {c} carries non-float value "
                        f"{n.name}:{oi} ({dtype}); the f32 handoff "
                        "buffer cannot round-trip it")
                bv = _BoundaryVal(id(n), oi, shape, dtype, off)
                off += bv.size
                layout.append(bv)
        if not layout:
            raise PipelineFallback(
                f"cut {c} carries no values (disconnected stages)")
        max_flat = max(max_flat, off)
        boundaries.append(layout)

    out_specs = []
    for n, oi in symbol._outputs:
        if n.is_variable:
            shape, dtype = input_specs[n.name]
            shape, dtype = tuple(shape), jnp.dtype(dtype)
        else:
            shape, dtype = val_info[(id(n), oi)]
        out_specs.append((shape, dtype))
    return StagePlan(stages, stage_costs, boundaries, out_specs,
                     node_index, var_ids, max_flat)


def _walk_nodes(nodes, env, key, train, node_index, loss_gate=None):
    """Evaluate a topo-ordered node subset into ``env`` — the executor's
    per-node dispatch (`symbol.executor._dispatch_node`, ONE home for the
    op-dispatch convention) restricted to one stage; ``node_index`` keys
    the RNG fold by GLOBAL topo index so stage splits never change which
    key a random op sees.

    ``loss_gate``: optional ``(node_id_set, fn)`` applying ``fn`` to the
    inputs of the named nodes — the pipeline's per-row pad mask on the
    graph-output (loss) nodes, whose custom vjps may emit gradients
    regardless of the incoming cotangent."""
    from ..symbol.executor import _dispatch_node

    for node in nodes:
        if node.is_variable:
            continue
        gate = loss_gate[1] if loss_gate is not None and \
            id(node) in loss_gate[0] else None
        _dispatch_node(node, env, key, train, node_index[id(node)],
                       gate=gate)


# ---------------------------------------------------------------------------
# The traced GPipe schedule
# ---------------------------------------------------------------------------

def _resolve_mesh(num_stages):
    """The 'pp' shard group: the ambient/env mesh when it carries a pp
    axis of the right size (so `MXNET_MESH_SHAPE='dp=2,pp=2'` composes),
    else a fresh 1-D pp mesh over the first S devices."""
    for m in (mesh_mod.current_mesh(), mesh_mod.mesh_from_env()):
        if m is not None and \
                mesh_mod.axis_size(m, mesh_mod.AXIS_PP) == num_stages:
            return m
    devices = jax.devices()
    if num_stages > len(devices):
        raise PipelineFallback(
            f"{num_stages} pipeline stages but only {len(devices)} devices")
    return mesh_mod.pp_mesh(num_stages)


class PipelineContext:
    """One module's pipeline schedule: the stage plan, the pp mesh, and
    the traced GPipe forward the fused step consumes in place of the
    plain graph function. Owned by `Module` (like `Zero1Context`); a
    plan/trace failure falls back to the unpipelined fused step."""

    def __init__(self, symbol, plan, batch_size, microbatches, batch_names,
                 mesh):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.symbol = symbol
        self.plan = plan
        self.batch_size = int(batch_size)
        self.microbatches = int(microbatches)
        self.batch_names = tuple(batch_names)
        self.mesh = mesh
        self.axis = mesh_mod.AXIS_PP
        self.mb = -(-self.batch_size // self.microbatches)  # ceil
        self.pad = self.mb * self.microbatches - self.batch_size
        self.repl = NamedSharding(mesh, P())
        # named CompileCache so `compile_cache.named_stats('pipeline')`
        # pins one compile per (symbol, shapes, stages, microbatches)
        # config — but PER CONTEXT, not process-global: the cached jitted
        # step closes over the executor, so a global cache would pin every
        # module it ever served (weights, multi-device buffers, census
        # providers) alive for the process lifetime, and donated entries
        # make every /memory scrape that walks live caches re-pay their
        # AOT analysis. The monotonic named totals still aggregate across
        # contexts, so accounting assertions survive the cache's death.
        from ..compile_cache import CompileCache

        self.cache = CompileCache("pipeline", maxsize=8)
        import zlib

        self._sym_crc = zlib.crc32(symbol.tojson().encode())
        # the schedule's shard_map is manual over EVERY mesh axis but only
        # 'pp' differentiates the work: compute replicates across the
        # other axes, and the vjp transpose SUMS those identical
        # per-coordinate cotangent contributions — gradients come back
        # scaled by the product of the extra axis sizes. The fused step
        # divides this back out (exact for power-of-2 meshes). Latent on
        # pure-pp meshes (factor 1); real for the documented
        # MXNET_MESH_SHAPE='dp=2,pp=2' composition and every MXNET_SPMD
        # mesh carrying pp beside fsdp/tp.
        self.grad_correction = 1
        for ax, sz in mesh.shape.items():
            if ax != self.axis:
                self.grad_correction *= int(sz)
        s, m = plan.num_stages, self.microbatches
        self.bubble_ratio = (s - 1) / (m + s - 1)
        costs = plan.stage_costs
        self.stage_cost_imbalance = \
            max(costs) / max(sum(costs) / len(costs), 1e-12)

    def record_step(self):
        """Per-step telemetry (called by `Executor.fused_step` after a
        successful pipelined dispatch). The config gauges are re-set here
        rather than once at construction so telemetry enabled mid-run
        still reports stages/micro-batches/bubble next to the counter."""
        if not telemetry._enabled:
            return
        telemetry.counter("pipeline.steps").inc()
        telemetry.gauge("pipeline.stages").set(self.plan.num_stages)
        telemetry.gauge("pipeline.microbatches").set(self.microbatches)
        telemetry.gauge("pipeline.bubble_ratio").set(self.bubble_ratio)
        telemetry.gauge("pipeline.stage_cost_imbalance").set(
            self.stage_cost_imbalance)

    # -- construction --------------------------------------------------------

    @staticmethod
    def build(symbol, executor, data_names, label_names, mesh=None):
        """Plan the schedule for a bound executor, or raise
        :class:`PipelineFallback` with the reason. ``mesh``: an explicit
        mesh carrying the 'pp' axis (the SPMD context's one-mesh
        composition — `Module` passes `spmd.mesh` so the schedule and
        the sharding plan live on the SAME device assignment)."""
        S = int(getenv("MXNET_PIPELINE_STAGES") or 0)
        M = int(getenv("MXNET_PIPELINE_MICROBATCHES") or 0) or 2 * S
        batch_names = tuple(n for n in list(data_names) + list(label_names)
                            if n in executor.arg_dict)
        if not batch_names:
            raise PipelineFallback("no bound batch inputs")
        B = int(executor.arg_dict[batch_names[0]].shape[0])
        if M > B:
            raise PipelineFallback(
                f"{M} micro-batches but only {B} batch rows")
        if mesh is not None:
            if mesh_mod.axis_size(mesh, mesh_mod.AXIS_PP) != S:
                raise PipelineFallback(
                    f"explicit mesh {dict(mesh.shape)} does not carry a "
                    f"'pp' axis of size {S}")
        else:
            mesh = _resolve_mesh(S)
        mb = -(-B // M)
        input_specs = {}
        for n in executor._arg_names:
            a = executor.arg_dict[n]
            shape = tuple(a.shape)
            if n in batch_names:
                if not shape or shape[0] != B:
                    raise PipelineFallback(
                        f"batch input {n!r} leading dim {shape} != {B}")
                shape = (mb,) + shape[1:]
            input_specs[n] = (shape, jnp.dtype(a.dtype))
        plan = partition_stages(symbol, S, input_specs,
                                batch_names=batch_names)
        for shape, _ in plan.out_specs:
            if not shape or shape[0] != mb:
                raise PipelineFallback(
                    f"output shape {shape} has no leading batch dim; "
                    "micro-batch results cannot be concatenated")
        ctx = PipelineContext(symbol, plan, B, M, batch_names, mesh)
        ctx._bound_sig = PipelineContext._exec_sig(executor)
        return ctx

    @staticmethod
    def _exec_sig(executor):
        return tuple((n, tuple(executor.arg_dict[n].shape),
                      str(executor.arg_dict[n].dtype))
                     for n in executor._arg_names)

    def matches(self, executor):
        """Whether this context still fits the executor's bound layout and
        the current env config. The FULL arg signature is compared — a
        reshape that keeps the batch dim but changes feature shapes would
        otherwise reuse a stale plan, fail its trace, and permanently
        disable pipelining for the module."""
        S = int(getenv("MXNET_PIPELINE_STAGES") or 0)
        M = int(getenv("MXNET_PIPELINE_MICROBATCHES") or 0) or 2 * S
        if (S, M) != (self.plan.num_stages, self.microbatches):
            return False
        try:
            return PipelineContext._exec_sig(executor) == self._bound_sig
        except KeyError:
            return False

    def key(self):
        """Compile-cache key component: everything that changes the traced
        schedule's layout."""
        return ("pipeline", self.plan.num_stages, self.microbatches,
                self.batch_size, self._sym_crc,
                mesh_mod.devices_key(self.mesh), self.plan.sig())

    def put_replicated(self, x):
        """Commit one fused-step input onto the pp mesh, replicated (the
        `Zero1Context.put_replicated` contract: steady state is a no-op
        for weights/state, per-step feeds broadcast once)."""
        arr = x if isinstance(x, jax.Array) or not hasattr(x, "_data") \
            else x._data
        try:
            if getattr(arr, "sharding", None) == self.repl:
                return arr
        except Exception:  # noqa: BLE001 — fall through to device_put
            pass
        return jax.device_put(arr, self.repl)

    # -- the traced forward --------------------------------------------------

    def wrap(self, executor, spmd=None):
        """The pipelined graph function with `Executor._fn(True)`'s
        contract — ``fn(key, args, auxs) -> (outputs, aux_updates)`` — so
        `Executor.fused_step` vjps and composes grad-sync/ZeRO-1/optimizer
        around it unchanged.

        ``spmd`` (a ``parallel.spmd.SpmdContext`` in pipeline mode):
        placed parameters ENTER the shard_map at their residency specs
        (each device holds 1/S of the parameter bytes between steps)
        and are all-gathered just-in-time at the top of the traced
        schedule — ``lax.all_gather``'s transpose reduce-scatters the
        accumulated micro-batch gradients straight back to the owning
        shards. Inside the schedule every mesh axis is manual, so this
        is residency placement, not propagated compute sharding."""
        from jax.sharding import PartitionSpec as P

        plan = self.plan
        S, M, mb, B, pad = (plan.num_stages, self.microbatches, self.mb,
                            self.batch_size, self.pad)
        axis = self.axis
        arg_names = list(executor._arg_names)
        batch_pos = frozenset(i for i, n in enumerate(arg_names)
                              if n in self.batch_names)
        out_entries = list(self.symbol._outputs)
        out_specs = plan.out_specs
        out_node_ids = frozenset(id(n) for n, _ in out_entries
                                 if not n.is_variable)
        perm = [(i, (i + 1) % S) for i in range(S)]
        max_flat = plan.max_flat
        # residency-placed params (SPMD composition): arg position ->
        # PartitionSpec; gathered once per step at the top of the traced
        # schedule, NOT per tick (the scan closes over the gathered value)
        placed = {}
        if spmd is not None:
            for pos, nm in enumerate(arg_names):
                spec = spmd.pp_spec(nm)
                if spec is not None and pos not in batch_pos:
                    placed[pos] = spec

        def _gather_full(x, spec):
            for d, ax in enumerate(tuple(spec)):
                if ax is not None:
                    x = lax.all_gather(x, ax, axis=d, tiled=True)
            return x

        def sched(key, *args):
            if placed:
                args = list(args)
                for pos, spec in placed.items():
                    args[pos] = _gather_full(args[pos], spec)
                args = tuple(args)
            idx = lax.axis_index(axis)

            def make_branch(si):
                stage_nodes = plan.stages[si]
                lin = plan.boundaries[si - 1] if si > 0 else ()
                lout = plan.boundaries[si] if si < S - 1 else ()

                def branch(operand):
                    state, t = operand
                    # stage si processes micro-batch t - si at tick t
                    mb_idx = jnp.clip(t - si, 0, M - 1)
                    # bubble-tick gate: every FLOAT input of the stage is
                    # scaled by 1.0 (active — bitwise identity) or 0.0
                    # (bubble). Masking only the OUTPUTS is not enough:
                    # loss-layer custom vjps (SoftmaxOutput) emit their
                    # gradient regardless of the incoming cotangent, so a
                    # warm-up tick would inject (p - onehot) into this
                    # stage's parameters; gating the inputs scales every
                    # such injection to exactly zero through the chain
                    # rule while leaving active ticks bit-identical.
                    act = ((t - si >= 0) & (t - si < M))

                    def gate(x):
                        if not jnp.issubdtype(x.dtype, jnp.floating):
                            return x  # no grad path through int inputs
                        return x * act.astype(x.dtype)

                    env = {}
                    for pos, nm in enumerate(arg_names):
                        a = args[pos]
                        env[(plan.var_ids[nm], 0)] = \
                            gate(a[mb_idx] if pos in batch_pos else a)
                    for bv in lin:
                        env[(bv.nid, bv.oi)] = gate(state[
                            bv.offset:bv.offset + bv.size].reshape(
                            bv.shape).astype(bv.dtype))
                    loss_gate = None
                    if pad:
                        # last micro-batch carries recycled pad rows whose
                        # outputs the [:B] slice discards — but a loss
                        # node's custom vjp ignores its cotangent, so the
                        # pad rows must be row-masked at the loss INPUTS
                        # (everything upstream then scales to zero; real
                        # rows multiply by exactly 1.0)
                        rowmask = (mb_idx * mb + jnp.arange(mb)) < B

                        def row_gate(x):
                            if not (hasattr(x, "ndim") and x.ndim >= 1
                                    and x.shape[0] == mb
                                    and jnp.issubdtype(x.dtype,
                                                       jnp.floating)):
                                return x
                            return x * rowmask.astype(x.dtype).reshape(
                                (mb,) + (1,) * (x.ndim - 1))

                        loss_gate = (out_node_ids, row_gate)
                    skey = jax.random.fold_in(key, mb_idx)
                    _walk_nodes(stage_nodes, env, skey, True,
                                plan.node_index, loss_gate=loss_gate)
                    if si == S - 1:
                        outs_t = tuple(env[(id(n), oi)]
                                       for n, oi in out_entries)
                        flat = jnp.zeros((max_flat,), jnp.float32)
                    else:
                        parts = [env[(bv.nid, bv.oi)].reshape(-1).astype(
                            jnp.float32) for bv in lout]
                        flat = parts[0] if len(parts) == 1 \
                            else jnp.concatenate(parts)
                        if flat.shape[0] < max_flat:
                            flat = jnp.pad(flat,
                                           (0, max_flat - flat.shape[0]))
                        outs_t = tuple(jnp.zeros(shape, dtype)
                                       for shape, dtype in out_specs)
                    return flat, outs_t

                return branch

            branches = [make_branch(i) for i in range(S)]
            # no varying-axis casts: this schedule's shard_map runs with
            # check_vma=False, where a cast's transpose (a psum over an
            # axis the untracked cotangent does not vary on) is refused
            state0 = jnp.zeros((max_flat,), jnp.float32)
            outs0 = tuple(jnp.zeros((M,) + shape, dtype)
                          for shape, dtype in out_specs)

            def tick(carry, t):
                state, outs = carry
                flat, outs_t = lax.switch(idx, branches, (state, t))
                # the last stage emits micro-batch t-(S-1)'s results
                out_t = t - (S - 1)
                valid = (idx == S - 1) & (out_t >= 0)
                new_outs = []
                for o, ot in zip(outs, outs_t):
                    upd = o.at[jnp.maximum(out_t, 0)].set(ot)
                    new_outs.append(jnp.where(valid, upd, o))
                # hand the activation buffer to the next stage — the
                # transpose of this ppermute IS the backward pipeline flow
                state = lax.ppermute(flat, axis, perm)
                return (state, tuple(new_outs)), None

            # lax.scan (reverse-differentiable): vjp through the tick loop
            # replays the schedule backward, accumulating per-stage grads
            (_, outs), _ = lax.scan(tick, (state0, outs0),
                                    jnp.arange(M + S - 1))
            # results live on the last stage only; the masked psum
            # broadcasts them over 'pp' (its transpose routes output
            # cotangents back to the emitting stage)
            return tuple(lax.psum(jnp.where(idx == S - 1, o, 0 * o), axis)
                         for o in outs)

        in_specs = (P(),) + tuple(placed.get(i, P())
                                  for i in range(len(arg_names)))
        fn = shard_map(sched, mesh=self.mesh,
                       in_specs=in_specs,
                       out_specs=tuple(P() for _ in out_entries),
                       check_vma=False)

        def pipelined(key, args, auxs):
            del auxs  # aux-state graphs fall back at plan time
            feed = list(args)
            for pos in batch_pos:
                a = feed[pos]
                if pad:
                    # recycle leading rows (real data, so inactive-tick
                    # compute stays finite); the [:B] slice below masks
                    # their cotangents to exactly zero through the vjp
                    a = jnp.concatenate([a, a[:pad]], axis=0)
                feed[pos] = a.reshape((M, mb) + tuple(a.shape[1:]))
            outs = fn(key, *feed)
            outs = tuple(o.reshape((M * mb,) + tuple(o.shape[2:]))[:B]
                         for o in outs)
            return outs, ()

        return pipelined


# ---------------------------------------------------------------------------
# Forward-only demo schedule (the original stub API; test_parallel.py)
# ---------------------------------------------------------------------------

def pipeline_step(stage_fn, params_stack, x_microbatches, axis_name, axis_size):
    """Run a GPipe forward inside `shard_map`.

    stage_fn(stage_params, h) -> h, applied by every device to the
    microbatch currently resident on it; `params_stack` is this device's
    stage parameters; `x_microbatches` [M, ...] local input microbatches
    (only stage 0's are consumed). Returns [M, ...] outputs valid on the
    LAST stage. M must be >= axis_size for full utilisation.
    """
    idx = lax.axis_index(axis_name)
    m = x_microbatches.shape[0]
    n_ticks = m + axis_size - 1
    perm = [(i, (i + 1) % axis_size) for i in range(axis_size)]

    h_shape = x_microbatches.shape[1:]
    # initial carry must carry the full varying-axes set up front (it picks
    # up pp-varying params and x's data-axes on the first tick; fori_loop
    # needs a fixed carry type): inherit x's axes via a zero of x, then add pp
    zero = x_microbatches[0] * 0
    state = lax.pcast(zero, (axis_name,), to="varying")
    outputs = lax.pcast(jnp.broadcast_to(zero, (m,) + h_shape), (axis_name,),
                        to="varying")

    def tick(carry, t):
        state, outputs = carry
        # stage 0 ingests microbatch t (when available)
        feed = jnp.where(t < m, 1, 0)
        mb = x_microbatches[jnp.minimum(t, m - 1)]
        state = jnp.where((idx == 0) & (feed == 1), mb, state)
        state = stage_fn(params_stack, state)
        # last stage emits result for microbatch t - (axis_size - 1)
        out_t = t - (axis_size - 1)
        valid = (idx == axis_size - 1) & (out_t >= 0)
        updated = outputs.at[jnp.maximum(out_t, 0)].set(state)
        outputs = jnp.where(valid, updated, outputs)
        # hand off to next stage
        state = lax.ppermute(state, axis_name, perm)
        return (state, outputs), None

    # lax.scan (not fori_loop): the tick loop must be REVERSE-differentiable
    # so pipeline training steps can backprop through the schedule
    (_, outputs), _ = lax.scan(tick, (state, outputs), jnp.arange(n_ticks))
    # results live on the last stage only; broadcast to every stage so the
    # output is replicated over 'pp' (a masked psum = one-to-all over ICI)
    outputs = lax.psum(jnp.where(idx == axis_size - 1, outputs, 0 * outputs),
                       axis_name)
    return outputs
