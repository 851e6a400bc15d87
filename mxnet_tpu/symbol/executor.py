"""Symbolic executor — bind a Symbol into a compiled XLA program.

Parity: `include/mxnet/executor.h` / `src/executor/graph_executor.cc`
(`GraphExecutor::Init`:309, `RunOps`:1302, `Forward`:65, `Backward`:78,
`SimpleBind`:1704) and the python wrapper `python/mxnet/executor.py`.

TPU-native redesign: the reference walks the bound graph node-by-node,
pushing each kernel onto the dependency engine (with bulked segments as an
optimization). Here the WHOLE graph is one pure jax function — built once
from the Symbol DAG over the shared op registry — and `jax.jit` compiles it
per (train-flag, shape signature); XLA owns memory planning (`MXPlanMemory`'s
role) and scheduling. Backward is `jax.vjp` over the same function (the
`MXGradient` pass's role), with the pullback captured during `forward(
is_train=True)` so backward never re-runs the forward.
"""
from __future__ import annotations

import numpy as _np

import jax
import jax.numpy as jnp

from .. import tracing
from ..base import MXNetError
from ..compile_cache import CompileCache
from ..ops import registry as _reg

__all__ = ["Executor"]


def _dispatch_node(node, env, key, train, nidx, gate=None):
    """Evaluate ONE non-variable node into ``env``: registry lookup,
    reserved-attr filtering, ``__opt_in__`` keyword binding, per-node RNG
    fold (``nidx`` — the node's GLOBAL topo index, so any walk over a node
    subset sees the same keys as the whole-graph walk), multi-output
    unpack. The single home of the op-dispatch convention — shared by the
    whole-graph walk below and `parallel.pipeline`'s per-stage walk.
    ``gate``: optional transform applied to every tensor input (the
    pipeline's pad-row mask on loss nodes)."""
    op = _reg.get_op(node.op)
    attrs = {k: v for k, v in node.attrs.items()
             if not k.startswith("__")}
    if op.needs_mode:
        attrs["_train"] = train
    f = _reg.bound_fn(node.op, **attrs)
    ins = [env[(id(c), oi)] for c, oi in node.inputs]
    if gate is not None:
        ins = [gate(x) for x in ins]
    # optional tensor inputs recorded by _apply_op bind by keyword
    opt_in = node.attrs.get("__opt_in__") or ""
    kw_ins = {}
    if opt_in:
        names = opt_in.split(",")
        n_pos = len(ins) - len(names)
        kw_ins = dict(zip(names, ins[n_pos:]))
        ins = ins[:n_pos]
    # the node's device-side scope: every instruction it lowers to carries
    # `<op>:<name>` in its op_name (jax wraps the backward's as
    # `transpose(jvp(<op>:<name>))`). Entered while the graph is traced into
    # a program, never per step
    with jax.named_scope(f"{node.op}:{node.name}"):
        if op.needs_rng:
            out = f(jax.random.fold_in(key, nidx), *ins, **kw_ins)
        else:
            out = f(*ins, **kw_ins)
    outs = out if isinstance(out, (tuple, list)) else (out,)
    for i, o in enumerate(outs):
        env[(id(node), i)] = o


def _graph_fn(sym, arg_names, aux_names, train):
    """Build the pure function of a Symbol graph:
    fn(key, args_tuple, auxs_tuple) -> (outputs_tuple, aux_updates_tuple)."""
    from .symbol import _topo_order

    nodes = _topo_order([n for n, _ in sym._outputs])
    arg_pos = {n: i for i, n in enumerate(arg_names)}
    aux_pos = {n: i for i, n in enumerate(aux_names)}

    # aux write-back map: aux var node id -> (producer node, output index)
    aux_writer = {}
    for node in nodes:
        if node.is_variable:
            continue
        maux = node.aux_input_indices()
        if not maux:
            continue
        n_user = node.num_outputs() - len(maux)
        for j, in_idx in enumerate(maux):
            if in_idx < len(node.inputs):
                child, _ = node.inputs[in_idx]
                if child.is_variable:
                    aux_writer[id(child)] = (node, n_user + j)

    def fn(key, args, auxs):
        env = {}
        for node in nodes:
            if not node.is_variable:
                continue
            if node.name in arg_pos:
                env[(id(node), 0)] = args[arg_pos[node.name]]
            elif node.name in aux_pos:
                env[(id(node), 0)] = auxs[aux_pos[node.name]]
            else:  # unbound variable — an error caught at bind time
                raise MXNetError(f"variable {node.name} is not bound")
        for nidx, node in enumerate(nodes):
            if node.is_variable:
                continue
            _dispatch_node(node, env, key, train, nidx)
        outputs = tuple(env[(id(n), oi)] for n, oi in sym._outputs)
        aux_new = []
        for node in nodes:
            if node.is_variable and node.name in aux_pos:
                w = aux_writer.get(id(node))
                if w is not None and (id(w[0]), w[1]) in env:
                    aux_new.append(env[(id(w[0]), w[1])])
                else:
                    aux_new.append(env[(id(node), 0)])
        return outputs, tuple(aux_new)

    return fn


class Executor:
    """A bound, compiled Symbol (reference `Executor::Forward/Backward`)."""

    def __init__(self, symbol, ctx=None, args=None, args_grad=None,
                 grad_req="write", aux_states=None):
        from ..ndarray import NDArray, zeros

        self._symbol = symbol
        self._ctx = ctx
        self._arg_names = symbol.list_arguments()
        self._aux_names = symbol.list_auxiliary_states()

        self.arg_dict = self._normalize(args, self._arg_names, "args")
        self.aux_dict = self._normalize(aux_states, self._aux_names, "aux_states",
                                        allow_missing=True)

        # grad_req per argument
        if isinstance(grad_req, str):
            self._grad_req = {n: grad_req for n in self._arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self._grad_req = dict(zip(self._arg_names, grad_req))
        else:
            self._grad_req = {n: grad_req.get(n, "null") for n in self._arg_names}

        if args_grad is None:
            self.grad_dict = {}
        else:
            self.grad_dict = self._normalize(args_grad, self._arg_names,
                                             "args_grad", allow_missing=True)
        for n in self._arg_names:
            if self._grad_req.get(n, "null") != "null" and n not in self.grad_dict:
                a = self.arg_dict[n]
                self.grad_dict[n] = zeros(a.shape, dtype=a.dtype)

        self.outputs = []
        self._vjp = None
        self._monitor_callback = None

        self._fns = {}
        # every compiled executable this executor holds, keyed by full shape
        # signature — shape churn (bucketing, unpadded partial batches) shows
        # up as compile.cache_misses instead of silently re-specializing.
        # Bounded: churn that escapes padding caps memory too (oldest out)
        self._cache = CompileCache("executor", maxsize=64)

        # memory census (live views — _data is reassigned every step):
        # weights are the args something backprops into, gradients their
        # bound cotangent buffers. Buffer-level dedup in the census makes
        # double-registration (several executors binding shared weights)
        # count once.
        from .. import memory

        memory.register_provider(
            "weights", self,
            lambda s: [a for n, a in s.arg_dict.items()
                       if s._grad_req.get(n, "null") != "null"])
        memory.register_provider("gradients", self,
                                 lambda s: list(s.grad_dict.values()))

    # -- helpers -------------------------------------------------------------

    def _normalize(self, values, names, what, allow_missing=False):
        from ..ndarray import NDArray, array as nd_array

        out = {}
        if values is None:
            values = {}
        if isinstance(values, (list, tuple)):
            if len(values) != len(names):
                raise MXNetError(f"{what}: expected {len(names)} entries "
                                 f"({names}), got {len(values)}")
            values = dict(zip(names, values))
        for n in names:
            v = values.get(n)
            if v is None:
                if allow_missing:
                    continue
                raise MXNetError(f"{what}: missing value for {n}")
            out[n] = v if isinstance(v, NDArray) else nd_array(v)
        return out

    def _fn(self, train):
        fn = self._fns.get(train)
        if fn is None:
            fn = _graph_fn(self._symbol, self._arg_names, self._aux_names, train)
            self._fns[train] = fn
        return fn

    def _sig(self, args, auxs):
        """Shape/dtype signature of one bound call — the compile-cache key
        (the CachedOp signature-match model, `cached_op.cc:295`). Built
        every call, so it uses hashable dtype objects, not strings."""
        return (tuple((a.shape, a.dtype) for a in args),
                tuple((a.shape, a.dtype) for a in auxs))

    def _jit_fwd(self, train, sig):
        return self._cache.get_or_build(
            ("fwd", train, sig), lambda: jax.jit(self._fn(train)))

    def _jit_fwd_vjp(self, train, sig):
        def build():
            base = self._fn(train)
            diff = tuple(i for i, n in enumerate(self._arg_names)
                         if self._grad_req.get(n, "null") != "null")

            def fwd(key, args, auxs):
                args = list(args)

                def f(*darrs):
                    full = list(args)
                    for i, a in zip(diff, darrs):
                        full[i] = a
                    outputs, aux_new = base(key, tuple(full), auxs)
                    return outputs, aux_new

                outputs, vjp, aux_new = jax.vjp(
                    f, *[args[i] for i in diff], has_aux=True)
                return outputs, aux_new, vjp

            return jax.jit(fwd)

        return self._cache.get_or_build(("fwd_vjp", train, sig), build)

    # -- API -----------------------------------------------------------------

    @property
    def arg_arrays(self):
        return [self.arg_dict[n] for n in self._arg_names]

    @property
    def grad_arrays(self):
        return [self.grad_dict.get(n) for n in self._arg_names]

    @property
    def aux_arrays(self):
        return [self.aux_dict[n] for n in self._aux_names]

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    def set_args(self, **kwargs):
        """Write input values into the bound argument buffers (the feed half
        of ``forward``, shared with the fused train step)."""
        from ..ndarray import NDArray, array as nd_array

        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"unknown argument {k}")
            tgt = self.arg_dict[k]
            src = v if isinstance(v, NDArray) else nd_array(v)
            tgt._data = jnp.asarray(src._data, tgt.dtype)

    def forward(self, is_train=False, **kwargs):
        from .. import random as _random
        from ..ndarray import NDArray

        self.set_args(**kwargs)

        key = _random.next_key()
        args = tuple(self.arg_dict[n]._data for n in self._arg_names)
        auxs = tuple(self.aux_dict[n]._data for n in self._aux_names)

        sig = self._sig(args, auxs)
        if is_train and any(r != "null" for r in self._grad_req.values()):
            outputs, aux_new, vjp = self._jit_fwd_vjp(True, sig)(key, args, auxs)
            self._vjp = vjp
        else:
            outputs, aux_new = self._jit_fwd(bool(is_train), sig)(key, args, auxs)
            self._vjp = None

        if is_train:
            # aux write-back (moving stats) — reference mutable aux NDArrays
            for n, a in zip(self._aux_names, aux_new):
                self.aux_dict[n]._data = a

        self.outputs = [NDArray(o) for o in outputs]
        if self._monitor_callback is not None:
            for name, out in zip(self._symbol.list_outputs(), self.outputs):
                self._monitor_callback(name, out)
        return self.outputs

    def backward(self, out_grads=None, is_train=True):
        from ..ndarray import NDArray

        if self._vjp is None:
            raise MXNetError("backward requires forward(is_train=True) first "
                             "(and at least one grad_req != 'null')")
        if out_grads is None:
            cts = tuple(jnp.ones(o.shape, o.dtype) for o in self.outputs)
        else:
            if isinstance(out_grads, (NDArray, _np.ndarray)):
                out_grads = [out_grads]
            cts = tuple(g._data if isinstance(g, NDArray) else jnp.asarray(g)
                        for g in out_grads)
        grads = _reg.run_vjp(self._vjp, cts)
        diff_names = [n for n in self._arg_names
                      if self._grad_req.get(n, "null") != "null"]
        for n, g in zip(diff_names, grads):
            req = self._grad_req[n]
            tgt = self.grad_dict[n]
            if req == "write":
                tgt._data = g.astype(tgt.dtype)
            elif req == "add":
                tgt._data = tgt._data + g.astype(tgt.dtype)

    def fused_step(self, optimizer, updater, param_names,
                   grad_sync_fn=None, grad_sync_key=None, zero1=None,
                   pipeline=None, spmd=None):
        """ONE training step — forward, backward (ones cotangents, the
        `backward(out_grads=None)` convention), gradient rescale/clip and
        the optimizer update for every parameter — as a single jitted XLA
        computation, with weight, optimizer-state and aux buffers donated
        so XLA updates them in place.

        This is the bulking limit the engine exists to approach (SURVEY L2):
        the eager path crosses the dispatch boundary once per forward, once
        per backward and ~once per parameter chunk in the update loop; here
        the whole step is one dispatch. The eager path remains the
        correctness reference (test_fused_step.py asserts parity).

        ``param_names`` must be the module's parameter list — updater state
        keys are positions in it, matching the eager ``Module.update``
        indexing. Returns the step outputs (also stored in ``self.outputs``).

        Gradients are consumed INSIDE the computation and never
        materialized: ``grad_dict`` is NOT updated by this path (reading it
        after a fused step sees the previous eager step's values, or the
        zeros from bind). Code that needs per-step gradients — Monitor,
        input grads, custom gradient manipulation — must run the eager
        decomposition (``Module._fused_step_ready`` gates the common cases).

        ``grad_sync_fn`` (a traceable ``grads_tuple -> grads_tuple``, from
        ``KVStore.fused_grad_sync_fn``) is applied to the gradients INSIDE
        the trace, between backward and the optimizer update — the
        cross-replica sum over the bucketed flat grads that the eager path
        dispatches as per-bucket collectives. ``grad_sync_key`` must
        identify the sync layout (store type + bucket cap): it keys the
        compile cache so a layout change re-specializes.

        ``zero1`` (a ``parallel.zero1.Zero1Context``, from Module when
        `MXNET_ZERO1=1`) replaces the replicated per-parameter update with
        the sharded one: gradients are constrained to the dp-sharded flat
        bucket layout (with the upstream cross-replica sum this lowers to
        ReduceScatter), the optimizer runs on each replica's 1/N shard of
        params and state (state lives SHARDED in the context, not in
        ``updater.states``), and the updated shards are allgathered back —
        still one donated-buffer XLA computation per signature.

        ``pipeline`` (a ``parallel.pipeline.PipelineContext``, from Module
        when `MXNET_PIPELINE_STAGES>=2`) swaps the plain graph function
        for the GPipe micro-batch schedule over the 'pp' mesh axis: the
        vjp below then differentiates THROUGH the scan/ppermute schedule
        (the reverse pipeline flow), micro-batch gradients accumulate
        inside the trace, and the grad-sync / ZeRO-1 / optimizer tail
        composes unchanged. Pipelined executables compile under the named
        CompileCache("pipeline") so accounting stays pinned per
        (symbol, shapes, stages, microbatches) key.

        ``spmd`` (a ``parallel.spmd.SpmdContext``, from Module when
        `MXNET_SPMD` is set) shards the program itself per GSPMD: bound
        weights are committed at their planned PartitionSpecs (tp
        column/row alternation, fsdp largest-dim — physical per-device
        residency ~1/N), the batch enters dp(+fsdp)-sharded so data
        parallelism lives INSIDE the program, gradients / updated
        weights / optimizer state are constrained to the same layouts
        (fsdp grads lower to ReduceScatter, state bytes follow the
        weight's 1/N), and XLA's SPMD partitioner propagates the rest —
        forward AND backward are sharded, not just the update. Composes
        with ``zero1`` (the flat update unpacks straight back to the
        planned layouts) and ``pipeline`` (residency placement gathered
        just-in-time inside the schedule). Sharded steps compile under
        the context's named CompileCache("spmd").
        """
        from .. import random as _random
        from ..ndarray import NDArray
        from ..optimizer.optimizer import (_any_donated_deleted,
                                           _restore_counts, _snapshot_counts,
                                           _state_sig, _state_to_jax,
                                           _state_writeback)

        upd = [(i, n) for i, n in enumerate(param_names)
               if self._grad_req.get(n, "null") != "null"]
        indices = [i for i, _ in upd]
        names = [n for _, n in upd]
        name_set = set(names)
        weights = [self.arg_dict[n] for n in names]
        if spmd is not None:
            # one-time physical placement: the bound weight buffers drop
            # to their planned 1/N residency HERE, so the first sharded
            # step already aliases its donated inputs
            spmd.place_params(names, weights)
        if zero1 is not None:
            # sharded state lives in the context (1/N per replica); the
            # per-parameter updater states are not materialized
            zero1.ensure(optimizer, updater, indices, weights)
            states = None
        else:
            updater.ensure_states(indices, weights)
        count_snap = _snapshot_counts(optimizer, indices)
        optimizer._update_count(indices)
        lrs, wds = optimizer._fused_hyperparams(indices)
        if zero1 is None:
            states = [updater.states[i] for i in indices]
            if spmd is not None:
                # state leaves shaped like the weight shard with it —
                # per-device optimizer-state bytes follow the same 1/N
                spmd.place_state_trees(names, states)
            state_sig = tuple(_state_sig(s) for s in states)
            states_arg = [_state_to_jax(s) for s in states]
        else:
            state_sig = zero1.key()
            states_arg = zero1.flat_states

        key = _random.next_key()
        params = tuple(self.arg_dict[n]._data for n in names)
        other_names = [n for n in self._arg_names if n not in name_set]
        others = tuple(self.arg_dict[n]._data for n in other_names)
        auxs = tuple(self.aux_dict[n]._data for n in self._aux_names)

        sig = (tuple(names),
               tuple((a.shape, a.dtype) for a in params),
               tuple((a.shape, a.dtype) for a in others),
               tuple((a.shape, a.dtype) for a in auxs),
               state_sig,
               optimizer._fused_static_key(),
               grad_sync_key,
               pipeline.key() if pipeline is not None else None,
               spmd.key() if spmd is not None else None)

        def build():
            base = pipeline.wrap(self, spmd=spmd) if pipeline is not None \
                else self._fn(True)
            arg_pos = {n: i for i, n in enumerate(self._arg_names)}
            param_pos = [arg_pos[n] for n in names]
            other_pos = [arg_pos[n] for n in other_names]
            opt = optimizer
            n_args = len(self._arg_names)

            def step(key, params, others, auxs, ss, lrs_, wds_, rescale):
                def f(*ps):
                    full = [None] * n_args
                    for p, i in zip(ps, param_pos):
                        full[i] = p
                    for o, i in zip(others, other_pos):
                        full[i] = o
                    return base(key, tuple(full), auxs)

                outputs, vjp, aux_new = jax.vjp(f, *params, has_aux=True)
                cts = tuple(jnp.ones(o.shape, o.dtype) for o in outputs)
                grads = vjp(cts)
                if pipeline is not None and \
                        getattr(pipeline, "grad_correction", 1) > 1:
                    # undo the shard_map replication over non-pp mesh
                    # axes (PipelineContext.grad_correction): the vjp
                    # transpose summed identical per-coordinate copies
                    inv = 1.0 / pipeline.grad_correction
                    grads = tuple(g * jnp.asarray(inv, g.dtype)
                                  for g in grads)
                if grad_sync_fn is not None:
                    # cross-replica gradient sync traced into the step
                    # (bucketed flat psum — KVStore.fused_grad_sync_fn)
                    grads = grad_sync_fn(tuple(grads))
                if spmd is not None:
                    # pin gradients to the planned weight layouts: with
                    # the batch-sharded sum upstream the fsdp constraint
                    # lowers to ReduceScatter (parallel/spmd.py)
                    grads = spmd.constrain_grads(names, grads)
                with jax.named_scope("optimizer.update"):
                    if zero1 is not None:
                        # sharded weight update: grads constrained to the
                        # dp-sharded flat buckets (sum+constraint lowers to
                        # ReduceScatter), 1/N-shard optimizer step, weights
                        # allgathered back replicated — or straight back to
                        # the spmd layouts when both compose
                        new_ws, new_ss = zero1.traced_update(
                            opt, list(params), list(grads), ss,
                            lrs_, wds_, rescale,
                            unpack_shardings=(spmd.param_shardings(names)
                                              if spmd is not None else None))
                    else:
                        new_ws, new_ss = opt.fused_update(
                            list(params), list(grads), ss, lrs_, wds_,
                            rescale)
                        if spmd is not None:
                            # updated weights/state persist at the planned
                            # layouts: donation aliases, residency stays 1/N
                            new_ws = spmd.constrain_params(names, new_ws)
                            new_ss = spmd.constrain_state_trees(names,
                                                                new_ss)
                return outputs, tuple(new_ws), new_ss, aux_new

            # Donate exactly what will ALIAS (the hlolint donation audit
            # enforces declared == aliased): params + auxs + states on the
            # elementwise-update paths, but under ZeRO-1 the updated
            # weights are SLICES of one all-gathered flat bucket — XLA
            # cannot reliably alias k outputs carved from a single gather
            # result into k separate donated buffers (dumps showed it
            # silently declining for most params), so donating them only
            # risked consuming buffers nothing aliased. The flat sharded
            # state and the aux states update elementwise and alias.
            donate = (3, 4) if zero1 is not None else (1, 3, 4)
            return jax.jit(step, donate_argnums=donate)

        # Pipelined steps compile under the
        # named "pipeline" cache, sharded ones under "spmd" (spmd wins
        # when both compose), so per-config accounting is assertable.
        # The audit tag names the hlolint contract row for the
        # COMPOSITION that actually shaped the program: a zero1 step in
        # the generic executor cache is still audited against the
        # reduce-scatter/all-gather contract (tools/hlolint/contracts.py).
        if spmd is not None:
            cache, audit = spmd.cache, "spmd"
        elif pipeline is not None:
            cache, audit = pipeline.cache, "pipeline"
        elif zero1 is not None:
            cache, audit = self._cache, "zero1"
        else:
            cache, audit = self._cache, "fused_step"
        fn = cache.get_or_build(("fused_step", sig), build, audit=audit)
        call_args = [key, params, others, auxs, states_arg,
                     jnp.asarray(lrs, jnp.float32),
                     jnp.asarray(wds, jnp.float32),
                     jnp.float32(optimizer.rescale_grad)]
        if spmd is not None:
            # params/feeds/state onto the mesh at their PLANNED layouts
            # (steady state is a no-op — they come back placed); the
            # zero1 flat state is already dp-sharded and rides untouched
            call_args[1] = tuple(spmd.put(n, a)
                                 for n, a in zip(names, params))
            call_args[2] = tuple(spmd.put(n, a)
                                 for n, a in zip(other_names, others))
            call_args[3] = tuple(spmd.put_replicated(a) for a in auxs)
            # (non-zero1 state leaves were already device_put at the
            # weight's layout by place_state_trees above)
            for i in (0, 5, 6, 7):
                call_args[i] = jax.tree_util.tree_map(spmd.put_replicated,
                                                      call_args[i])
        elif zero1 is not None:
            # everything but the (already-sharded) state enters the mesh
            # replicated; steady state is a no-op for weights/aux (they
            # come back replicated), feeds broadcast here once per step
            put = zero1.put_replicated
            call_args = [jax.tree_util.tree_map(put, a) if i != 4 else a
                         for i, a in enumerate(call_args)]
        elif pipeline is not None:
            # same replication discipline onto the pp mesh: donated
            # buffers must already live replicated on the mesh or the
            # donation silently degrades to a copy
            put = pipeline.put_replicated
            call_args = [jax.tree_util.tree_map(put, a) for a in call_args]
        try:
            with tracing.span("fused.dispatch", cat="train",
                              params=len(names),
                              zero1=zero1 is not None,
                              pipeline=pipeline is not None):
                outputs, new_ws, new_ss, aux_new = fn(*call_args)
        except Exception as e:
            donated = [w._data for w in weights]
            if zero1 is not None:
                # the sharded flat state (donated via states_arg) is the
                # only copy once dirty — a consumed state buffer is as
                # fatal as a consumed weight
                donated += jax.tree_util.tree_leaves(zero1.flat_states or [])
            if _any_donated_deleted(donated):
                # donated inputs were consumed before execution failed —
                # the bound weights/states are unrecoverable in-process;
                # say so instead of a later "Array deleted" crash
                raise MXNetError(
                    "fused train step failed mid-execution; weight/"
                    "optimizer-state buffers were donated and may be "
                    "invalidated — restore from the last checkpoint before "
                    f"continuing ({e!r})") from e
            # trace/compile failed BEFORE any buffer was consumed: weights
            # are intact — undo the count bump so the caller's eager
            # fallback doesn't double-count the step, and let the original
            # error through (Module.fused_step turns it into a fallback)
            _restore_counts(optimizer, count_snap)
            raise

        for n, w in zip(names, new_ws):
            self.arg_dict[n]._data = w
        if zero1 is not None:
            zero1.flat_states = new_ss
            zero1.dirty = True
        else:
            for s, ns in zip(states, new_ss):
                _state_writeback(s, ns)
        for n, a in zip(self._aux_names, aux_new):
            self.aux_dict[n]._data = a
        self._vjp = None  # grads were consumed inside the step
        self.outputs = [NDArray(o) for o in outputs]
        if pipeline is not None:
            pipeline.record_step()
        if spmd is not None:
            spmd.record_step(names, weights)
        return self.outputs

    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        from ..ndarray import NDArray

        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._data = jnp.asarray(
                    v._data if isinstance(v, NDArray) else v,
                    self.arg_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError(f"unknown arg {k}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._data = jnp.asarray(
                    v._data if isinstance(v, NDArray) else v,
                    self.aux_dict[k].dtype)
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux {k}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Rebind with new input shapes (cheap — jit re-specializes)."""
        from ..ndarray import zeros

        new_shapes = dict(kwargs)
        arg_shapes, _, aux_shapes = self._symbol.infer_shape_partial(**{
            k: v for k, v in new_shapes.items() if k in self._arg_names})
        args = {}
        for n, s in zip(self._arg_names, arg_shapes):
            cur = self.arg_dict[n]
            if s is not None and tuple(cur.shape) != tuple(s):
                args[n] = zeros(s, dtype=cur.dtype)
            else:
                args[n] = cur
        auxs = {}
        for n, s in zip(self._aux_names, aux_shapes):
            cur = self.aux_dict[n]
            if s is not None and tuple(cur.shape) != tuple(s):
                auxs[n] = zeros(s, dtype=cur.dtype)
            else:
                auxs[n] = cur
        new = Executor(self._symbol, self._ctx, args=args,
                       grad_req=self._grad_req, aux_states=auxs)
        # an installed monitor must survive the rebind (it also gates the
        # fused-step fallback in Module._fused_step_ready)
        new._monitor_callback = self._monitor_callback
        return new

    def set_monitor_callback(self, callback, monitor_all=False):
        """Install a per-output monitor (reference
        `MXExecutorSetMonitorCallbackEX`, `graph_executor.cc:115`)."""
        self._monitor_callback = callback

    def debug_str(self):
        return self._symbol.debug_str()
