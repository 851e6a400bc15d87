"""Module — the symbolic trainer.

Parity: `python/mxnet/module/module.py` (`bind`:422 creating the executor
group, `init_params`, `init_optimizer`:503, `forward`/`backward`,
`update`:664) and `executor_group.py` (`DataParallelExecutorGroup`:143).

TPU-native redesign: the reference binds one executor PER DEVICE and
slices each batch across them (`executor_group.py:65`), reducing grads
through KVStore. Here a single bound executor is one XLA program for the
whole batch; multi-chip data parallelism is GSPMD sharding of that same
program (`MXNET_SPMD=dp=N`, `parallel/spmd.py`), so there is no per-device
executor list to manage — a list of several distinct devices is refused
with that advice, never bound on its first entry in silence.
"""
from __future__ import annotations

import logging

import numpy as _np

from ..base import MXNetError, getenv
from .. import context as ctx_mod
from .. import ndarray as nd
from .. import optimizer as opt
from ..model import _create_kvstore
from ..initializer import Uniform, InitDesc
from ..io import staging as _staging
from ..io.io import DataDesc
from .base_module import BaseModule

__all__ = ["Module"]


class Module(BaseModule):
    """Bind a Symbol + data/label names into a trainable module."""

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 logger=logging, context=None, work_load_list=None,
                 fixed_param_names=None, state_names=None, group2ctxs=None,
                 compression_params=None):
        super().__init__(logger=logger)
        self._symbol = symbol
        self._data_names = list(data_names or [])
        self._label_names = list(label_names or [])
        self._fixed_param_names = list(fixed_param_names or [])
        self._context = context if context is not None else ctx_mod.current_context()
        if isinstance(self._context, (list, tuple)):
            self._context = list(self._context)
        else:
            self._context = [self._context]
        if len(set(self._context)) > 1:
            # the reference slices the batch over one executor per device;
            # here ONE executor is bound on ONE device, so a device list
            # would train on its first entry and say nothing
            raise MXNetError(
                f"Module(context={self._context}): a Module binds one "
                f"executor on one device and does not split the batch over "
                f"a device list. To train across {len(set(self._context))} "
                f"devices pass a single context and set "
                f"MXNET_SPMD=dp={len(set(self._context))} — one program "
                f"sharded over one mesh (docs/faq/distributed_training.md)")

        arg_names = symbol.list_arguments()
        input_names = self._data_names + self._label_names
        self._param_names = [n for n in arg_names if n not in input_names]
        self._aux_names = symbol.list_auxiliary_states()
        self._output_names = symbol.list_outputs()

        self._arg_params = None
        self._aux_params = None
        self._exec = None
        self._optimizer = None
        self._kvstore = None
        self._update_on_kvstore = None
        self._updater = None
        self._preload_opt_states = None
        self._grad_req = "write"
        # rows of padding applied to the current batch (short last batch
        # padded up to the bound batch size; outputs/metrics sliced back)
        self._pad = 0
        self._pad_bound = 0  # the batch dim the pad filled up to
        self._last_short_shape = None  # pad-vs-reshape hysteresis
        self._has_custom_op = None  # memoized graph scan (fused-step gate)
        self._fused_failed = False  # fused trace failed once — stay eager
        self._grad_sync = None  # bucketed gradient-sync scheduler (lazy)
        self._zero1 = None  # ZeRO-1 sharded-update context (MXNET_ZERO1=1)
        self._zero1_failed = False  # zero1 trace failed — stay replicated
        self._pipeline = None  # GPipe schedule ctx (MXNET_PIPELINE_STAGES)
        self._pipeline_failed = False  # plan/trace failed — stay unpipelined
        self._spmd = None  # SPMD sharding plan (MXNET_SPMD)
        self._spmd_failed = False  # plan/trace failed — stay replicated
        self._stager = None  # DeviceStager ring (lazy)
        self._staged_meta = []  # [(batch, pad/hysteresis meta)] FIFO

    # -- properties ----------------------------------------------------------

    @property
    def data_names(self):
        return self._data_names

    @property
    def label_names(self):
        return self._label_names

    @property
    def output_names(self):
        return self._output_names

    @property
    def data_shapes(self):
        assert self.binded
        return self._data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return [(n, tuple(o.shape))
                for n, o in zip(self._output_names, self._exec.outputs)] \
            if self._exec.outputs else None

    # -- bind ----------------------------------------------------------------

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req

        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [l if isinstance(l, DataDesc) else DataDesc(*l)
                              for l in (label_shapes or [])]

        shape_kwargs = {d.name: d.shape for d in self._data_shapes}
        shape_kwargs.update({l.name: l.shape for l in self._label_shapes})
        arg_shapes, _, aux_shapes = self._symbol.infer_shape(**shape_kwargs)
        arg_names = self._symbol.list_arguments()

        type_dict = {d.name: getattr(d, "dtype", _np.float32)
                     for d in self._data_shapes + self._label_shapes}
        args = {n: nd.zeros(s, dtype=type_dict.get(n, "float32"))
                for n, s in zip(arg_names, arg_shapes)}
        auxs = {n: nd.zeros(s)
                for n, s in zip(self._aux_names, aux_shapes)}

        req = {}
        for n in arg_names:
            if n in self._data_names:
                req[n] = "write" if inputs_need_grad else "null"
            elif n in self._label_names or n in self._fixed_param_names:
                req[n] = "null"
            else:
                req[n] = grad_req if for_training else "null"

        from ..symbol.executor import Executor

        self._exec = Executor(self._symbol, self._context[0], args=args,
                              grad_req=req, aux_states=auxs)
        self.binded = True

        if shared_module is not None and shared_module.params_initialized:
            arg_p, aux_p = shared_module.get_params()
            self._exec.copy_params_from(arg_p, aux_p, allow_extra_params=True)
            self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
            self._aux_params = dict(self._exec.aux_dict)
            self.params_initialized = True

    # -- params --------------------------------------------------------------

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded, "call bind before initializing parameters"
        if initializer is None and not (arg_params or aux_params):
            initializer = Uniform(0.01)

        for name in self._param_names:
            arr = self._exec.arg_dict[name]
            if arg_params is not None and name in arg_params:
                arr[:] = arg_params[name].asnumpy() if isinstance(arg_params[name], nd.NDArray) \
                    else arg_params[name]
            elif initializer is not None:
                initializer(InitDesc(name), arr)
            elif not allow_missing:
                raise MXNetError(f"no initializer and no value for param {name}")
        for name in self._aux_names:
            arr = self._exec.aux_dict[name]
            if aux_params is not None and name in aux_params:
                arr[:] = aux_params[name].asnumpy() if isinstance(aux_params[name], nd.NDArray) \
                    else aux_params[name]
            elif initializer is not None:
                initializer(InitDesc(name), arr)
        self._arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        self._aux_params = dict(self._exec.aux_dict)
        self.params_initialized = True

    def get_params(self):
        assert self.binded and self.params_initialized
        return ({n: self._exec.arg_dict[n].copy() for n in self._param_names},
                {n: v.copy() for n, v in self._exec.aux_dict.items()})

    # -- optimizer -----------------------------------------------------------

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return

        if isinstance(optimizer, str):
            # default rescale_grad = 1/batch_size (reference module.py:503ff:
            # SoftmaxOutput-style heads emit per-example grads summed over
            # the batch; the optimizer normalizes)
            batch_size = self._data_shapes[0].shape[0] if self._data_shapes else 1
            params = dict(optimizer_params or ())
            params.setdefault("rescale_grad", 1.0 / max(batch_size, 1))
            idx2name = {i: n for i, n in enumerate(self._param_names)}
            optimizer = opt.create(optimizer, param_idx2name=idx2name, **params)
        self._optimizer = optimizer

        arg_params = {n: self._exec.arg_dict[n] for n in self._param_names}
        kv, update_on_kvstore = _create_kvstore(
            kvstore, len(self._context), arg_params)
        self._kvstore = kv
        self._update_on_kvstore = update_on_kvstore
        if kv is not None:
            if update_on_kvstore:
                kv.set_optimizer(self._optimizer)
            for i, name in enumerate(self._param_names):
                kv.init(name, self._exec.arg_dict[name])
        if not update_on_kvstore:
            self._updater = opt.get_updater(self._optimizer)
        self.optimizer_initialized = True
        if self._preload_opt_states is not None:
            self.load_optimizer_states(self._preload_opt_states)
            self._preload_opt_states = None

    # -- compute -------------------------------------------------------------

    def _make_feed(self, data_batch):
        """Build the name→array feed. A short last batch is PADDED up to the
        bound batch size (recycling rows from the batch start) so the
        already-compiled executable is reused — one compile-cache entry per
        bucket instead of a per-epoch recompile; `self._pad` records the
        rows to slice back off outputs/metrics. Genuine shape changes
        (bucketing, a larger batch, a persistently smaller batch stream)
        still rebind via reshape."""
        feed = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed[name] = arr
        if data_batch.label is not None and self._label_names:
            for name, arr in zip(self._label_names, data_batch.label):
                feed[name] = arr
        self._pad = 0
        cur = self._exec.arg_dict
        mismatched = [n for n, a in feed.items()
                      if n in cur and tuple(cur[n].shape) != tuple(a.shape)]
        if not mismatched:
            self._last_short_shape = None
            return feed
        short_shape = tuple(sorted((n, tuple(feed[n].shape))
                                   for n in mismatched))
        # 0-row batches reshape; so does inputs_need_grad — input gradients
        # must come back at the true batch shape, and with cross-row ops
        # (BatchNorm) padded rows would perturb every row's grad
        is_short = not self.inputs_need_grad and all(
            tuple(feed[n].shape[1:]) == tuple(cur[n].shape[1:])
            and 0 < feed[n].shape[0] < cur[n].shape[0]
            for n in mismatched)
        # hysteresis: ONE short batch (the per-epoch tail) pads up to the
        # bound shape; the SAME short shape arriving twice in a row is a
        # persistently smaller stream (e.g. predict at a smaller batch
        # size) — reshape once and run natively instead of paying the
        # bound-size forward on every batch
        if is_short and short_shape != getattr(self, "_last_short_shape", None):
            from ..io.io import pad_arrays

            pads = []
            for n in mismatched:
                padded, p = pad_arrays([feed[n]], cur[n].shape[0])
                feed[n] = padded[0]
                pads.append(p)
            self._pad = max(pads)
            # the CURRENT bound batch dim (the executor may have been
            # reshaped since bind, so _data_shapes could be stale)
            self._pad_bound = cur[mismatched[0]].shape[0]
            self._last_short_shape = short_shape
        else:
            self._exec = self._exec.reshape(**{n: tuple(a.shape)
                                               for n, a in feed.items()})
            self._last_short_shape = None
        return feed

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        if is_train is None:
            is_train = self.for_training
        feed = self._make_feed(data_batch)
        self._exec.forward(is_train=is_train, **feed)

    def backward(self, out_grads=None):
        assert self.binded and self.params_initialized
        self._exec.backward(out_grads=out_grads)

    def update(self):
        """Apply gradients (reference module.py:664 → model.py:150/162).

        Gradient sync is BUCKETED by default (`parallel/grad_sync.py`):
        one grouped kvstore call — O(#buckets) collectives — instead of one
        push+pull per parameter, and for the allreduce-then-local-update
        flow the bucket collectives are issued asynchronously so comm
        overlaps the remaining host work. `MXNET_GRAD_BUCKETING=0` restores
        the eager per-key loop, the correctness reference."""
        assert self.binded and self.params_initialized and self.optimizer_initialized
        if self._kvstore is not None:
            from ..parallel import grad_sync as _gs

            live = [(i, name, self._exec.grad_dict[name],
                     self._exec.arg_dict[name])
                    for i, name in enumerate(self._param_names)
                    if self._exec.grad_dict.get(name) is not None]
            if not live:
                return
            # compressed stores keep the per-key path for the flat-bucket
            # allreduce (quantization lives inside push, per key); grouped
            # push/pull (update_on_kvstore) still compresses per key
            if _gs.bucketing_enabled() and (
                    self._update_on_kvstore
                    or _gs.sync_compatible(self._kvstore)):
                idxs = [i for i, _, _, _ in live]
                names = [n for _, n, _, _ in live]
                grads = [g for _, _, g, _ in live]
                weights = [w for _, _, _, w in live]
                prios = [-i for i in idxs]
                if self._update_on_kvstore:
                    # grouped push/pull: the store buckets the keys of one
                    # call (dist `_push_dense`) — collectives O(#buckets)
                    self._kvstore.push(names, grads, priority=prios)
                    self._kvstore.pull(names, out=weights, priority=prios)
                else:
                    # pure allreduce: overlapped flat-bucket collectives,
                    # then ONE aggregated local updater call
                    if self._grad_sync is None:
                        self._grad_sync = _gs.GradSync(self._kvstore)
                    self._grad_sync.configure_from(grads, priorities=prios)
                    self._grad_sync.sync(grads)
                    self._updater(idxs, grads, weights)
            else:
                for i, name, g, w in live:
                    if self._update_on_kvstore:
                        self._kvstore.push(name, g, priority=-i)
                        self._kvstore.pull(name, out=w, priority=-i)
                    else:
                        self._kvstore.push(name, g, priority=-i)
                        self._kvstore.pull(name, out=g, priority=-i)
                        self._updater(i, g, w)
        else:
            # ONE updater call for the whole step: lr/wd lookups batch once
            # per step, SGD rides the aggregated multi_sgd_* path, and
            # fused-capable optimizers collapse the loop into a single
            # jitted program (Updater._fused_call)
            indices, grads, weights = [], [], []
            for i, name in enumerate(self._param_names):
                g = self._exec.grad_dict.get(name)
                if g is None:
                    continue
                indices.append(i)
                grads.append(g)
                weights.append(self._exec.arg_dict[name])
            if indices:
                self._updater(indices, grads, weights)

    # -- fused train step ----------------------------------------------------

    def _fused_step_ready(self):
        """Whether one jitted fwd+bwd+update computation can replace the
        eager decomposition for this module. Anything that needs per-op or
        per-gradient visibility — an on-kvstore updater, a Monitor, custom
        (python-callback) ops, input grads, grad_req='add' — falls back to
        the eager path, which stays the correctness reference.

        A kvstore is NOT by itself a fallback anymore: with
        `update_on_kvstore=False` and a store whose gradient sync is
        traceable (`local`/`device`, and `dist_tpu_sync` in a
        single-process group — `fused_step_compatible`), the cross-replica
        sum over the bucketed flat grads is traced INTO the jitted step
        (`KVStore.fused_grad_sync_fn`), so the fused path keeps its one-
        dispatch-per-step shape instead of auto-falling back to eager."""
        if self._fused_failed or not getenv("MXNET_FUSED_STEP"):
            return False
        if not (self.binded and self.params_initialized
                and self.optimizer_initialized and self.for_training):
            return False
        if self._updater is None:
            return False
        if self._kvstore is not None:
            if self._update_on_kvstore:
                return False  # the optimizer lives on the store, per key
            if not getattr(self._kvstore, "fused_step_compatible", False):
                return False
        if not getattr(self._optimizer, "fused_update_supported", False):
            return False
        if self._exec._monitor_callback is not None or self.inputs_need_grad:
            return False
        if any(self._exec._grad_req.get(n, "null") not in ("write", "null")
               for n in self._param_names):
            return False
        if self._has_custom_op is None:
            from ..ops import registry as _reg
            from ..symbol.symbol import _topo_order

            def _needs_eager(node):
                if node.is_variable:
                    return False
                if node.op == "Custom":
                    return True
                return bool(getattr(_reg.get_op(node.op), "eager_only", False))

            nodes = _topo_order([n for n, _ in self._symbol._outputs])
            self._has_custom_op = any(_needs_eager(n) for n in nodes)
        return not self._has_custom_op

    def fused_step(self, data_batch):
        """One XLA computation for the whole training step (forward +
        backward + optimizer update, donated buffers) — `Executor.fused_step`
        compiled per shape signature. Returns True when taken; False tells
        the caller (BaseModule.fit) to run forward_backward() + update()."""
        if not self._fused_step_ready():
            return False
        # overlap lane: a batch the staging thread already padded/cast/
        # placed rides straight into the executor (set_args' asarray is a
        # no-op on device-resident arrays of the bound dtype); a miss
        # falls back to the host-side lockstep feed prep
        feed = self._consume_staged(data_batch)
        if feed is None:
            feed = self._make_feed(data_batch)
        self._exec.set_args(**feed)
        # SPMD one-mesh composition: when MXNET_SPMD is set, the schedule
        # and the sharding plan must share ONE device assignment — resolve
        # the spec's mesh up front and hand it to the pipeline planner
        spmd_mesh_hint = None
        if not self._spmd_failed:
            from ..parallel.spmd import SpmdFallback, spmd_enabled, spmd_mesh

            if spmd_enabled():
                try:
                    spmd_mesh_hint = spmd_mesh()
                except SpmdFallback as e:
                    self._spmd_failed = True
                    self.logger.warning(
                        "SPMD sharding unavailable (%s); using the "
                        "replicated fused step", e)
        pl = None
        if not self._pipeline_failed:
            from ..parallel.pipeline import (PipelineContext,
                                             PipelineFallback,
                                             pipeline_enabled)
            from ..parallel import mesh as _mesh_mod

            if pipeline_enabled():
                pp_mesh_arg = None
                if spmd_mesh_hint is not None:
                    S = int(getenv("MXNET_PIPELINE_STAGES") or 0)
                    pp_sz = _mesh_mod.axis_size(spmd_mesh_hint,
                                                _mesh_mod.AXIS_PP)
                    if pp_sz == S:
                        pp_mesh_arg = spmd_mesh_hint
                    else:
                        # the schedule and the sharding plan must share
                        # ONE mesh; an MXNET_SPMD spec whose pp axis is
                        # absent or mismatched drops the SPMD plan (the
                        # pipeline keeps its own mesh) rather than
                        # putting two meshes in one program
                        self._spmd_failed = True
                        spmd_mesh_hint = None
                        if self._spmd is not None:
                            # an earlier sharded step placed 1/N buffers;
                            # the replicated step must not inherit them
                            self._spmd.unplace(self._exec, self._updater)
                            self._spmd = None
                        self.logger.warning(
                            "MXNET_SPMD mesh has pp=%d but "
                            "MXNET_PIPELINE_STAGES=%d; using the "
                            "replicated fused step under the pipeline "
                            "schedule", pp_sz, S)
                if self._pipeline is None or \
                        not self._pipeline.matches(self._exec) or \
                        (pp_mesh_arg is not None
                         and self._pipeline.mesh is not pp_mesh_arg):
                    try:
                        self._pipeline = PipelineContext.build(
                            self._symbol, self._exec, self._data_names,
                            self._label_names, mesh=pp_mesh_arg)
                    except Exception as e:  # noqa: BLE001 — a plan
                        # failure is PipelineFallback, but bad env (e.g.
                        # a malformed MXNET_MESH_SHAPE the unpipelined
                        # step never consults) raises plain errors and
                        # must take the same graceful fallback
                        self._pipeline = None
                        self._pipeline_failed = True
                        self.logger.warning(
                            "pipeline schedule unavailable (%s); using "
                            "the unpipelined fused step",
                            e if isinstance(e, PipelineFallback)
                            else repr(e))
                pl = self._pipeline
            elif self._pipeline is not None:
                self._pipeline = None  # gate flipped off between fits
        sp = None
        if not self._spmd_failed and spmd_mesh_hint is not None:
            from ..parallel.spmd import SpmdContext, SpmdFallback

            pl_active = pl is not None
            if self._spmd is not None and \
                    not self._spmd.matches(self._exec,
                                           pipeline_active=pl_active):
                self._spmd = None
            if self._spmd is None:
                try:
                    self._spmd = SpmdContext.build(
                        self._symbol, self._exec, self._data_names,
                        self._label_names, pipeline=pl_active)
                except Exception as e:  # noqa: BLE001 — a plan failure
                    # is SpmdFallback, but bad env/graph edge cases must
                    # take the same graceful replicated fallback
                    self._spmd_failed = True
                    self.logger.warning(
                        "SPMD sharding plan unavailable (%s); using the "
                        "replicated fused step",
                        e if isinstance(e, SpmdFallback) else repr(e))
            sp = self._spmd
        elif self._spmd is not None:
            # gate flipped off (or the spec went unsatisfiable) between
            # fits: re-replicate the placed buffers so the replicated
            # step sees the layouts it would without the gate
            self._spmd.unplace(self._exec, self._updater)
            self._spmd = None
        z1 = None
        if not self._zero1_failed:
            from ..parallel.zero1 import zero1_enabled

            # the update must shard over the SAME mesh as the schedule/
            # sharding plan — two meshes in one program would conflict
            shared_mesh = pl.mesh if pl is not None else (
                sp.mesh if sp is not None else None)
            if zero1_enabled():
                if self._zero1 is not None and shared_mesh is not None and \
                        self._zero1.mesh is not shared_mesh:
                    # a pipeline/spmd context appeared (or was rebuilt)
                    # after this ctx was created on another mesh. Gather
                    # the live shards first (they are the only copy),
                    # then rebuild on the shared mesh below.
                    self._zero1.export_to_updater(self._updater)
                    self._zero1 = None
                if self._zero1 is None:
                    from ..parallel.zero1 import Zero1Context

                    try:
                        self._zero1 = Zero1Context(mesh=shared_mesh)
                    except Exception as e:  # noqa: BLE001 — bad mesh/env
                        # (e.g. MXNET_ZERO1_NDEV > device count): same
                        # graceful fallback as the Updater path
                        self._zero1_failed = True
                        self.logger.warning(
                            "ZeRO-1 context unavailable (%r); using the "
                            "replicated fused step", e)
                z1 = self._zero1
                if z1 is not None:
                    # register on the updater: checkpoint save/load stays
                    # transparent (get_states gathers shards, set_states
                    # invalidates so the next step re-shards)
                    self._updater._zero1 = z1
        gs_fn, gs_key = None, None
        if self._kvstore is not None:
            from ..parallel.grad_sync import bucket_cap_bytes

            # memoized ON the executor (a reshape creates a fresh executor
            # with no memo, so a recycled id() can never resurrect a stale
            # layout): the sync closure is layout-invariant per executor,
            # and rebuilding entries + bucket plan every step would be
            # pure host overhead on the hot path. id(self._kvstore) is
            # stable while self._kvstore holds the reference.
            memo_key = (id(self._kvstore), bucket_cap_bytes())
            cached = getattr(self._exec, "_fused_gsync_memo", None)
            if cached is not None and cached[0] == memo_key:
                _, gs_fn, gs_key = cached
            else:
                # entries aligned with the traced grads (params with a
                # grad, in param order — Executor.fused_step's `upd` list)
                entries = [(tuple(self._exec.arg_dict[n].shape),
                            self._exec.arg_dict[n].dtype, -i)
                           for i, n in enumerate(self._param_names)
                           if self._exec._grad_req.get(n, "null") != "null"]
                gs_fn = self._kvstore.fused_grad_sync_fn(entries)
                if gs_fn is not None:
                    gs_key = (self._kvstore.type, bucket_cap_bytes())
                self._exec._fused_gsync_memo = (memo_key, gs_fn, gs_key)
        try:
            self._exec.fused_step(self._optimizer, self._updater,
                                  self._param_names,
                                  grad_sync_fn=gs_fn, grad_sync_key=gs_key,
                                  zero1=z1, pipeline=pl, spmd=sp)
        except MXNetError:
            raise  # donation failure / graph error the eager path shares
        except Exception as e:
            # blame order when several are active: drop ZeRO-1 FIRST (the
            # pre-existing fallback precedence), then the SPMD plan, then
            # the pipeline schedule — each retry keeps the outer features
            # on; if one of those was the real culprit the retried step
            # fails again and lands in the next branch down
            if sp is not None and z1 is None:
                # the sharded step failed to trace/compile with buffers
                # intact (counts already restored): retry THIS step
                # replicated (still fused) and stay replicated from now on
                self._spmd_failed = True
                self._spmd = None
                # the replicated retry must see replicated buffers — a
                # failed sharded attempt must not leave 1/N layouts behind
                sp.unplace(self._exec, self._updater)
                self.logger.warning(
                    "SPMD sharded step failed to build (%r); falling "
                    "back to the replicated fused step", e)
                return self.fused_step(data_batch)
            if pl is not None and z1 is None:
                # the schedule failed to trace/compile with buffers intact
                # (counts already restored): retry THIS step unpipelined
                # (still fused) and stay unpipelined from now on
                self._pipeline_failed = True
                self._pipeline = None
                self.logger.warning(
                    "pipelined fused step failed to build (%r); falling "
                    "back to the unpipelined fused step", e)
                return self.fused_step(data_batch)
            if z1 is not None:
                # the ZeRO-1 trace failed with buffers intact: retry THIS
                # step on the replicated fused path (still fused), and stay
                # replicated from now on. The ctx stays registered on the
                # updater — its ensure_states hook gathers any dirty
                # shards from earlier sharded steps before the replicated
                # path consumes per-parameter states
                self._zero1_failed = True
                self._zero1 = None
                self.logger.warning(
                    "ZeRO-1 sharded step failed to build (%r); falling "
                    "back to the replicated fused step", e)
                return self.fused_step(data_batch)
            # trace/compile failure with buffers intact (Executor.fused_step
            # already restored the update counts): run this and all later
            # steps on the eager decomposition
            self._fused_failed = True
            self.logger.warning(
                "fused train step failed to build (%r); falling back to "
                "the eager forward_backward+update path", e)
            return False
        return True

    def get_outputs(self, merge_multi_context=True):
        assert self.binded and self.params_initialized
        outs = self._exec.outputs
        if self._pad:
            bound = self._pad_bound
            keep = bound - self._pad
            outs = [o[0:keep] if o.ndim and o.shape[0] == bound else o
                    for o in outs]
        return outs

    def get_input_grads(self, merge_multi_context=True):
        assert self.binded and self.inputs_need_grad
        return [self._exec.grad_dict.get(n) for n in self._data_names]

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        eval_metric.update_dict(
            dict(zip(self._label_names, labels)),
            dict(zip(self._output_names, self.get_outputs())))

    # -- batch staging -------------------------------------------------------

    def stage_batch(self, data_batch):
        """Decide stageability on the MAIN thread (executor shapes + the
        pad-vs-reshape hysteresis state are only coherent here), then hand
        the pad/cast/device-placement to the staging thread. Mirrors
        `_make_feed`'s decision tree exactly: a reshape-bound batch is not
        staged — host-side feed prep owns rebinds."""
        if not self._fused_step_ready():
            return False
        if isinstance(data_batch, list) or data_batch.data is None:
            return False
        feed_src = {}
        for name, arr in zip(self._data_names, data_batch.data):
            feed_src[name] = arr
        if data_batch.label is not None and self._label_names:
            for name, arr in zip(self._label_names, data_batch.label):
                feed_src[name] = arr
        cur = self._exec.arg_dict
        if not feed_src or any(n not in cur for n in feed_src):
            return False
        mismatched = [n for n, a in feed_src.items()
                      if tuple(cur[n].shape) != tuple(a.shape)]
        short_shape = None
        if mismatched:
            short_shape = tuple(sorted((n, tuple(feed_src[n].shape))
                                       for n in mismatched))
            is_short = not self.inputs_need_grad and all(
                tuple(feed_src[n].shape[1:]) == tuple(cur[n].shape[1:])
                and 0 < feed_src[n].shape[0] < cur[n].shape[0]
                for n in mismatched)
            if not is_short or short_shape == getattr(
                    self, "_last_short_shape", None):
                return False  # reshape path — host rebind, never staged
        shapes = {n: tuple(cur[n].shape) for n in feed_src}
        dtypes = {n: cur[n].dtype for n in feed_src}
        pad_names = frozenset(mismatched)
        bound = cur[mismatched[0]].shape[0] if mismatched else 0
        sp = self._spmd
        exec_ref = self._exec

        def prep():  # staging thread: pad -> cast -> place
            import jax.numpy as jnp

            from ..io.io import pad_arrays
            from ..ndarray import NDArray

            feed, pad = {}, 0
            for n, src in feed_src.items():
                a = src
                if n in pad_names:
                    padded, p = pad_arrays([a], shapes[n][0])
                    a = padded[0]
                    pad = max(pad, p)
                data = a._data if isinstance(a, NDArray) else a
                data = jnp.asarray(data, dtypes[n])
                if sp is not None:
                    # land already laid out per the dp plan's input
                    # shardings; dispatch's spmd.put then no-ops
                    data = sp.put(n, data)
                feed[n] = NDArray(data)
            return feed, pad

        if self._stager is None:
            self._stager = _staging.DeviceStager()
        accepted = self._stager.stage(
            data_batch, prep,
            # a reshape swaps the executor: its staged layout is stale
            guard=lambda: self._exec is exec_ref)
        if accepted:
            self._staged_meta.append(
                (data_batch, {"short_shape": short_shape, "bound": bound}))
            del self._staged_meta[:-self._stager.depth - 2]
        return accepted

    def _consume_staged(self, data_batch):
        """The staged feed for this exact batch (device-resident, already
        padded/cast/placed), applying the same pad/hysteresis state
        `_make_feed` would have set — or None (lockstep fallback)."""
        st = self._stager
        if st is None or isinstance(data_batch, list):
            return None
        meta = None
        for i, (b, m) in enumerate(self._staged_meta):
            if b is data_batch:
                meta = m
                del self._staged_meta[:i + 1]  # drop stale earlier entries
                break
        if meta is None:
            return None
        hit = st.take(data_batch)
        if hit is None:
            return None
        feed, pad = hit
        self._pad = pad
        if pad:
            self._pad_bound = meta["bound"]
        self._last_short_shape = meta["short_shape"]
        return feed

    def retire_staged(self):
        st = self._stager
        return st.retire() if st is not None else False

    def _overlap_teardown(self):
        st = self._stager
        if st is not None:
            self._stager = None
            self._staged_meta = []
            st.close()

    # -- checkpoint ----------------------------------------------------------

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        remove_amp_cast=True):
        from ..model import save_checkpoint

        arg_params, aux_params = self.get_params()
        save_checkpoint(prefix, epoch, self.symbol, arg_params, aux_params,
                        remove_amp_cast=remove_amp_cast)
        if save_optimizer_states:
            self.save_optimizer_states(f"{prefix}-{epoch:04d}.states")

    @staticmethod
    def load(prefix, epoch, load_optimizer_states=False, **kwargs):
        from ..model import load_checkpoint

        sym, args, auxs = load_checkpoint(prefix, epoch)
        mod = Module(symbol=sym, **kwargs)
        mod._arg_params = args
        mod._aux_params = auxs
        mod.params_initialized = True
        mod._preloaded_params = (args, auxs)
        if load_optimizer_states:
            mod._preload_opt_states = f"{prefix}-{epoch:04d}.states"
        # params are applied at bind time
        orig_bind = mod.bind

        def bind_and_set(*a, **kw):
            orig_bind(*a, **kw)
            mod.init_params(arg_params=args, aux_params=auxs, force_init=True)

        mod.bind = bind_and_set
        return mod

    def save_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.save_optimizer_states(fname)
        else:
            with open(fname, "wb") as f:
                f.write(self._updater.get_states())

    def load_optimizer_states(self, fname):
        assert self.optimizer_initialized
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
        else:
            with open(fname, "rb") as f:
                self._updater.set_states(f.read())

    def as_predictor(self, buckets=None, **kwargs):
        """This module's trained weights behind a thread-safe
        ``serving.Predictor``: per-bucket ``for_training=False`` executors,
        compile-ahead ``warmup()``, and dynamic micro-batching when wrapped
        in a ``serving.DynamicBatcher``. The Predictor takes COPIES of the
        current parameters (``get_params``), so continuing to train this
        module never mutates a live server."""
        from ..serving import Predictor

        return Predictor.from_module(self, buckets=buckets, **kwargs)

    def install_monitor(self, mon):
        assert self.binded
        mon.install(self._exec)

    def reshape(self, data_shapes, label_shapes=None):
        assert self.binded
        self._data_shapes = [d if isinstance(d, DataDesc) else DataDesc(*d)
                             for d in data_shapes]
        self._label_shapes = [l if isinstance(l, DataDesc) else DataDesc(*l)
                              for l in (label_shapes or [])]
        kwargs = {d.name: d.shape for d in self._data_shapes + self._label_shapes}
        self._exec = self._exec.reshape(**kwargs)
