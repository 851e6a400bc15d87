"""gluon.Trainer — applies an Optimizer to a set of Parameters.

Parity: `python/mxnet/gluon/trainer.py:27` (`_init_kvstore`:169,
`step`:298, `allreduce_grads`:327, `update`:359) and the kvstore wiring
helper `python/mxnet/model.py:82 _create_kvstore`.

TPU-native notes: for single-process multi-device the grads are reduced by
the local kvstore (one fused XLA reduction per parameter); for multi-host
the 'dist_tpu_sync' kvstore allreduces over ICI/DCN — `update_on_kvstore`
is forced False there (no server processes exist; the reference's
server-side optimizer `kvstore_dist_server.h:346` maps to
allreduce-then-local-update, the Horovod-style flow the reference itself
uses at `gluon/trainer.py:327`).

ZeRO-1 (`MXNET_ZERO1=1`): the aggregated updater call `step()` makes per
context rides `Updater._zero1_call` — the optimizer state lives dp-SHARDED
in flat buckets (1/N per replica, `parallel/zero1.py`) and the update runs
on each replica's shard, allgathered back into the full weights.
`save_states`/`load_states` stay transparent: the updater gathers shards
into ordinary per-parameter states before pickling and re-shards on load.

The step as one launch: a plain `backward()` over hybridized calls leaves
its gradients pending (`autograd._CallsBackward`), and where they are all
this Trainer's to apply — one context, no kvstore, dense gradients, no
ZeRO-1, an optimizer with `fused_update`, `MXNET_FUSED_STEP` on —
`_update` runs forward, pullback and `fused_update` as ONE program with
weights and optimizer states donated (`_fused_step`). Anything else reads
the gradients, which runs the forward+pullback program, and updates as
before.
"""
from __future__ import annotations

import logging

import numpy as _np
from jax import tree_util as _jtu

from .. import optimizer as opt
from .. import telemetry
from .. import tracing
from .._cached_op import PendingGrad
from ..model import _create_kvstore
from ..ndarray import NDArray
from ..optimizer.optimizer import (_raise_if_donated_consumed,
                                   _restore_counts, _snapshot_counts,
                                   _state_sig, _state_to_jax)
from .parameter import ParameterDict, Parameter

__all__ = ["Trainer"]


class _StepPlan:
    """Where a deferred backward's wanted leaves sit among a Trainer's
    parameters: found once per program (`Trainer._plan_step`), checked by
    identity every step (`holds`)."""

    __slots__ = ("indices", "weights", "grads", "others", "states",
                 "state_nds", "update_key")

    def __init__(self, indices, weights, grads, others):
        self.indices = indices    # the optimizer's index of each wanted leaf
        self.weights = weights    # its weight array (the leaf's NDArray)
        self.grads = grads        # and that array's .grad
        self.others = others      # trainable arrays the backward left out
        self.states = None        # the updater's state trees, by identity;
        # with them (take_states): state_nds, update_key (the key's tail)

    def holds(self, backward, ignore_stale_grad):
        """Every gradient to apply is still pending in ``backward``, for
        the weight buffers it differentiated, and nothing else is due."""
        if self.others and not (ignore_stale_grad and not any(
                arr._fresh_grad for arr in self.others)):
            return False
        leaf_nds, leaves = backward.leaf_nds, backward.leaves
        for slot, arr, grad, pending in zip(backward.wanted, self.weights,
                                            self.grads, backward.pending):
            if leaf_nds[slot] is not arr or arr._buf is not leaves[slot] \
                    or grad._buf is not pending:
                return False
        return True

    def take_states(self, states, opt_key):
        self.states = states
        # NDArray leaves, in the order the program returns their new values
        self.state_nds = _jtu.tree_leaves(states)
        self.update_key = (("update", opt_key,
                            tuple(_state_sig(s) for s in states)),)


class Trainer:
    def __init__(self, params, optimizer, optimizer_params=None, kvstore="device",
                 compression_params=None, update_on_kvstore=None):
        param_list = []
        if isinstance(params, (dict, ParameterDict)):
            for key in sorted(list(params.keys())):
                param_list.append(params[key])
            params = param_list
        if not isinstance(params, (list, tuple)):
            raise ValueError(
                "First argument must be a list or dict of Parameters, "
                f"got {type(params)}.")
        self._params = []
        self._param2idx = {}
        for param in params:
            if not isinstance(param, Parameter):
                raise ValueError(
                    "First argument must be a list or dict of Parameters, "
                    f"got list of {type(param)}.")
            # keyed by identity: Parameter NAMES may repeat across sibling
            # blocks (2.x-style direct attributes, e.g. two "weight"s) and a
            # name-keyed table would silently collapse two params onto one
            # kvstore slot in multi-context/dist runs
            if id(param) in self._param2idx:
                # the SAME Parameter passed twice (tied weights collected
                # under two keys, or a duplicated list): register once — a
                # second slot would double-apply its update and warn about
                # a stale gradient on the first step
                continue
            self._param2idx[id(param)] = len(self._params)
            self._params.append(param)
            param._set_trainer(self)
        self._compression_params = compression_params
        optimizer_params = optimizer_params if optimizer_params else {}
        self._scale = float(optimizer_params.get("rescale_grad", 1.0))
        self._contexts = self._check_contexts()
        self._init_optimizer(optimizer, optimizer_params)
        self._kvstore_params = {"kvstore": kvstore,
                                "update_on_kvstore": update_on_kvstore}
        self._kv_initialized = False
        self._kvstore = None
        self._update_on_kvstore = None
        self._distributed = None
        self._params_to_init = []
        # program key of a deferred backward -> _StepPlan; None once the
        # one-program step failed to build
        self._step_plans = {}
        self._reset_kvstore()

    def __getstate__(self):
        # the optimizer's param_dict pickles its Parameters' Trainer with
        # save_states: the plans name compiled ops and are found again
        state = self.__dict__.copy()
        state["_step_plans"] = None if self._step_plans is None else {}
        return state

    def _check_contexts(self):
        contexts = None
        for param in self._params:
            ctx = param.list_ctx()
            assert contexts is None or contexts == ctx, \
                f"All Parameters must be initialized on the same set of contexts, " \
                f"but Parameter {param.name} is initialized on {str(ctx)} while previous " \
                f"Parameters are initialized on {str(contexts)}."
            contexts = ctx
        return contexts

    def _init_optimizer(self, optimizer, optimizer_params):
        param_dict = {i: param for i, param in enumerate(self._params)}
        if isinstance(optimizer, opt.Optimizer):
            assert not optimizer_params, \
                "optimizer_params must be None if optimizer is an Optimizer instance"
            self._optimizer = optimizer
            self._optimizer.param_dict = param_dict
        else:
            self._optimizer = opt.create(optimizer, param_dict=param_dict,
                                         **optimizer_params)
        self._updaters = [opt.get_updater(self._optimizer)
                          for _ in self._contexts]

    def _reset_kvstore(self):
        if self._kvstore and "dist" in self._kvstore.type:
            raise RuntimeError("Cannot reset distributed KVStore.")
        self._kv_initialized = False
        self._kvstore = None
        self._distributed = None
        self._update_on_kvstore = None
        self._grad_sync = None  # bucketed sync scheduler (lazy, per store)
        self._params_to_init = [param for param in self._params]

    def _init_kvstore(self):
        """Create kvstore and set update-on-kvstore (parity trainer.py:169)."""
        config = self._kvstore_params
        arg_arrays = {f"{i}_{param.name}": param.data(self._contexts[0])
                      for i, param in enumerate(self._params)}
        kvstore, update_on_kvstore = _create_kvstore(
            config["kvstore"], len(self._contexts), arg_arrays)
        self._distributed = "dist" in kvstore.type if kvstore else False
        if self._distributed:
            # allreduce-over-ICI has no server; update locally after sync
            update_on_kvstore = False
        if any(p._grad_stype == "row_sparse" for p in self._params):
            # sparse grads aggregate through the sparse merge path and update
            # locally (reference trainer.py:169: update_on_kvstore=False when
            # grads are sparse but weights dense)
            update_on_kvstore = False
        if config["update_on_kvstore"] is not None:
            update_on_kvstore = config["update_on_kvstore"]
        if kvstore:
            if self._compression_params:
                kvstore.set_gradient_compression(self._compression_params)
            if update_on_kvstore:
                kvstore.set_optimizer(self._optimizer)
            self._kvstore = kvstore
            self._update_on_kvstore = update_on_kvstore
        else:
            self._kvstore = None
            self._update_on_kvstore = False
        self._kv_initialized = True

    def _init_params(self):
        """Push uninitialized-on-kv params into the kvstore."""
        assert self._kv_initialized, \
            "Cannot initialize parameters in KVStore when KVStore is not initialized."
        params_to_init = []
        if self._kvstore:
            for param in self._params_to_init:
                if param._deferred_init:
                    params_to_init.append(param)
                else:
                    param_arrays = param._check_and_get(param._data, list)
                    idx = self._param2idx[id(param)]
                    self._kvstore.init(idx, param_arrays[0])
                    if param._stype == "default" and self._update_on_kvstore:
                        self._kvstore.pull(idx, param_arrays, priority=-idx)
        self._params_to_init = params_to_init

    @property
    def learning_rate(self):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate can be accessed.")
        return self._optimizer.learning_rate if hasattr(self._optimizer, "learning_rate") \
            else self._optimizer.lr

    def set_learning_rate(self, lr):
        if not isinstance(self._optimizer, opt.Optimizer):
            raise UserWarning("Optimizer has to be defined before its learning "
                              "rate is mutated.")
        self._optimizer.set_learning_rate(lr)

    def step(self, batch_size, ignore_stale_grad=False):
        """Make one parameter-update step: rescale by 1/batch_size, allreduce
        grads, update (parity trainer.py:298)."""
        with tracing.span("trainer.step", cat="gluon",
                          params=len(self._params)):
            rescale_grad = self._scale / batch_size
            self._check_and_rescale_grad(rescale_grad)
            if not self._kv_initialized:
                self._init_kvstore()
            if self._params_to_init:
                self._init_params()
            with tracing.span("trainer.allreduce", cat="gluon"):
                self._allreduce_grads()
            with tracing.span("trainer.update", cat="gluon"):
                self._update(ignore_stale_grad)

    def _check_and_rescale_grad(self, scale):
        if self._update_on_kvstore and self._distributed and self._kv_initialized:
            if self._optimizer.rescale_grad != scale:
                raise UserWarning("Possible change in the `batch_size` from previous "
                                  "`step` detected. Optimizer gradient normalizing "
                                  "factor will not change w.r.t new batch_size when "
                                  "update_on_kvstore=True")
        self._optimizer.rescale_grad = scale

    def allreduce_grads(self):
        """Reduce gradients over devices/workers WITHOUT updating — for
        gradient manipulation between backward and update
        (parity trainer.py:327)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "allreduce_grads() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` " \
            "to False when creating trainer."
        with tracing.span("trainer.allreduce", cat="gluon"):
            self._allreduce_grads()

    def _allreduce_grads(self):
        """Bucketed by default (`parallel/grad_sync.py`): dense grads ride
        O(#buckets) flat collectives — issued asynchronously in gradient
        readiness order, drained in priority order — instead of one
        push(+pull) per parameter. `MXNET_GRAD_BUCKETING=0` restores the
        per-key reference loop."""
        if not self._kvstore:
            return
        from ..parallel import grad_sync as _gs

        # compressed stores keep the per-key push (quantization + error
        # feedback live inside push); grouped update_on_kvstore pushes
        # still compress per key, so only the flat-allreduce path gates
        bucketed = _gs.bucketing_enabled() and (
            self._update_on_kvstore or _gs.sync_compatible(self._kvstore))
        dense = []
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if param._grad_stype == "row_sparse":
                # row_sparse grads never ride the dense push/pull (which
                # would densify the table): merge sparse pieces directly
                self._allreduce_sparse_grads(i, param)
                continue
            if bucketed:
                dense.append((i, param.list_grad()))
                continue
            self._kvstore.push(i, param.list_grad(), priority=-i)
            if not self._update_on_kvstore:
                self._kvstore.pull(i, param.list_grad(), priority=-i,
                                   ignore_sparse=self._distributed)
        if dense:
            grads = [g for _, g in dense]
            prios = [-i for i, _ in dense]
            if self._update_on_kvstore:
                # optimizer lives on the store: one grouped push (the store
                # buckets the keys), weights come back in `_update`'s pull
                self._kvstore.push([i for i, _ in dense], grads,
                                   priority=prios)
            else:
                if self._grad_sync is None:
                    self._grad_sync = _gs.GradSync(self._kvstore)
                self._grad_sync.configure_from(grads, priorities=prios)
                self._grad_sync.sync(grads)

    def _allreduce_sparse_grads(self, i, param):
        """Aggregate row_sparse grads across device replicas (and worker
        processes for dist) while staying O(touched rows) — the role of the
        reference's row_sparse CommCPU reduce (`comm.h` ReduceRowSparse) +
        ps-lite row_sparse push (`kvstore_dist.h:676`)."""
        import jax.numpy as jnp
        from .. import autograd
        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray

        grads = [g for g in param.list_grad() if isinstance(g, RowSparseNDArray)]
        if not grads:
            return
        idx = jnp.concatenate([g.indices._data.astype(jnp.int32) for g in grads])
        data = jnp.concatenate([g.data._data for g in grads])
        if self._distributed:
            # one padded all-gather of the occupied rows over the workers
            merged_local = RowSparseNDArray(
                NDArray(data), NDArray(idx), tuple(grads[0].shape))
            self._kvstore.push(i, merged_local, priority=-i)
            uniq, summed = self._kvstore.pull_sparse_grad(i)
        else:
            ct = autograd._RowSparseCT(idx, data, tuple(grads[0].shape),
                                       grads[0].dtype)
            uniq, summed = ct.dedup()
        for g in grads:
            g._aux = {"data": NDArray(jnp.asarray(summed, g.dtype)),
                      "indices": NDArray(uniq)}
            g._dense_cache = None
            g._aux_stale = False

    def update(self, batch_size, ignore_stale_grad=False):
        """Update parameters WITHOUT allreduce — second half of the split
        step (parity trainer.py:359)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        assert not (self._kvstore and self._update_on_kvstore), \
            "update() when parameters are updated on kvstore " \
            "is not supported. Try setting `update_on_kvstore` " \
            "to False when creating trainer."
        self._check_and_rescale_grad(self._scale / batch_size)
        with tracing.span("trainer.update", cat="gluon"):
            self._update(ignore_stale_grad)

    def _update(self, ignore_stale_grad=False):
        if self._fused_step(ignore_stale_grad):
            return
        updates = [[] for _ in self._updaters]

        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if not ignore_stale_grad:
                for data in param._check_and_get(param._data, list):
                    if not data._fresh_grad:
                        raise UserWarning(
                            f"Gradient of Parameter `{param.name}` on context "
                            f"{str(data.context)} has not been updated by backward "
                            f"since last `step`. This could mean a bug in your model "
                            f"that made it only use a subset of the Parameters (Blocks) "
                            f"for this iteration. If you are intentionally only using "
                            f"a subset, call step with ignore_stale_grad=True to "
                            f"suppress this warning")
            if self._kvstore and self._update_on_kvstore:
                # optimizer ran on the kvstore; fetch the updated weights
                # (reference trainer.py:411-415)
                if param._stype == "default":
                    self._kvstore.pull(i, param.list_data(), priority=-i)
                continue
            for upd, arr, grad in zip(updates, param.list_data(), param.list_grad()):
                if not ignore_stale_grad or arr._fresh_grad:
                    upd.append((i, grad, arr))
                    arr._fresh_grad = False

        if not (self._kvstore and self._update_on_kvstore):
            for updater, upd in zip(self._updaters, updates):
                if upd:
                    i, g, w = zip(*upd)
                    updater(list(i), list(g), list(w))

    def _plan_step(self, backward):
        """The `_StepPlan` of ``backward``'s program, or None where its
        wanted leaves are not exactly dense weights of this Trainer."""
        by_array = {}
        for i, param in enumerate(self._params):
            if param.grad_req == "null":
                continue
            if param._stype != "default" or param._grad_stype != "default":
                return None
            by_array[id(param.list_data()[0])] = (i, param.list_grad()[0])
        indices, weights, grads = [], [], []
        for slot in backward.wanted:
            arr = backward.leaf_nds[slot]
            i, grad = by_array.pop(id(arr), (None, None))
            if grad is None or grad is not arr.grad:
                return None
            indices.append(i)
            weights.append(arr)
            grads.append(grad)
        others = [self._params[i].list_data()[0] for i, _ in by_array.values()]
        if len(self._step_plans) >= 16:  # shapes that churn: start over
            self._step_plans.clear()
        plan = self._step_plans[backward.key] = _StepPlan(
            indices, weights, grads, others)
        return plan

    def _fused_step(self, ignore_stale_grad):
        """Forward, pullback and the optimizer update as ONE program with
        weights and states donated, where the gradients to apply are the
        pending ones of one deferred `backward()` and all of them. False,
        with nothing touched, where they are not: `_update` then reads the
        gradients (the forward+pullback program runs) and updates."""
        from ..parallel.zero1 import zero1_enabled  # not at import time

        updater = self._updaters[0]
        if self._step_plans is None or self._kvstore \
                or len(self._updaters) != 1 or not updater.fused_ready() \
                or zero1_enabled():
            return False
        grad = next((g for p in self._params if p._grad
                     for g in p._grad.values()), None)
        # (a row_sparse gradient densifies when asked for its buffer)
        pending = grad._buf if type(grad) is NDArray else None
        if type(pending) is not PendingGrad or pending.owner is None:
            return False
        backward = pending.owner
        plan = self._step_plans.get(backward.key) or self._plan_step(backward)
        if plan is None or not plan.holds(backward, ignore_stale_grad):
            return False

        optimizer, indices = updater.optimizer, plan.indices
        updater.ensure_states(indices, plan.weights)
        states = [updater.states[i] for i in indices]
        if plan.states is None or any(
                a is not b for a, b in zip(states, plan.states)):
            plan.take_states(states, optimizer._fused_static_key())
        wanted = set(backward.wanted)
        weights = [backward.leaves[s] for s in backward.wanted]
        rest = [leaf for s, leaf in enumerate(backward.leaves)
                if s not in wanted]
        count_snap = _snapshot_counts(optimizer, indices)
        optimizer._update_count(indices)
        try:
            lrs, wds = optimizer._fused_hyperparams(indices)
            program = backward.program(optimizer.fused_update,
                                       plan.update_key)
            with tracing.span("trainer.dispatch", cat="gluon"):
                emitted, new_weights, new_states = program(
                    tuple(call.key for call in backward.calls), weights, rest,
                    [_state_to_jax(s) for s in states],
                    _np.asarray(lrs, _np.float32),
                    _np.asarray(wds, _np.float32),
                    _np.float32(optimizer.rescale_grad))
        except Exception as e:
            _raise_if_donated_consumed(weights, e)
            # the build failed before any buffer was consumed: the
            # gradients stay pending, and reading them takes the two
            # programs from now on
            _restore_counts(optimizer, count_snap)
            self._step_plans = None
            logging.getLogger("mxnet_tpu.gluon").warning(
                "the one-program training step failed to build (%r); "
                "backward and update run as two programs", e)
            return False
        backward.consumed(emitted)
        for arr, value in zip(plan.weights, new_weights):
            arr._buf = value
            arr._fresh_grad = False
        for state, value in zip(plan.state_nds, new_states):
            state._buf = value
        telemetry.counter("trainer.fused_step").inc()
        return True

    def _row_sparse_pull(self, parameter, row_id, full_idx=False):
        """Refresh the requested rows of a sparse parameter from the kvstore
        (parity trainer.py:289 `_row_sparse_pull`).

        Only meaningful when the optimizer runs ON the kvstore (the store
        then holds the authority copy, like the reference's servers); with
        local updates — the TPU dist default — every worker's weight is
        already authoritative and this is a no-op."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._kvstore is None or not self._update_on_kvstore:
            return
        import jax.numpy as jnp
        from ..ndarray import NDArray
        from ..ndarray.sparse import RowSparseNDArray

        idx = self._param2idx[id(parameter)]
        w = parameter._check_and_get(parameter._data, None)
        # a row_sparse out makes the store hand back only (indices, rows)
        tmp = RowSparseNDArray(
            NDArray(jnp.zeros((0,) + tuple(w.shape[1:]), w.dtype)),
            NDArray(jnp.zeros((0,), jnp.int32)), tuple(w.shape))
        self._kvstore.row_sparse_pull(idx, out=tmp, row_ids=row_id, priority=-idx)
        rows = tmp.indices._data.astype(jnp.int32)
        if rows.size:
            w._data = w._data.at[rows].set(tmp.data._data.astype(w.dtype))

    def save_states(self, fname):
        """Save optimizer (updater) states (parity trainer.py:419)."""
        assert self._optimizer is not None
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            assert not self._params_to_init, "Cannot save trainer states when some " \
                                             "parameters are not yet initialized in kvstore."
            self._kvstore.save_optimizer_states(fname, dump_optimizer=True)
        else:
            with open(fname, "wb") as fout:
                fout.write(self._updaters[0].get_states(dump_optimizer=True))

    def load_states(self, fname):
        """Load optimizer (updater) states (parity trainer.py:451)."""
        if not self._kv_initialized:
            self._init_kvstore()
        if self._params_to_init:
            self._init_params()
        if self._update_on_kvstore:
            self._kvstore.load_optimizer_states(fname)
            self._optimizer = self._kvstore._updater.optimizer
        else:
            with open(fname, "rb") as f:
                states = f.read()
            for updater in self._updaters:
                updater.set_states(states)
                updater.optimizer = self._updaters[0].optimizer
            self._optimizer = self._updaters[0].optimizer
        param_dict = {i: param for i, param in enumerate(self._params)}
        self._optimizer.param_dict = param_dict
