"""Optimizer class zoo.

Parity: `python/mxnet/optimizer/optimizer.py` — Optimizer base (registry,
lr/wd mult, index→param maps, num_update counting):46; SGD:511, Signum:657,
FTML:724, NAG:1031, SGLD:1083, Adam:1120, AdaGrad:1204, RMSProp:1263,
AdaDelta:1341, Ftrl:1401, Adamax:1477, Nadam:1534; Updater:1621 (serializable
state used by kvstore servers), get_updater:1712.

Each optimizer calls the fused update ops (`src/operator/optimizer_op.cc`
equivalents in `mxnet_tpu/ops/optimizer_ops.py`): one XLA program per
(op, shape) — weight, grad and state stream through HBM exactly once.

Fused whole-step path: optimizers that define :meth:`Optimizer.fused_update`
(SGD, NAG, Adam — others fall back to the eager per-op loop automatically)
expose the update as a *pure function* ``(weights, grads, states, lrs, wds,
rescale) -> (new_weights, new_states)`` over raw jax arrays. The
:class:`Updater` jits ONE such program for the entire parameter set
(donating weight+state buffers so XLA updates them in place), and
``Module``'s fused train step traces the same function together with
forward+backward — the whole training step as one XLA computation.
Hyperparameters that change every step (lr schedules, Adam bias
correction, rescale_grad) are *traced arguments*, so a changing lr never
recompiles.
"""
from __future__ import annotations

import logging
import math
import os
import pickle
import warnings

import numpy

from ..base import MXNetError, getenv
from ..compile_cache import CompileCache
from ..ndarray import NDArray, zeros, ones, full
from .. import ndarray as nd


def _is_low_precision(dtype):
    """True for dtypes that want a fp32 master copy under multi_precision.
    The reference checks fp16 only (`optimizer.py:230`); on TPU the native
    half type is bfloat16, so it gets the same master-copy treatment."""
    name = numpy.dtype(dtype).name if not hasattr(dtype, "name") else dtype.name
    return name in ("float16", "bfloat16")

__all__ = [
    "Optimizer", "SGD", "Signum", "FTML", "DCASGD", "NAG", "SGLD", "Adam", "AdaGrad",
    "RMSProp", "AdaDelta", "Ftrl", "Adamax", "Nadam", "LBSGD", "AdamW", "Test", "Updater",
    "get_updater", "register", "create",
]


class Optimizer:
    """The base class inherited by all optimizers (parity optimizer.py:46)."""

    opt_registry = {}

    @staticmethod
    def register(klass):
        assert isinstance(klass, type)
        name = klass.__name__.lower()
        if name in Optimizer.opt_registry:
            warnings.warn(f"WARNING: New optimizer {klass.__name__}.{name} is overriding "
                          f"existing optimizer {Optimizer.opt_registry[name].__name__}.{name}")
        Optimizer.opt_registry[name] = klass
        return klass

    @staticmethod
    def create_optimizer(name, **kwargs):
        """Instantiate an optimizer by registered name (parity :117)."""
        if name.lower() in Optimizer.opt_registry:
            return Optimizer.opt_registry[name.lower()](**kwargs)
        raise ValueError(f"Cannot find optimizer {name}")

    def __init__(self, rescale_grad=1.0, param_idx2name=None, wd=0.0,
                 clip_gradient=None, learning_rate=0.01, lr_scheduler=None, sym=None,
                 begin_num_update=0, multi_precision=False, param_dict=None):
        self.rescale_grad = rescale_grad
        self.lr = learning_rate
        self.lr_scheduler = lr_scheduler
        if lr_scheduler is not None:
            self.lr_scheduler.base_lr = learning_rate

        self.wd = wd
        self.lr_mult = {}
        self.wd_mult = {}
        self.begin_num_update = begin_num_update
        self.num_update = begin_num_update
        self._index_update_count = {}
        self.clip_gradient = clip_gradient
        self.multi_precision = multi_precision
        self.aggregate_num = 0

        if param_idx2name is None:
            param_idx2name = {}
        assert isinstance(param_idx2name, dict), \
            "param_idx2name should be a dict of param indexes to names."
        self.idx2name = param_idx2name.copy()
        self.sym_info = ()
        self.param_dict = param_dict if param_dict else {}

        self.set_lr_mult({})
        self.set_wd_mult({})

    def create_state(self, index, weight):
        """Create auxiliary state for a given weight."""

    def create_state_multi_precision(self, index, weight):
        """Create aux state + fp32 master copy when multi_precision and
        weight is fp16 (parity :230)."""
        weight_master_copy = None
        if self.multi_precision and _is_low_precision(weight.dtype):
            weight_master_copy = weight.astype(numpy.float32)
            return (weight_master_copy,) + (self.create_state(index, weight_master_copy),)
        if _is_low_precision(weight.dtype) and not self.multi_precision:
            warnings.warn("Accumulating with float16 in optimizer can lead to "
                          "poor accuracy or slow convergence. "
                          "Consider using multi_precision=True option of the "
                          "optimizer")
        return self.create_state(index, weight)

    def update(self, index, weight, grad, state):
        """Update weight given gradient and state."""
        raise NotImplementedError()

    def update_multi_precision(self, index, weight, grad, state):
        if self.multi_precision and _is_low_precision(weight.dtype):
            weight_master_copy = state[0]
            original_state = state[1]
            grad32 = grad.astype(numpy.float32)
            self.update(index, weight_master_copy, grad32, original_state)
            weight[:] = weight_master_copy.astype(weight.dtype)
        else:
            self.update(index, weight, grad, state)

    # -- fused (jitted) whole-step update ------------------------------------
    #
    # The functional rendering of update_multi_precision over ALL parameters
    # at once: pure jax math over raw arrays, traceable inside one jitted
    # train step. Semantics must mirror the eager per-op path exactly (same
    # fp32 casts, same op order) — the eager loop stays the correctness
    # reference and tests/python/unittest/test_fused_step.py asserts parity.

    fused_update_supported = False

    def fused_update(self, weights, grads, states, lrs, wds, rescale_grad):
        """Pure functional update over raw jax arrays.

        ``weights``/``grads`` are lists of arrays; ``states`` the per-weight
        state trees from :meth:`create_state_multi_precision` with NDArray
        leaves replaced by arrays; ``lrs``/``wds`` per-weight scalars (traced
        — any step-dependent correction is already applied by
        :meth:`_fused_hyperparams`); ``rescale_grad`` a traced scalar.
        Returns ``(new_weights, new_states)`` with the same structure."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused update; the caller must "
            "check fused_update_supported and fall back to the eager loop")

    def _fused_hyperparams(self, indices):
        """Per-index (lrs, wds) with any update-count-dependent correction
        (e.g. Adam bias correction) applied host-side in float64 — exactly
        the numbers the eager path bakes into its op attrs. Call AFTER
        :meth:`_update_count`."""
        return self._get_lrs(indices), self._get_wds(indices)

    def _fused_static_key(self):
        """Everything trace-relevant that is NOT a traced argument — part of
        the CompileCache key, so mutating one of these recompiles instead of
        silently reusing a stale executable."""
        return (type(self).__name__, self.clip_gradient, self.multi_precision)

    def fused_state_init(self, w32, dtype):
        """Fresh optimizer state for ONE flat weight bucket of ``dtype``,
        as the tree :meth:`fused_update` expects for a single parameter —
        the traceable rendering of :meth:`create_state_multi_precision`
        over a packed bucket. ``w32`` is the fp32 cast of the bucket (the
        master copy under multi-precision). Used by the ZeRO-1 sharded
        update (`parallel/zero1.py`), which jits this with a dp-sharded
        output layout so only 1/N of the state ever materializes per
        replica; optimizers without it fall back to the replicated path."""
        raise NotImplementedError(
            f"{type(self).__name__} has no fused flat-state init; the "
            "caller must fall back to the replicated update")

    def set_learning_rate(self, lr):
        if self.lr_scheduler is not None:
            raise UserWarning("LRScheduler of the optimizer has already been defined. "
                              "Note that set_learning_rate can mutate the value of "
                              "the learning rate of the optimizer only when "
                              "the LRScheduler of the optimizer is undefined.")
        self.lr = lr

    def set_lr_mult(self, args_lr_mult):
        """Set individual learning-rate multipliers (parity :330)."""
        self.lr_mult = {}
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__lr_mult__" in attr[name]:
                    self.lr_mult[name] = float(attr[name]["__lr_mult__"])
        self.lr_mult.update(args_lr_mult)

    def set_wd_mult(self, args_wd_mult):
        """Set individual weight-decay multipliers (parity :372). By default
        wd is not applied to biases/gamma/beta (names not ending in _weight
        or _gamma get 0 only via attr route in reference; gluon passes
        param_dict so wd_mult comes from Parameters)."""
        self.wd_mult = {}
        for n in self.idx2name.values():
            is_weight = n.endswith("_weight")
            if not is_weight:
                self.wd_mult[n] = 0.0
        if self.sym_info:
            attr, arg_names = self.sym_info
            for name in arg_names:
                if name in attr and "__wd_mult__" in attr[name]:
                    self.wd_mult[name] = float(attr[name]["__wd_mult__"])
        self.wd_mult.update(args_wd_mult)

    def _update_count(self, index):
        if not isinstance(index, (list, tuple)):
            index = [index]
        for idx in index:
            if idx not in self._index_update_count:
                self._index_update_count[idx] = self.begin_num_update
            self._index_update_count[idx] += 1
            self.num_update = max(self._index_update_count[idx], self.num_update)

    def _get_lrs(self, indices):
        """Learning rates for indices (parity :437). The scheduler is
        consulted once per num_update value, not once per parameter/chunk —
        a 160-param step costs one scheduler call, not 160."""
        if self.lr_scheduler is not None:
            memo = getattr(self, "_lr_sched_memo", None)
            if memo is None or memo[0] != self.num_update:
                memo = (self.num_update, self.lr_scheduler(self.num_update))
                self._lr_sched_memo = memo
            lr = memo[1]
        else:
            lr = self.lr

        lrs = [lr for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                lrs[i] *= self.param_dict[index].lr_mult
            elif index in self.lr_mult:
                lrs[i] *= self.lr_mult[index]
            elif index in self.idx2name:
                lrs[i] *= self.lr_mult.get(self.idx2name[index], 1.0)
        return lrs

    def _get_lr(self, index):
        return self._get_lrs([index])[0]

    def _get_wds(self, indices):
        wds = [self.wd for _ in indices]
        for i, index in enumerate(indices):
            if index in self.param_dict:
                wds[i] *= self.param_dict[index].wd_mult
            elif index in self.wd_mult:
                wds[i] *= self.wd_mult[index]
            elif index in self.idx2name:
                wds[i] *= self.wd_mult.get(self.idx2name[index], 1.0)
        return wds

    def _get_wd(self, index):
        return self._get_wds([index])[0]

    def __getstate__(self):
        ret = self.__dict__.copy()
        del ret["sym_info"]
        return ret

    def __setstate__(self, state):
        self.__dict__ = state
        self.sym_info = ()


register = Optimizer.register
create = Optimizer.create_optimizer


def _flatten_list(nested_list):
    return [item for sublist in nested_list for item in sublist]


def _sparse_sgd_update(weight, grad, state, lr, wd, rescale_grad,
                       clip_gradient, momentum):
    """Lazy (rows-only) SGD for row_sparse grads — the reference's
    sgd(_mom)_update with lazy_update=True on a row_sparse grad
    (`src/operator/optimizer_op.cc` SGDUpdateRspImpl): weight, momentum and
    wd touch ONLY the occupied rows; a 1M-row table costs O(batch) per step."""
    import jax.numpy as jnp

    rows = grad.indices._data.astype(jnp.int32)
    if rows.size == 0:
        return
    g = grad.data._data.astype(weight.dtype) * rescale_grad
    if clip_gradient:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    w = weight._data
    wr = jnp.take(w, rows, axis=0)
    g = g + wd * wr
    if momentum and state is not None:
        m = state._data
        mr = jnp.take(m, rows, axis=0) * momentum - lr * g
        state._data = m.at[rows].set(mr)
        weight._data = w.at[rows].set(wr + mr)
    else:
        weight._data = w.at[rows].set(wr - lr * g)


def _sparse_adam_update(weight, grad, state, lr, wd, rescale_grad,
                        clip_gradient, beta1, beta2, epsilon):
    """Lazy (rows-only) Adam for row_sparse grads (reference
    AdamUpdateRspImpl, `optimizer_op.cc`): mean/var state rows decay only
    where the grad has rows."""
    import jax.numpy as jnp

    rows = grad.indices._data.astype(jnp.int32)
    if rows.size == 0:
        return
    g = grad.data._data.astype(weight.dtype) * rescale_grad
    if clip_gradient:
        g = jnp.clip(g, -clip_gradient, clip_gradient)
    mean, var = state
    w = weight._data
    wr = jnp.take(w, rows, axis=0)
    g = g + wd * wr
    mr = beta1 * jnp.take(mean._data, rows, axis=0) + (1 - beta1) * g
    vr = beta2 * jnp.take(var._data, rows, axis=0) + (1 - beta2) * g * g
    mean._data = mean._data.at[rows].set(mr)
    var._data = var._data.at[rows].set(vr)
    weight._data = w.at[rows].set(wr - lr * mr / (jnp.sqrt(vr) + epsilon))


@register
class SGD(Optimizer):
    """Stochastic gradient descent w/ momentum and multi-precision
    (parity optimizer.py:511; fused ops sgd_update/sgd_mom_update/mp_*)."""

    def __init__(self, momentum=0.0, lazy_update=True, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.lazy_update = lazy_update
        # fused multi-weight updates (reference optimizer.py:530: aggregation
        # over MXNET_OPTIMIZER_AGGREGATION_SIZE weights per multi_sgd_* call)
        self.aggregate_num = max(1, min(
            60, int(os.getenv("MXNET_OPTIMIZER_AGGREGATION_SIZE", "4"))))

    def create_state_multi_precision(self, index, weight):
        weight_master_copy = None
        if self.multi_precision and _is_low_precision(weight.dtype):
            weight_master_copy = weight.astype(numpy.float32)
            return (self.create_state(index, weight_master_copy), weight_master_copy)
        if _is_low_precision(weight.dtype) and not self.multi_precision:
            warnings.warn("Accumulating with float16 in optimizer can lead to "
                          "poor accuracy or slow convergence. "
                          "Consider using multi_precision=True option of the "
                          "SGD optimizer")
        return self.create_state(index, weight)

    def create_state(self, index, weight):
        momentum = None
        if self.momentum != 0.0:
            momentum = zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        return momentum

    def _update_impl(self, index, weight, grad, state, multi_precision=False):
        aggregate = isinstance(index, (list, tuple))
        if aggregate:
            return self._update_aggregate(index, weight, grad, state,
                                          multi_precision)
        use_multi_precision = multi_precision and isinstance(state, (list, tuple))
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        kwargs = {"rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient

        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray) and self.lazy_update and \
                not use_multi_precision:
            _sparse_sgd_update(weight, grad, state, lr, wd, self.rescale_grad,
                               self.clip_gradient, self.momentum)
            return
        if not use_multi_precision:
            if state is not None:
                nd.sgd_mom_update(weight, grad, state, out=weight,
                                  lazy_update=self.lazy_update, **kwargs)
            else:
                nd.sgd_update(weight, grad, out=weight,
                              lazy_update=self.lazy_update, **kwargs)
        else:
            if state[0] is not None:
                nd.mp_sgd_mom_update(weight, grad, state[0], state[1], out=weight,
                                     lazy_update=self.lazy_update, **kwargs)
            else:
                nd.mp_sgd_update(weight, grad, state[1], out=weight,
                                 lazy_update=self.lazy_update, **kwargs)

    def _update_aggregate(self, indices, weights, grads, states,
                          multi_precision):
        """One fused multi_sgd_* call over a group of weights (reference
        optimizer.py:559-595 aggregate branch → `optimizer_op.cc`
        MultiSGDUpdate): a single XLA program streams every (weight, grad,
        state) through HBM, amortizing dispatch over the group."""
        self._update_count(indices)
        lrs = self._get_lrs(indices)
        wds = self._get_wds(indices)
        kwargs = {"rescale_grad": self.rescale_grad, "lrs": lrs, "wds": wds,
                  "num_weights": len(indices)}
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient
        if not multi_precision:
            if self.momentum > 0:
                data = _flatten_list(zip(weights, grads, states))
                nd.multi_sgd_mom_update(*data, out=list(weights), **kwargs)
            else:
                data = _flatten_list(zip(weights, grads))
                nd.multi_sgd_update(*data, out=list(weights), **kwargs)
        else:
            if self.momentum > 0:
                data = _flatten_list(
                    (w, g, s[0], s[1]) for w, g, s in zip(weights, grads, states))
                nd.multi_mp_sgd_mom_update(*data, out=list(weights), **kwargs)
            else:
                data = _flatten_list(
                    (w, g, s[1]) for w, g, s in zip(weights, grads, states))
                nd.multi_mp_sgd_update(*data, out=list(weights), **kwargs)

    def update(self, index, weight, grad, state):
        self._update_impl(index, weight, grad, state, multi_precision=False)

    def update_multi_precision(self, index, weight, grad, state):
        if isinstance(index, (list, tuple)):
            use_multi_precision = self.multi_precision and \
                _is_low_precision(weight[0].dtype)
        else:
            use_multi_precision = self.multi_precision and \
                _is_low_precision(weight.dtype)
        self._update_impl(index, weight, grad, state,
                          multi_precision=use_multi_precision)

    fused_update_supported = True

    def _fused_static_key(self):
        return super()._fused_static_key() + (self.momentum,)

    def fused_state_init(self, w32, dtype):
        """Flat-bucket state matching create_state_multi_precision: mp ->
        (momentum|None in fp32, master); else momentum|None in weight
        dtype."""
        import jax.numpy as jnp

        mp = self.multi_precision and _is_low_precision(dtype)
        mom = None
        if self.momentum != 0.0:
            mom = jnp.zeros_like(w32, dtype=jnp.float32 if mp else dtype)
        return (mom, w32) if mp else mom

    def fused_update(self, weights, grads, states, lrs, wds, rescale_grad):
        """Mirrors sgd_update / sgd_mom_update / mp_sgd_* (optimizer_ops.py)
        over the whole parameter list: fp32 math, results cast back."""
        import jax.numpy as jnp

        clip = float(self.clip_gradient) if self.clip_gradient else 0.0
        mom = float(self.momentum)
        new_ws, new_ss = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            mp = self.multi_precision and _is_low_precision(w.dtype)
            if mp:
                m, w32 = s  # create_state_multi_precision: (mom|None, master)
            else:
                m, w32 = s, w.astype(jnp.float32)
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip > 0:
                g32 = jnp.clip(g32, -clip, clip)
            g32 = g32 + wd * w32
            # branch on STATE PRESENCE exactly like the eager path's
            # `if state is not None: sgd_mom_update else sgd_update` — a
            # momentum later set to 0 still updates the existing state
            # (with mom==0), never nulls it
            if m is not None:
                new_m = mom * (m if mp else m.astype(jnp.float32)) - lr * g32
                new_w32 = w32 + new_m
            else:
                new_m = None
                new_w32 = w32 - lr * g32
            new_ws.append(new_w32.astype(w.dtype))
            if mp:
                new_ss.append((new_m, new_w32))
            else:
                new_ss.append(None if new_m is None else new_m.astype(m.dtype))
        return new_ws, new_ss


@register
class Signum(Optimizer):
    """SignSGD / Signum (parity optimizer.py:657)."""

    def __init__(self, learning_rate=0.01, momentum=0.9, wd_lh=0.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.momentum = momentum
        self.wd_lh = wd_lh

    def create_state(self, index, weight):
        momentum = None
        if self.momentum != 0.0:
            momentum = zeros(weight.shape, ctx=weight.context, dtype=weight.dtype)
        return momentum

    def _update_impl(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        kwargs = {"rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient
        if self.wd_lh:
            kwargs["wd_lh"] = self.wd_lh

        if state is not None:
            nd.signum_update(weight, grad, state, out=weight, **kwargs)
        else:
            nd.signsgd_update(weight, grad, out=weight, **kwargs)

    def update(self, index, weight, grad, state):
        self._update_impl(index, weight, grad, state)


@register
class FTML(Optimizer):
    """The FTML optimizer (parity optimizer.py:724)."""

    def __init__(self, beta1=0.6, beta2=0.999, epsilon=1e-8, **kwargs):
        super().__init__(**kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # d_0
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype),  # v_0
                zeros(weight.shape, ctx=weight.context, dtype=weight.dtype))  # z_0

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]

        kwargs = {"lr": lr, "wd": wd, "t": t, "beta1": self.beta1, "beta2": self.beta2,
                  "epsilon": self.epsilon, "rescale_grad": self.rescale_grad}
        if self.clip_gradient:
            kwargs["clip_grad"] = self.clip_gradient
        prev_d, prev_v, prev_z = state
        nd.ftml_update(weight, grad, prev_d, prev_v, prev_z, out=weight, **kwargs)


@register
class DCASGD(Optimizer):
    """Delay-compensated async SGD (parity optimizer.py:975)."""

    def __init__(self, momentum=0.0, lamda=0.04, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum
        self.weight_previous = {}
        self.lamda = lamda

    def create_state(self, index, weight):
        if self.momentum == 0.0:
            return (None, weight.copy())
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                weight.copy())

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)

        mom, previous_weight = state
        if mom:
            mom[:] *= self.momentum
            mom[:] += -lr * (grad + wd * weight + self.lamda
                             * grad * grad * (weight - previous_weight))
        else:
            assert self.momentum == 0.0
            mom = -lr * (grad + wd * weight + self.lamda
                         * grad * grad * (weight - previous_weight))
        previous_weight[:] = weight
        weight[:] += mom


@register
class NAG(Optimizer):
    """Nesterov accelerated gradient (parity optimizer.py:1031)."""

    def __init__(self, momentum=0.0, **kwargs):
        super().__init__(**kwargs)
        self.momentum = momentum

    def create_state(self, index, weight):
        momentum = None
        if self.momentum != 0.0:
            momentum = zeros(weight.shape, weight.context, dtype=weight.dtype)
        return momentum

    def create_state_multi_precision(self, index, weight):
        weight_master_copy = None
        if self.multi_precision and weight.dtype == numpy.float16:
            weight_master_copy = weight.astype(numpy.float32)
            return (self.create_state(index, weight_master_copy), weight_master_copy)
        return self.create_state(index, weight)

    def _update_impl(self, index, weight, grad, state, multi_precision=False):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        kwargs = {"rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient

        if not multi_precision:
            if state is not None:
                nd.nag_mom_update(weight, grad, state, out=weight, **kwargs)
            else:
                nd.sgd_update(weight, grad, out=weight, **kwargs)
        else:
            if state[0] is not None:
                nd.mp_nag_mom_update(weight, grad, state[0], state[1],
                                     out=weight, **kwargs)
            else:
                nd.mp_sgd_update(weight, grad, state[1], out=weight, **kwargs)

    def update(self, index, weight, grad, state):
        self._update_impl(index, weight, grad, state, multi_precision=False)

    def update_multi_precision(self, index, weight, grad, state):
        use_multi_precision = self.multi_precision and weight.dtype == numpy.float16
        self._update_impl(index, weight, grad, state,
                          multi_precision=use_multi_precision)

    fused_update_supported = True

    def _fused_static_key(self):
        return super()._fused_static_key() + (self.momentum,)

    def fused_state_init(self, w32, dtype):
        """Like SGD's, but NAG's multi-precision check is fp16-only
        (parity :1031)."""
        import jax.numpy as jnp

        mp = self.multi_precision and numpy.dtype(dtype) == numpy.float16
        mom = None
        if self.momentum != 0.0:
            mom = jnp.zeros_like(w32, dtype=jnp.float32 if mp else dtype)
        return (mom, w32) if mp else mom

    def fused_update(self, weights, grads, states, lrs, wds, rescale_grad):
        """Mirrors nag_mom_update / mp_nag_mom_update / sgd_update."""
        import jax.numpy as jnp

        clip = float(self.clip_gradient) if self.clip_gradient else 0.0
        mom = float(self.momentum)
        new_ws, new_ss = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            # NAG's eager mp check is fp16-only (parity :1031)
            mp = self.multi_precision and numpy.dtype(w.dtype) == numpy.float16
            if mp:
                m, w32 = s
            else:
                m, w32 = s, w.astype(jnp.float32)
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip > 0:
                g32 = jnp.clip(g32, -clip, clip)
            g32 = g32 + wd * w32
            # state presence decides the branch (eager: `if state is not
            # None: nag_mom_update`), so a zeroed momentum keeps its state
            if m is not None:
                new_m = mom * (m if mp else m.astype(jnp.float32)) + g32
                new_w32 = w32 - lr * (g32 + mom * new_m)
            else:
                new_m = None
                new_w32 = w32 - lr * g32
            new_ws.append(new_w32.astype(w.dtype))
            if mp:
                new_ss.append((new_m, new_w32))
            else:
                new_ss.append(None if new_m is None else new_m.astype(m.dtype))
        return new_ws, new_ss


@register
class SGLD(Optimizer):
    """Stochastic Gradient Langevin Dynamics (parity optimizer.py:1083)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return None

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)
        weight[:] += -lr / 2 * (grad + wd * weight)
        weight[:] += nd.random.normal(0, math.sqrt(lr), shape=weight.shape,
                                      dtype=weight.dtype, ctx=weight.context)


@register
class Adam(Optimizer):
    """Adam (parity optimizer.py:1120; fused op adam_update)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 lazy_update=True, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.lazy_update = lazy_update

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, weight.context, dtype=weight.dtype))  # variance

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        t = self._index_update_count[index]
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1

        kwargs = {"beta1": self.beta1, "beta2": self.beta2, "epsilon": self.epsilon,
                  "rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient

        from ..ndarray.sparse import RowSparseNDArray

        if isinstance(grad, RowSparseNDArray) and self.lazy_update:
            _sparse_adam_update(weight, grad, state, lr, wd, self.rescale_grad,
                                self.clip_gradient, self.beta1, self.beta2,
                                self.epsilon)
            return

        mean, var = state
        nd.adam_update(weight, grad, mean, var, out=weight,
                       lazy_update=self.lazy_update, **kwargs)

    fused_update_supported = True

    def _fused_static_key(self):
        return super()._fused_static_key() + (self.beta1, self.beta2,
                                              self.epsilon)

    def fused_state_init(self, w32, dtype):
        """Flat-bucket state matching the base-class multi-precision
        convention: mp -> (master, (mean, var) in fp32); else (mean, var)
        in weight dtype."""
        import jax.numpy as jnp

        mp = self.multi_precision and _is_low_precision(dtype)
        sd = jnp.float32 if mp else dtype
        mean = jnp.zeros_like(w32, dtype=sd)
        var = jnp.zeros_like(w32, dtype=sd)
        return (w32, (mean, var)) if mp else (mean, var)

    def _fused_hyperparams(self, indices):
        """Bias correction applied host-side in float64 — bit-identical to
        the lr the eager update() bakes into adam_update's attrs."""
        lrs, wds = super()._fused_hyperparams(indices)
        out = []
        for lr, index in zip(lrs, indices):
            t = self._index_update_count[index]
            coef1 = 1. - self.beta1 ** t
            coef2 = 1. - self.beta2 ** t
            out.append(lr * math.sqrt(coef2) / coef1)
        return out, wds

    def fused_update(self, weights, grads, states, lrs, wds, rescale_grad):
        """Mirrors adam_update (optimizer_ops.py) with the base-class
        multi-precision convention: state = (master, (mean, var))."""
        import jax.numpy as jnp

        clip = float(self.clip_gradient) if self.clip_gradient else 0.0
        b1, b2, eps = float(self.beta1), float(self.beta2), float(self.epsilon)
        new_ws, new_ss = [], []
        for w, g, s, lr, wd in zip(weights, grads, states, lrs, wds):
            mp = self.multi_precision and _is_low_precision(w.dtype)
            if mp:
                w32, (mean, var) = s
            else:
                w32, (mean, var) = w.astype(jnp.float32), s
            g32 = g.astype(jnp.float32) * rescale_grad
            if clip > 0:
                g32 = jnp.clip(g32, -clip, clip)
            g32 = g32 + wd * w32
            new_mean = b1 * mean.astype(jnp.float32) + (1 - b1) * g32
            new_var = b2 * var.astype(jnp.float32) + (1 - b2) * jnp.square(g32)
            new_w32 = w32 - lr * new_mean / (jnp.sqrt(new_var) + eps)
            new_ws.append(new_w32.astype(w.dtype))
            if mp:
                new_ss.append((new_w32, (new_mean, new_var)))
            else:
                new_ss.append((new_mean.astype(mean.dtype),
                               new_var.astype(var.dtype)))
        return new_ws, new_ss


@register
class AdamW(Optimizer):
    """Adam with decoupled weight decay (contrib `adamw.cc`; the transformer
    default — north-star config 3)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 eta=1.0, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.eta = eta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),
                zeros(weight.shape, weight.context, dtype=weight.dtype))

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]
        coef1 = 1. - self.beta1 ** t
        coef2 = 1. - self.beta2 ** t
        lr *= math.sqrt(coef2) / coef1
        kwargs = {"beta1": self.beta1, "beta2": self.beta2, "epsilon": self.epsilon,
                  "rescale_grad": self.rescale_grad, "lr": lr, "wd": wd, "eta": self.eta}
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient
        mean, var = state
        nd.contrib_adamw_update(weight, grad, mean, var, out=weight, **kwargs)


@register
class AdaGrad(Optimizer):
    """AdaGrad (parity optimizer.py:1204)."""

    def __init__(self, eps=1e-7, **kwargs):
        super().__init__(**kwargs)
        self.float_stable_eps = eps

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context, stype=weight.stype)

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)
        history = state
        history[:] += nd.square(grad)
        div = grad / nd.sqrt(history + self.float_stable_eps)
        weight[:] += (div + weight * wd) * -lr


@register
class RMSProp(Optimizer):
    """RMSProp (parity optimizer.py:1263; centered=True uses Graves 2013)."""

    def __init__(self, learning_rate=0.001, gamma1=0.9, gamma2=0.9, epsilon=1e-8,
                 centered=False, clip_weights=None, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.gamma1 = gamma1
        self.gamma2 = gamma2
        self.centered = centered
        self.epsilon = epsilon
        self.clip_weights = clip_weights

    def create_state(self, index, weight):
        if self.centered:
            return (zeros(weight.shape, weight.context, stype=weight.stype),  # n
                    zeros(weight.shape, weight.context, stype=weight.stype),  # g
                    zeros(weight.shape, weight.context, stype=weight.stype))  # delta
        return (zeros(weight.shape, weight.context, stype=weight.stype),)  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        kwargs = {"gamma1": self.gamma1, "epsilon": self.epsilon,
                  "rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.centered:
            kwargs["gamma2"] = self.gamma2
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient
        if self.clip_weights:
            kwargs["clip_weights"] = self.clip_weights

        if not self.centered:
            (n,) = state
            nd.rmsprop_update(weight, grad, n, out=weight, **kwargs)
        else:
            n, g, delta = state
            nd.rmspropalex_update(weight, grad, n, g, delta, out=weight, **kwargs)


@register
class AdaDelta(Optimizer):
    """AdaDelta (parity optimizer.py:1341)."""

    def __init__(self, rho=0.90, epsilon=1e-5, **kwargs):
        super().__init__(**kwargs)
        self.rho = rho
        self.epsilon = epsilon

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context),  # accumulated g
                zeros(weight.shape, weight.context))  # accumulated delta

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)

        grad = grad * self.rescale_grad
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)

        acc_g, acc_delta = state
        acc_g[:] *= self.rho
        acc_g[:] += (1. - self.rho) * grad * grad
        current_delta = nd.sqrt(acc_delta + self.epsilon) / \
            nd.sqrt(acc_g + self.epsilon) * grad
        acc_delta[:] *= self.rho
        acc_delta[:] += (1. - self.rho) * current_delta * current_delta
        weight[:] -= current_delta + wd * weight


@register
class Ftrl(Optimizer):
    """FTRL (parity optimizer.py:1401; fused op ftrl_update)."""

    def __init__(self, lamda1=0.01, learning_rate=0.1, beta=1, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.lamda1 = lamda1
        self.beta = beta

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, stype=weight.stype),  # z
                zeros(weight.shape, weight.context, stype=weight.stype))  # n

    def update(self, index, weight, grad, state):
        self._update_count(index)
        wd = self._get_wd(index)
        lr = self._get_lr(index)

        kwargs = {"lamda1": self.lamda1, "beta": self.beta,
                  "rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient

        z, n = state
        nd.ftrl_update(weight, grad, z, n, out=weight, **kwargs)


@register
class Adamax(Optimizer):
    """AdaMax, infinity-norm Adam variant (parity optimizer.py:1477)."""

    def __init__(self, learning_rate=0.002, beta1=0.9, beta2=0.999, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, weight.context, dtype=weight.dtype))  # variance

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        t = self._index_update_count[index]
        lr /= (1. - self.beta1 ** t)

        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)

        m_t, u_t = state
        m_t[:] *= self.beta1
        m_t[:] += (1. - self.beta1) * grad
        u_t[:] = nd.maximum(self.beta2 * u_t, nd.abs(grad))
        weight[:] -= lr * m_t / u_t


@register
class Nadam(Optimizer):
    """Nesterov Adam (parity optimizer.py:1534)."""

    def __init__(self, learning_rate=0.001, beta1=0.9, beta2=0.999, epsilon=1e-8,
                 schedule_decay=0.004, **kwargs):
        super().__init__(learning_rate=learning_rate, **kwargs)
        self.beta1 = beta1
        self.beta2 = beta2
        self.epsilon = epsilon
        self.schedule_decay = schedule_decay
        self.m_schedule = 1.

    def create_state(self, index, weight):
        return (zeros(weight.shape, weight.context, dtype=weight.dtype),  # mean
                zeros(weight.shape, weight.context, dtype=weight.dtype))  # variance

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)
        t = self._index_update_count[index]

        grad = grad * self.rescale_grad + wd * weight
        if self.clip_gradient is not None:
            grad = nd.clip(grad, -self.clip_gradient, self.clip_gradient)

        momentum_t = self.beta1 * (1. - 0.5 * (pow(0.96, t * self.schedule_decay)))
        momentum_t_1 = self.beta1 * (1. - 0.5 * (pow(0.96, (t + 1) * self.schedule_decay)))
        self.m_schedule = self.m_schedule * momentum_t
        m_schedule_next = self.m_schedule * momentum_t_1

        m_t, v_t = state
        m_t[:] *= self.beta1
        m_t[:] += (1. - self.beta1) * grad
        v_t[:] *= self.beta2
        v_t[:] += (1. - self.beta2) * grad * grad

        grad_prime = grad / (1. - self.m_schedule)
        m_t_prime = m_t / (1. - m_schedule_next)
        v_t_prime = v_t / (1. - pow(self.beta2, t))
        m_t_bar = (1. - momentum_t) * grad_prime + momentum_t_1 * m_t_prime

        weight[:] -= lr * m_t_bar / (nd.sqrt(v_t_prime) + self.epsilon)


@register
class LBSGD(Optimizer):
    """Large-batch SGD with LARS layer-wise adaptive rates
    (parity optimizer.py:782; simplified warmup strategies)."""

    def __init__(self, momentum=0.0, multi_precision=False, warmup_strategy="linear",
                 warmup_epochs=5, batch_scale=1, updates_per_epoch=32, begin_epoch=0,
                 num_epochs=60, **kwargs):
        super().__init__(**kwargs)
        logging.info("Running Large-Batch SGD Algorithm")
        logging.info("(Batch_scale=%f, warmup_epochs=%d, warmup_strategy=%s, "
                     "updates_per_epoch=%d)", batch_scale, warmup_epochs,
                     warmup_strategy, updates_per_epoch)
        self.momentum = momentum
        self.multi_precision = multi_precision
        self.warmup_strategy = warmup_strategy
        self.warmup_epochs = warmup_epochs
        self.batch_scale = batch_scale
        self.updates_per_epoch = updates_per_epoch
        self.init_updates = begin_epoch * updates_per_epoch
        self.num_epochs = num_epochs
        self.lbmult = 1
        self.cumgrads = {}
        self.adaptive = False
        self.admult = 1

    def create_state(self, index, weight):
        momentum = None
        weight_master_copy = None
        if self.multi_precision and weight.dtype == numpy.float16:
            weight_master_copy = weight.astype(numpy.float32)
            if self.momentum != 0.0:
                momentum = zeros(weight.shape, weight.context, dtype=numpy.float32)
            return (momentum, weight_master_copy)
        if self.momentum != 0.0:
            momentum = zeros(weight.shape, weight.context, dtype=weight.dtype)
        return momentum

    def _get_lbmult(self, nup):
        nwup = self.warmup_epochs * self.updates_per_epoch
        strategy = self.warmup_strategy
        maxmult = float(self.batch_scale)
        if nup >= nwup:
            mult = maxmult
        elif nwup <= 1:
            mult = 1.0
        else:
            if strategy == "linear":
                mult = 1.0 + (maxmult - 1) * nup / nwup
            elif strategy == "power2":
                mult = 1.0 + (maxmult - 1) * (nup * nup) / (nwup * nwup)
            elif strategy == "sqrt":
                mult = 1.0 + (maxmult - 1) * math.sqrt(float(nup) / nwup)
            else:
                mult = 1.0
        return mult

    def _get_lars(self, weight, g, wd):
        """LARS trust ratio for one weight."""
        weight2 = (weight * weight).sum().asscalar()
        grad2 = (g * g).sum().asscalar()
        lars = math.sqrt(weight2 / (grad2 + wd * weight2 + 1e-18))
        if lars < 0.01:
            lars = 0.01
        elif lars > 100:
            lars = 100
        return lars

    def update(self, index, weight, grad, state):
        self._update_count(index)
        lr = self._get_lr(index)
        wd = self._get_wd(index)

        if self.warmup_strategy == "lars":
            lbmult = self._get_lars(weight, grad, wd)
        else:
            lbmult = self._get_lbmult(self.num_update)
        lr = lr * lbmult

        kwargs = {"rescale_grad": self.rescale_grad, "lr": lr, "wd": wd}
        if self.momentum > 0:
            kwargs["momentum"] = self.momentum
        if self.clip_gradient:
            kwargs["clip_gradient"] = self.clip_gradient

        use_multi_precision = isinstance(state, (list, tuple))
        if not use_multi_precision:
            if state is not None:
                nd.sgd_mom_update(weight, grad, state, out=weight, **kwargs)
            else:
                nd.sgd_update(weight, grad, out=weight, **kwargs)
        else:
            if state[0] is not None:
                nd.mp_sgd_mom_update(weight, grad, state[0], state[1], out=weight,
                                     **kwargs)
            else:
                nd.mp_sgd_update(weight, grad, state[1], out=weight, **kwargs)


@register
class Test(Optimizer):
    """Simple test optimizer (parity optimizer.py Test)."""

    def __init__(self, **kwargs):
        super().__init__(**kwargs)

    def create_state(self, index, weight):
        return zeros(weight.shape, weight.context)

    def update(self, index, weight, grad, state):
        weight[:] += grad * self.rescale_grad
        state[:] = weight


create = Optimizer.create_optimizer


def _state_sig(s):
    """Hashable shape/dtype signature of one state tree (CompileCache key).
    Built every step — dtype objects, not strings."""
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_state_sig(x) for x in s)
    return (s._data.shape, s._data.dtype)


def _state_to_jax(s):
    """NDArray leaves -> raw jax arrays (same structure)."""
    if s is None:
        return None
    if isinstance(s, (tuple, list)):
        return tuple(_state_to_jax(x) for x in s)
    return s._data


def _state_writeback(s, new):
    """Swap each NDArray leaf's buffer for the corresponding new array —
    the functional rendering of the reference's in-place state mutation.
    A None in ``new`` against a live leaf means the update did not touch
    that state — keep the old buffer, never null a live NDArray."""
    if s is None or new is None:
        return
    if isinstance(s, (tuple, list)):
        for a, b in zip(s, new):
            _state_writeback(a, b)
    else:
        s._data = new


def _snapshot_counts(opt, indices):
    """Snapshot update-count bookkeeping so a fused step that fails BEFORE
    executing (trace/compile error — buffers untouched) can fall back to
    the eager loop without double-counting the step."""
    return (opt.num_update,
            {i: opt._index_update_count.get(i) for i in indices})


def _restore_counts(opt, snap):
    num_update, counts = snap
    for i, v in counts.items():
        if v is None:
            opt._index_update_count.pop(i, None)
        else:
            opt._index_update_count[i] = v
    opt.num_update = num_update


def _any_donated_deleted(arrays):
    """True when any donated input buffer was actually consumed — the line
    between 'retry eagerly' (trace/compile failed, weights intact) and
    'weights are gone, restore from checkpoint'."""
    out = False
    for a in arrays:
        try:
            out = out or a.is_deleted()
        except Exception:  # noqa: BLE001 — conservative: treat as deleted
            out = True
    return out


def _raise_if_donated_consumed(arrays, err):
    """After a fused update raised: execution consumed donated inputs
    before failing — weights/states are unrecoverable in-process."""
    if _any_donated_deleted(arrays):
        raise MXNetError(
            "fused optimizer update failed mid-execution; weight/"
            "state buffers were donated and may be invalidated — "
            "restore from the last checkpoint before continuing "
            f"({err!r})") from err


# one executable per (optimizer fingerprint, weight shapes/dtypes, state
# structure) — shared across Updater instances (gluon Trainer keeps one
# Updater per context; all hit the same cache). Bounded: each entry's build
# closure pins its Optimizer instance, so a long-lived process cycling
# through many Trainers must not accumulate them forever (oldest out)
_fused_updater_cache = None


def _updater_cache():
    global _fused_updater_cache
    if _fused_updater_cache is None:
        _fused_updater_cache = CompileCache("optimizer.fused_update",
                                            maxsize=64)
    return _fused_updater_cache


class Updater:
    """Updater for kvstore (parity optimizer.py:1621): holds per-key states,
    serializable so a kvstore server process can resume it."""

    def __init__(self, optimizer):
        self.optimizer = optimizer
        self.states = {}
        self.states_synced = {}
        self.aggregate_updates = optimizer.aggregate_num > 0
        # set after a fused trace/compile failure: stop re-paying the
        # failed trace every step and stay on the eager loop
        self._fused_disabled = False
        # ZeRO-1 sharded-update context (parallel/zero1.py): owns the
        # dp-sharded flat optimizer state when MXNET_ZERO1=1
        self._zero1 = None
        self._zero1_failed = False
        # memory census: the replicated per-parameter states (the sharded
        # ones census through the Zero1Context's own provider). A live
        # view — fused updates replace the state arrays every step.
        from .. import memory
        from jax import tree_util as _jtu

        memory.register_provider(
            "optimizer_state", self,
            lambda s: [leaf for st in s.states.values()
                       for leaf in _jtu.tree_leaves(st)
                       if hasattr(leaf, "nbytes") or hasattr(leaf, "_data")])

    def ensure_states(self, indices, weights):
        """Create (or context-sync) the optimizer state for each index —
        the lazy-creation half of ``__call__``, callable on its own by the
        fused train step (which needs the states before tracing)."""
        z1 = getattr(self, "_zero1", None)
        if z1 is not None and z1.dirty:
            # a sharded run handing over to an eager/replicated step (or a
            # checkpoint save): gather the shards into the per-parameter
            # states FIRST, or this path would consume stale ones
            z1.export_to_updater(self)
        for i, idx in enumerate(indices):
            if idx not in self.states:
                self.states[idx] = self.optimizer.create_state_multi_precision(
                    idx, weights[i])
                self.states_synced[idx] = True
            elif not self.states_synced[idx]:
                self.states[idx] = self.sync_state_context(self.states[idx],
                                                           weights[i].context)
                self.states_synced[idx] = True

    def __call__(self, index, grad, weight):
        if not isinstance(index, (list, tuple)):
            indices = [index]
            grads = [grad]
            weights = [weight]
        else:
            indices = index
            grads = grad
            weights = weight
        if len(indices) > 1 and self._fused_call(indices, grads, weights):
            return
        self.ensure_states(indices, weights)
        if self.aggregate_updates and len(indices) > 1:
            self._aggregated_update(indices, grads, weights)
            return
        for i, idx in enumerate(indices):
            self.optimizer.update_multi_precision(idx, weights[i], grads[i],
                                                  self.states[idx])

    def fused_ready(self):
        """Whether ``Optimizer.fused_update`` may run inside a program: the
        optimizer has one, no earlier build of it failed, and
        ``MXNET_FUSED_STEP`` is on."""
        return not self._fused_disabled \
            and self.optimizer.fused_update_supported \
            and bool(getenv("MXNET_FUSED_STEP"))

    def _fused_call(self, indices, grads, weights):
        """One jitted Optimizer.fused_update over the whole parameter group
        with weight and state buffers donated — the entire optimizer step is
        a single XLA computation instead of one dispatch per (chunk of)
        parameters. Returns False (caller falls back to the eager loop) for
        optimizers without a fused path, sparse grads, or MXNET_FUSED_STEP=0
        — the eager loop remains the correctness reference."""
        opt = self.optimizer
        if not self.fused_ready():
            return False
        from ..ndarray.sparse import RowSparseNDArray

        if any(isinstance(g, RowSparseNDArray) or isinstance(w, RowSparseNDArray)
               for g, w in zip(grads, weights)):
            return False

        from ..parallel.zero1 import zero1_enabled

        if zero1_enabled() and not getattr(self, "_zero1_failed", False):
            took = self._zero1_call(indices, grads, weights)
            if took is not None:
                return took
            # zero1 declined (unsupported optimizer / trace failure with
            # buffers intact): fall through to the replicated fused path

        import jax
        import jax.numpy as jnp

        self.ensure_states(indices, weights)
        count_snap = _snapshot_counts(opt, indices)
        opt._update_count(indices)
        try:
            lrs, wds = opt._fused_hyperparams(indices)
            states = [self.states[idx] for idx in indices]
            key = (opt._fused_static_key(),
                   tuple((w._data.shape, w._data.dtype) for w in weights),
                   tuple((g._data.shape, g._data.dtype) for g in grads),
                   tuple(_state_sig(s) for s in states))

            def build():
                def step(ws, gs, ss, lrs_, wds_, rescale):
                    return opt.fused_update(ws, gs, ss, lrs_, wds_, rescale)

                return jax.jit(step, donate_argnums=(0, 2))

            fn = _updater_cache().get_or_build(key, build)
            new_ws, new_ss = fn([w._data for w in weights],
                                [g._data for g in grads],
                                [_state_to_jax(s) for s in states],
                                jnp.asarray(lrs, jnp.float32),
                                jnp.asarray(wds, jnp.float32),
                                jnp.float32(opt.rescale_grad))
        except Exception as e:
            _raise_if_donated_consumed((w._data for w in weights), e)
            # trace/compile failed BEFORE any buffer was consumed (e.g. an
            # Optimizer subclass whose states the fused path can't unpack):
            # weights are intact — undo the count bump and stay eager
            _restore_counts(opt, count_snap)
            self._fused_disabled = True
            logging.getLogger("mxnet_tpu.optimizer").warning(
                "fused update failed to build (%r); falling back to the "
                "eager per-op update loop", e)
            return False
        for w, nw in zip(weights, new_ws):
            w._data = nw
        for s, ns in zip(states, new_ss):
            _state_writeback(s, ns)
        return True

    def _zero1_call(self, indices, grads, weights):
        """ZeRO-1 variant of :meth:`_fused_call` (`MXNET_ZERO1=1`): ONE
        jitted program whose weight update runs on each replica's 1/N
        shard of the flat parameter buckets with 1/N optimizer state
        (`parallel/zero1.py`), weights allgathered back replicated.
        Returns True when taken, None to fall through to the replicated
        fused path (buffers intact)."""
        import jax.numpy as jnp

        from ..parallel.zero1 import Zero1Context

        opt = self.optimizer
        if self._zero1 is None:
            try:
                self._zero1 = Zero1Context()
            except Exception as e:  # noqa: BLE001 — bad mesh/env (e.g.
                # MXNET_ZERO1_NDEV > device count): no buffer was touched,
                # stay on the replicated fused path
                self._zero1_failed = True
                logging.getLogger("mxnet_tpu.optimizer").warning(
                    "ZeRO-1 context unavailable (%r); falling back to the "
                    "replicated fused update", e)
                return None
        ctx = self._zero1
        count_snap = _snapshot_counts(opt, indices)
        opt._update_count(indices)
        try:
            lrs, wds = opt._fused_hyperparams(indices)
            ctx.ensure(opt, self, indices, weights)
            key = ("zero1", ctx.key(), opt._fused_static_key(),
                   tuple((w._data.shape, w._data.dtype) for w in weights),
                   tuple((g._data.shape, g._data.dtype) for g in grads))

            def build():
                import jax

                def step(ws, gs, flat, lrs_, wds_, rescale):
                    return ctx.traced_update(opt, list(ws), list(gs), flat,
                                             lrs_, wds_, rescale)

                # donate only the flat sharded state: the updated weights
                # are slices of one all-gathered bucket, which XLA cannot
                # reliably alias into the k donated weight buffers (the
                # hlolint donation audit showed it declining silently) —
                # declared donations must actually alias
                return jax.jit(step, donate_argnums=(2,))

            # audit="zero1": this is the gluon/aggregated rendering of the
            # sharded update — same reduce-scatter/all-gather contract row
            # as the executor-side fused step (tools/hlolint/contracts.py)
            fn = _updater_cache().get_or_build(key, build, audit="zero1")
            new_ws, new_flat = fn(
                [ctx.put_replicated(w._data) for w in weights],
                [ctx.put_replicated(g._data) for g in grads],
                ctx.flat_states,
                ctx.put_replicated(jnp.asarray(lrs, jnp.float32)),
                ctx.put_replicated(jnp.asarray(wds, jnp.float32)),
                ctx.put_replicated(jnp.float32(opt.rescale_grad)))
        except Exception as e:
            from jax import tree_util as jtu

            # the sharded flat state was donated too — and it is the ONLY
            # copy once dirty, so a consumed state buffer is just as fatal
            # as a consumed weight
            donated = [w._data for w in weights]
            donated += jtu.tree_leaves(ctx.flat_states or [])
            if _any_donated_deleted(donated):
                raise MXNetError(
                    "ZeRO-1 fused update failed mid-execution; weight/"
                    "state buffers were donated and may be invalidated — "
                    "restore from the last checkpoint before continuing "
                    f"({e!r})") from e
            # trace/compile failed before any buffer was consumed: undo the
            # count bump and let the replicated fused path take the step
            _restore_counts(opt, count_snap)
            self._zero1_failed = True
            logging.getLogger("mxnet_tpu.optimizer").warning(
                "ZeRO-1 sharded update failed to build (%r); falling back "
                "to the replicated fused update", e)
            return None
        for w, nw in zip(weights, new_ws):
            w._data = nw
        ctx.flat_states = new_flat
        ctx.dirty = True
        return True

    def _aggregated_update(self, indices, grads, weights):
        """Group same-dtype dense updates into multi_sgd_*-sized chunks
        (parity optimizer.py:1637-1664: the aggregate_updates branch of
        Updater.__call__; dtype segregation then aggregate_num chunking)."""
        from ..ndarray.sparse import RowSparseNDArray

        by_type = {}
        order = []
        for idx, g, w in zip(indices, grads, weights):
            if isinstance(g, RowSparseNDArray):
                # sparse updates keep the per-key lazy path
                self.optimizer.update_multi_precision(idx, w, g,
                                                      self.states[idx])
                continue
            key = str(w.dtype)
            if key not in by_type:
                by_type[key] = []
                order.append(key)
            by_type[key].append((idx, g, w))
        step = self.optimizer.aggregate_num
        for key in order:
            group = by_type[key]
            for start in range(0, len(group), step):
                chunk = group[start:start + step]
                idxs = [c[0] for c in chunk]
                self.optimizer.update_multi_precision(
                    idxs, [c[2] for c in chunk], [c[1] for c in chunk],
                    [self.states[i] for i in idxs])

    def sync_state_context(self, state, context):
        if isinstance(state, NDArray):
            return state.as_in_context(context)
        if isinstance(state, (tuple, list)):
            synced_state = (self.sync_state_context(i, context) for i in state)
            if isinstance(state, tuple):
                return tuple(synced_state)
            return list(synced_state)
        return state

    def set_states(self, states):
        """Set updater states from serialized bytes. A live ZeRO-1 context
        is invalidated so the next sharded step re-shards the LOADED
        per-parameter states instead of keeping pre-load shards."""
        states = pickle.loads(states)
        if isinstance(states, tuple) and len(states) == 2:
            self.states, self.optimizer = states
        else:
            self.states = states
        self.states_synced = dict.fromkeys(self.states.keys(), False)
        z1 = getattr(self, "_zero1", None)
        if z1 is not None:
            z1.invalidate()

    def get_states(self, dump_optimizer=False):
        """Serialized states. Under ZeRO-1 the shards are gathered back
        into ordinary per-parameter states first (checkpoints stay
        store-format-identical to replicated runs; loading re-shards)."""
        z1 = getattr(self, "_zero1", None)
        if z1 is not None and z1.dirty:
            z1.export_to_updater(self)
        return pickle.dumps((self.states, self.optimizer) if dump_optimizer
                            else self.states)


def get_updater(optimizer):
    """Return a closure of the updater needed for kvstore (parity :1712)."""
    return Updater(optimizer)
