"""BucketingModule — per-bucket executors sharing one parameter set.

Parity: `python/mxnet/module/bucketing_module.py:36`. The reference keeps a
Module per bucket key (sequence length), re-binding executors that share
arg arrays. Here each bucket's Module shares the same underlying NDArray
parameters (shared_module), and jit simply compiles one executable per
bucket shape — the compile-cache-by-signature design means switching
buckets is a dict lookup, the exact CachedOp signature-match model
(`cached_op.cc:295`).
"""
from __future__ import annotations

import logging

from ..base import MXNetError
from .base_module import BaseModule
from .module import Module

__all__ = ["BucketingModule"]


class BucketingModule(BaseModule):
    def __init__(self, sym_gen, default_bucket_key=None, logger=logging,
                 context=None, work_load_list=None, fixed_param_names=None,
                 state_names=None, group2ctxs=None, compression_params=None):
        super().__init__(logger=logger)
        assert default_bucket_key is not None
        self._sym_gen = sym_gen
        self._default_bucket_key = default_bucket_key
        self._context = context
        self._fixed_param_names = fixed_param_names
        self._buckets = {}
        self._curr_module = None
        self._curr_bucket_key = None
        self._grad_req = None
        self._monitor = None

    @property
    def default_bucket_key(self):
        return self._default_bucket_key

    @property
    def symbol(self):
        assert self.binded
        return self._curr_module.symbol

    @property
    def data_names(self):
        if self.binded:
            return self._curr_module.data_names
        sym, data_names, _ = self._call_sym_gen(self._default_bucket_key)
        return data_names

    @property
    def output_names(self):
        if self.binded:
            return self._curr_module.output_names
        sym, _, _ = self._call_sym_gen(self._default_bucket_key)
        return sym.list_outputs()

    @property
    def data_shapes(self):
        assert self.binded
        return self._curr_module.data_shapes

    @property
    def label_shapes(self):
        assert self.binded
        return self._curr_module.label_shapes

    @property
    def output_shapes(self):
        assert self.binded
        return self._curr_module.output_shapes

    def _call_sym_gen(self, bucket_key):
        res = self._sym_gen(bucket_key)
        if not isinstance(res, tuple):
            return res, ("data",), ("softmax_label",)
        return res

    def _gen_module(self, bucket_key):
        sym, data_names, label_names = self._call_sym_gen(bucket_key)
        return Module(sym, data_names, label_names, logger=self.logger,
                      context=self._context,
                      fixed_param_names=self._fixed_param_names)

    # -- bind / params -------------------------------------------------------

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        if self.binded and not force_rebind:
            self.logger.warning("Already bound, ignoring bind()")
            return
        assert shared_module is None, \
            "shared_module for BucketingModule is not supported"
        self.for_training = for_training
        self.inputs_need_grad = inputs_need_grad
        self._grad_req = grad_req

        module = self._gen_module(self._default_bucket_key)
        module.bind(data_shapes, label_shapes, for_training, inputs_need_grad,
                    force_rebind=False, grad_req=grad_req)
        self._buckets = {self._default_bucket_key: module}
        self._curr_module = module
        self._curr_bucket_key = self._default_bucket_key
        self.binded = True

    def switch_bucket(self, bucket_key, data_shapes, label_shapes=None):
        assert self.binded, "call bind before switching bucket"
        if bucket_key not in self._buckets:
            module = self._gen_module(bucket_key)
            module.bind(data_shapes, label_shapes, self._curr_module.for_training,
                        self._curr_module.inputs_need_grad,
                        force_rebind=False,
                        shared_module=self._buckets[self._default_bucket_key],
                        grad_req=self._grad_req)
            if self.params_initialized:
                arg_p, aux_p = self._buckets[self._default_bucket_key].get_params()
                module.init_params(arg_params=arg_p, aux_params=aux_p,
                                   force_init=True)
            if self.optimizer_initialized:
                module._optimizer = self._curr_module._optimizer
                module._updater = self._curr_module._updater
                module._kvstore = self._curr_module._kvstore
                module._update_on_kvstore = self._curr_module._update_on_kvstore
                module.optimizer_initialized = True
            if self._monitor is not None:
                module.install_monitor(self._monitor)
            self._buckets[bucket_key] = module
        else:
            # sync params into the target bucket (shared array semantics)
            if self.params_initialized and bucket_key != self._curr_bucket_key:
                arg_p, aux_p = self._curr_module.get_params()
                self._buckets[bucket_key].init_params(
                    arg_params=arg_p, aux_params=aux_p, force_init=True)
        self._curr_module = self._buckets[bucket_key]
        self._curr_bucket_key = bucket_key

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        if self.params_initialized and not force_init:
            return
        assert self.binded
        self._curr_module.init_params(initializer, arg_params, aux_params,
                                      allow_missing, force_init, allow_extra)
        self.params_initialized = True

    def get_params(self):
        assert self.binded and self.params_initialized
        return self._curr_module.get_params()

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        assert self.binded and self.params_initialized
        if self.optimizer_initialized and not force_init:
            return
        self._curr_module.init_optimizer(kvstore, optimizer, optimizer_params,
                                         force_init=force_init)
        for mod in self._buckets.values():
            if mod is not self._curr_module:
                mod._optimizer = self._curr_module._optimizer
                mod._updater = self._curr_module._updater
                mod._kvstore = self._curr_module._kvstore
                mod._update_on_kvstore = self._curr_module._update_on_kvstore
                mod.optimizer_initialized = True
        self.optimizer_initialized = True

    # -- compute -------------------------------------------------------------

    def _switch_for_batch(self, data_batch):
        """Switch to the batch's bucket, syncing params from the previous
        bucket (shared-array semantics)."""
        bucket_key = getattr(data_batch, "bucket_key", None)
        if bucket_key is None:
            bucket_key = self._default_bucket_key
        prev = self._curr_module
        self.switch_bucket(bucket_key, data_batch.provide_data,
                           data_batch.provide_label)
        if prev is not self._curr_module and self.params_initialized:
            arg_p, aux_p = prev.get_params()
            self._curr_module.init_params(arg_params=arg_p, aux_params=aux_p,
                                          force_init=True)

    def forward(self, data_batch, is_train=None):
        assert self.binded and self.params_initialized
        self._switch_for_batch(data_batch)
        self._curr_module.forward(data_batch, is_train=is_train)

    def fused_step(self, data_batch):
        """Fused train step per bucket: each bucket's Module compiles its
        own fused executable (one compile-cache entry per bucket key — the
        signature-match model of `cached_op.cc:295`); bucket switching
        stays a dict lookup."""
        assert self.binded and self.params_initialized
        self._switch_for_batch(data_batch)
        return self._curr_module.fused_step(data_batch)

    def backward(self, out_grads=None):
        self._curr_module.backward(out_grads)

    def update(self):
        self._curr_module.update()

    def get_outputs(self, merge_multi_context=True):
        return self._curr_module.get_outputs(merge_multi_context)

    def get_input_grads(self, merge_multi_context=True):
        return self._curr_module.get_input_grads(merge_multi_context)

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        self._curr_module.update_metric(eval_metric, labels, pre_sliced)

    # -- batch staging -------------------------------------------------------
    # Batch staging is NOT delegated: the next batch's bucket module isn't
    # switched in until its own fused_step, so its executor shapes aren't
    # knowable here — bucketed fits keep host-side feed prep.

    def retire_staged(self):
        if self._curr_module is None:
            return False
        return self._curr_module.retire_staged()

    def _overlap_teardown(self):
        for mod in self._buckets.values():
            mod._overlap_teardown()

    def install_monitor(self, mon):
        self._monitor = mon
        for mod in self._buckets.values():
            mod.install_monitor(mon)

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass
