"""BaseModule — the training-loop contract of the Module API.

Parity: `python/mxnet/module/base_module.py` (`fit`:409 with its
epoch/metric/checkpoint choreography, `score`, `predict`,
`forward_backward`:193). The subclass contract (bind → init_params →
init_optimizer → forward/backward/update) is preserved verbatim so
reference training scripts port unchanged.
"""
from __future__ import annotations

import logging
import time

import numpy as _np

from .. import health
from .. import telemetry
from .. import tracing
from ..base import MXNetError
from .. import metric as _metric
from .. import ndarray as nd
from ..io.io import DataDesc


class BaseModule:
    def __init__(self, logger=logging):
        self.logger = logger
        self.binded = False
        self.for_training = False
        self.inputs_need_grad = False
        self.params_initialized = False
        self.optimizer_initialized = False
        self._symbol = None

    # -- properties subclasses provide ---------------------------------------

    @property
    def symbol(self):
        return self._symbol

    @property
    def data_names(self):
        raise NotImplementedError

    @property
    def output_names(self):
        raise NotImplementedError

    @property
    def data_shapes(self):
        raise NotImplementedError

    @property
    def label_shapes(self):
        raise NotImplementedError

    @property
    def output_shapes(self):
        raise NotImplementedError

    # -- core subclass API ---------------------------------------------------

    def bind(self, data_shapes, label_shapes=None, for_training=True,
             inputs_need_grad=False, force_rebind=False, shared_module=None,
             grad_req="write"):
        raise NotImplementedError

    def init_params(self, initializer=None, arg_params=None, aux_params=None,
                    allow_missing=False, force_init=False, allow_extra=False):
        raise NotImplementedError

    def init_optimizer(self, kvstore="local", optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.01),),
                       force_init=False):
        raise NotImplementedError

    def forward(self, data_batch, is_train=None):
        raise NotImplementedError

    def backward(self, out_grads=None):
        raise NotImplementedError

    def update(self):
        raise NotImplementedError

    def get_outputs(self, merge_multi_context=True):
        raise NotImplementedError

    def get_params(self):
        raise NotImplementedError

    def update_metric(self, eval_metric, labels, pre_sliced=False):
        raise NotImplementedError

    # -- composite helpers ---------------------------------------------------

    def forward_backward(self, data_batch):
        """(reference base_module.py:193)"""
        self.forward(data_batch, is_train=True)
        self.backward()

    def fused_step(self, data_batch):
        """Hook: run forward+backward+update as ONE compiled computation.
        Subclasses that can (Module, when no kvstore/Monitor/custom op needs
        per-op visibility) return True; the default False tells `fit` to run
        the eager forward_backward() + update() decomposition."""
        return False

    def set_params(self, arg_params, aux_params, allow_missing=False,
                   force_init=True, allow_extra=False):
        self.init_params(initializer=None, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init, allow_extra=allow_extra)

    def score(self, eval_data, eval_metric, num_batch=None,
              batch_end_callback=None, score_end_callback=None,
              reset=True, epoch=0, sparse_row_id_fn=None):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)
        eval_metric.reset()
        actual_num_batch = 0
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            self.update_metric(eval_metric, eval_batch.label)
            if batch_end_callback is not None:
                for cb in _as_list(batch_end_callback):
                    cb(_BatchEndParam(epoch, nbatch, eval_metric, locals()))
            actual_num_batch += 1
        if score_end_callback:
            for cb in _as_list(score_end_callback):
                cb(_BatchEndParam(epoch, actual_num_batch, eval_metric, locals()))
        return eval_metric.get_name_value()

    def iter_predict(self, eval_data, num_batch=None, reset=True):
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad
            outputs = [out[0:out.shape[0] - (pad or 0)]
                       for out in self.get_outputs()]
            yield outputs, nbatch, eval_batch

    def predict(self, eval_data, num_batch=None, merge_batches=True,
                reset=True, always_output_list=False, sparse_row_id_fn=None):
        """Run inference over an eval iterator (reference base_module.py).

        This is the single-caller, iterator-driven path. For concurrent
        request traffic (a server), use ``mxnet_tpu.serving`` — a
        ``Module.as_predictor()`` behind a ``DynamicBatcher`` coalesces
        callers into bucket-padded batches instead of recompiling or
        serializing them here."""
        assert self.binded and self.params_initialized
        if reset:
            eval_data.reset()
        output_list = []
        for nbatch, eval_batch in enumerate(eval_data):
            if num_batch is not None and nbatch == num_batch:
                break
            self.forward(eval_batch, is_train=False)
            pad = eval_batch.pad or 0
            outputs = [out[0:out.shape[0] - pad].copy()
                       for out in self.get_outputs()]
            output_list.append(outputs)
        if len(output_list) == 0:
            return output_list
        if merge_batches:
            num_outputs = len(output_list[0])
            for out in output_list:
                if len(out) != num_outputs:
                    raise ValueError("Cannot merge batches: different number "
                                     "of outputs per batch")
            output_list2 = [nd.concat(*[out[i] for out in output_list], dim=0)
                            for i in range(num_outputs)]
            if num_outputs == 1 and not always_output_list:
                return output_list2[0]
            return output_list2
        return output_list

    def _fetch_next_batch(self, data_iter, sparse_row_id_fn):
        """`fit`'s data phase as one live span: ``step.data`` holding
        ``step.data.next`` (the iterator) and ``step.data.stage``
        (``prepare``, and ``stage_batch``: the batch is staged while the
        step runs). None when the epoch is over."""
        with tracing.span("step.data", cat="train"):
            with tracing.span("step.data.next", cat="train"):
                try:
                    batch = next(data_iter)
                except StopIteration:
                    return None
            with tracing.span("step.data.stage", cat="train"):
                self.prepare(batch, sparse_row_id_fn=sparse_row_id_fn)
                self.stage_batch(batch)
        return batch

    def fit(self, train_data, eval_data=None, eval_metric="acc",
            epoch_end_callback=None, batch_end_callback=None, kvstore="local",
            optimizer="sgd", optimizer_params=(("learning_rate", 0.01),),
            eval_end_callback=None, eval_batch_end_callback=None,
            initializer=None, arg_params=None, aux_params=None,
            allow_missing=False, force_rebind=False, force_init=False,
            begin_epoch=0, num_epoch=None, validation_metric=None,
            monitor=None, sparse_row_id_fn=None):
        """The full training loop (reference base_module.py:409)."""
        assert num_epoch is not None, "please specify number of epochs"
        from ..initializer import Uniform

        if initializer is None:
            initializer = Uniform(0.01)

        self.bind(data_shapes=train_data.provide_data,
                  label_shapes=train_data.provide_label,
                  for_training=True, force_rebind=force_rebind)
        if monitor is not None:
            self.install_monitor(monitor)
        self.init_params(initializer=initializer, arg_params=arg_params,
                         aux_params=aux_params, allow_missing=allow_missing,
                         force_init=force_init)
        self.init_optimizer(kvstore=kvstore, optimizer=optimizer,
                            optimizer_params=optimizer_params)

        if validation_metric is None:
            validation_metric = eval_metric
        if not isinstance(eval_metric, _metric.EvalMetric):
            eval_metric = _metric.create(eval_metric)

        # stall-watchdog progress beacon: armed while the training
        # loop owes steps, touched per completed step — a hang inside
        # forward/backward/update/data surfaces as a watchdog stall
        # with a diagnostic bundle instead of an opaque dead process
        fit_beacon = health.beacon("fit.step") if health._enabled \
            else None
        try:
            for epoch in range(begin_epoch, num_epoch):
                if fit_beacon is not None:
                    # armed per EPOCH: the validation/checkpoint tail
                    # between epochs has no step cadence, so its silence
                    # must not be judged by the training-step median
                    fit_beacon.arm()
                tic = time.time()
                eval_metric.reset()
                nbatch = 0
                end_of_batch = False
                data_iter = iter(train_data)
                next_data_batch = next(data_iter)
                while not end_of_batch:
                    data_batch = next_data_batch
                    if monitor is not None:
                        monitor.tic()
                    # telemetry: per-step breakdown — where a training step's
                    # wall time actually goes (data wait / fwd-bwd dispatch /
                    # optimizer update / metric sync). The metric update fetches
                    # values, so it doubles as the device sync segment.
                    # tracing: the same boundaries are live spans under one
                    # "step" root whose trace id is DETERMINISTIC in
                    # (epoch, step) — every dist worker labels the same step
                    # identically, so tools/trace_merge.py can join their
                    # dumps. Nested spans (grad_sync issue/drain, fused
                    # dispatch, zero1 phases) parent to the phase they run
                    # in through the context var; the finished tree feeds
                    # the slow-step flight recorder.
                    tele = telemetry._enabled
                    trc = tracing._enabled
                    step_span = tracing.span(
                        "step", cat="train",
                        trace_id=(tracing.deterministic_trace_id(
                            "fit", epoch, nbatch) if trc else None),
                        epoch=epoch, step=nbatch)
                    with step_span:
                        # the four phase children are LIVE spans (also
                        # `mx:step.*` in any jax.profiler trace); the perf
                        # marks beside them feed the step.*_us histograms
                        t0 = time.perf_counter() if tele else 0.0
                        with tracing.span("step.fwdbwd", cat="train"):
                            # fused path: fwd+bwd+update as one XLA
                            # computation (its whole cost lands here)
                            fused = self.fused_step(data_batch)
                            if not fused:
                                self.forward_backward(data_batch)
                        t_fb = time.perf_counter() if tele else 0.0
                        with tracing.span("step.update", cat="train"):
                            if not fused:
                                self.update()
                        t_up = time.perf_counter() if tele else 0.0
                        if tele:
                            telemetry.gauge("step.fused").set(1 if fused else 0)
                        # dispatch-then-prepare: fetch + device-stage
                        # batch t+1 while step t executes
                        fetched = self._fetch_next_batch(
                            data_iter, sparse_row_id_fn)
                        if fetched is None:
                            end_of_batch = True
                        else:
                            next_data_batch = fetched
                        t_data = time.perf_counter() if tele else 0.0
                        # `mx.metric` keeps step t's buffers and settles
                        # step t-1 inside this call (its outputs finished
                        # at least one step ago, so this rarely blocks);
                        # the epoch's last step settles where the metric
                        # is read. The outputs are still step t's, sliced
                        # by step t's pad: the next batch's pad and bucket
                        # apply only inside the next fused_step/forward.
                        with tracing.span("step.sync", cat="train"):
                            if isinstance(data_batch, list):
                                self.update_metric(
                                    eval_metric,
                                    [db.label for db in data_batch],
                                    pre_sliced=True)
                            else:
                                self.update_metric(eval_metric,
                                                   data_batch.label)
                            self.retire_staged()
                        t_sync = time.perf_counter() if tele else 0.0
                        marks = (("fwdbwd", t0, t_fb),
                                 ("update", t_fb, t_up),
                                 ("data", t_up, t_data),
                                 ("sync", t_data, t_sync))
                        step_span.set(fused=fused)
                    if trc:
                        tracing.flight_recorder.observe(step_span.tree())
                    step_stats = None
                    if tele:
                        total_h = telemetry.histogram("step.total_us")
                        for seg, a, b in marks:
                            telemetry.histogram(
                                f"step.{seg}_us").record((b - a) * 1e6)
                        total_us = (t_sync - t0) * 1e6
                        total_h.record(total_us)
                        # wall-clock denominator for the derived pipeline
                        # stall ratio (prefetch wait + stage wait over wall)
                        telemetry.counter("step.wall_us_total").inc(
                            int(total_us))
                        if batch_end_callback is not None:
                            # quantiles sort the reservoir, so they are NOT
                            # computed here each batch — the histogram rides
                            # along and consumers (Speedometer) pull
                            # hist.quantiles(50, 99) only on their log ticks
                            seg_ms = {f"{seg}_ms": (b - a) * 1e3
                                      for seg, a, b in marks}
                            step_stats = dict(seg_ms, total_ms=total_us / 1e3,
                                              hist=total_h)
                    if monitor is not None:
                        monitor.toc_print()
                    if batch_end_callback is not None:
                        for cb in _as_list(batch_end_callback):
                            cb(_BatchEndParam(epoch, nbatch, eval_metric,
                                              locals(), step_stats=step_stats))
                    nbatch += 1
                    if fit_beacon is not None:
                        # progress: one full step (data/fwdbwd/update/sync)
                        # completed — the watchdog's rolling median learns
                        # the step cadence from these
                        fit_beacon.touch()
                if fit_beacon is not None:
                    fit_beacon.idle()
                for name, val in eval_metric.get_name_value():
                    self.logger.info("Epoch[%d] Train-%s=%f", epoch, name, val)
                self.logger.info("Epoch[%d] Time cost=%.3f", epoch, time.time() - tic)

                arg_p, aux_p = self.get_params()
                self.set_params(arg_p, aux_p)
                if epoch_end_callback is not None:
                    for cb in _as_list(epoch_end_callback):
                        cb(epoch, self.symbol, arg_p, aux_p)
                if eval_data is not None:
                    res = self.score(eval_data, validation_metric,
                                     score_end_callback=eval_end_callback,
                                     batch_end_callback=eval_batch_end_callback,
                                     epoch=epoch)
                    for name, val in res:
                        self.logger.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
                train_data.reset()
        finally:
            self._overlap_teardown()
            if fit_beacon is not None:
                fit_beacon.idle()

    def prepare(self, data_batch, sparse_row_id_fn=None):
        pass

    # -- batch staging hooks -------------------------------------------------
    # Subclasses that can stage the next batch on the device override
    # these; under the base defaults every batch takes host-side feed prep.

    def stage_batch(self, data_batch):
        """Hand ``data_batch`` to the device-staging thread so its
        pad/cast/placement overlaps the in-flight step. False = not
        staged (consumers fall back to host-side feed prep)."""
        return False

    def retire_staged(self):
        """Release the oldest staged buffer in flight — called by ``fit``
        once a step's metric update has been made."""
        return False

    def _overlap_teardown(self):
        """Stop any staging thread and drop staged buffers (fit exit)."""
        return None

    def install_monitor(self, mon):
        raise NotImplementedError

    def get_input_grads(self, merge_multi_context=True):
        raise NotImplementedError

    def save_params(self, fname):
        arg_params, aux_params = self.get_params()
        save_dict = {f"arg:{k}": v for k, v in arg_params.items()}
        save_dict.update({f"aux:{k}": v for k, v in aux_params.items()})
        nd.save(fname, save_dict)

    def load_params(self, fname):
        save_dict = nd.load(fname)
        arg_params, aux_params = {}, {}
        for k, value in save_dict.items():
            arg_type, _, name = k.partition(":")
            if arg_type == "arg":
                arg_params[name] = value
            elif arg_type == "aux":
                aux_params[name] = value
            else:
                raise ValueError(f"Invalid param file {fname}")
        self.set_params(arg_params, aux_params)


class _BatchEndParam:
    def __init__(self, epoch, nbatch, eval_metric, locals_, step_stats=None):
        self.epoch = epoch
        self.nbatch = nbatch
        self.eval_metric = eval_metric
        self.locals = locals_
        # per-step telemetry breakdown (None when MXNET_TELEMETRY is off)
        self.step_stats = step_stats


def _as_list(obj):
    if isinstance(obj, (list, tuple)):
        return obj
    return [obj]
