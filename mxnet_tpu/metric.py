"""Evaluation metrics.

Parity: `python/mxnet/metric.py` — EvalMetric base (:68), CompositeEvalMetric
(:278), Accuracy (:440), TopKAccuracy (:513), F1 (:751), MCC (:845),
Perplexity (:960), MAE/MSE/RMSE (:1084-1213), CrossEntropy (:1278),
NegativeLogLikelihood (:1350), PearsonCorrelation (:1422), Loss (:1610),
CustomMetric (:1662), np()/create() helpers.

An ``update`` whose labels or predictions hold an :class:`NDArray` is settled
one call late. The call keeps the buffers those arrays hold (a later
``out[:] = ...`` swaps the NDArray's buffer, not the kept one; the pending
output of a recorded hybridized call is kept as it is), settles the update the
previous call kept, and returns: the host fetches step *i - 1*, which finished
before step *i* could start, instead of draining the step it has just
launched, so the next batch's copy and launch run under it. Settling is the
subclass's own ``update`` on the kept arrays, so the numbers are those of
lockstep. Every read (``get``, ``get_name_value``, ``reset``, ``str``,
``sum_metric``, ``num_inst``, pickling and copying) settles first. The lag is
one call, never more. All-numpy arguments, and everything under
:func:`immediate`, settle at once. This module is the lag's one owner:
``Module.fit`` and a gluon loop both call ``update`` plainly.

Counters ``metric.deferred`` (updates kept), ``metric.settled_late`` (settled
by the next ``update``) and ``metric.settled_on_read``; the fetch is under the
span ``metric.settle``.
"""
from __future__ import annotations

import contextlib
import functools
import math
import threading

import numpy

from . import telemetry
from . import tracing
from .base import numeric_types, string_types
from .ndarray.ndarray import NDArray

__all__ = ["EvalMetric", "CompositeEvalMetric", "Accuracy", "TopKAccuracy", "F1", "MCC",
           "Perplexity", "MAE", "MSE", "RMSE", "CrossEntropy", "NegativeLogLikelihood",
           "PearsonCorrelation", "Loss", "Torch", "Caffe", "CustomMetric", "np", "create",
           "check_label_shapes", "immediate"]

_METRIC_REGISTRY = {}


def register(klass, *names):
    for n in (names or [klass.__name__.lower()]):
        _METRIC_REGISTRY[n] = klass
    return klass


def create(metric, *args, **kwargs):
    if callable(metric):
        return CustomMetric(metric, *args, **kwargs)
    if isinstance(metric, EvalMetric):
        return metric
    if isinstance(metric, list):
        composite = CompositeEvalMetric()
        for child in metric:
            composite.add(create(child, *args, **kwargs))
        return composite
    if isinstance(metric, string_types):
        return _METRIC_REGISTRY[metric.lower()](*args, **kwargs)
    raise ValueError(f"metric {metric} cannot be created")


def check_label_shapes(labels, preds, wrap=False, shape=False):
    if not shape:
        label_shape, pred_shape = len(labels), len(preds)
    else:
        label_shape, pred_shape = labels.shape, preds.shape
    if label_shape != pred_shape:
        raise ValueError(f"Shape of labels {label_shape} does not match shape of predictions {pred_shape}")
    if wrap:
        if not isinstance(labels, (list, tuple)):
            labels = [labels]
        if not isinstance(preds, (list, tuple)):
            preds = [preds]
    return labels, preds


def _asnp(x):
    return x.asnumpy() if hasattr(x, "asnumpy") else numpy.asarray(x)


class _Lockstep(threading.local):
    depth = 0


_lockstep = _Lockstep()


@contextlib.contextmanager
def immediate():
    """Updates made inside are applied at once: lockstep numbers at every
    call (``with mx.metric.immediate(): mod.fit(...)``), at the price of
    draining the step just launched. Settling itself runs under it."""
    _lockstep.depth += 1
    try:
        yield
    finally:
        _lockstep.depth -= 1


def _holds_ndarray(x):
    if isinstance(x, (list, tuple)):
        return any(_holds_ndarray(v) for v in x)
    return isinstance(x, NDArray)


def _keep(x):
    """`x` as it is now: an NDArray's buffer, a numpy array's values."""
    if isinstance(x, (list, tuple)):
        return [_keep(v) for v in x]
    if isinstance(x, NDArray):
        return x.detach()
    if isinstance(x, numpy.ndarray):
        return x.copy()
    return x


def _deferring(update):
    @functools.wraps(update)
    def deferred(self, labels, preds):
        defer = (not _lockstep.depth
                 and (_holds_ndarray(labels) or _holds_ndarray(preds)))
        kept = (_keep(labels), _keep(preds)) if defer else None
        try:
            self._settle("metric.settled_late")
        finally:
            self._pending = kept
        if not defer:
            return update(self, labels, preds)
        telemetry.counter("metric.deferred").inc()

    deferred._settles = True
    return deferred


def _settling_first(read):
    @functools.wraps(read)
    def settled(self, *args, **kwargs):
        self._settle()
        return read(self, *args, **kwargs)

    settled._settles = True
    return settled


def _settled_attribute(name):
    """A public accumulator: reading or writing it settles first."""

    def read(self):
        self._settle()
        return getattr(self, name)

    def write(self, value):
        self._settle()
        setattr(self, name, value)

    return property(read, write)


class EvalMetric:
    # the one update not settled yet: (labels, preds) as `_keep` left them
    _pending = None

    def __init_subclass__(cls, defer=True, **kwargs):
        super().__init_subclass__(**kwargs)
        if not defer:
            return
        for name, wrap in (("update", _deferring), ("get", _settling_first),
                           ("get_name_value", _settling_first),
                           ("reset", _settling_first)):
            method = cls.__dict__.get(name)
            if callable(method) and not getattr(method, "_settles", False):
                setattr(cls, name, wrap(method))

    def __init__(self, name, output_names=None, label_names=None, **kwargs):
        self.name = str(name)
        self.output_names = output_names
        self.label_names = label_names
        self._kwargs = kwargs
        self.reset()

    def _settle(self, counter="metric.settled_on_read"):
        kept = self._pending
        if kept is None:
            return
        self._pending = None
        telemetry.counter(counter).inc()
        with immediate(), tracing.span("metric.settle"):
            self.update(*kept)

    sum_metric = _settled_attribute("_sum_metric")
    num_inst = _settled_attribute("_num_inst")

    def __getstate__(self):
        self._settle()
        return self.__dict__

    def __str__(self):
        return f"EvalMetric: {dict(self.get_name_value())}"

    def get_config(self):
        config = self._kwargs.copy()
        config.update({"metric": self.__class__.__name__, "name": self.name,
                       "output_names": self.output_names, "label_names": self.label_names})
        return config

    def update_dict(self, label, pred):
        if self.output_names is not None:
            pred = [pred[name] for name in self.output_names if name in pred]
        else:
            pred = list(pred.values())
        if self.label_names is not None:
            label = [label[name] for name in self.label_names if name in label]
        else:
            label = list(label.values())
        self.update(label, pred)

    def update(self, labels, preds):
        raise NotImplementedError

    def reset(self):
        self.num_inst = 0
        self.sum_metric = 0.0

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, self.sum_metric / self.num_inst)

    def get_name_value(self):
        name, value = self.get()
        if not isinstance(name, list):
            name = [name]
        if not isinstance(value, list):
            value = [value]
        return list(zip(name, value))


@register
class CompositeEvalMetric(EvalMetric, defer=False):
    """Defers and settles through its children."""

    def __init__(self, metrics=None, name="composite", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)
        self.metrics = [create(m) for m in (metrics or [])]

    def add(self, metric):
        self.metrics.append(create(metric))

    def get_metric(self, index):
        return self.metrics[index]

    def update_dict(self, labels, preds):
        for metric in self.metrics:
            metric.update_dict(labels, preds)

    def update(self, labels, preds):
        for metric in self.metrics:
            metric.update(labels, preds)

    def reset(self):
        for metric in getattr(self, "metrics", []):
            metric.reset()

    def get(self):
        names, values = [], []
        for metric in self.metrics:
            name, value = metric.get()
            if isinstance(name, str):
                name = [name]
            if isinstance(value, numeric_types):
                value = [value]
            names.extend(name)
            values.extend(value)
        return (names, values)


@register
class Accuracy(EvalMetric):
    def __init__(self, axis=1, name="accuracy", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names, axis=axis)
        self.axis = axis

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred_label in zip(labels, preds):
            pred_np = _asnp(pred_label)
            label_np = _asnp(label)
            if pred_np.ndim > label_np.ndim:
                pred_np = numpy.argmax(pred_np, axis=self.axis)
            pred_np = pred_np.astype("int32").reshape(-1)
            label_np = label_np.astype("int32").reshape(-1)
            check_label_shapes(label_np, pred_np)
            self.sum_metric += (pred_np == label_np).sum()
            self.num_inst += len(pred_np)


@register
class TopKAccuracy(EvalMetric):
    def __init__(self, top_k=1, name="top_k_accuracy", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names, top_k=top_k)
        self.top_k = top_k
        assert self.top_k > 1, "Please use Accuracy if top_k is no more than 1"
        self.name += f"_{self.top_k}"

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred_label in zip(labels, preds):
            assert len(pred_label.shape) <= 2, "Predictions should be no more than 2 dims"
            pred_np = numpy.argsort(_asnp(pred_label).astype("float32"), axis=1)
            label_np = _asnp(label).astype("int32")
            num_samples = pred_np.shape[0]
            num_dims = len(pred_np.shape)
            if num_dims == 1:
                self.sum_metric += (pred_np.reshape(-1) == label_np.reshape(-1)).sum()
            elif num_dims == 2:
                num_classes = pred_np.shape[1]
                top_k = min(num_classes, self.top_k)
                for j in range(top_k):
                    self.sum_metric += (pred_np[:, num_classes - 1 - j].reshape(-1) == label_np.reshape(-1)).sum()
            self.num_inst += num_samples


@register
class F1(EvalMetric):
    def __init__(self, name="f1", output_names=None, label_names=None, average="macro"):
        self.average = average
        self._tp = self._fp = self._fn = 0.0
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            pred_np = _asnp(pred)
            label_np = _asnp(label).astype("int32").reshape(-1)
            pred_lab = numpy.argmax(pred_np, axis=-1).reshape(-1) if pred_np.ndim > 1 else (pred_np > 0.5).astype("int32")
            self._tp += float(((pred_lab == 1) & (label_np == 1)).sum())
            self._fp += float(((pred_lab == 1) & (label_np == 0)).sum())
            self._fn += float(((pred_lab == 0) & (label_np == 1)).sum())
            prec = self._tp / (self._tp + self._fp) if self._tp + self._fp > 0 else 0.0
            rec = self._tp / (self._tp + self._fn) if self._tp + self._fn > 0 else 0.0
            f1 = 2 * prec * rec / (prec + rec) if prec + rec > 0 else 0.0
            self.sum_metric = f1
            self.num_inst = 1

    def reset(self):
        self._tp = self._fp = self._fn = 0.0
        self.sum_metric = 0.0
        self.num_inst = 0


@register
class MCC(EvalMetric):
    def __init__(self, name="mcc", output_names=None, label_names=None, average="macro"):
        self._tp = self._fp = self._tn = self._fn = 0.0
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            pred_np = _asnp(pred)
            label_np = _asnp(label).astype("int32").reshape(-1)
            pred_lab = numpy.argmax(pred_np, axis=-1).reshape(-1) if pred_np.ndim > 1 else (pred_np > 0.5).astype("int32")
            self._tp += float(((pred_lab == 1) & (label_np == 1)).sum())
            self._fp += float(((pred_lab == 1) & (label_np == 0)).sum())
            self._tn += float(((pred_lab == 0) & (label_np == 0)).sum())
            self._fn += float(((pred_lab == 0) & (label_np == 1)).sum())
            num = self._tp * self._tn - self._fp * self._fn
            den = math.sqrt((self._tp + self._fp) * (self._tp + self._fn) *
                            (self._tn + self._fp) * (self._tn + self._fn))
            self.sum_metric = num / den if den else 0.0
            self.num_inst = 1

    def reset(self):
        self._tp = self._fp = self._tn = self._fn = 0.0
        self.sum_metric = 0.0
        self.num_inst = 0


@register
class Perplexity(EvalMetric):
    def __init__(self, ignore_label=None, axis=-1, name="perplexity", output_names=None,
                 label_names=None):
        super().__init__(name, output_names, label_names, ignore_label=ignore_label, axis=axis)
        self.ignore_label = ignore_label
        self.axis = axis

    def update(self, labels, preds):
        assert len(labels) == len(preds)
        loss = 0.0
        num = 0
        for label, pred in zip(labels, preds):
            label_np = _asnp(label).astype("int32").reshape(-1)
            pred_np = _asnp(pred)
            pred_np = pred_np.reshape(-1, pred_np.shape[-1])
            probs = pred_np[numpy.arange(label_np.shape[0]), label_np]
            if self.ignore_label is not None:
                ignore = (label_np == self.ignore_label).astype(pred_np.dtype)
                probs = probs * (1 - ignore) + ignore
                num -= int(ignore.sum())
            loss -= numpy.sum(numpy.log(numpy.maximum(1e-10, probs)))
            num += label_np.shape[0]
        self.sum_metric += loss
        self.num_inst += num

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.exp(self.sum_metric / self.num_inst))


@register
class MAE(EvalMetric):
    def __init__(self, name="mae", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label_np = _asnp(label)
            pred_np = _asnp(pred)
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            if len(pred_np.shape) == 1:
                pred_np = pred_np.reshape(pred_np.shape[0], 1)
            self.sum_metric += numpy.abs(label_np - pred_np).mean()
            self.num_inst += 1


@register
class MSE(EvalMetric):
    def __init__(self, name="mse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label_np = _asnp(label)
            pred_np = _asnp(pred)
            if len(label_np.shape) == 1:
                label_np = label_np.reshape(label_np.shape[0], 1)
            if len(pred_np.shape) == 1:
                pred_np = pred_np.reshape(pred_np.shape[0], 1)
            self.sum_metric += ((label_np - pred_np) ** 2.0).mean()
            self.num_inst += 1


@register
class RMSE(MSE):
    def __init__(self, name="rmse", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def get(self):
        if self.num_inst == 0:
            return (self.name, float("nan"))
        return (self.name, math.sqrt(self.sum_metric / self.num_inst))


@register
class CrossEntropy(EvalMetric):
    def __init__(self, eps=1e-12, name="cross-entropy", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            label_np = _asnp(label).ravel()
            pred_np = _asnp(pred)
            assert label_np.shape[0] == pred_np.shape[0]
            prob = pred_np[numpy.arange(label_np.shape[0]), numpy.int64(label_np)]
            self.sum_metric += (-numpy.log(prob + self.eps)).sum()
            self.num_inst += label_np.shape[0]


@register
class NegativeLogLikelihood(EvalMetric):
    def __init__(self, eps=1e-12, name="nll-loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names, eps=eps)
        self.eps = eps

    update = CrossEntropy.update


@register
class PearsonCorrelation(EvalMetric):
    def __init__(self, name="pearsonr", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, labels, preds):
        labels, preds = check_label_shapes(labels, preds, True)
        for label, pred in zip(labels, preds):
            check_label_shapes(label, pred, False, True)
            label_np = _asnp(label).ravel()
            pred_np = _asnp(pred).ravel()
            self.sum_metric += numpy.corrcoef(pred_np, label_np)[0, 1]
            self.num_inst += 1


@register
class Loss(EvalMetric):
    def __init__(self, name="loss", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)

    def update(self, _, preds):
        if isinstance(preds, (list, tuple)):
            for pred in preds:
                loss = _asnp(pred).sum()
                self.sum_metric += loss
                self.num_inst += pred.size
        else:
            self.sum_metric += _asnp(preds).sum()
            self.num_inst += preds.size


class Torch(Loss):
    def __init__(self, name="torch", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


class Caffe(Loss):
    def __init__(self, name="caffe", output_names=None, label_names=None):
        super().__init__(name, output_names, label_names)


@register
class CustomMetric(EvalMetric):
    def __init__(self, feval, name=None, allow_extra_outputs=False,
                 output_names=None, label_names=None):
        if name is None:
            name = feval.__name__
            if name.find("<") != -1:
                name = "custom(%s)" % name
        super().__init__(name, output_names, label_names, feval=feval,
                         allow_extra_outputs=allow_extra_outputs)
        self._feval = feval
        self._allow_extra_outputs = allow_extra_outputs

    def update(self, labels, preds):
        if not self._allow_extra_outputs:
            labels, preds = check_label_shapes(labels, preds, True)
        for pred, label in zip(preds, labels):
            label = _asnp(label)
            pred = _asnp(pred)
            reval = self._feval(label, pred)
            if isinstance(reval, tuple):
                (sum_metric, num_inst) = reval
                self.sum_metric += sum_metric
                self.num_inst += num_inst
            else:
                self.sum_metric += reval
                self.num_inst += 1


def np(numpy_feval, name=None, allow_extra_outputs=False):
    def feval(label, pred):
        return numpy_feval(label, pred)

    feval.__name__ = numpy_feval.__name__
    return CustomMetric(feval, name, allow_extra_outputs)


# short aliases matching the reference registry (`metric.py` @alias decorators)
for _alias, _cls_name in (
    ("acc", "accuracy"), ("top_k_accuracy", "topkaccuracy"),
    ("top_k_acc", "topkaccuracy"), ("ce", "crossentropy"),
    ("nll_loss", "negativeloglikelihood"), ("pearsonr", "pearsoncorrelation"),
    ("composite", "compositeevalmetric"),
):
    if _cls_name in _METRIC_REGISTRY:
        _METRIC_REGISTRY[_alias] = _METRIC_REGISTRY[_cls_name]
