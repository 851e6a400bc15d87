"""`mx.metric` settles an update whose arguments are NDArrays one call late.

The contract (`mxnet_tpu/metric.py`): the numbers are those of lockstep
(every update under `mx.metric.immediate()`) bit for bit, call *k* fetches the arguments of call
*k - 1* and nothing else, every read settles first, and the kept values are
those the arrays held at call time.
"""
import copy
import pickle

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu._cached_op import PendingOutput
from mxnet_tpu.metric import _METRIC_REGISTRY
from mxnet_tpu.ndarray.ndarray import NDArray

N, CLASSES, STEPS = 12, 5, 4
BINARY = (mx.metric.F1, mx.metric.MCC)
REGRESSION = (mx.metric.MAE, mx.metric.MSE, mx.metric.PearsonCorrelation)
COUNTERS = ("metric.deferred", "metric.settled_late", "metric.settled_on_read")


def _mean_abs(label, pred):
    return float(np.abs(label - pred.argmax(axis=1)).sum()), label.shape[0]


def make(name):
    cls = _METRIC_REGISTRY[name]
    if cls is mx.metric.CustomMetric:
        return cls(_mean_abs)
    if cls is mx.metric.CompositeEvalMetric:
        return cls(metrics=["acc", "ce", mx.metric.TopKAccuracy(top_k=3)])
    if cls is mx.metric.TopKAccuracy:
        return cls(top_k=3)
    return cls()


def argument_sets(metric, steps=STEPS, seed=0):
    """`steps` pairs (labels, preds) of host arrays that suit `metric`."""
    rng = np.random.default_rng(seed)
    sets = []
    for _ in range(steps):
        if isinstance(metric, REGRESSION):
            label = rng.normal(size=N).astype(np.float32)
            pred = rng.normal(size=N).astype(np.float32)
        else:
            classes = 2 if isinstance(metric, BINARY) else CLASSES
            label = rng.integers(0, classes, N).astype(np.float32)
            logits = rng.normal(size=(N, classes)).astype(np.float32)
            e = np.exp(logits)
            pred = e / e.sum(axis=1, keepdims=True)
        sets.append(([label], [pred]))
    return sets


def on_device(sets):
    return [([nd.array(a) for a in labels], [nd.array(a) for a in preds])
            for labels, preds in sets]


def state(metric):
    """Everything a reader can see, as plain values (a float by its repr:
    nan equals nan)."""
    names, values = metric.get()
    out = {"get": (names, [repr(float(v)) for v in np.atleast_1d(values)]),
           "name_value": [(n, repr(float(v)))
                          for n, v in metric.get_name_value()]}
    if not isinstance(metric, mx.metric.CompositeEvalMetric):
        out["sum_metric"] = repr(float(metric.sum_metric))
        out["num_inst"] = int(metric.num_inst)
    return out


def counters():
    return {name: telemetry.counter(name).value for name in COUNTERS}


def moved(before):
    now = counters()
    return tuple(now[name] - before[name] for name in COUNTERS)


def _concrete(buf):
    return buf.value if type(buf) is PendingOutput else buf


class FetchLog:
    """Counts `asnumpy` calls by the device array they read: an NDArray the
    metric kept shares its buffer with the one the caller passed."""

    def __init__(self, monkeypatch):
        self.count = {}
        self.alive = []     # an id is only unique while its object lives
        real = NDArray.asnumpy

        def asnumpy(arr):
            value = real(arr)
            buf = _concrete(arr._buf)
            self.alive.append(buf)
            self.count[id(buf)] = self.count.get(id(buf), 0) + 1
            return value

        monkeypatch.setattr(NDArray, "asnumpy", asnumpy)

    def of(self, arrays):
        return [self.count.get(id(_concrete(a._buf)), 0) for a in arrays]

    def fetched_sets(self, sets):
        """Indices of the argument sets any of whose arrays was fetched."""
        return [i for i, (labels, preds) in enumerate(sets)
                if any(self.of(labels + preds))]


@pytest.fixture
def fetches(monkeypatch):
    return FetchLog(monkeypatch)


@pytest.fixture
def lockstep():
    def run(fn):
        with mx.metric.immediate():
            return fn()
    return run


# ---------------------------------------------------------------------------
# every registered metric
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("name", sorted(_METRIC_REGISTRY))
def test_equals_lockstep_bit_for_bit(name, lockstep):
    sets = argument_sets(make(name))

    def run():
        metric = make(name)
        before = counters()
        for labels, preds in on_device(sets):
            metric.update(labels, preds)
        kept = moved(before)[0]
        return state(metric), kept

    deferred, kept = run()
    reference, kept_in_lockstep = lockstep(run)
    children = len(make(name).metrics) if name in (
        "composite", "compositeevalmetric") else 1
    assert kept == STEPS * children and kept_in_lockstep == 0
    assert deferred == reference
    # and the reference is what plain numpy arguments give
    plain = make(name)
    for labels, preds in sets:
        plain.update(labels, preds)
    assert state(plain) == reference


@pytest.mark.parametrize("name", sorted(_METRIC_REGISTRY))
def test_call_k_fetches_the_arguments_of_call_k_minus_1(name, fetches):
    metric = make(name)
    sets = on_device(argument_sets(metric))
    for k, (labels, preds) in enumerate(sets, start=1):
        metric.update(labels, preds)
        assert fetches.fetched_sets(sets) == list(range(k - 1))
    metric.get()
    assert fetches.fetched_sets(sets) == list(range(STEPS))
    # each array once per metric that reads it (`Loss` reads no label)
    readers = len(metric.metrics) if hasattr(metric, "metrics") else 1
    for labels, preds in sets:
        assert fetches.of(preds) == [readers]
        assert fetches.of(labels) in ([readers], [0])


# ---------------------------------------------------------------------------
# every read settles first
# ---------------------------------------------------------------------------

READS = {
    "get": lambda m: m.get(),
    "get_name_value": lambda m: m.get_name_value(),
    "reset": lambda m: m.reset(),
    "str": str,
    "sum_metric": lambda m: m.sum_metric,
    "num_inst": lambda m: m.num_inst,
    "deepcopy": copy.deepcopy,
    "pickle": lambda m: pickle.loads(pickle.dumps(m)),
}


@pytest.mark.parametrize("read", sorted(READS))
@pytest.mark.parametrize("name", ["acc", "f1", "perplexity", "loss"])
def test_a_read_settles_first(name, read, fetches, lockstep):
    sets = argument_sets(make(name), steps=1)

    def run():
        metric = make(name)
        (labels, preds), = on_device(sets)
        before = counters()
        metric.update(labels, preds)
        pending = metric._pending is not None
        untouched = fetches.of(preds) == [0]
        result = READS[read](metric)
        assert metric._pending is None and fetches.of(preds) == [1]
        if isinstance(result, mx.metric.EvalMetric):
            result = state(result)
        return pending, untouched, moved(before), result, state(metric)

    pending, untouched, counted, result, after = run()
    assert pending and untouched and counted == (1, 0, 1)
    pending, untouched, counted, ref_result, ref_after = lockstep(run)
    assert not pending and not untouched and counted == (0, 0, 0)
    assert (result, after) == (ref_result, ref_after)
    if read == "reset":
        assert after["num_inst"] == 0


# ---------------------------------------------------------------------------
# what is kept
# ---------------------------------------------------------------------------


def test_a_later_write_does_not_change_what_is_settled():
    metric = mx.metric.Accuracy()
    (labels, preds), = argument_sets(metric, steps=1)
    y, p = nd.array(labels[0]), nd.array(preds[0])
    metric.update([y], [p])
    p[:] = 0
    y[:] = CLASSES + 1
    reference = mx.metric.Accuracy()
    reference.update(labels, preds)
    assert reference.get()[1] > 0
    assert state(metric) == state(reference)


def test_numpy_arguments_settle_at_once(fetches):
    metric = mx.metric.Accuracy()
    (labels, preds), = argument_sets(metric, steps=1)
    before = counters()
    metric.update(labels, preds)
    assert metric._pending is None and moved(before) == (0, 0, 0)
    assert metric._num_inst == N
    # one NDArray among them defers the call, and the numpy label is kept by
    # value; the call before it is settled first, in order
    label = labels[0].copy()
    metric.update([label], [nd.array(preds[0])])
    label[:] = CLASSES + 1
    metric.update(labels, preds)
    assert metric._pending is None and moved(before) == (1, 1, 0)
    assert metric.num_inst == 3 * N
    assert metric.sum_metric == 3 * (preds[0].argmax(1) == labels[0]).sum()


def test_immediate_applies_at_once_and_in_order():
    metric = mx.metric.Loss()
    metric.update(None, [nd.array([1.0, 2.0])])
    before = counters()
    with mx.metric.immediate():
        metric.update(None, [nd.array([3.0])])
        assert metric._pending is None and metric._num_inst == 3
    assert moved(before) == (0, 1, 0)
    assert metric.get() == ("loss", 2.0)


def test_an_error_of_call_i_surfaces_at_call_i_plus_1_or_at_a_read(lockstep):
    good = ([nd.array([0.0, 1.0])], [nd.array([[0.9, 0.1], [0.2, 0.8]])])
    bad = ([nd.array([0.0, 1.0, 1.0])], [nd.array([[0.9, 0.1], [0.2, 0.8]])])
    with pytest.raises(ValueError, match="Shape of labels"):
        lockstep(lambda: mx.metric.Accuracy().update(*bad))
    metric = mx.metric.Accuracy()
    metric.update(*bad)
    with pytest.raises(ValueError, match="Shape of labels"):
        metric.update(*good)
    assert metric.get() == ("accuracy", 1.0)    # call i + 1 itself was kept
    metric.update(*bad)
    with pytest.raises(ValueError, match="Shape of labels"):
        metric.get()


def _net():
    net = gluon.nn.HybridSequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(CLASSES))
    net.initialize(mx.init.Xavier())
    net.hybridize(static_alloc=True)
    return net


def test_a_pending_output_is_settled_by_backward_not_by_a_forward():
    """The order of a loop that updates the metric before `backward()`: the
    kept buffer is the recorded call's pending output, which `backward()`
    fills, so settling it runs no forward-only program."""
    mx.random.seed(3)
    net = _net()
    sce = gluon.loss.SoftmaxCrossEntropyLoss()
    sce.hybridize()
    trainer = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    metric, reference = mx.metric.Accuracy(), mx.metric.Accuracy()
    rng = np.random.default_rng(0)
    forced = telemetry.counter("autograd.forced_forward")
    fused = telemetry.counter("autograd.fused_backward")
    forced0, fused0 = forced.value, fused.value
    for _ in range(3):
        x = nd.array(rng.normal(size=(N, 8)).astype(np.float32))
        y = nd.array(rng.integers(0, CLASSES, N).astype(np.float32))
        with autograd.record():
            out = net(x)
            loss = sce(out, y)
        metric.update([y], [out])
        assert type(metric._pending[1][0]._buf) is PendingOutput
        loss.backward()
        trainer.step(N)
        reference.update([y.asnumpy()], [out.asnumpy()])
    assert state(metric) == state(reference)
    assert forced.value == forced0 and fused.value == fused0 + 3


# ---------------------------------------------------------------------------
# composites and user subclasses
# ---------------------------------------------------------------------------


class CountsOnes(mx.metric.EvalMetric):
    """A user metric with state of its own and a `get` that reads only that."""

    def __init__(self):
        super().__init__("ones")

    def update(self, labels, preds):
        for label in labels:
            self.ones += int((label.asnumpy() == 1).sum())
            self.calls += 1

    def reset(self):
        self.ones = self.calls = 0

    def get(self):
        return self.name, (self.ones, self.calls)


class AccuracyAndCalls(mx.metric.Accuracy):
    """A user metric whose `update` goes on to its parent's."""

    calls = 0

    def update(self, labels, preds):
        self.calls += 1
        super().update(labels, preds)


class Both(mx.metric.EvalMetric):
    """A user composite: its children's updates are made while it settles,
    so they apply at once and the lag stays one call."""

    def __init__(self):
        self.children = [mx.metric.Accuracy(), mx.metric.CrossEntropy()]
        super().__init__("both")

    def update(self, labels, preds):
        for child in self.children:
            child.update(labels, preds)

    def reset(self):
        for child in self.children:
            child.reset()

    def get(self):
        return self.name, [child.get()[1] for child in self.children]


@pytest.mark.parametrize("make_metric", [
    CountsOnes, AccuracyAndCalls, Both,
    lambda: mx.metric.create(["acc", "ce"]),
    lambda: mx.metric.np(lambda label, pred: float((label == pred.argmax(1)).mean())),
], ids=["own_state", "calls_super", "user_composite", "composite", "np"])
def test_composites_and_user_subclasses_defer(make_metric, fetches, lockstep):
    sets = argument_sets(mx.metric.Accuracy())

    def run():
        metric = make_metric()
        before = counters()
        device = on_device(sets)
        for k, (labels, preds) in enumerate(device, start=1):
            metric.update(labels, preds)
            lag = fetches.fetched_sets(device) == list(range(k - 1))
        names, values = metric.get()
        return names, np.asarray(values).tolist(), lag, moved(before)

    names, values, lag, counted = run()
    ref_names, ref_values, ref_lag, ref_counted = lockstep(run)
    assert (names, values) == (ref_names, ref_values)
    assert lag and not ref_lag and ref_counted == (0, 0, 0)
    kept = counted[0]
    assert kept >= STEPS and counted == (kept, kept - kept // STEPS,
                                         kept // STEPS)


def test_the_counters_count():
    metric = mx.metric.MSE()
    sets = on_device(argument_sets(metric))
    before = counters()
    for labels, preds in sets[:3]:
        metric.update(labels, preds)
    assert moved(before) == (3, 2, 0)
    metric.get()
    metric.get()
    assert moved(before) == (3, 2, 1)
    metric.update(*sets[3])
    metric.reset()
    assert moved(before) == (4, 2, 2)


# ---------------------------------------------------------------------------
# the two loops
# ---------------------------------------------------------------------------


class Watched(mx.metric.Accuracy):
    applied = 0

    def update(self, labels, preds):
        self.applied += 1
        super().update(labels, preds)


def _fit_watched(on_batch=None, num_epoch=1):
    """A five-step `fit` of a one-layer net; returns the counters it moved
    and the metric's final state."""
    rng = np.random.RandomState(0)
    X = rng.uniform(-1, 1, (40, 6)).astype(np.float32)
    Y = rng.randint(0, CLASSES, (40,)).astype(np.float32)
    data = mx.sym.Variable("data")
    fc = mx.sym.FullyConnected(data, num_hidden=CLASSES, name="fc")
    mx.random.seed(7)
    metric = Watched()
    before = counters()
    mod = mx.mod.Module(mx.sym.SoftmaxOutput(fc, name="softmax"),
                        context=mx.cpu())
    mod.fit(mx.io.NDArrayIter(X, Y, batch_size=8), num_epoch=num_epoch,
            eval_metric=metric, batch_end_callback=on_batch,
            optimizer_params=(("learning_rate", 0.1),))
    return moved(before), state(metric)


def test_fit_keeps_its_one_step_of_lag(lockstep):
    """`fit` calls the metric plainly and the metric lags: at the end of
    batch t, t updates have applied and step t's is the one kept. The
    epoch's last settles where `fit` reads the metric."""
    steps = 5

    def run():
        seen = []

        def on_batch(param):
            metric = param.eval_metric
            seen.append((param.nbatch, metric.applied,
                         metric._pending is not None))

        return (seen,) + _fit_watched(on_batch)

    seen, counted, final = run()
    assert seen == [(t, t, True) for t in range(steps)]
    assert counted == (steps, steps - 1, 1)
    ref_seen, ref_counted, ref_final = lockstep(run)
    assert ref_seen == [(t, t + 1, False) for t in range(steps)]
    assert ref_counted == (0, 0, 0)
    assert final == ref_final


def test_fit_defers_every_step_and_settles_all_but_the_last_late():
    """The lag has one owner: over N steps an epoch `fit` defers N updates
    through `mx.metric` and N - 1 of them settle inside the next step's
    call; only the epoch's last waits for a read (`fit`'s own, of the
    epoch's value)."""
    steps, epochs = 5, 3
    counted, _ = _fit_watched(num_epoch=epochs)
    assert counted == (epochs * steps, epochs * (steps - 1), epochs)


def test_the_gluon_loop_of_the_benchmark_for_four_steps(fetches, lockstep):
    """benchmark/runners/train_gluon.py's loop (as
    example/gluon/image_classification.py writes it) on a tiny ResNet: the
    metric call that closes iteration i fetches the outputs of step i - 1."""
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1

    batch, pool = 4, 2
    rng = np.random.default_rng(11)
    data = rng.random((batch * pool, 3, 40, 40), dtype=np.float32) * 2 - 1
    label = rng.integers(0, 10, batch * pool).astype(np.float32)
    ctx = mx.cpu()

    def run():
        train = mx.io.NDArrayIter(data, label, batch_size=batch)
        mx.random.seed(11)
        net = ResNetV1(BottleneckV1, [1, 1], [8, 16, 32], classes=10)
        net.initialize(mx.init.Xavier(), ctx=ctx)
        net.hybridize(static_alloc=True)
        net.cast("bfloat16")
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05, "momentum": 0.9,
                                 "multi_precision": True})
        sce = gluon.loss.SoftmaxCrossEntropyLoss()
        sce.hybridize()
        metric = mx.metric.Accuracy()
        before = counters()
        outs, lags = [], []
        for _ in range(4):
            try:
                b = train.next()
            except StopIteration:
                train.reset()
                b = train.next()
            x = b.data[0].as_in_context(ctx).astype("bfloat16")
            y = b.label[0].as_in_context(ctx)
            with autograd.record():
                out = net(x)
                loss = sce(out, y)
            loss.backward()
            trainer.step(batch)
            metric.update([y], [out])
            outs.append(out)
            lags.append(sum(fetches.of(outs)))
        counted = moved(before)
        return lags, counted, state(metric)

    forced = telemetry.counter("autograd.forced_forward").value
    lags, counted, final = run()
    assert lags == [0, 1, 2, 3] and counted == (4, 3, 0)
    assert telemetry.counter("autograd.forced_forward").value == forced
    ref_lags, ref_counted, ref_final = lockstep(run)
    assert ref_lags == [1, 2, 3, 4] and ref_counted == (0, 0, 0)
    assert final == ref_final and final["num_inst"] == 16
