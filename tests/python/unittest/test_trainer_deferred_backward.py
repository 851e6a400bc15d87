"""A plain ``backward()`` over hybridized calls leaves its gradients pending,
and ``Trainer.step`` runs forward, pullback and the optimizer update as ONE
donated program; any read in between runs the two programs as before.

Every case trains a small hybridized conv-BN-dense net + loss twice from the
same seed and compares weights, optimizer states and losses: once the way
the case says, once with ``backward(retain_graph=True)``, which never defers
(the launch at ``backward()``, the update through ``Updater._fused_call``).
The two take the same jaxprs through the same ``fused_update``, so the
results are equal, not close. The counters say which way each step went.
"""

import jax
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, telemetry
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gluon import nn

BATCH, CLASSES, STEPS = 8, 5, 3
COUNTERS = ("autograd.backward_deferred", "trainer.fused_step",
            "autograd.deferred_forced")
SGD_BF16 = ("bfloat16", "sgd", {"learning_rate": 0.1, "momentum": 0.9,
                                "wd": 1e-4, "multi_precision": True})
OPTIMIZERS = {
    "sgd_momentum_multi_precision_bf16": SGD_BF16,
    "sgd_fp32": ("float32", "sgd", {"learning_rate": 0.1, "wd": 1e-4}),
    "nag": ("float32", "nag", {"learning_rate": 0.05, "momentum": 0.9}),
    "adam": ("float32", "adam", {"learning_rate": 0.01, "wd": 1e-4}),
}


def _counts():
    return {c: telemetry.counter(c).value for c in COUNTERS}


def _moved(before):
    return tuple(telemetry.counter(c).value - before[c] for c in COUNTERS)


class _Job:
    """Net, loss, Trainer and two batches, the same for the same seed."""

    def __init__(self, dtype="float32", optimizer="sgd", params=None,
                 grad_req="write", trainers=1, spare=False):
        rng = np.random.RandomState(0)
        mx.random.seed(0)
        self.net = nn.HybridSequential()
        self.net.add(nn.Conv2D(4, 3, padding=1, in_channels=3),
                     nn.BatchNorm(in_channels=4), nn.Activation("relu"),
                     nn.Flatten(), nn.Dense(CLASSES, in_units=4 * 6 * 6))
        self.net.collect_params().setattr("grad_req", grad_req)
        self.net.initialize(mx.init.Xavier(magnitude=2.0))
        self.net.hybridize()
        self.net.cast(dtype)
        self.sce = gluon.loss.SoftmaxCrossEntropyLoss()
        self.sce.hybridize()
        every = self.net.collect_params()
        self.params = list(every.values())
        if spare:                       # a parameter no forward uses
            self.spare = nn.Dense(2, in_units=3)
            self.spare.initialize()
            every = dict(every)
            every.update(self.spare.collect_params())
        names = sorted(every)
        groups = [names] if trainers == 1 else [names[::2], names[1::2]]
        self.trainers = [gluon.Trainer(
            {n: every[n] for n in group}, optimizer,
            dict(params or {"learning_rate": 0.1, "momentum": 0.9}))
            for group in groups]
        self.trainer = self.trainers[0]
        self.xs = [nd.array(rng.randn(BATCH, 3, 6, 6).astype(np.float32))
                   .astype(dtype) for _ in range(2)]
        self.y = nd.array((np.arange(BATCH) % CLASSES).astype(np.float32))

    def forward(self, i=0):
        with autograd.record():
            out = self.net(self.xs[i % 2])
            return self.sce(out, self.y)

    def train(self, steps=STEPS, between=None, **backward):
        """``steps`` of record / backward / ``between`` / step; the losses."""
        losses = []
        for i in range(steps):
            loss = self.forward(i)
            loss.backward(**backward)
            if between is not None:
                between(self, loss)
            for trainer in self.trainers:
                trainer.step(BATCH)
            losses.append(loss.asnumpy())
        return losses

    def state(self):
        """Weights, then every optimizer state leaf, as numpy."""
        leaves = [p.data() for p in self.params]
        for trainer in self.trainers:
            states = trainer._updaters[0].states
            leaves += jax.tree_util.tree_leaves(
                [states[i] for i in sorted(states)])
        return [leaf.asnumpy() for leaf in leaves]


def _same(job, losses, other, other_losses):
    for a, b in zip(losses, other_losses):
        np.testing.assert_array_equal(a, b)
    mine, theirs = job.state(), other.state()
    assert len(mine) == len(theirs)
    for a, b in zip(mine, theirs):
        np.testing.assert_array_equal(a, b)


def _undeferred(**job_args):
    """The reference: the same job with the launch at ``backward()``."""
    job = _Job(**job_args)
    return job, job.train(retain_graph=True)


@pytest.mark.parametrize("name", OPTIMIZERS)
def test_one_program_equals_two(name):
    dtype, optimizer, params = OPTIMIZERS[name]
    args = dict(dtype=dtype, optimizer=optimizer, params=params)
    job = _Job(**args)
    before = _counts()
    losses = job.train()
    assert _moved(before) == (STEPS, STEPS, 0)      # engagement 1
    forced = _Job(**args)
    before = _counts()
    forced_losses = forced.train(
        between=lambda j, loss: j.params[0].grad().asnumpy())
    assert _moved(before) == (STEPS, 0, STEPS)
    _same(job, losses, forced, forced_losses)
    _same(job, losses, *_undeferred(**args))
    if dtype == "bfloat16":                          # masters and momenta
        assert len(job.state()) == len(job.params) + 2 * 6


def _read_gradient(job, loss):
    assert np.isfinite(job.params[-1].grad().asnumpy()).all()


def _read_loss(job, loss):
    assert np.isfinite(loss.asnumpy()).all()


def _clip(job, loss):
    gluon.utils.clip_global_norm(
        [p.grad() for p in job.params if p.grad_req != "null"], 0.5)


def _zero_grad(job, loss):
    job.net.collect_params().zero_grad()


@pytest.mark.parametrize("between", [_read_gradient, _read_loss, _clip,
                                     _zero_grad],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_read_before_step_runs_two_programs(between):
    dtype, optimizer, params = SGD_BF16
    args = dict(dtype=dtype, optimizer=optimizer, params=params)
    job = _Job(**args)
    before = _counts()
    losses = job.train(between=between)
    assert _moved(before) == (STEPS, 0, STEPS)
    reference = _Job(**args)
    _same(job, losses, reference,
          reference.train(between=between, retain_graph=True))


def _grad_add(job):
    return _Job(grad_req="add"), {}


def _retain_graph(job):
    return job, {"retain_graph": True}


def _head_gradient(job):
    return job, {"out_grad": nd.array(np.linspace(0.5, 1.5, BATCH)
                                      .astype(np.float32))}


def _switch_off(job):
    return job, {}


@pytest.mark.parametrize("case", [_grad_add, _retain_graph, _head_gradient,
                                  _switch_off],
                         ids=lambda f: f.__name__.strip("_"))
def test_backward_launches_at_once(case, monkeypatch):
    if case is _switch_off:
        monkeypatch.setenv("MXNET_FUSED_STEP", "0")
    job, backward = case(_Job())
    before = _counts()
    fused = telemetry.counter("autograd.fused_backward").value
    loss = job.forward()
    loss.backward(**backward)
    assert telemetry.counter("autograd.fused_backward").value == fused + 1
    assert type(job.params[0].grad()._buf).__name__ != "PendingGrad"
    job.trainer.step(BATCH)
    assert _moved(before) == (0, 0, 0)


def test_autograd_grad_launches_at_once():
    job = _Job()
    before = _counts()
    loss = job.forward()
    grads = autograd.grad(loss, [p.data() for p in job.params
                                 if p.grad_req != "null"])
    assert all(np.isfinite(g.asnumpy()).all() for g in grads)
    assert _moved(before) == (0, 0, 0)


def _two_trainers():
    return dict(trainers=2)


def _no_fused_update():
    return dict(optimizer="rmsprop", params={"learning_rate": 0.01})


@pytest.mark.parametrize("case", [_two_trainers, _no_fused_update],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_trainer_that_cannot_take_it_whole_reads_the_gradients(case):
    args = case()
    job = _Job(**args)
    before = _counts()
    losses = job.train()
    assert _moved(before) == (STEPS, 0, STEPS)
    _same(job, losses, *_undeferred(**args))


def test_a_second_backward_leaves_the_first_dead():
    """Its gradients all overwritten, the first never runs: its loss, read,
    is the forward-only program's; the step is the second's, whole."""
    job = _Job()
    before = _counts()
    forwards = telemetry.counter("autograd.forced_forward").value
    first = job.forward(0)
    first.backward()
    second = job.forward(1)
    second.backward()
    seen = first.asnumpy()
    assert telemetry.counter("autograd.forced_forward").value == forwards + 2
    job.trainer.step(BATCH)
    assert _moved(before) == (2, 1, 0)
    reference = _Job()
    want = reference.forward(0)
    want.backward(retain_graph=True)
    np.testing.assert_array_equal(seen, want.asnumpy())
    want = reference.forward(1)
    want.backward(retain_graph=True)
    reference.trainer.step(BATCH)
    _same(job, [second.asnumpy()], reference, [want.asnumpy()])


def test_a_gradient_read_after_the_step_is_explained():
    job = _Job()
    job.train(steps=1)
    with pytest.raises(MXNetError, match="never materialized"):
        job.params[0].grad().asnumpy()
    job.net.collect_params().zero_grad()        # overwrites: nothing to run
    assert not job.params[0].grad().asnumpy().any()
    job.train(steps=1)
    job.net.cast("float32")                     # nor anything to cast
    assert not job.params[0].grad().asnumpy().any()


def test_ignore_stale_grad_with_an_unused_parameter():
    job = _Job(spare=True)
    before = _counts()
    loss = job.forward()
    loss.backward()
    with pytest.raises(UserWarning, match="has not been updated"):
        job.trainer.step(BATCH)
    job.trainer.step(BATCH, ignore_stale_grad=True)
    assert _moved(before) == (1, 1, 0)
    reference = _Job(spare=True)
    want = reference.forward()
    want.backward(retain_graph=True)
    reference.trainer.step(BATCH, ignore_stale_grad=True)
    _same(job, [loss.asnumpy()], reference, [want.asnumpy()])


def test_update_after_allreduce_grads():
    job = _Job()
    before = _counts()
    losses = []
    for i in range(STEPS):
        loss = job.forward(i)
        loss.backward()
        job.trainer.allreduce_grads()
        job.trainer.update(BATCH)
        losses.append(loss.asnumpy())
    assert _moved(before) == (STEPS, STEPS, 0)
    _same(job, losses, *_undeferred())


def test_save_and_load_states_between_steps(tmp_path):
    def drive(job, **backward):
        losses = job.train(steps=1, **backward)
        job.trainer.save_states(str(tmp_path / "states"))
        losses += job.train(steps=1, **backward)
        job.trainer.load_states(str(tmp_path / "states"))
        return losses + job.train(steps=2, **backward)

    job = _Job()
    before = _counts()
    losses = drive(job)
    assert _moved(before) == (4, 4, 0)
    reference = _Job()
    _same(job, losses, reference, drive(reference, retain_graph=True))


def test_a_new_learning_rate_or_batch_size_compiles_nothing():
    job = _Job()
    job.train(steps=2)
    caches = (job.net._cached_op._cache, job.sce._cached_op._cache)
    misses = sum(c.misses for c in caches)
    traces = telemetry.counter("compile.jax_traces").value
    before = _counts()
    weights = job.params[0].data().asnumpy()
    job.trainer.set_learning_rate(0.0)
    job.train(steps=1)
    assert job.trainer.learning_rate == 0.0  # momentum 0.9 still moves them
    assert (job.params[0].data().asnumpy() != weights).any()
    loss = job.forward()
    loss.backward()
    job.trainer.step(BATCH * 2)
    assert _moved(before) == (2, 2, 0)
    assert sum(c.misses for c in caches) == misses
    assert telemetry.counter("compile.jax_traces").value == traces
    keys = [k for k in caches[1].keys() if k[0] == "bwd"]
    assert len(keys) == 1 and keys[0][-1][0] == "update"


def test_the_step_program_returns_no_gradient():
    """Outputs: logits, loss, a new weight a parameter, the states' leaves;
    weights and states are donated."""
    dtype, optimizer, params = SGD_BF16
    job = _Job(dtype=dtype, optimizer=optimizer, params=params)
    job.train(steps=1)
    cache = job.sce._cached_op._cache
    (key,) = [k for k in cache.keys() if k[0] == "bwd"]
    args, kwargs = cache._entry_stats[key]["avals"]
    emitted, weights, states = jax.eval_shape(cache._entries[key]._fn,
                                              *args, **kwargs)
    assert sorted(o.shape for o in emitted) == [(BATCH,), (BATCH, CLASSES)]
    trainable = [p for p in job.params if p.grad_req != "null"]
    assert sorted(w.shape for w in weights) \
        == sorted(p.shape for p in trainable)
    assert len(states) == 2 * len(trainable)
    lowered = cache._entries[key]._fn.lower(*args, **kwargs)
    donated = [a.donated for a in
               jax.tree_util.tree_leaves(lowered.args_info)]
    assert sum(donated) == 3 * len(trainable)
