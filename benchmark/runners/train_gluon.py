"""Runner `train_gluon`: the model-zoo ResNet v1, hybridized (`static_alloc`),
cast to the job's dtype, trained the way example/gluon/image_classification.py
does — `autograd.record` / `backward` / `Trainer.step` / accuracy metric every
batch — on batches that `mx.io.NDArrayIter` serves from a host pool and the
loop copies to the chip every step.
"""
import time

import numpy as np

import harness
import train_common
from harness import log


def build_net(config):
    from mxnet_tpu.gluon.model_zoo.vision.resnet import BottleneckV1, ResNetV1

    return ResNetV1(BottleneckV1, config["units"],
                    [config["stem_filters"]] + config["stage_filters"],
                    classes=config["num_classes"])


def run(run):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon import Trainer, loss as gloss

    cfg, job = run.config, run.traffic
    batch = job["batch_per_chip"] * run.chips
    warmup, pool, dtype = job["warmup_steps"], job["pool_batches"], job["dtype"]
    annotate = run.tracer.annotate
    ctx = harness.device_context(0)
    t0 = time.perf_counter()
    data, label = train_common.make_pool(run.seed, batch, pool, cfg)
    log(f"[setup] pool of {pool} host batches of {batch} "
        f"({data.nbytes / 2**20:.0f} MiB) in {time.perf_counter() - t0:.1f}s")
    train = mx.io.NDArrayIter(data, label, batch_size=batch)

    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    net = build_net(cfg)
    net.initialize(mx.init.Xavier(), ctx=ctx)
    net.hybridize(static_alloc=True)
    if dtype != "float32":
        net.cast(dtype)
    opt = job["optimizer"]
    trainer = Trainer(net.collect_params(), opt["name"],
                      {"learning_rate": opt["learning_rate"],
                       "momentum": opt["momentum"], "wd": opt["wd"],
                       "multi_precision": bool(opt.get("multi_precision"))})
    sce = gloss.SoftmaxCrossEntropyLoss()
    sce.hybridize()
    metric = mx.metric.Accuracy()

    def to_chip(b):
        x = b.data[0].as_in_context(ctx)
        if dtype != "float32":
            x = x.astype(dtype)
        return x, b.label[0].as_in_context(ctx)

    # Shapes are deferred until the first forward: run it once (recorded, so
    # it is the program the loop uses; no backward, no update) and copy the
    # initial weights out for the reference.
    x, y = to_chip(train.next())
    train.reset()
    with autograd.record():
        net(x)
    params = net.collect_params()
    names = list(params.keys())
    arrays0 = [params[n].data().astype("float32").asnumpy() for n in names]

    clock = train_common.StepClock(run, warmup)
    losses, logp0 = [], []
    i = 0
    while True:
        with annotate("data_iter.next"):
            try:
                b = train.next()
            except StopIteration:
                train.reset()
                b = train.next()
        with annotate("to_chip"):
            x, y = to_chip(b)
        with annotate("record_forward"):
            with autograd.record():
                out = net(x)
                loss = sce(out, y)
        with annotate("backward"):
            loss.backward()
        with annotate("trainer.step"):
            trainer.step(batch)
        with annotate("metric.update"):
            metric.update([y], [out])
        if i < warmup:
            losses.append(float(loss.asnumpy().astype(np.float64).mean()))
            if i == 0:
                logits = out.astype("float32").asnumpy().astype(np.float64)
                logp0.append(logits)
        if clock.step_done(i):
            break
        i += 1
    run.tracer.maybe_stop(force=True)
    if not any(str(p.data().dtype) == dtype for p in params.values()):
        raise SystemExit(f"train_gluon: no parameter is {dtype}")
    obs = clock.observations(batch)

    batches = [(data[(k % pool) * batch:][:batch],
                label[(k % pool) * batch:][:batch]) for k in range(warmup)]
    ok = train_common.check_against_reference(run, names, arrays0, batches,
                                              losses, logp0[0])
    obs.update(correct=ok and obs["compiles_in_window"] == 0,
               host_label="gluon-loop")
    return obs
