"""Runner `train_module`: the symbolic ResNet through `Module.fit` itself —
`fit` binds, makes the optimizer and runs its own loop (fused step, accuracy
metric every batch, next batch); the benchmark only supplies the data iterator
and the `batch_end_callback`, which is where it reads the clock. Under
`MXNET_SPMD` (set from the traffic file's `env`) the same job runs sharded over
the cell's chips at `chips x batch_per_chip`.

`fit` cannot be stopped on the clock from outside, so the iterator ends the
(only) epoch when the callback says the window is closed.
"""
import time

import numpy as np

import harness
import train_common
from harness import log


class ClockedIter:
    """`mx.io.NDArrayIter` over the pool, cycled until `stop` is set. The
    host-to-device copy of every batch happens where `fit` puts it."""

    def __init__(self, inner, tracer):
        self.inner = inner
        self.tracer = tracer
        self.batch_size = inner.batch_size
        self.provide_data = inner.provide_data
        self.provide_label = inner.provide_label
        self.stop = False

    def __iter__(self):
        return self

    def reset(self):
        self.inner.reset()

    def __next__(self):
        if self.stop:
            raise StopIteration
        with self.tracer.annotate("data_iter.next"):
            try:
                return self.inner.next()
            except StopIteration:
                self.inner.reset()
                return self.inner.next()

    next = __next__


def build_symbol(config):
    from mxnet_tpu.models.resnet import resnet

    size = config["image_size"]
    return resnet(units=config["units"], num_stages=len(config["units"]),
                  filter_list=[config["stem_filters"]] + config["stage_filters"],
                  num_classes=config["num_classes"],
                  image_shape=(config["image_channels"], size, size),
                  bottle_neck=True)


def run(run):
    import mxnet_tpu as mx

    cfg, job = run.config, run.traffic
    batch = job["batch_per_chip"] * run.chips
    warmup = job["warmup_steps"]
    ctx = harness.device_context(0)
    t0 = time.perf_counter()
    data, label = train_common.make_pool(run.seed, batch, job["pool_batches"],
                                         cfg)
    log(f"[setup] pool of {job['pool_batches']} host batches of {batch} "
        f"({data.nbytes / 2**20:.0f} MiB) in {time.perf_counter() - t0:.1f}s")
    it = ClockedIter(mx.io.NDArrayIter(data, label, batch_size=batch),
                     run.tracer)

    mx.random.seed(run.seed)
    np.random.seed(run.seed)
    mod = mx.mod.Module(build_symbol(cfg), context=ctx)
    # bind and initialise before fit (fit finds both done) so that the
    # initial weights can be copied out for the reference
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(mx.init.Xavier())
    args0, auxs0 = mod.get_params()
    names = list(args0) + list(auxs0)
    arrays0 = [args0[n].asnumpy() for n in args0] + \
              [auxs0[n].asnumpy() for n in auxs0]

    clock = train_common.StepClock(run, warmup)
    losses, logp0 = [], []
    fused, shard_devices = [], set()

    class Acc(mx.metric.Accuracy):
        """`acc`, with its update on the profiler's clock."""

        def update(self, labels, preds):
            with run.tracer.annotate("metric.update"):
                super().update(labels, preds)

    def batch_end(param):
        with run.tracer.annotate("batch_end_callback"):
            i = param.nbatch
            if i < warmup:
                probs = mod.get_outputs()[0].asnumpy()
                logp = np.log(np.maximum(probs.astype(np.float64), 1e-300))
                y = label[(i % job["pool_batches"]) * batch:][:batch]
                losses.append(train_common.softmax_xent(logp, y))
                if i == 0:
                    logp0.append(logp)
                fused.append(param.locals["fused"])
                # (read here: fit re-places the weights when the epoch ends)
                shard_devices.update(
                    sh.device for n in mod._param_names
                    for sh in mod._exec.arg_dict[n]._data.addressable_shards)
            if clock.step_done(i):
                it.stop = True

    opt = job["optimizer"]
    mod.fit(it, eval_metric=Acc(), batch_end_callback=batch_end,
            optimizer=opt["name"],
            optimizer_params=(("learning_rate", opt["learning_rate"]),
                              ("momentum", opt["momentum"]),
                              ("wd", opt["wd"])),
            num_epoch=1)
    run.tracer.maybe_stop(force=True)
    if clock.t1 is None:
        raise SystemExit("train_module: fit returned before the window closed")
    if not all(f is True for f in fused):
        raise SystemExit(f"train_module: Module.fused_step was not taken "
                         f"({fused}) — the eager path would have run in "
                         f"silence")
    if run.chips > 1:
        if mod._spmd is None or mod._spmd_failed:
            raise SystemExit("train_module: the SPMD plan was not built; the "
                             "replicated step would have run on one chip")
        log(f"[spmd] MXNET_SPMD={job['env'].get('MXNET_SPMD')}: parameter "
            f"shards on {len(shard_devices)} devices")
        if len(shard_devices) != run.chips:
            raise SystemExit(f"train_module: shards on {len(shard_devices)} "
                             f"devices, not {run.chips}")
    obs = clock.observations(batch)

    pool = job["pool_batches"]
    batches = [(data[(i % pool) * batch:][:batch],
                label[(i % pool) * batch:][:batch]) for i in range(warmup)]
    ok = train_common.check_against_reference(run, names, arrays0, batches,
                                              losses, logp0[0])
    obs.update(correct=ok and obs["compiles_in_window"] == 0,
               host_label="fit-loop")
    return obs
