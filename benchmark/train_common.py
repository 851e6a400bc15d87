"""What the two training runners share: the seeded pool of host batches, the
clock that opens and closes the measured window on step boundaries, and the
comparison with the plain reference that decides `correct`.

The timing basis (settled on the chip in PR 21): the host clock, read after a
host fetch of the step's outputs — both loops fetch them every batch for the
accuracy metric, as `BaseModule.fit` and example/gluon/image_classification.py
do, so a step that has "ended" has finished on the device.
"""
import gc
import time

import numpy as np

from harness import log

# Tolerances, with their reasons (as chip_smoke.py, PR 21).
#
# Logits, program vs reference at "highest" precision: the TPU's DEFAULT
# matmul/conv precision rounds both multiplicands to bfloat16 (2^-9 relative
# each) and accumulates in fp32. Over the 53 convolutions of ResNet-50,
# re-normalised by BatchNorm, the errors add like a random walk: a typical
# logit is off by ~sqrt(53) * 2^-9 = 1.4e-2 of the logit scale and the worst
# of 128,000 by ~4x that (measured on the v5e in PR 21: 5.3e-2). A bfloat16
# net also stores every activation in 8 bits of mantissa, which about doubles
# the walk. A program that dropped a layer or mis-strided one would be off by
# the whole scale.
LOGIT_RTOL = {"float32": 1e-1, "bfloat16": 2e-1}
# Loss of the same steps from the same start, program vs reference: different
# reduction orders, bf16 multiplicands, and ReLU gates at zero that flip single
# gradient components. 2e-2 of the loss; a wrong learning rate, a missing
# momentum or a gradient scaled by the batch would move the loss by more
# within the warm-up steps.
LOSS_RTOL = 2e-2


def make_pool(seed, batch, pool_batches, config):
    """`pool_batches` distinct host batches: uniform noise in [-1, 1) and
    uniform labels, float32, from the seed, in bulk."""
    rng = np.random.default_rng([int(seed), 0x706f6f6c])
    n = batch * pool_batches
    size = config["image_size"]
    data = rng.random((n, config["image_channels"], size, size),
                      dtype=np.float32)
    data *= 2.0
    data -= 1.0
    label = rng.integers(0, config["num_classes"], n).astype(np.float32)
    return data, label


def softmax_xent(logp, labels):
    idx = np.asarray(labels).astype(np.int64)
    return float(-np.asarray(logp, np.float64)[np.arange(len(idx)), idx].mean())


# Steps the loop runs at its own pace between the last warm-up step (which
# the runner syncs to read the loss) and the window's opening. After them the
# dispatch runs as far ahead of the device as it does for the rest of the run
# (`fit` defers the accuracy metric one step), so the opening edge is read
# right after a blocking host fetch of the previous step's outputs, like every
# later step end and like the closing edge. Opening on the last synced step
# counted one step too many: +0.9% on the Module cell (v5e, PR 22).
SETTLE_STEPS = 2


class CollectorPauses:
    """Seconds the interpreter's cyclic collector held the loop while the
    window was open (a diagnostic on the `[window]` line: a stall that is a
    collection names its cause)."""

    def __init__(self, clock):
        self.clock = clock
        self.total = self.longest = 0.0
        self._began = None
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self._began = time.perf_counter()
        elif self._began is not None and self.clock.t0 is not None \
                and self.clock.t1 is None:
            took = time.perf_counter() - self._began
            self.total += took
            self.longest = max(self.longest, took)


class StepClock:
    """Opens the measured window `SETTLE_STEPS` steps after the warm-up steps
    and closes it at the end of the first step that ends `run.seconds` later
    (so the window is a whole number of steps and a little longer than
    asked). Drives the profiler window of a traced run from the same
    boundaries."""

    def __init__(self, run, warmup_steps):
        self.run = run
        self.opens_after = int(warmup_steps) + SETTLE_STEPS - 1
        self.t0 = self.t1 = None
        self.ends = []                  # perf_counter at each measured step's end
        self.compiles0 = self.compiles1 = None
        self.pauses = CollectorPauses(self)

    def step_done(self, i):
        """Step `i` (0-based) has finished. True once the window is closed."""
        now = time.perf_counter()
        if self.t1 is not None:
            return True
        if i < self.opens_after:
            return False
        if i == self.opens_after:
            self.compiles0 = self.run.events.backend_compiles
            self.t0 = now
            return False
        self.ends.append(now)
        tracer = self.run.tracer
        elapsed = now - self.t0
        tracer.maybe_start(elapsed)
        tracer.maybe_stop()
        if elapsed >= self.run.seconds and not tracer.active:
            self.t1 = now
            self.compiles1 = self.run.events.backend_compiles
            return True
        return False

    def observations(self, items_per_step):
        steps = len(self.ends)
        tr = self.run.tracer
        traced = [t for t in self.ends
                  if tr.started_at is not None and tr.stopped_at is not None
                  and tr.started_at <= t <= tr.stopped_at]
        step_s = ((traced[-1] - traced[0]) / (len(traced) - 1)
                  if len(traced) > 1 else None)
        period = np.diff([self.t0] + self.ends)
        slowest = np.argsort(period)[::-1][:3]
        log(f"[window] {steps} steps of {items_per_step} images ended in "
            f"{self.t1 - self.t0:.3f}s = "
            f"{steps * items_per_step / (self.t1 - self.t0):.1f} images/s "
            f"(the window's mean, which is judged); by the median step "
            f"{items_per_step / np.median(period):.1f}; step ms p50 "
            f"{np.median(period) * 1e3:.2f} p90 "
            f"{np.percentile(period, 90) * 1e3:.2f}, slowest "
            + ", ".join(f"#{k} {period[k] * 1e3:.1f}" for k in slowest)
            + f"; collector pauses {self.pauses.total * 1e3:.1f} ms in all, "
            f"longest {self.pauses.longest * 1e3:.1f}; XLA compiles in the "
            f"window: {self.compiles1 - self.compiles0}")
        return dict(window=(self.t0, self.t1), step_ends=self.ends,
                    items_per_step=items_per_step,
                    setup_s=self.t0 - self.run.t_process_start,
                    compiles_in_window=self.compiles1 - self.compiles0,
                    traced_step_s=step_s, attempted=steps, failed=0)


def check_against_reference(run, names, arrays, batches, program_losses,
                            program_logp0):
    """The program's warm-up steps against the plain reference from the same
    weights over the same batches: centred log-probabilities of step 0 (they
    equal the centred logits) and the loss of every warm-up step."""
    import jax
    import jax.numpy as jnp

    import harness

    ref = harness.load_plugin("reference", run.config["reference"])
    opt = run.traffic["optimizer"]
    arch, dtype = run.config["arch"], run.traffic["dtype"]
    step = ref.make_train_step(run.config, arch, names, opt["learning_rate"],
                               opt["momentum"], opt["wd"])
    t0 = time.perf_counter()
    # One chip holds the reference of a one-chip cell. The float32 backward
    # pass of a four-chip cell's global batch does not fit one chip, so there
    # the same plain program is given its batch split over the cell's chips
    # and the compiler partitions it (BatchNorm statistics stay global: same
    # arithmetic, another reduction order).
    mesh = jax.sharding.Mesh(np.asarray(run.devices), ("batch",))
    whole = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    split = jax.sharding.NamedSharding(mesh,
                                       jax.sharding.PartitionSpec("batch"))
    arrays = [jax.device_put(np.asarray(a, np.float32), whole)
              for a in arrays]
    moms = [jnp.zeros_like(a) for a in arrays]
    ref_losses, ok = [], True
    for i, (x, y) in enumerate(batches):
        arrays, moms, loss, logits = step(
            arrays, moms, jax.device_put(x, split), jax.device_put(y, split))
        ref_losses.append(float(loss))
        if i == 0:
            ref0 = np.asarray(logits, np.float64)
            ref0 -= ref0.mean(-1, keepdims=True)
            got0 = np.asarray(program_logp0, np.float64)
            got0 -= got0.mean(-1, keepdims=True)
            scale = np.abs(ref0).max()
            err = np.abs(got0 - ref0).max() / scale
            tol = LOGIT_RTOL[dtype]
            log(f"[correct] step-0 logits {got0.shape} vs plain reference at "
                f"highest precision: max|d|/max|ref| {err:.2e} (tol {tol:.0e})")
            ok &= bool(np.isfinite(got0).all() and err <= tol)
    rel = [abs(a - b) / abs(b) for a, b in zip(program_losses, ref_losses)]
    log(f"[correct] loss over {len(ref_losses)} warm-up steps: program "
        f"{[round(v, 4) for v in program_losses]} reference "
        f"{[round(v, 4) for v in ref_losses]} worst rel {max(rel):.2e} "
        f"(tol {LOSS_RTOL:.0e}); reference took "
        f"{time.perf_counter() - t0:.1f}s")
    ok &= bool(np.isfinite(program_losses).all() and max(rel) <= LOSS_RTOL)
    return ok
