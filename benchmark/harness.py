"""What every cell shares: the platform check, jax's own compile events, the
profiler window, the device line of the result, and the file lookups that make
the harness data-driven. Nothing here knows a model, a traffic mix or a metric.

Copied from `chip_smoke.py` (proven on the chip in PR 21), not imported from
it: `require_platform`, `CompileEvents`.
"""
import glob
import importlib
import json
import os
import threading
import time
import types

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
# benchmark/tests steers this (and `device_context`) to rehearse on the CPU;
# run.py has no option for it.
REQUIRED_PLATFORM = "tpu"
TRACE_DIR = os.path.join(ROOT, ".bench_trace")
# every host annotation the benchmark writes into the profiler's trace starts
# with this, so the reduction tells them from the program's and jax's own
ANNOTATION_PREFIX = "bench:"


def log(msg):
    print(msg, flush=True)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def place_compile_cache():
    """jax's persistent cache at `JAX_COMPILATION_CACHE_DIR` if the machine
    sets it, else at the fixed in-checkout path the program itself uses
    (`mxnet_tpu/compile_cache.py`). Small programs are kept too: PR 21's warm
    process spent ~20 s re-compiling sub-second eager programs that jax's 1 s
    default threshold never persists."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", "-1")
    os.environ.setdefault("TF_CPP_MIN_LOG_LEVEL", "2")


def load_cell(workload):
    """`(bench, cell, config, traffic)` of one entry of BENCHMARK.json's
    `workloads`: the whole file, the entry, its configuration file and its
    traffic file."""
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = find_entry(bench["workloads"], workload, "workload")
    config = load_json(ROOT, find_entry(bench["configs"], cell["config"],
                                        "configuration")["file"])
    traffic = load_json(BENCH_DIR, "traffic", cell["traffic"] + ".json")
    return bench, cell, config, traffic


def new_run(cell, config, traffic, seed, seconds, trace, t_process_start):
    """What a runner is handed. Sets the traffic file's `env` (the program's
    gates, such as MXNET_SPMD, are read from the environment and have to be
    there before it is imported), places the compile cache, and refuses to
    go on without the chips."""
    for k, v in traffic.get("env", {}).items():
        os.environ[k] = str(v)
    place_compile_cache()
    devs = require_platform(cell["chips"])
    return types.SimpleNamespace(
        workload=cell["name"], cell=cell, config=config, traffic=traffic,
        seed=seed, seconds=seconds, trace=bool(trace), devices=devs,
        chips=cell["chips"], t_process_start=t_process_start,
        events=CompileEvents(),
        tracer=Tracer(trace, cell["name"], traffic.get("trace", {})),
        peaks=peaks_for(devs[0].device_kind))


def require_platform(chips):
    """Refuse to run — before any model is built — unless jax found the
    accelerator and as many chips as the cell asks for. Returns the devices
    the cell uses."""
    import jax

    devs = jax.devices()
    if devs[0].platform != REQUIRED_PLATFORM:
        raise SystemExit(
            f"benchmark: needs a {REQUIRED_PLATFORM} device, jax found "
            f"{devs[0].platform} ({devs}); nothing was run")
    if len(devs) < chips:
        raise SystemExit(
            f"benchmark: the cell needs {chips} chip(s), jax found "
            f"{len(devs)}; nothing was run")
    return devs[:chips]


def device_context(i=0):
    """The mxnet_tpu context of chip `i` (steered to `mx.cpu` by the CPU
    rehearsal in benchmark/tests)."""
    import mxnet_tpu as mx

    return mx.tpu(i)


class CompileEvents:
    """Counts what jax itself did: XLA backend compiles (with seconds) and
    persistent-cache hits/misses."""

    def __init__(self):
        from jax import monitoring

        self.backend_compiles = 0
        self.backend_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def _on_duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.backend_compiles += 1
            self.backend_compile_s += duration

    def line(self):
        return (f"jax compiled or loaded {self.backend_compiles} programs in "
                f"{self.backend_compile_s:.1f}s; persistent cache hits="
                f"{self.cache_hits} misses={self.cache_misses}")


class Tracer:
    """One `jax.profiler` window inside the steady part of a `--trace 1` run.
    Runners call `maybe_start(elapsed)` / `maybe_stop()` from their
    loop with the seconds since the measured window opened; with `--trace 0`
    both do nothing. The window's length and offset come from the traffic
    file (`trace: {"after_s", "seconds"}`)."""

    def __init__(self, enabled, workload, spec):
        self.enabled = bool(enabled)
        self.after_s = float(spec.get("after_s", 2.0))
        self.seconds = float(spec.get("seconds", 3.0))
        self.dir = os.path.join(TRACE_DIR, workload)
        self.started_at = None      # time.perf_counter() at start
        self.stopped_at = None      # when the stop was asked for
        self._stopper = None

    def annotate(self, name):
        """A host span on the profiler's clock (a no-op context when the
        profiler is off)."""
        import jax

        return jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)

    def maybe_start(self, elapsed):
        if not self.enabled or self.started_at is not None \
                or elapsed < self.after_s:
            return
        import shutil

        import jax

        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0    # TraceAnnotations only, not every call
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.started_at = time.perf_counter()

    def maybe_stop(self, force=False):
        """Ends the profiler window once it has lasted `seconds` (`force`:
        now, and wait for the file). `stop_trace` writes the file, which
        takes seconds for a long trace, so it runs in a helper thread and
        the runner's loop goes on; `active` stays true until it is done."""
        if self.started_at is not None and self.stopped_at is None and (
                force or time.perf_counter() - self.started_at
                >= self.seconds):
            self.stopped_at = time.perf_counter()
            self._stopper = threading.Thread(target=self._stop, daemon=True)
            self._stopper.start()
        if force and self._stopper is not None:
            self._stopper.join()

    def _stop(self):
        import jax

        jax.profiler.stop_trace()
        log(f"[trace] profiler window {self.stopped_at - self.started_at:.2f}s"
            f" -> {self.dir} (stop_trace took "
            f"{time.perf_counter() - self.stopped_at:.1f}s)")

    @property
    def active(self):
        return self.started_at is not None and (
            self.stopped_at is None or self._stopper.is_alive())

    def xplane_path(self):
        if self.stopped_at is None or self._stopper.is_alive():
            return None
        found = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        return found[-1] if found else None


def device_line(devs):
    """The result's `device` object, as jax reports it; the peak is that of
    the fullest chip the cell used. On the TPU `peak_bytes_in_use` counts the
    buffers (weights, batches, slab, residuals) and `peak_bytes_reserved` the
    scratch memory the runtime holds for the compiled programs' temporaries
    (11.1 GB for the ResNet fp32 step, which `peak_bytes_in_use` does not
    show; v5e, PR 22). The two peaks need not coincide (their sum exceeded
    the chip's memory in the gluon cell), so the larger of the two is
    reported: a lower bound of the true peak."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        log(f"[device] {d}: memory_stats {stats}")
        peaks.append(max(int(stats.get("peak_bytes_in_use", 0)),
                         int(stats.get("peak_bytes_reserved", 0))))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(peaks)}


def peaks_for(device_kind):
    """Published peaks of the chip; an unknown `device_kind` is an error."""
    table = load_json(BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise SystemExit(f"benchmark: no published peaks for device_kind "
                         f"{device_kind!r} in peaks.json")
    return table[device_kind]


def find_entry(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"benchmark: no {what} named {name!r} in BENCHMARK.json")


def load_plugin(package, name):
    """`benchmark/<package>/<name>.py` — a runner, a reference, or a metric's
    reader — found by the name a data file or BENCHMARK.json gives."""
    return importlib.import_module(f"{package}.{name}")


def metric_applies(metric, workload):
    return "workloads" not in metric or workload in metric["workloads"]


def percentile(values, q):
    """Linear-interpolated percentile, None of nothing."""
    import numpy as np

    return float(np.percentile(values, q)) if len(values) else None
