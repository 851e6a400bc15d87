#!/usr/bin/env python3
"""One run of one benchmark cell on the chip.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell is looked up in BENCHMARK.json; its configuration is
`benchmark/configs/<config>.json`, its traffic mix or training job
`benchmark/traffic/<traffic>.json`, the code that drives the program
`benchmark/runners/<runner>.py` (named by the traffic file), and each metric is
computed by a reader of its own, `benchmark/end_to_end/<metric>.py` or
`benchmark/layer_metrics/<metric>.py`. A later PR adds a cell, a model family or
a metric by adding files and entries; no file here needs an edit.

No CPU fallback: without the accelerator, or with fewer chips than the cell
asks for, the exit code is non-zero and no result is printed. The LAST line of
stdout is the result object; everything else is on earlier lines.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    from harness import log

    bench, cell, config, traffic = harness.load_cell(args.workload)
    run = harness.new_run(cell, config, traffic, args.seed, args.seconds,
                          args.trace, T_PROCESS_START)
    devs = run.devices
    log(f"[env] cell {cell['name']}: config {cell['config']}, traffic "
        f"{cell['traffic']}, runner {traffic['runner']}, {len(devs)} x "
        f"{devs[0].device_kind} ({devs[0].platform}); seed {args.seed}, "
        f"window {args.seconds}s, trace {args.trace}; compile cache "
        f"{os.environ['JAX_COMPILATION_CACHE_DIR']}")

    runner = harness.load_plugin("runners", traffic["runner"])
    obs = runner.run(run)
    log(f"[total] {time.perf_counter() - T_PROCESS_START:.1f}s wall; "
        f"{run.events.line()}")

    device = harness.device_line(devs)
    log(f"[device] peak bytes in use on the fullest chip: "
        f"{device['memory_peak_bytes']} "
        f"({device['memory_peak_bytes'] / 2**30:.2f} GiB)")
    result = {"correct": bool(obs["correct"]),
              "attempted": int(obs["attempted"]),
              "failed": int(obs["failed"]), "metrics": {}, "device": device}
    if args.trace:
        import trace_reduce

        path = run.tracer.xplane_path()
        if path is None:
            raise SystemExit("benchmark: --trace 1 but the runner left no "
                             "profiler trace")
        t0 = time.perf_counter()
        obs["trace"] = trace_reduce.load(
            path, n_devices=len(devs), host_label=obs.get("host_label"))
        device["busy_s"] = obs["trace"].busy_s
        device["window_s"] = obs["trace"].window_s
        result["breakdown"] = obs["trace"].breakdown()
        log(f"[trace] reduced {os.path.getsize(path) / 2**20:.1f} MiB of "
            f"xplane in {time.perf_counter() - t0:.1f}s: busy "
            f"{device['busy_s']:.3f}s of {device['window_s']:.3f}s")
        if not device["busy_s"] > 0:
            raise SystemExit("benchmark: the trace shows no operation on the "
                             "device")
    kind = "per_layer" if args.trace else "end_to_end"
    package = "layer_metrics" if args.trace else "end_to_end"
    for metric in bench[kind]:
        if not harness.metric_applies(metric, cell["name"]):
            continue
        value = harness.load_plugin(package, metric["name"]).read(obs, run)
        if value is None:       # the reader found nothing to read
            continue
        result["metrics"][metric["name"]] = {"value": float(value),
                                             "unit": metric["unit"]}
    if "also" in obs:       # unjudged numbers of the runner; the driver skips them
        result["also"] = obs["also"]
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
