#!/usr/bin/env python3
"""Find the knee of a serving cell once, on the chip: the highest of a few
fixed arrival rates that the system sustains. The cell's traffic file then
carries `0.8 x knee` as a plain number (`arrivals.rate_per_s`); the benchmark
itself never searches.

    python3 benchmark/sweep_knee.py --workload gpt2xl_chat --rates 3,4,5,6,7,8 \
        --seconds 20 [--seed 0]

One process, one engine: the rates run one after the other through the cell's
own runner (`serve_engine.drive`), each with the cell's lead-in, a window of
`--seconds`, and a drain before the next. A rate is *sustained* when
  * the backlog (requests sent that have no token yet) does not grow over the
    window: by its least-squares slope it gains under 2 requests plus 2% of
    the window's arrivals, and
  * at least 90% of the window's requests had their first token within 1 s of
    being due (a failed request misses).
The sweep's table goes into PERF.md beside the rate chosen from it, and the
result file, as the chip wrote it, into `benchmark/sweeps/`; a test applies
the rule above, as committed, to the recorded rows (a recorded `sustained` may
predate a change of the rule) and holds the cell's rate to the knee it finds.
"""
import argparse
import copy
import json
import os
import sys
import time

T_PROCESS_START = time.perf_counter()
BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for p in (ROOT, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

TTFT_LIMIT_MS = 1000.0
ATTAINMENT = 0.90
SLOPE_SHARE = 0.02


def sustained(row, seconds):
    """The rule of the docstring on one row of a sweep."""
    return bool(row["backlog_slope_per_s"] * seconds
                <= 2 + SLOPE_SHARE * row["requests"]
                and row["ttft_attainment"] >= ATTAINMENT)


def knee(rows, seconds):
    """(knee, the cell's rate at 0.8 x knee), None where nothing held."""
    held = [r["rate_per_s"] for r in rows if sustained(r, seconds)]
    return (max(held), round(0.8 * max(held), 2)) if held else (None, None)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated arrival rates, requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import numpy as np

    import harness
    from harness import log
    from runners import serve_engine

    _, cell, config, job = harness.load_cell(args.workload)
    run = harness.new_run(cell, config, job, args.seed, args.seconds, False,
                          T_PROCESS_START)
    devs = run.devices
    params, eng = serve_engine.build_engine(run, devs[0])
    rows = []
    try:
        for rate in (float(r) for r in args.rates.split(",")):
            trial = copy.deepcopy(job)
            trial["arrivals"]["rate_per_s"] = rate
            trial["parity_requests"] = 0        # checked by the benchmark
            trial.pop("limits", None)           # `failed` counts errors here
            obs = serve_engine.drive(run, trial, params, eng)
            t, b = np.asarray(obs["backlog"]).T
            slope = float(np.polyfit(t, b, 1)[0])
            ttft = np.asarray(obs["ttft_ms"])
            row = dict(
                rate_per_s=rate, requests=obs["attempted"],
                failed=obs["failed"], backlog_slope_per_s=slope,
                backlog_end=int(b[-1]),
                ttft_attainment=float((ttft <= TTFT_LIMIT_MS).mean()),
                ttft_p50_ms=harness.percentile(ttft, 50),
                ttft_p90_ms=harness.percentile(ttft, 90),
                itl_p90_ms=harness.percentile(obs["itl_ms"], 90),
                tokens_per_s=obs["also"]["serve_tokens_per_s"],
                mean_live_slots=obs["mean_live_slots"])
            row["sustained"] = sustained(row, args.seconds)
            rows.append(row)
            log("[sweep] " + json.dumps(row))
    finally:
        eng.close(timeout=30)
    knee_per_s, cell_rate = knee(rows, args.seconds)
    out = {"workload": args.workload, "seconds": args.seconds,
           "seed": args.seed, "device": harness.device_line(devs),
           "rows": rows, "knee_per_s": knee_per_s,
           "cell_rate_per_s": cell_rate}
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"sweep_{args.workload}.json"), "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
