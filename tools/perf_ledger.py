#!/usr/bin/env python
"""Cross-run perf ledger: an append-only JSONL of bench outcomes.

Every ``bench.py`` run appends one schema-versioned record — backend,
probe verdict, measured roofline peaks, and per-lane throughput with
MFU/MBU — so perf history survives across checkouts and the CI can ask
"did this run regress against the recent past?" without diffing raw
BENCH sidecars by hand.

Commands::

    python -m tools.perf_ledger ingest BENCH_r0*.json MULTICHIP_r0*.json
        Backfill historical sidecars (stamped ``historical: true``).
        Tolerates failed runs (``parsed: null`` wrappers keep their
        error tail and contribute no lanes).

    python -m tools.perf_ledger check [--window N] [--threshold F]
        Rolling-baseline regression check: the newest record's lanes vs
        the median of up to N prior same-backend records. Direction-
        aware. Exit 1 on regression, 2 on no-baseline/unusable ledger.
        A regression also present in the previous record's own check is
        marked ``confirmed`` — the CI gate stays advisory until two
        consecutive runs agree (see ci/run.sh).

    python -m tools.perf_ledger show
        Render the ledger as one line per record.

The ledger path defaults to the git-ignored ``bench_ledger.jsonl`` at the
repo root (``PERF_LEDGER.jsonl`` there is the driver's record of every PR —
nothing in this repository writes it);
``MXNET_PERF_LEDGER`` overrides it (``0`` disables stamping from
bench.py). Records are append-only: `ingest` and bench.py never rewrite
history, and `check` never writes at all.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys
import time

SCHEMA_VERSION = 1
_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DEFAULT_LEDGER = os.path.join(_REPO, "bench_ledger.jsonl")

# (lane.metric, direction). "up" = bigger is better. The roofline
# utilisation rows (mfu/mbu) are first-class regression metrics: a
# throughput drop with flat MFU is a workload change, a throughput drop
# WITH an MFU drop is the framework leaving the hardware idle.
METRICS = [
    ("train.img_per_s", "up"),
    ("train.mfu", "up"),
    ("train.mbu", "up"),
    ("serving.req_per_s", "up"),
    ("serving.p99_ms", "down"),
    ("serving.mfu", "up"),
    ("serving.mbu", "up"),
    ("generation.tokens_per_s", "up"),
    ("generation.ttft_p99_ms", "down"),
    ("generation.tick_mbu", "up"),
    ("qos.interactive_ttft_p99_ms", "down"),
    ("qos.ttft_degradation", "down"),
    ("train.host_gap_us", "down"),
    ("serving.host_gap_us", "down"),
    ("generation.host_gap_us", "down"),
    ("overlap.train_host_gap_us", "down"),
    ("overlap.serving_host_gap_us", "down"),
    ("overlap.generation_host_gap_us", "down"),
    ("lazy.lazy_vs_eager", "up"),
    ("lazy_fused.rewrite_speedup", "up"),
    ("lazy_fused.compile_speedup", "up"),
    ("spmd.spmd_vs_replicated", "up"),
    ("multichip.avg_gb_per_sec_per_device", "up"),
]


def ledger_path(path=None):
    if path:
        return path
    env = os.environ.get("MXNET_PERF_LEDGER")
    if env and env != "0":
        return env
    return DEFAULT_LEDGER


def read_ledger(path=None):
    """All parseable records, in file order. Bad lines are skipped, not
    fatal: the ledger is append-only across tool versions."""
    path = ledger_path(path)
    out = []
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if isinstance(rec, dict):
                    out.append(rec)
    except OSError:
        pass
    return out


def next_run_id(path=None):
    recs = read_ledger(path)
    return 1 + max([int(r.get("run_id") or 0) for r in recs] or [0])


def append(rec, path=None):
    """Append one record (adds schema_version/ts/run_id when absent)."""
    path = ledger_path(path)
    rec.setdefault("schema_version", SCHEMA_VERSION)
    rec.setdefault("ts", time.strftime("%Y-%m-%dT%H:%M:%S%z"))
    if rec.get("run_id") is None:
        rec["run_id"] = next_run_id(path)
    with open(path, "a") as f:
        f.write(json.dumps(rec, sort_keys=True, default=repr) + "\n")
    return path


def _num(v):
    return v if isinstance(v, (int, float)) and not isinstance(v, bool) else None


def _lane(dst, name, src, fields):
    """Copy the numeric subset of ``fields`` (dst_key -> src_key) from a
    bench sub-dict into a ledger lane; empty lanes are dropped."""
    if not isinstance(src, dict):
        return
    lane = {}
    for dst_key, src_key in fields:
        v = _num(src.get(src_key))
        if v is not None:
            lane[dst_key] = v
    if lane:
        dst[name] = lane


def record_from_bench(rec, source="bench.py", historical=False):
    """One ledger record from a parsed bench result dict (the JSON line
    bench.py emits, current or historical schema)."""
    lanes = {}
    _lane(lanes, "train", rec, [
        ("img_per_s", "framework_module_fused"),
        ("mfu", "mfu"), ("mbu", "mbu"),
        ("predicted_floor_s", "predicted_floor_s"),
        ("host_gap_us", "host_gap_us"),
    ])
    if "train" not in lanes or "img_per_s" not in lanes.get("train", {}):
        # historical schema: headline value was the gluon path, MFU was
        # mfu_vs_measured_peak (nominal-free, so comparable in kind)
        _lane(lanes, "train", rec, [
            ("img_per_s", "value"), ("mfu", "mfu_vs_measured_peak"),
        ])
    elif _num(rec.get("mfu")) is None:
        v = _num(rec.get("mfu_vs_measured_peak"))
        if v is not None:
            lanes["train"]["mfu"] = v
    if isinstance(rec.get("roofline_bound"), str) and "train" in lanes:
        lanes["train"]["roofline_bound"] = rec["roofline_bound"]
    _lane(lanes, "serving", rec.get("serving"), [
        ("req_per_s", "req_per_s"), ("p99_ms", "p99_ms"),
        ("mfu", "mfu"), ("mbu", "mbu"),
        ("predicted_floor_s", "predicted_floor_s"),
        ("host_gap_us", "host_gap_us"),
    ])
    _lane(lanes, "generation", rec.get("generation"), [
        ("tokens_per_s", "tokens_per_s"), ("ttft_p99_ms", "ttft_p99_ms"),
        ("tick_mbu", "tick_mbu"), ("mfu", "mfu"),
        ("predicted_floor_s", "predicted_floor_s"),
        ("host_gap_us", "host_gap_us"),
    ])
    _lane(lanes, "qos", rec.get("qos"), [
        ("interactive_ttft_p99_ms", "interactive_ttft_p99_ms"),
        ("ttft_degradation", "ttft_degradation"),
        ("preemptions", "preemptions"),
        ("qos_steady_state_compiles", "qos_steady_state_compiles"),
    ])
    ovl = rec.get("overlap") if isinstance(rec.get("overlap"), dict) else {}
    flat_ovl = {}
    for plane in ("train", "serving", "generation"):
        sub = ovl.get(plane)
        on = sub.get("on") if isinstance(sub, dict) else None
        v = _num(on.get("host_gap_us")) if isinstance(on, dict) else None
        if v is not None:
            flat_ovl[plane + "_host_gap_us"] = v
    _lane(lanes, "overlap", flat_ovl, [(k, k) for k in flat_ovl])
    _lane(lanes, "lazy", rec.get("lazy"), [("lazy_vs_eager", "lazy_vs_eager")])
    _lane(lanes, "lazy_fused", rec.get("lazy_fused"), [
        ("rewrite_speedup", "rewrite_speedup"),
        ("compile_speedup", "compile_speedup"),
        ("shrink_ratio", "shrink_ratio"),
    ])
    _lane(lanes, "spmd", rec.get("spmd"), [
        ("spmd_vs_replicated", "spmd_vs_replicated"),
        ("mfu", "mfu"), ("mbu", "mbu"),
    ])
    roofline = rec.get("roofline") if isinstance(rec.get("roofline"), dict) else {}
    out = {
        "schema_version": SCHEMA_VERSION,
        "source": source,
        "historical": bool(historical),
        "backend": rec.get("backend"),
        "device_kind": rec.get("device_kind"),
        "lanes": lanes,
    }
    if _num(rec.get("run_id")) is not None:
        out["run_id"] = rec["run_id"]
    probe = rec.get("probe")
    if isinstance(probe, dict):
        out["probe"] = probe
    verdict = roofline.get("probe_verdict") or rec.get("probe_verdict")
    if verdict:
        out["probe_verdict"] = verdict
    peaks = roofline.get("peaks")
    if isinstance(peaks, dict):
        out["peaks"] = {
            "matmul_flops": peaks.get("matmul_flops"),
            "hbm_bytes_per_s": peaks.get("hbm_bytes_per_s"),
            "collective_bytes_per_s": peaks.get("collective_bytes_per_s"),
            "source": peaks.get("source"),
        }
    elif _num(rec.get("measured_peak_tflops")) is not None:
        out["peaks"] = {
            "matmul_flops": rec["measured_peak_tflops"] * 1e12,
            "source": "historical:measured_peak_tflops",
        }
    if rec.get("error"):
        out["error"] = str(rec.get("error"))[:500]
    return out


def record_from_multichip(rec, source, historical=True):
    """Ledger record from a MULTICHIP_r0x sidecar (collective-bandwidth
    sweep schema: avg_gb_per_sec_per_device + sweeps)."""
    lanes = {}
    _lane(lanes, "multichip", rec, [
        ("avg_gb_per_sec_per_device", "avg_gb_per_sec_per_device"),
        ("ndev_local", "ndev_local"),
        ("num_workers", "num_workers"),
    ])
    out = {
        "schema_version": SCHEMA_VERSION,
        "source": source,
        "historical": bool(historical),
        "backend": "multichip",
        "lanes": lanes,
    }
    if rec.get("network"):
        out["network"] = rec["network"]
    if rec.get("error"):
        out["error"] = str(rec.get("error"))[:500]
    return out


def _load_sidecar(path):
    """(parsed_record_or_None, error_tail_or_None) from a sidecar file.
    Handles the wrapper schema {"n","cmd","rc","tail","parsed"} with
    parsed possibly null (failed historical runs keep their traceback
    tail and no JSON line), a bare result dict, or a raw log whose last
    JSON-looking line is the record."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except ValueError:
        doc = None
    if isinstance(doc, dict):
        if "parsed" in doc or "tail" in doc:
            parsed = doc.get("parsed")
            if isinstance(parsed, dict):
                return parsed, None
            tail = doc.get("tail") or ""
            for line in reversed(tail.splitlines()):
                line = line.strip()
                if line.startswith("{"):
                    try:
                        return json.loads(line), None
                    except ValueError:
                        break
            if doc.get("skipped"):
                err = "skipped" if doc["skipped"] is True else \
                    f"skipped: {doc['skipped']}"
            elif tail.strip():
                err = tail.strip().splitlines()[-1]
            elif doc.get("ok"):
                err = "empty sidecar (ok wrapper, no result line)"
            else:
                err = f"rc={doc.get('rc')}"
            return None, err
        return doc, None
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line), None
            except ValueError:
                continue
    return None, "no JSON record found"


_RUN_ID_RE = re.compile(r"_r(\d+)\b")


def ingest(files, path=None):
    """Backfill sidecar files into the ledger (stamped historical).
    Returns the number of records appended; failed runs are recorded
    with their error and no lanes, so run ids stay dense."""
    path = ledger_path(path)
    n = 0
    for fname in files:
        base = os.path.basename(fname)
        try:
            parsed, err = _load_sidecar(fname)
        except OSError as e:
            print(f"perf_ledger: skip {base}: {e}", file=sys.stderr)
            continue
        if parsed is not None and any(
                k in parsed for k in ("avg_gb_per_sec_per_device",
                                      "zero1_sweep", "spmd_sweep",
                                      "bucket_sweep", "pipeline_sweep")):
            rec = record_from_multichip(parsed, source=base)
        elif parsed is not None:
            rec = record_from_bench(parsed, source=base, historical=True)
        else:
            rec = {"schema_version": SCHEMA_VERSION, "source": base,
                   "historical": True, "backend": None, "lanes": {},
                   "error": (err or "unparseable sidecar")[:500]}
        m = _RUN_ID_RE.search(base)
        if m:
            rec["round"] = int(m.group(1))
        try:
            rec["ts"] = time.strftime("%Y-%m-%dT%H:%M:%S%z",
                                      time.localtime(os.path.getmtime(fname)))
        except OSError:
            pass
        append(rec, path)
        n += 1
    return n


def _get_metric(rec, dotted):
    lane, _, key = dotted.partition(".")
    return _num((rec.get("lanes") or {}).get(lane, {}).get(key))


def _median(vals):
    vals = sorted(vals)
    n = len(vals)
    return vals[n // 2] if n % 2 else 0.5 * (vals[n // 2 - 1] + vals[n // 2])


def _check_one(series, idx, window, threshold):
    """Regression rows for series[idx] vs the median of up to ``window``
    prior records that carry each metric."""
    newest = series[idx]
    rows = []
    for dotted, direction in METRICS:
        new = _get_metric(newest, dotted)
        if new is None:
            continue
        prior = [v for v in (_get_metric(r, dotted) for r in series[:idx])
                 if v is not None][-window:]
        if not prior:
            continue
        base = _median(prior)
        if base == 0:
            continue
        delta = (new - base) / abs(base)
        worse = -delta if direction == "up" else delta
        rows.append({"metric": dotted, "direction": direction,
                     "baseline": base, "new": new,
                     "delta": round(delta, 4), "n_baseline": len(prior),
                     "regressed": worse > threshold})
    return rows


def check(path=None, window=5, threshold=0.10, out=sys.stdout):
    """Newest record vs rolling same-backend baseline. Returns exit
    code: 0 ok, 1 regression, 2 nothing to compare."""
    recs = read_ledger(path)
    usable = [r for r in recs if r.get("lanes")]
    if not usable:
        print("perf_ledger: no usable records in ledger", file=out)
        return 2
    newest = usable[-1]
    series = [r for r in usable if r.get("backend") == newest.get("backend")]
    idx = len(series) - 1
    if idx == 0:
        print(f"perf_ledger: first {newest.get('backend')} record — "
              "no baseline yet", file=out)
        return 2
    rows = _check_one(series, idx, window, threshold)
    prev_regressed = {r["metric"] for r in _check_one(series, idx - 1,
                                                      window, threshold)
                      if r["regressed"]} if idx > 1 else set()
    bad = 0
    for r in rows:
        if r["regressed"]:
            confirmed = r["metric"] in prev_regressed
            tag = "REGRESSION (confirmed ×2)" if confirmed else \
                "REGRESSION (first occurrence)"
            bad += 1
        else:
            tag = "ok"
        arrow = "↑" if r["direction"] == "up" else "↓"
        print(f"  {r['metric']:<42s} {arrow} base={r['baseline']:<12.6g} "
              f"new={r['new']:<12.6g} delta={r['delta']:+.1%}  {tag}",
              file=out)
    src = newest.get("source", "?")
    print(f"perf_ledger: run_id={newest.get('run_id')} source={src} "
          f"backend={newest.get('backend')} — "
          f"{bad} regression(s) past {threshold:.0%} vs median of last "
          f"{window}", file=out)
    return 1 if bad else 0


def show(path=None, out=sys.stdout):
    for r in read_ledger(path):
        lanes = r.get("lanes") or {}
        bits = []
        for dotted, _ in METRICS:
            v = _get_metric(r, dotted)
            if v is not None:
                bits.append(f"{dotted}={v:g}")
        flag = " [historical]" if r.get("historical") else ""
        err = " ERROR" if r.get("error") else ""
        print(f"run {r.get('run_id')} {r.get('ts', '?')} "
              f"{r.get('source', '?')} backend={r.get('backend')}{flag}{err}"
              f"{(': ' + ', '.join(bits)) if bits else ''}", file=out)
        if not lanes and r.get("error"):
            print(f"    error: {r['error'].splitlines()[-1][:120]}", file=out)
    return 0


def main(argv=None):
    ap = argparse.ArgumentParser(prog="perf_ledger", description=__doc__,
                                 formatter_class=argparse.RawDescriptionHelpFormatter)
    ap.add_argument("--ledger", default=None,
                    help="ledger path (default bench_ledger.jsonl at repo "
                         "root; env MXNET_PERF_LEDGER overrides)")
    sub = ap.add_subparsers(dest="cmd", required=True)
    p_in = sub.add_parser("ingest", help="backfill sidecar files")
    p_in.add_argument("files", nargs="+")
    p_ck = sub.add_parser("check", help="rolling-baseline regression check")
    p_ck.add_argument("--window", type=int, default=5)
    p_ck.add_argument("--threshold", type=float, default=0.10)
    sub.add_parser("show", help="one line per record")
    args = ap.parse_args(argv)
    if args.cmd == "ingest":
        n = ingest(args.files, args.ledger)
        print(f"perf_ledger: appended {n} record(s) to "
              f"{ledger_path(args.ledger)}")
        return 0
    if args.cmd == "check":
        return check(args.ledger, window=args.window,
                     threshold=args.threshold)
    return show(args.ledger)


if __name__ == "__main__":
    sys.exit(main())
