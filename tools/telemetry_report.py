#!/usr/bin/env python
"""Render a dumped telemetry snapshot as a human-readable table.

Usage::

    python tools/telemetry_report.py telemetry.json [--sort-by total|count|avg|min|max]

The input is a ``mxnet_tpu.telemetry.dumps()`` JSON snapshot — written by
``MXNET_TELEMETRY_DUMP=<path>`` at exit or ``telemetry.dump(path)``. The
rendering is ``telemetry.dumps_table`` — the same visual format as
``profiler.dumps_aggregate``, so perf rounds read one table language for
both planes.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("snapshot", help="path to a telemetry JSON snapshot")
    ap.add_argument("--sort-by", default="total",
                    choices=("total", "count", "avg", "min", "max"),
                    help="histogram sort key (default: total time)")
    args = ap.parse_args(argv)

    with open(args.snapshot) as f:
        snap = json.load(f)
    for key in ("counters", "gauges", "histograms"):
        if key not in snap:
            sys.stderr.write(
                f"{args.snapshot}: not a telemetry snapshot (missing {key!r})\n")
            return 2

    from mxnet_tpu import telemetry

    sys.stdout.write(telemetry.dumps_table(snap, sort_by=args.sort_by))
    counters = snap.get("counters", {})
    hits = counters.get("compile.cache_hits", 0)
    misses = counters.get("compile.cache_misses", 0)
    if hits or misses:
        secs = counters.get("compile.seconds", 0.0)
        ratio = snap.get("derived", {}).get("compile.cache_hit_ratio")
        line = (f"\ncompile cache: {misses} programs compiled "
                f"({secs:.1f}s total), {hits} cache hits")
        if ratio is not None:
            line += f", hit ratio {ratio:.3f}"
        line += ("\n  (a hit ratio well below 1 at steady state means "
                 "recompile churn — docs/faq/perf.md)\n")
        sys.stdout.write(line)
    caches = snap.get("compile_caches") or {}
    if caches:
        # per-name ledger: op-level (op_eager/op_vjp), lazy segments,
        # executors and the serving planes read in one accounting language
        rows = ", ".join(
            f"{n} {v.get('misses', 0)} compiled/{v.get('hits', 0)} hits"
            for n, v in sorted(caches.items()))
        sys.stdout.write(f"\nnamed compile caches: {rows}\n")
    blamed = counters.get("compile.blamed_misses", 0)
    if blamed:
        axes = {k.split("compile.blame_axis.", 1)[1]: v
                for k, v in counters.items()
                if k.startswith("compile.blame_axis.")}
        line = f"\nhlolint: {blamed} steady-state recompile(s) blamed"
        if axes:
            line += " — axes: " + ", ".join(
                f"{k} {v}" for k, v in
                sorted(axes.items(), key=lambda kv: -kv[1]))
        line += ("\n  (each is a compile_blame health-journal event naming "
                 "the key axis that changed vs the nearest warmed "
                 "executable — docs/faq/perf.md \"Auditing the compiled "
                 "program\")\n")
        sys.stdout.write(line)
    lazy_segs = counters.get("lazy.segments", 0)
    lazy_ops = counters.get("lazy.ops_captured", 0)
    if lazy_segs or lazy_ops:
        derived = snap.get("derived", {})
        hists = snap.get("histograms", {})
        line = f"\nlazy: {lazy_ops} ops captured in {lazy_segs} segments"
        mean = derived.get("lazy.mean_ops_per_segment")
        if mean is not None:
            line += f" (mean {mean:.1f} ops/segment)"
        seg = hists.get("lazy.segment_ops") or {}
        if seg.get("count"):
            line += f", p99 {seg['p99']:.0f} ops"
        reasons = {k.split("lazy.flush_reason.", 1)[1]: v
                   for k, v in counters.items()
                   if k.startswith("lazy.flush_reason.")}
        if reasons:
            top = sorted(reasons.items(), key=lambda kv: -kv[1])[:4]
            line += "; flushes: " + ", ".join(f"{k} {v}" for k, v in top)
        line += (f"; fallback ops {counters.get('lazy.fallback_ops', 0)},"
                 f" hysteresis trips "
                 f"{counters.get('lazy.hysteresis_trips', 0)}")
        line += ("\n  (mean ops/segment near 1 = flush-happy code; see "
                 "docs/faq/perf.md \"Reading lazy-segment telemetry\")\n")
        sys.stdout.write(line)
    rw_segs = counters.get("lazy.rewrite.segments", 0)
    rw_errs = counters.get("lazy.rewrite.plan_errors", 0)
    if rw_segs or rw_errs:
        derived = snap.get("derived", {})
        pre = derived.get("lazy.rewrite.mean_ops_pre")
        post = derived.get("lazy.rewrite.mean_ops_post")
        shrink = derived.get("lazy.rewrite.shrink_ratio")
        line = f"\nrewrite: {rw_segs} segments rewritten"
        if pre is not None and post is not None:
            line += f", mean nodes {pre:.1f} -> {post:.1f}"
        if shrink is not None:
            line += f" (shrink {shrink:.0%})"
        rules = {k.split("lazy.rewrite.rules_applied.", 1)[1]: v
                 for k, v in counters.items()
                 if k.startswith("lazy.rewrite.rules_applied.")}
        if rules:
            line += "; rules: " + ", ".join(
                f"{k} {v}" for k, v in
                sorted(rules.items(), key=lambda kv: -kv[1]))
        if rw_errs:
            line += (f"; WARNING {rw_errs} plan errors (those segments "
                     "ran unrewritten)")
        line += ("\n  (which rules paid and when CSE loses: "
                 "docs/faq/perf.md \"Reading rewrite telemetry\")\n")
        sys.stdout.write(line)
    dropped = counters.get("profiler.dropped_events", 0)
    t_dropped = counters.get("tracing.dropped_events", 0)
    if dropped or t_dropped:
        sys.stdout.write(
            f"\nWARNING: event loss — profiler dropped {dropped}, tracing "
            f"dropped {t_dropped} events (buffer overflow); traces from "
            "this process are INCOMPLETE. Raise profiler max_events / "
            "MXNET_TRACING_MAX_EVENTS or dump more often.\n")
    staged = counters.get("overlap.staged_batches", 0)
    steps = (snap.get("histograms", {}).get("step.total_us")
             or {}).get("count", 0)
    if staged or steps:
        derived = snap.get("derived", {})
        line = (f"\nstage: {staged} batches device-staged over "
                f"{steps} steps")
        fb = counters.get("overlap.fallback_batches", 0)
        full = counters.get("io.stage_ring_full", 0)
        if fb or full:
            line += f"; fallbacks {fb}, ring-full refusals {full}"
        swait = counters.get("io.stage_wait_us_total", 0)
        sprep = counters.get("io.stage_prep_us_total", 0)
        line += (f"; wait {swait / 1e3:.1f}ms / prep {sprep / 1e3:.1f}ms")
        ratio = derived.get("io.stage_wait_ratio")
        if ratio is not None:
            line += f" (stage_wait_ratio {ratio:.2f})"
        stall = derived.get("io.pipeline_stall_ratio")
        if stall is not None:
            line += f"; pipeline_stall_ratio {stall:.2f}"
        line += ("\n  (stage_wait_ratio near 1 = staging hides nothing; "
                 "pipeline_stall_ratio = all input waits over step wall; "
                 "docs/faq/perf.md \"Closing the host gap\")\n")
        sys.stdout.write(line)
    req = counters.get("serving.requests", 0)
    if req:
        hists = snap.get("histograms", {})
        derived = snap.get("derived", {})
        batches = counters.get("serving.batches", 0)
        line = f"\nserving: {req} requests in {batches} batches"
        fill = derived.get("serving.batch_fill_ratio")
        if fill is not None:
            line += f", fill ratio {fill:.3f}"
        e2e = hists.get("serving.e2e_us") or {}
        if e2e.get("count"):
            line += (f"; e2e p50 {e2e['p50'] / 1e3:.2f} ms"
                     f" / p99 {e2e['p99'] / 1e3:.2f} ms")
        line += (f"; timeouts {counters.get('serving.timeouts', 0)},"
                 f" rejected {counters.get('serving.rejected', 0)}")
        line += ("\n  (low fill ratio = padding waste - resize the bucket "
                 "ladder or flush window, docs/faq/perf.md \"Sizing serving "
                 "buckets\")\n")
        sys.stdout.write(line)
    sess = counters.get("serving.generation.sessions", 0)
    if sess:
        gauges = snap.get("gauges", {})
        hists = snap.get("histograms", {})
        derived = snap.get("derived", {})
        toks = counters.get("serving.generation.tokens", 0)
        line = (f"\ngeneration: {sess} sessions, {toks} tokens"
                f" (live slots {gauges.get('serving.generation.live_slots', 0)},"
                f" queued {gauges.get('serving.generation.queue_depth', 0)})")
        tps = gauges.get("serving.generation.tokens_per_s")
        if tps:
            line += f"; {tps:.1f} tok/s"
        ttft = hists.get("serving.generation.ttft_us") or {}
        if ttft.get("count"):
            line += (f"; TTFT p50 {ttft['p50'] / 1e3:.2f} ms"
                     f" / p99 {ttft['p99'] / 1e3:.2f} ms")
        line += (f"; evictions {counters.get('serving.generation.evictions', 0)}"
                 f" (deadline {counters.get('serving.generation.evict_deadline', 0)}),"
                 f" rejected {counters.get('serving.generation.rejected', 0)}")
        fill = derived.get("serving.generation.slot_fill_ratio")
        if fill is not None:
            line += f", slot fill {fill:.3f}"
        line += ("\n  (low slot fill = the KV slab outruns arrivals - "
                 "shrink MXNET_GENERATION_SLOTS or add replicas, "
                 "docs/faq/perf.md \"Sizing the KV slab\")\n")
        ph = counters.get("serving.generation.prefix.hits", 0)
        pm = counters.get("serving.generation.prefix.misses", 0)
        if ph + pm:
            line2 = (f"  prefix cache: {ph} hits / {pm} misses"
                     f" (ratio {derived.get('serving.generation.prefix.hit_ratio', 0):.3f}),"
                     f" {counters.get('serving.generation.prefix.forks', 0):.0f} forks,"
                     f" {counters.get('serving.generation.prefix.inserts', 0):.0f} inserts,"
                     f" {counters.get('serving.generation.prefix.evictions', 0):.0f} evictions,"
                     f" {gauges.get('serving.generation.prefix.cached_tokens', 0):.0f} tokens cached\n")
            sys.stdout.write(line + line2)
            line = ""
        prop = counters.get("serving.generation.spec.proposed", 0)
        if prop:
            line3 = (f"  speculative: {prop:.0f} proposed /"
                     f" {counters.get('serving.generation.spec.accepted', 0):.0f} accepted"
                     f" (ratio {derived.get('serving.generation.spec.acceptance_ratio', 0):.3f}),"
                     f" {counters.get('serving.generation.spec.rolled_back', 0):.0f} rolled back;"
                     f" {derived.get('serving.generation.spec.accepted_tokens_per_tick', 0):.2f} tokens/tick"
                     " (plain floor 1.0)\n")
            sys.stdout.write(line + line3)
            line = ""
        if line:
            sys.stdout.write(line)

    def _labels(name):
        # "qos.admitted|class=interactive|tenant=acme" -> {"class": ...}
        return dict(tok.partition("=")[::2] for tok in name.split("|")[1:])

    qos_admitted = {k: v for k, v in counters.items()
                    if k.startswith("qos.admitted|")}
    if qos_admitted:
        hists = snap.get("histograms", {})
        by_class = {}
        for metric in ("admitted", "rejected", "preempted", "resumed"):
            for k, v in counters.items():
                if k.startswith(f"qos.{metric}|"):
                    cls = _labels(k).get("class", "?")
                    by_class.setdefault(cls, {}).setdefault(metric, 0)
                    by_class[cls][metric] += v
        parts = []
        for cls in ("interactive", "standard", "batch"):
            row = by_class.get(cls)
            if not row:
                continue
            bit = f"{cls} {row.get('admitted', 0)} admitted"
            if row.get("rejected"):
                bit += f"/{row['rejected']} rejected"
            if row.get("preempted"):
                bit += f"/{row['preempted']} preempted"
            parts.append(bit)
        line = "\nqos: " + ", ".join(parts)
        # worst tenant by TTFT p99 — the single number a multi-tenant
        # operator pages on (one noisy neighbour hides inside any average)
        worst = None
        for k, h in hists.items():
            if k.startswith("qos.ttft_us|") and h.get("count"):
                t = _labels(k).get("tenant", "?")
                if worst is None or h["p99"] > worst[1]:
                    worst = (t, h["p99"])
        if worst is not None:
            line += (f"; worst tenant TTFT p99: {worst[0]} "
                     f"{worst[1] / 1e3:.2f} ms")
        line += ("\n  (per-tenant quotas/classes come from MXNET_QOS_SPEC; "
                 "docs/faq/perf.md \"Operating a multi-tenant fleet\")\n")
        sys.stdout.write(line)
    pp_steps = counters.get("pipeline.steps", 0)
    if pp_steps:
        gauges = snap.get("gauges", {})
        line = (f"\npipeline: {pp_steps} pipelined steps at "
                f"{gauges.get('pipeline.stages', 0):.0f} stages x "
                f"{gauges.get('pipeline.microbatches', 0):.0f} micro-batches")
        bubble = gauges.get("pipeline.bubble_ratio")
        if bubble is not None:
            line += f", bubble ratio {bubble:.3f}"
        imb = gauges.get("pipeline.stage_cost_imbalance")
        if imb is not None:
            line += f", stage imbalance {imb:.2f}x"
        line += ("\n  (high bubble = raise MXNET_PIPELINE_MICROBATCHES - "
                 "docs/faq/perf.md \"Choosing micro-batch count\")\n")
        sys.stdout.write(line)
    spmd_steps = counters.get("spmd.steps", 0)
    if spmd_steps:
        gauges = snap.get("gauges", {})
        mesh = "x".join(
            f"{ax}={gauges.get(f'spmd.{ax}', 1):.0f}"
            for ax in ("dp", "pp", "fsdp", "tp")
            if gauges.get(f"spmd.{ax}", 1) > 1) or "1-device"
        line = f"\nspmd: {spmd_steps} sharded steps on mesh {mesh}"
        per_dev = gauges.get("spmd.param_bytes_per_device")
        total = gauges.get("spmd.param_bytes_total")
        if per_dev is not None and total:
            line += (f", param bytes/device {per_dev / 1e6:.2f} MB of "
                     f"{total / 1e6:.2f} MB total "
                     f"(ratio {per_dev / max(total, 1):.3f})")
        line += ("\n  (ratio should track 1/N of the sharded axes - "
                 "docs/faq/perf.md \"One mesh, one program\")\n")
        sys.stdout.write(line)
    gauges = snap.get("gauges", {})
    slo_keys = sorted({k[len("slo."):-len(".ok")]
                       for k in gauges if k.startswith("slo.")
                       and k.endswith(".ok")})
    stalls = counters.get("health.stalls", 0)
    h_events = counters.get("health.events", 0)
    if slo_keys or stalls or h_events:
        violated = [k for k in slo_keys if not gauges.get(f"slo.{k}.ok", 1)]
        line = (f"\nhealth: {len(slo_keys) - len(violated)}/{len(slo_keys)} "
                f"SLOs ok")
        if violated:
            burns = []
            for k in violated:
                b = gauges.get(f"slo.{k}.burn_short")
                burns.append(f"{k}" + (f" (burn {b:.1f}x)"
                                       if b is not None else ""))
            line += "; VIOLATED: " + ", ".join(burns)
        if gauges.get("slo.budget_exhausted"):
            line += "; ERROR BUDGET EXHAUSTED"
        line += (f"; stalls {stalls}, drains "
                 f"{counters.get('health.drains', 0)}, journal events "
                 f"{h_events}")
        de = gauges.get("health.desired_engines")
        if de is not None:
            line += (f"; autoscale wants {de:.0f} engine(s) of "
                     f"{gauges.get('health.ready_engines', 0):.0f} ready")
        line += ("\n  (read /slo and /events for the full picture - "
                 "docs/faq/perf.md \"Operating a fleet\")\n")
        sys.stdout.write(line)
    inversions = counters.get("analysis.lock_inversions", 0)
    hazards = counters.get("analysis.blocking_hazards", 0)
    edges = gauges.get("analysis.lock_edges", 0)
    if inversions or hazards or edges:
        line = (f"\nanalysis: {edges:.0f} lock-order edges, "
                f"{inversions} inversion(s), {hazards} blocking hazard(s)")
        if inversions or hazards:
            line += ("\n  DEADLOCK RISK: re-run under MXNET_DEBUG_SYNC=1 "
                     "and read analysis.report() for both stacks - "
                     "docs/faq/perf.md \"Machine-checked invariants\"")
        else:
            line += (" (MXNET_DEBUG_SYNC recorder was on and the run "
                     "stayed clean)")
        sys.stdout.write(line + "\n")
    lost = counters.get("elastic.lost_workers", 0)
    shrinks = counters.get("elastic.shrinks", 0)
    gen = snap.get("gauges", {}).get("elastic.generation", 0)
    if lost or shrinks or gen:
        hists = snap.get("histograms", {})
        line = (f"\nelastic: generation {gen:.0f}, {lost} lost worker(s), "
                f"{shrinks} shrink(s), world "
                f"{snap.get('gauges', {}).get('elastic.world_size', 0):.0f}")
        sh = hists.get("elastic.shrink_us") or {}
        if sh.get("count"):
            line += f"; shrink p50 {sh['p50'] / 1e3:.1f} ms"
        line += ("\n  (a lost worker raised WorkerLostError instead of a "
                 "hung barrier; survivors resumed from the latest "
                 "checkpoint)\n")
        sys.stdout.write(line)
    ts = snap.get("ts")
    if ts is not None:
        import datetime

        when = datetime.datetime.fromtimestamp(ts, datetime.timezone.utc)
        sys.stdout.write(f"\nsnapshot: pid={snap.get('pid')} "
                         f"at {when:%Y-%m-%d %H:%M:%S} UTC\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
