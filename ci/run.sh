#!/usr/bin/env bash
# CI entrypoint — the repo's rendering of the reference's ci/build.py +
# runtime_functions.sh (e.g. unittest stages at runtime_functions.sh:1099):
# clean-build the native runtime, then run every test tier from scratch.
#
#   ci/run.sh            # full pipeline (native build + unit + train + dist)
#   ci/run.sh unit       # one stage
#
# Stages mirror the reference's Jenkins stage split; everything runs on the
# CPU backend (the unit suite executes on a virtual 8-device mesh, see
# tests/conftest.py) so CI needs no accelerator.
set -euo pipefail
cd "$(dirname "$0")/.."

# CI is CPU-only: pin the platform for every python this script starts
export JAX_PLATFORMS=cpu

stage="${1:-all}"

log() { printf '\n== %s ==\n' "$*"; }

build_native() {
  log "native: clean build of librt_tpu.so + libcapi_tpu.so"
  rm -f mxnet_tpu/_native/librt_tpu.so mxnet_tpu/_native/libcapi_tpu.so \
        2>/dev/null || true
  make -C src
  test -f mxnet_tpu/_native/librt_tpu.so
  python -c "from mxnet_tpu import lib; assert lib.native_available(), 'native runtime failed to load'"
  # the JPEG decode workers must be compiled in (libjpeg-dev is a CI dep;
  # without this assert a silent HAS_JPEG=0 build skips every native
  # image test and regressions in imgpipe.cc pass green)
  python -c "from mxnet_tpu import lib; assert lib.native_imgpipe() is not None, 'imgpipe (libjpeg) missing from native build'"
  log "native self-test (engine race stress + shm), plain and ASAN+UBSAN"
  make -C src check
  make -C src check-asan
}

unit() {
  # tpulint FIRST and BLOCKING: the framework-invariant static gate
  # (executable-cache / cache-memory-tracking / gate-discipline /
  # tracer-hygiene / env-var-registry). A violation fails CI before any
  # test runs — cheaper to read one findings list than to bisect the
  # suite failure it would eventually cause
  log "tpulint gate (framework-invariant static analysis, blocking)"
  python -m tools.tpulint mxnet_tpu tools --strict
  # hlolint dump dir: the suites below that warm the audited caches
  # (serving/generation/zero1/pipeline/lazy/spmd) run with
  # MXNET_HLOLINT_DUMP set, so each process writes its compiled-program
  # summaries at exit; the blocking contract gate audits them afterwards
  hlolint_dump="$(mktemp -d)"
  log "unit suite (includes the 4-process dist kvstore run and CI-guarded examples)"
  python -m pytest tests/python/unittest -q -x \
      --ignore=tests/python/unittest/test_resilience.py \
      --ignore=tests/python/unittest/test_telemetry.py \
      --ignore=tests/python/unittest/test_fused_step.py \
      --ignore=tests/python/unittest/test_grad_sync.py \
      --ignore=tests/python/unittest/test_serving.py \
      --ignore=tests/python/unittest/test_generation.py \
      --ignore=tests/python/unittest/test_generation_scale.py \
      --ignore=tests/python/unittest/test_qos.py \
      --ignore=tests/python/unittest/test_rollout.py \
      --ignore=tests/python/unittest/test_zero1.py \
      --ignore=tests/python/unittest/test_tracing.py \
      --ignore=tests/python/unittest/test_pipeline.py \
      --ignore=tests/python/unittest/test_elastic.py \
      --ignore=tests/python/unittest/test_lazy.py \
      --ignore=tests/python/unittest/test_health.py \
      --ignore=tests/python/unittest/test_tpulint.py \
      --ignore=tests/python/unittest/test_overlap.py \
      --ignore=tests/python/unittest/test_spmd.py
  # resilience gate, run standalone (not twice) so a fault-injection
  # failure is attributed loudly. CI runs the whole suite including the
  # slow-marked kill-and-resume convergence case; the ROADMAP tier-1
  # command (-m 'not slow') keeps only the fast fault-injection cases
  log "fault-injection resilience suite (kill-and-resume, torn writes, EIO)"
  python -m pytest tests/python/unittest/test_resilience.py -q
  # telemetry gate, standalone for the same loud-attribution reason: these
  # tests flip the process-global registry on/off and assert on metric
  # values, so an instrumentation regression fails HERE, not as a
  # mysterious count mismatch inside an unrelated suite
  log "telemetry suite (registry, instrumentation under fault injection, trace merge)"
  python -m pytest tests/python/unittest/test_telemetry.py -q
  # fused-step gate, standalone: these tests flip MXNET_FUSED_STEP and the
  # telemetry registry and assert exact compile-cache hit/miss counts, so a
  # fusion or cache-accounting regression fails HERE with clean attribution
  log "fused train step suite (fused-vs-eager parity, donation, compile-cache accounting)"
  python -m pytest tests/python/unittest/test_fused_step.py -q
  # grad-sync gate, standalone: these tests flip MXNET_GRAD_BUCKETING /
  # MXNET_UPDATE_ON_KVSTORE and assert exact telemetry collective counts,
  # so a bucketing or sync-scheduling regression fails HERE, attributed
  log "grad-sync suite (bucketed-vs-per-key parity, collective counts, overlap telemetry)"
  python -m pytest tests/python/unittest/test_grad_sync.py -q
  # serving gate, standalone: these tests spin batcher worker threads,
  # flip the telemetry registry and pin EXACT serving compile-cache miss
  # counts (warmup-then-serve must compile zero at steady state), so a
  # batching, admission or warmup regression fails HERE, attributed
  log "serving suite (predictor parity, micro-batching, admission control, warmup compile pinning)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_serving.py -q
  # generation gate, standalone: these tests spin engine scheduler
  # threads, flip the telemetry registry and pin EXACT generation
  # compile-cache miss counts (continuous batching must never recompile
  # mid-stream) plus continuous-vs-sequential BIT-EXACT token parity — a
  # scheduler, KV-slab or compile-discipline regression fails HERE
  log "generation suite (slot KV-cache sessions, continuous batching parity, streaming deadlines, router)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_generation.py -q
  # generation-scale gate, standalone: these tests pin spec-vs-plain
  # greedy BIT-EXACT parity, fork isolation (no KV bleed after the
  # source prefix evicts), refcount-safe LRU eviction under slot
  # pressure, EXACT per-feature warmup compile counts with zero
  # steady-state misses, router prefix-affinity + the autoscale
  # actuator, and the 1k shared-system-prompt acceptance run — a
  # prefix-cache, draft, verify-lane or fleet-routing regression fails
  # HERE, attributed
  log "generation-scale suite (radix prefix cache + KV forking, speculative decoding, fleet affinity/autoscale)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_generation_scale.py -q
  # qos gate, standalone: these tests flip the process-global tenant
  # registry (qos.install/clear), spin engine scheduler threads and pin
  # (a) MXNET_QOS_SPEC unset => admission order, compile-cache keys AND
  # miss counts bit-identical to the pre-QoS engine, and (b) spec set =>
  # priority/deadline ordering, quota fast-rejects, preempt-to-park with
  # greedy BIT-EXACT resume and ZERO new steady-state executables — a
  # scheduling, parking or accounting regression fails HERE, attributed
  log "qos suite (tenant registry, priority admission, quotas, preempt/resume parity, migration)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_qos.py -q
  # rollout gate, standalone: the chaos swap suite — publish/subscribe
  # fault rejects (torn/corrupt/stale via the publish fault point),
  # zero-compile hot swaps with bit-exact drain pinning on BOTH serving
  # stacks, SLO-burn-gated fleet rollout with journaled rollback, and
  # the named_stats assertion that the rollout subsystem owns ZERO new
  # cached executables — a swap, drain-pinning or rollback regression
  # fails HERE, attributed. Warms only the already-required serving/
  # generation caches (no cache of its own, by design)
  log "rollout suite (zero-downtime weight swap, publish faults, burn-gated rollback, chaos fleet acceptance)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_rollout.py -q
  # zero1 gate, standalone: these tests flip MXNET_ZERO1/MXNET_ZERO1_NDEV
  # and pin sharding invariance, 1/N state allocation, checkpoint
  # round-trips and exact compile-cache miss counts — a sharded-update
  # regression fails HERE, attributed
  log "ZeRO-1 suite (sharded-vs-replicated update parity, 1/N state, checkpoint round-trip)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_zero1.py -q
  # tracing gate, standalone: these tests flip the process-global tracing
  # and telemetry state and assert exact span-tree shapes, so an
  # instrumentation or propagation regression fails HERE, attributed. The
  # slow-marked case is the two-process dist smoke: real workers produce
  # per-worker traces and tools/trace_merge.py must yield one CONNECTED
  # trace per step (both workers joined, zero orphans)
  log "tracing suite (span trees, memory census, prom/HTTP export, 2-proc dist trace merge)"
  python -m pytest tests/python/unittest/test_tracing.py -q
  # pipeline gate, standalone: these tests flip MXNET_PIPELINE_* and pin
  # pipelined-vs-unpipelined parity (incl. uneven micro-batches whose pad
  # rows must contribute ZERO gradient), exact CompileCache("pipeline")
  # miss counts, bubble-ratio math and every fallback trigger — a
  # schedule, partition or masking regression fails HERE, attributed
  log "pipeline suite (GPipe parity, stage balance, compile pinning, fallbacks)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_pipeline.py -q
  # elastic gate, standalone: these tests spin heartbeat/guard threads and
  # the slow case runs 2 REAL workers (tools/launch.py --restart-policy
  # shrink), SIGKILLs one mid-epoch and asserts detection-within-grace,
  # shrink 2->1, re-exec and checkpoint-resume convergence — a lease,
  # guard or rendezvous regression fails HERE, attributed
  log "elastic suite (heartbeat leases, guarded collectives, kill->shrink->resume smoke)"
  python -m pytest tests/python/unittest/test_elastic.py -q
  # lazy gate, standalone: these tests flip MXNET_LAZY and the per-thread
  # capture state, pin EXACT CompileCache("lazy") miss counts (warm
  # predict AND train loops must compile ZERO segments at steady state)
  # and sweep the existing ndarray op tests under the gate for barrier
  # completeness — a capture, flush-ordering or accounting regression
  # fails HERE, attributed. Includes the slow end-to-end case: a fit loop
  # with Monitor attached (the fused step's forced-eager-fallback path)
  # under MXNET_LAZY=1, parity-checked against eager
  log "lazy suite (deferred capture parity, barrier sweep, zero-steady-state compiles, fit+Monitor e2e)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_lazy.py -q
  # rewrite gate, standalone: per-rule bit/ulp parity vs the unrewritten
  # replay, the randomized 50-chain differential sweep, autograd through
  # rewritten forwards, EXACT post-rewrite-signature compile accounting
  # (one compile per rewritten signature, zero warm), per-rule disable
  # gates and the tp=1 zero-collectives pin (hlolint 'lazy' contract on
  # a live dump) — a rule, keying or fallback regression fails HERE,
  # attributed
  log "lazy rewrite gate (rule parity, differential sweep, post-rewrite cache keying, tp=1 zero collectives)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_lazy_rewrite.py -q
  # the full lazy suite again with the rewriter FORCED on: every barrier,
  # autograd and accounting invariant must hold identically over
  # rewritten programs (the rewrite defaults on, but this pins the
  # combination even if the default ever flips)
  log "lazy suite rerun (MXNET_LAZY_REWRITE=1 forced over every capture invariant)"
  env MXNET_LAZY_REWRITE=1 MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_lazy.py -q
  # health gate, standalone: these tests flip the process-global health/
  # telemetry/tracing state, spin engine scheduler threads and the
  # telemetry HTTP endpoint, and drive deterministic watchdog sweeps
  # (incl. the chaos acceptance run with an artificially wedged engine)
  # — an SLO, readiness, drain or watchdog regression fails HERE,
  # attributed, not as a flaky assertion inside an unrelated suite
  log "health suite (SLO tracker, liveness/readiness, stall watchdog + capture, router drain, chaos acceptance)"
  python -m pytest tests/python/unittest/test_health.py -q
  # overlap gate, standalone: these tests flip the telemetry registry,
  # spin the DeviceStager staging thread and pin N-step BIT-EXACT
  # parameter and epoch-metric parity of `fit` vs a lockstep reference
  # loop written over the module's public calls (SGD+Adam across
  # fused/zero1/spmd, a short last batch, a bucket switch between steps),
  # staged-buffer donation safety under in-flight reuse, serving flush
  # parity with zero steady-state compiles, and pad-buffer identity
  # stability — an ordering or staging regression fails HERE, attributed
  log "overlap suite (fit vs lockstep reference loop, staged donation safety, the metric's one step of lag)"
  python -m pytest tests/python/unittest/test_overlap.py -q
  # spmd gate, standalone: these tests flip MXNET_SPMD / MXNET_ZERO1 /
  # MXNET_PIPELINE_* and pin sharded-vs-replicated whole-run parity,
  # MEASURED 1/N per-device param+state residency, tp x fsdp x pp x
  # zero1 composition, checkpoint interchange with replicated runs,
  # exact CompileCache("spmd") accounting, sharded serving/generation
  # binds and every fallback trigger — a planner, placement or
  # constraint regression fails HERE, attributed
  log "spmd suite (GSPMD sharding parity, 1/N residency, compositions, serving bind, fallbacks)"
  env MXNET_HLOLINT_DUMP="$hlolint_dump" \
      python -m pytest tests/python/unittest/test_spmd.py -q
  # hlolint gate, BLOCKING: audit the compiled programs the suites above
  # actually warmed (dumped at each process's exit) against the
  # checked-in contract registry — donation aliasing (every declared
  # donation >= the byte floor must carry an input_output_alias),
  # collective discipline (zero cross-device collectives in a tp=1
  # decode, no full-bucket all-reduce in a zero1 step, only the declared
  # kinds elsewhere), and sharding residency (a 1/N plan must be visible
  # in the compiled input layout). --require fails the gate if a suite
  # silently stopped warming its cache; --explain prints the offending
  # executable's collective inventory under each finding
  log "hlolint gate (compiled-program contract audit over the warmed caches, blocking)"
  python -m tools.hlolint check "$hlolint_dump" \
      --require spmd,zero1,pipeline,serving,generation,lazy \
      --strict --explain
  rm -rf "$hlolint_dump"
  # analysis gate, standalone: the tpulint rule fixtures (each rule must
  # trip on its positive fixture and stay quiet on the negative) and the
  # MXNET_DEBUG_SYNC lock-order recorder unit tests (ABBA inversion,
  # blocking hazards, zero-overhead-off subprocess pin) — a checker or
  # recorder regression fails HERE, attributed
  log "analysis suite (tpulint rule fixtures, lock-order recorder, zero-overhead pins)"
  python -m pytest tests/python/unittest/test_tpulint.py -q
  # lock-order race hunt: re-run the CONCURRENCY suites (threaded
  # batcher, generation scheduler, lazy cross-thread, elastic heartbeats)
  # under the runtime recorder. tests/conftest.py's sessionfinish hook
  # fails the run on ANY lock-order inversion or blocking hazard the
  # suites drove, with both stacks printed — the dynamic complement of
  # the static tpulint gate (the PR 10 / PR 12 deadlock classes)
  log "lock-order race detector rerun (MXNET_DEBUG_SYNC=1 over serving/generation/qos/rollout/lazy/rewrite/elastic/overlap)"
  env MXNET_DEBUG_SYNC=1 python -m pytest \
      tests/python/unittest/test_overlap.py \
      tests/python/unittest/test_serving.py \
      tests/python/unittest/test_generation.py \
      tests/python/unittest/test_generation_scale.py \
      tests/python/unittest/test_qos.py \
      tests/python/unittest/test_rollout.py \
      tests/python/unittest/test_lazy.py \
      tests/python/unittest/test_lazy_rewrite.py \
      tests/python/unittest/test_elastic.py -q
}

train() {
  log "trainer-level tests"
  python -m pytest tests/python/train -q -x
}

dist() {
  log "multi-process dist kvstore invariants (tools/launch.py -n 4)"
  python -m pytest tests/dist -q -x
}

entrypoints() {
  log "driver entrypoints: single-chip compile check + 8-device dryrun"
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python __graft_entry__.py
  log "grad-sync bucketing smoke (8 virtual devices, measure.py --bucket-mb)"
  # bucketing regressions fail fast without TPUs: the sweep must complete
  # with an EXACT reduction (error==0 asserted by the harness json) and
  # the small tier must collapse to O(#buckets) collectives
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      timeout 600 python tools/bandwidth/measure.py \
      --network resnet18_v1 --image-shape 3,32,32 --ndev 8 \
      --kv-store device --num-batches 2 --tiers 1 --bucket-mb 0,1 \
      --json-out /tmp/ci_grad_sync_bw.jsonl
  python - <<'PY'
import json
rec = json.loads(open("/tmp/ci_grad_sync_bw.jsonl").read().strip().splitlines()[-1])
sweep = rec["bucket_sweep"]["small_lt_256KB"]
assert sweep["per_key"]["error"] == 0.0 and sweep["1MB"]["error"] == 0.0, sweep
assert sweep["1MB"]["buckets"] < sweep["per_key"]["buckets"], sweep
print("grad-sync smoke OK:", {k: v["buckets"] for k, v in sweep.items()})
PY
  rm -f /tmp/ci_grad_sync_bw.jsonl

  log "ZeRO-1 sharded-update smoke (8 virtual devices, measure.py --zero1)"
  # weight-update sharding regressions fail fast without TPUs: the sweep
  # must complete with ulp-level exactness vs the unsharded flat update
  # and the MEASURED per-replica state bytes must be 1/N of replicated
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      timeout 600 python tools/bandwidth/measure.py \
      --network mobilenet0.25 --image-shape 3,32,32 --num-classes 10 \
      --ndev 8 --kv-store device --num-batches 1 --test-results 0 \
      --zero1 2,4 --json-out /tmp/ci_zero1_bw.jsonl
  python - <<'PY'
import json
rec = json.loads(open("/tmp/ci_zero1_bw.jsonl").read().strip().splitlines()[-1])
sweep = rec["zero1_sweep"]
assert set(sweep) == {"2", "4"}, sweep
for n, r in sweep.items():
    assert r["error_vs_unsharded"] < 1e-5, (n, r)
    assert abs(r["state_ratio"] - 1.0 / int(n)) < 0.01, (n, r)
print("zero1 smoke OK:", {n: (r["state_ratio"], r["error_vs_unsharded"])
                          for n, r in sweep.items()})
PY
  rm -f /tmp/ci_zero1_bw.jsonl

  log "pipeline GPipe smoke (8 virtual devices, measure.py --pp)"
  # pipeline regressions fail fast without TPUs: the sweep must complete
  # with whole-run parity vs the unpipelined fused step (< 1e-5 asserted)
  # and the measured bubble ratio must equal the (S-1)/(M+S-1) analytic
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      timeout 600 python tools/bandwidth/measure.py \
      --network mobilenet0.25 --image-shape 3,32,32 --num-classes 10 \
      --ndev 8 --kv-store device --num-batches 1 --test-results 0 \
      --pp 2,4 --json-out /tmp/ci_pp_bw.jsonl
  python - <<'PY'
import json
rec = json.loads(open("/tmp/ci_pp_bw.jsonl").read().strip().splitlines()[-1])
sweep = rec["pipeline_sweep"]
assert set(sweep) == {"2", "4"}, sweep
for s, r in sweep.items():
    assert r["error_vs_unpipelined"] < 1e-5, (s, r)
    assert abs(r["bubble_ratio"] - r["bubble_ratio_analytic"]) < 1e-9, (s, r)
print("pipeline smoke OK:", {s: (r["bubble_ratio"], r["error_vs_unpipelined"])
                             for s, r in sweep.items()})
PY
  rm -f /tmp/ci_pp_bw.jsonl

  log "SPMD sharding smoke (8 virtual devices, measure.py --tp/--fsdp)"
  # weight/activation-sharding regressions fail fast without TPUs: the
  # sweep must complete with whole-run parity vs the replicated fused
  # step (< 1e-5 asserted), the MEASURED per-device param+state bytes
  # must be ~1/N, and the 'spmd' cache must stay steady-state cold
  env JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      timeout 600 python tools/bandwidth/measure.py \
      --network mobilenet0.25 --image-shape 3,32,32 --num-classes 10 \
      --ndev 8 --kv-store device --num-batches 1 --test-results 0 \
      --tp 2,4 --fsdp 2,4 --json-out /tmp/ci_spmd_bw.jsonl
  python - <<'PY'
import json
rec = json.loads(open("/tmp/ci_spmd_bw.jsonl").read().strip().splitlines()[-1])
sweep = rec["spmd_sweep"]
assert set(sweep) == {"tp", "fsdp"}, sweep
for axis, runs in sweep.items():
    assert set(runs) == {"2", "4"}, (axis, runs)
    for n, r in runs.items():
        assert r["error_vs_replicated"] < 1e-5, (axis, n, r)
        assert abs(r["param_state_ratio"] - 1.0 / int(n)) < 0.02, (axis, n, r)
        assert r["steady_state_compiles"] == 0, (axis, n, r)
print("spmd smoke OK:", {ax: {n: round(r["param_state_ratio"], 3)
                              for n, r in runs.items()}
                         for ax, runs in sweep.items()})
PY
  rm -f /tmp/ci_spmd_bw.jsonl
}

case "$stage" in
  native)      build_native ;;
  unit)        unit ;;
  train)       train ;;
  dist)        dist ;;
  entrypoints) entrypoints ;;
  all)         build_native; unit; train; dist; entrypoints ;;
  *) echo "unknown stage: $stage (native|unit|train|dist|entrypoints|all)"; exit 2 ;;
esac

log "stage '$stage' OK"
