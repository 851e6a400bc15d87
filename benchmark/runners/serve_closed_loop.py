"""Runner `serve_closed_loop`: a model behind one `GenerationEngine`, in this
process, under a closed loop of `workers.count` workers (`closed_loop.py`):
each submits its next request the moment its previous one has finished, so
the engine's slots stay full and every tick sweeps the same number of live
slots whatever the seed. The model is `mxnet_tpu.models.HybridLM`, built
from the configuration's published keys.

One thread besides the engine's own, as in `serve_engine`: it sweeps the live
streams' public `tokens` every half millisecond to stamp each new token with
the client's clock, and hands a worker its next request when its stream is
done. Phases: a lead-in of `workers.lead_in_s` (the workers start evenly over
its first `workers.ramp_s`), the window of `--seconds`, then the workers go on
until every request submitted inside the window has finished. Judged:
`itl_p90_ms` over all gaps of the requests submitted inside the window; such a
request that errs, is refused, does not finish, or whose first token comes
later than `limits.ttft_s` after its submission counts as `failed`.

`correct`, outside the window: the first `parity_requests` lead-in requests
teacher-forced through the plain reference (every generated token the
reference argmax or within LOGIT_RTOL of the logit scale of it), the
recurrent state a probe request leaves in its slot against the reference's
(STATE_RTOL_FIRST, STATE_RTOL_MEDIAN of its scale), and no compile inside the
window.
"""
import time

import numpy as np

import closed_loop
import harness
from harness import log
from runners.serve_engine import MISSED_MS, POLL_S

# Greedy parity, as serve_engine.LM_LOGIT_RTOL: two evaluation orders of a deep
# bfloat16 network agree to a few 2^-8 of the logit scale (the logits carry
# the model's 1/logits_scaling, which the scale shares). The two readings the
# limit lies between are in PERF.md section 6 (PR 26).
LOGIT_RTOL = 2 ** -5
# The slot's recurrent state against the reference's: per Mamba layer the
# largest difference over the largest magnitude. bfloat16 activations feed the
# float32 state, and the residual stream's rounding builds up with depth, so
# two limits, each between two readings on the v5e (PERF.md section 6, PR 26):
# the FIRST Mamba layer, which only the embedding, one norm and one projection
# precede, read 0.0036-0.0111 over 15 seeds, and the median over the 36 layers
# 0.011-0.017. A state held in bfloat16 loses 2^-9 of every entry a step over
# decay windows of up to a thousand steps and read 0.026-0.028 and 0.033-0.050:
# it misses both.
STATE_RTOL_FIRST = 1.7e-2
STATE_RTOL_MEDIAN = 2.4e-2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "state_slots_live", "state_bytes_touched")
HISTOGRAMS = ("tick_us", "prefill_us", "ttft_us")
GRANITE_NAMES = {
    "norm1": "input_layernorm.weight",
    "norm2": "post_attention_layernorm.weight",
    "w_in": "shared_mlp.input_linear.weight",
    "w_out": "shared_mlp.output_linear.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "m_in": "mamba.in_proj.weight", "conv_b": "mamba.conv1d.bias",
    "dt_bias": "mamba.dt_bias", "A_log": "mamba.A_log", "D": "mamba.D",
    "m_norm": "mamba.norm.weight", "m_out": "mamba.out_proj.weight"}


def granite_published(params):
    """HybridLM's flat weights under the published names. Matrices stay
    input-major (`x @ W`, reference/granite_hybrid.py); the convolution
    weight `[kernel, channels]` goes back to `[channels, 1, kernel]`."""
    out = {}
    for name, arr in params.items():
        layer, _, leaf = name.rpartition(".")
        if not layer:
            out[{"embed": "embed_tokens.weight",
                 "norm_f": "norm.weight"}[leaf]] = arr
        elif leaf == "conv_w":
            out[f"layers.{layer[1:]}.mamba.conv1d.weight"] = \
                arr.T[:, None, :]
        else:
            out[f"layers.{layer[1:]}.{GRANITE_NAMES[leaf]}"] = arr
    return out


def telemetry_mark():
    from mxnet_tpu import telemetry

    pre = "serving.generation."
    mark = {c: telemetry.counter(pre + c).value for c in COUNTERS}
    for h in HISTOGRAMS:
        snap = telemetry.histogram(pre + h).snapshot()
        mark[h + ".sum"], mark[h + ".count"] = snap["sum"], snap["count"]
    return mark


def build_engine(run, dev):
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    lm = HybridLM(HybridLMConfig.from_config(run.config),
                  par.create_mesh(devices=[dev], dp=1))
    # every weight in one jitted call, on the device, in the served dtype
    params = jax.jit(lm.init_params)(jax.random.PRNGKey(run.seed % 2 ** 31))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    t1 = time.perf_counter()
    e = run.traffic["engine"]
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]),
                           prefix_cache=False, spec_k=0)
    warm = eng.warm()
    log(f"[setup] {n_params / 1e6:.1f}M params {lm.cfg.dtype} in "
        f"{t1 - t0:.1f}s; engine slots={eng.max_slots} max_len={eng.max_len} "
        f"buckets={list(eng.prefill_buckets)} cache "
        f"{eng.kv_slab_bytes() / 2**30:.2f} GiB; warm-up compiled or loaded "
        f"{warm['compiles']} programs in {warm['seconds']:.1f}s")
    return params, eng


def greedy_parity(run, weights, records):
    """Teacher-forced greedy parity of finished requests, as
    `serve_engine.greedy_parity`."""
    ref = harness.load_plugin("reference", run.config["reference"])
    ok = True
    t0 = time.perf_counter()
    for rec in records:
        prompt, gen = rec["prompt"], np.asarray(rec["stream"].tokens)
        n, g = len(prompt), len(gen)
        seq = np.concatenate([prompt, gen[:-1]])
        rows = np.asarray(ref.logits(run.config, weights, seq,
                                     np.arange(n - 1, n - 1 + g)), np.float64)
        scale = np.abs(rows).max()
        gaps = rows.max(-1) - rows[np.arange(g), gen]
        exact = int((rows.argmax(-1) == gen).sum())
        tol = LOGIT_RTOL * scale
        log(f"[correct] request of {n} prompt tokens: {exact}/{g} generated "
            f"tokens equal the reference argmax; worst reference-logit gap "
            f"{gaps.max():.5f} = {gaps.max() / scale:.5f} of max|logit| "
            f"{scale:.4f} (tol {LOGIT_RTOL:.5f})")
        ok &= bool(np.isfinite(rows).all() and (gaps <= tol).all())
    log(f"[correct] reference forward of {len(records)} requests took "
        f"{time.perf_counter() - t0:.1f}s")
    return ok


def state_parity(run, weights, eng, prompt):
    """One request alone through the idle engine; what its slot holds when
    it has finished — the recurrent state after the prompt and all but the
    last generated token — against the reference's full forward. True for a
    model whose cache keeps no such state."""
    ref = harness.load_plugin("reference", run.config["reference"])
    if not hasattr(ref, "forward"):
        return True
    t0 = time.perf_counter()
    stream = eng.submit(prompt, max_new_tokens=run.traffic["state_probe"][
        "max_new_tokens"])
    gen = np.asarray(stream.result(timeout=300))
    leaves = eng.slot_snapshot(stream.slot)
    seq = np.concatenate([prompt, gen[:-1]])
    _, states = ref.forward(run.config, weights, seq, [len(seq) - 1])
    if not states:
        return True
    want = np.stack([np.asarray(s, np.float32) for s in states])
    got = [leaf for leaf in leaves if leaf.shape == want.shape]
    if len(got) != 1:
        log(f"[correct] no cache member of the state's shape {want.shape}")
        return False
    got = got[0].astype(np.float32)
    errs = np.asarray([np.abs(g - w).max() / np.abs(w).max()
                       for g, w in zip(got, want)])
    log(f"[correct] state probe: {len(prompt)} prompt + {len(gen)} generated "
        f"tokens; recurrent state of {len(want)} layers, difference over the "
        f"layer's max|S|: first layer {errs[0]:.5f} (tol {STATE_RTOL_FIRST}), "
        f"median {np.median(errs):.5f} (tol {STATE_RTOL_MEDIAN}), worst "
        f"{errs.max():.5f} (layer {int(errs.argmax())}); "
        f"{time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(got).all() and errs[0] <= STATE_RTOL_FIRST
                and np.median(errs) <= STATE_RTOL_MEDIAN)


def run(run):
    from mxnet_tpu import telemetry

    if run.trace:
        telemetry.enable()      # counters and host histograms: traced run only
    params, eng = build_engine(run, run.devices[0])
    try:
        return drive(run, run.traffic, params, eng)
    finally:
        eng.close(timeout=30)


def drive(run, job, params, eng):
    tracer, seconds = run.tracer, run.seconds
    vocab = run.config["vocab_size"]
    requests = closed_loop.pool(job, vocab, run.seed)
    starts = closed_loop.worker_starts(job)
    n_workers = len(starts)
    lead_in = float(job["workers"]["lead_in_s"])
    log(f"[traffic] closed loop of {n_workers} workers over a pool of "
        f"{len(requests)} requests: prompts "
        f"{min(len(r['prompt']) for r in requests)}-"
        f"{max(len(r['prompt']) for r in requests)} tokens, outputs "
        f"{min(r['max_new_tokens'] for r in requests)}-"
        f"{max(r['max_new_tokens'] for r in requests)}; lead-in {lead_in}s, "
        f"workers start over its first {job['workers']['ramp_s']}s")
    t_start = time.monotonic()
    t_open = t_start + lead_in
    workers = [None] * n_workers
    records = []
    drawn = 0
    window_left = 0             # window requests not finished yet
    submitting = True
    opened = closed = None
    marks = {}
    live_pos_dt = live_slots_dt = sampled_dt = 0.0
    last_sample = None
    while True:
        now = time.monotonic()
        rel = now - t_open
        pos = slots = 0
        for w in range(n_workers):
            rec = workers[w]
            if rec is not None:
                s = rec["stream"]
                n = len(s.tokens)
                rec["times"].extend([now] * (n - len(rec["times"])))
                if s.done and len(rec["times"]) == len(s.tokens):
                    window_left -= rec["phase"] == "window"
                    workers[w] = rec = None
                elif n:
                    slots += 1
                    pos += len(rec["prompt"]) + n
            if rec is None and submitting and now - t_start >= starts[w]:
                r = requests[drawn % len(requests)]
                drawn += 1
                phase = ("lead_in" if rel < 0 else
                         "window" if rel < seconds else "tail")
                rec = dict(r, phase=phase, worker=w, times=[], error=None,
                           submitted=time.monotonic())
                try:
                    with tracer.annotate("submit"):
                        rec["stream"] = eng.submit(
                            r["prompt"], max_new_tokens=r["max_new_tokens"])
                    workers[w] = rec
                    window_left += phase == "window"
                except Exception as e:  # noqa: BLE001 — a refusal is a failure
                    rec["stream"], rec["error"] = None, repr(e)
                records.append(rec)
        if opened is None and rel >= 0:
            opened = time.perf_counter()
            marks["compiles0"] = run.events.backend_compiles
            if run.trace:
                marks["tele0"] = telemetry_mark()
            last_sample = now
        if opened is not None and closed is None:
            dt = now - last_sample
            live_pos_dt += pos * dt
            live_slots_dt += slots * dt
            sampled_dt += dt
            last_sample = now
            was_tracing = tracer.started_at is not None
            tracer.maybe_start(rel)
            if run.trace and not was_tracing \
                    and tracer.started_at is not None:
                marks["trace0"] = telemetry_mark()
            was_stopped = tracer.stopped_at is not None
            tracer.maybe_stop()
            if run.trace and not was_stopped \
                    and tracer.stopped_at is not None:
                marks["trace1"] = telemetry_mark()
            if rel >= seconds and not tracer.active:
                closed = time.perf_counter()
                marks["compiles1"] = run.events.backend_compiles
                if run.trace:
                    marks["tele1"] = telemetry_mark()
        if closed is not None and window_left == 0:
            submitting = False          # the rest only drains
            if all(rec is None for rec in workers):
                break
        if rel > seconds + 120:
            log(f"[traffic] gave up {rel:.0f}s after the window opened: "
                f"{window_left} window requests unfinished")
            break
        time.sleep(POLL_S)
    tracer.maybe_stop(force=True)

    for rec in records:
        s = rec["stream"]
        if rec["error"] is None:
            if not s.done:
                rec["error"] = "unfinished"
            elif len(s.tokens) != rec["max_new_tokens"] or not all(
                    0 <= t < vocab for t in s.tokens):
                rec["error"] = f"{len(s.tokens)} tokens delivered"
            else:
                try:
                    s.result(timeout=0)
                except Exception as e:  # noqa: BLE001
                    rec["error"] = repr(e)
    win = [r for r in records if r["phase"] == "window"]
    errors = [r for r in win if r["error"] is not None]
    for r in errors[:5]:
        log(f"[traffic] failed request of worker {r['worker']}: {r['error']}")
    ttft = np.asarray([MISSED_MS if r["error"] else
                       (r["stream"].first_token_at - r["submitted"]) * 1e3
                       for r in win])
    limit_ms = job.get("limits", {}).get("ttft_s", float("inf")) * 1e3
    failed = sum(r["error"] is not None or t > limit_ms
                 for r, t in zip(win, ttft))
    gaps = [g * 1e3 for r in win if not r["error"]
            for g in np.diff(r["times"])]
    lo, hi = t_open, t_open + seconds
    delivered = sum(lo <= t < hi for r in records for t in r["times"])
    also = {"ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p90_ms": harness.percentile(ttft, 90),
            "ttft_max_ms": float(ttft.max()) if len(ttft) else None,
            "itl_p50_ms": harness.percentile(gaps, 50),
            "itl_p99_ms": harness.percentile(gaps, 99),
            "serve_tokens_per_s": delivered / seconds,
            "requests_submitted": len(records)}
    compiles = marks["compiles1"] - marks["compiles0"]
    log(f"[window] {len(win)} requests submitted in the window, "
        f"{len(errors)} in error, {failed} failed (error or first token "
        f"later than {limit_ms:.0f} ms); TTFT ms p50 "
        f"{also['ttft_p50_ms']:.1f} p90 {also['ttft_p90_ms']:.1f} max "
        f"{also['ttft_max_ms']:.1f}; ITL ms p50 {also['itl_p50_ms']:.2f} p90 "
        f"{harness.percentile(gaps, 90):.2f} p99 {also['itl_p99_ms']:.2f} "
        f"(n={len(gaps)}); {delivered} tokens delivered in {seconds}s = "
        f"{also['serve_tokens_per_s']:.1f}/s; mean live slots "
        f"{live_slots_dt / sampled_dt:.1f}, mean live positions "
        f"{live_pos_dt / sampled_dt:.0f}; {len(records)} requests in all; "
        f"XLA compiles in the window: {compiles}")

    weights = granite_published(params)
    parity = [r for r in records if r["phase"] == "lead_in"
              and r["error"] is None][:job["parity_requests"]]
    ok = len(parity) == job["parity_requests"] \
        and greedy_parity(run, weights, parity) \
        and state_parity(run, weights, eng, parity[0]["prompt"])
    obs = dict(correct=ok and compiles == 0, attempted=len(win),
               failed=failed, setup_s=opened - run.t_process_start,
               ttft_ms=ttft, itl_ms=gaps, also=also,
               window_s=seconds, compiles_in_window=compiles,
               mean_live_positions=live_pos_dt / sampled_dt,
               mean_live_slots=live_slots_dt / sampled_dt,
               max_slots=eng.max_slots, host_label="engine-thread")
    if run.trace:
        obs["telemetry"] = {k: marks["tele1"][k] - marks["tele0"][k]
                            for k in marks["tele0"]}
        if "trace0" in marks and "trace1" in marks:
            obs["trace_telemetry"] = {
                k: marks["trace1"][k] - marks["trace0"][k]
                for k in marks["trace0"]}
    return obs
