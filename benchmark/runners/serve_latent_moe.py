"""Runner `serve_latent_moe`: `mxnet_tpu.models.LatentMoELM` (latent
attention, a dropless expert layer told which experts it holds) behind one
`GenerationEngine`, in this process, under the closed loop of
`closed_loop.py`: `workers.count` workers, each submitting its next request
the moment its previous one has finished. The loop, its phases and what is
judged are `serve_closed_loop`'s (a lead-in of `workers.lead_in_s`, the
window of `--seconds`, a drain; `itl_p90_ms` over all gaps of the requests
submitted inside the window; such a request that errs, is refused, does not
finish, or whose first token comes later than `limits.ttft_s` counts as
`failed`).

`correct`, outside the window, against the plain reference
(`reference/sarvam_mla_moe.py`, float32, given the same share of the
experts and of the vocabulary):

* the first `parity_requests` lead-in requests teacher-forced through the
  reference. **Router near-ties decide how.** The program's bfloat16 hidden
  states and the reference's float32 ones may order the 8th and 9th biased
  router score differently; the two then compute different experts for
  that token, and no rounding tolerance holds for it. With 8 of 128 chosen
  in each of 4 expert layers the reference's own margin (the distance
  between its last chosen and first rejected biased score) is under
  NEAR_TIE in some layer for most tokens (PERF.md section 6), so a
  per-token exemption would exempt nearly all of them. Instead every
  generated token is held to LOGIT_RTOL_WORST of the logit scale (what a
  few swapped experts can move), and a share of at least LOGIT_CLOSE_SHARE
  of them to LOGIT_RTOL (the reference argmax, or within a rounding
  tolerance of it): a minority of swapped experts cannot move the second,
  a lower precision does. The (token, layer) pairs under NEAR_TIE are
  counted from the reference's margins — the program's routing is never
  shown to the reference — and their share is bounded by NEAR_TIE_SHARE (a
  router whose scores bunch up would show there);
* the latent probe: one request alone through the idle engine, and the rows
  it leaves in its slot — the normalised latent and the rotated shared key
  of every position, prefill's and decode's — against the reference's. The
  layers before the first expert layer's output (no routing upstream) are
  held at every position (LATENT_RTOL_FIRST); every layer is held in the
  median over positions (LATENT_RTOL_MEDIAN), which a minority of tokens
  with a swapped expert cannot move;
* no compile inside the window.
"""
import time

import numpy as np

import closed_loop
import harness
from harness import log
from runners.serve_engine import MISSED_MS, POLL_S

# The limits, each between two readings on the v5e (PERF.md section 6, PR 31):
# the stated precision over nine runs | everything the configuration states as
# float32 (norms, rotary angles, router scores) computed in bfloat16.
# Greedy parity, as serve_engine.LM_LOGIT_RTOL and serve_closed_loop's: two
# evaluation orders of a deep bfloat16 network agree to a few 2^-8 of the
# logit scale ...
LOGIT_RTOL = 2 ** -5
# ... which at least this share of the generated tokens must meet: read
# 0.937-0.954 | 0.010
LOGIT_CLOSE_SHARE = 0.85
# every token: what a few swapped experts (weight ~0.3 each of eight) move.
# Worst gap read 0.189-0.339 | 1.03 (a wrong token is ~0.4-0.8 away)
LOGIT_RTOL_WORST = 0.6
# biased router scores closer than this are a near-tie: sigmoid scores of
# bfloat16 hidden states differ from float32 ones by a few 1e-3, and the
# reference's margins have a median of 0.0065 a layer. Pairs under it read
# 0.337-0.350 with or without the lower precision: a property of the scores
NEAR_TIE = 4e-3
NEAR_TIE_SHARE = 0.45       # of the (token, expert layer) pairs
# latent rows, difference over the layer's max: layers 0-1 at every position
# read 0.0115-0.0131 | 1.50; the median over positions, worst layer,
# 0.0158-0.0231 | 0.62
LATENT_RTOL_FIRST = 2.0e-2
LATENT_RTOL_MEDIAN = 4.0e-2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "expert_assignments", "experts_hit",
            "expert_tokens_max", "latent_rows_live")
HISTOGRAMS = ("tick_us", "prefill_us", "ttft_us")
TOP_NAMES = {"embed": "embed_tokens.weight", "head": "lm_head.weight",
             "norm_f": "norm.weight"}
LAYER_NAMES = {
    "norm1": "input_layernorm.weight",
    "norm2": "post_attention_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "q_norm": "self_attn.q_norm.weight",
    "w_dkv": "self_attn.kv_a_proj_with_mqa.weight",
    "kv_norm": "self_attn.kv_a_layernorm.weight",
    "w_ukv": "self_attn.kv_b_proj.weight", "wo": "self_attn.o_proj.weight",
    "w_in": "mlp.gate_up_proj.weight", "w_out": "mlp.down_proj.weight",
    "router": "mlp.gate.weight",
    "router_bias": "mlp.gate.e_score_correction_bias",
    "experts_in": "mlp.experts.gate_up_proj",
    "experts_out": "mlp.experts.down_proj",
    "shared_in": "mlp.shared_experts.gate_up_proj.weight",
    "shared_out": "mlp.shared_experts.down_proj.weight"}


def published(params):
    """LatentMoELM's flat weights under the family's published names: a
    renaming, no copy (the stacked experts and the fused gate|up matrices
    stay as they are; reference/sarvam_mla_moe.py takes them so)."""
    out = {}
    for name, arr in params.items():
        layer, _, leaf = name.rpartition(".")
        out[f"layers.{layer[1:]}.{LAYER_NAMES[leaf]}" if layer
            else TOP_NAMES[leaf]] = arr
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
    from mxnet_tpu.models import LatentMoELM, LatentMoELMConfig
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    e = run.traffic["engine"]
    lm = LatentMoELM(
        LatentMoELMConfig.from_config(run.config, max_len=e["max_len"]),
        par.create_mesh(devices=[dev], dp=1))
    # a leaf at a time, on the device, in the served dtype: one program for
    # 9 GB of weights would hold its random bits beside them
    params = lm.init_params(jax.random.PRNGKey(run.seed % 2 ** 31))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    n_bytes = sum(int(v.nbytes) for v in params.values())
    t1 = time.perf_counter()
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]),
                           prefix_cache=False, spec_k=0)
    warm = eng.warm()
    log(f"[setup] {n_params / 1e6:.1f}M params {lm.cfg.dtype} "
        f"({n_bytes / 1e9:.2f} GB) in {t1 - t0:.1f}s: {lm.cfg.experts_held} "
        f"of {lm.cfg.num_experts} experts from {lm.cfg.expert_first}, "
        f"{lm.cfg.num_hidden_layers} layers, {lm.cfg.vocab_size} vocabulary "
        f"rows; engine slots={eng.max_slots} max_len={eng.max_len} "
        f"buckets={list(eng.prefill_buckets)} cache "
        f"{eng.kv_slab_bytes() / 1e9:.2f} GB; warm-up compiled or loaded "
        f"{warm['compiles']} programs in {warm['seconds']:.1f}s")
    return params, eng


def reference_forward(run, weights, prompt, generated):
    """The reference over `prompt + generated[:-1]`: `(logit rows of the
    generated tokens, latents, router margins [expert layers, generated] at
    the positions that produced them)`."""
    ref = harness.load_plugin("reference", run.config["reference"])
    n, g = len(prompt), len(generated)
    seq = np.concatenate([prompt, generated[:-1]])
    rows = np.arange(n - 1, n - 1 + g)
    logits, latents, margins = ref.forward(run.config, weights, seq, rows)
    return (np.asarray(logits, np.float64), latents,
            np.stack([m[rows] for m in margins]))


def greedy_parity(run, weights, records):
    """Teacher-forced greedy parity of finished requests (module
    docstring). Returns `(ok, the first request's latents)`."""
    first, finite = None, True
    gaps, margins = [], []
    t0 = time.perf_counter()
    for rec in records:
        prompt, gen = rec["prompt"], np.asarray(rec["stream"].tokens)
        rows, latents, margin = reference_forward(run, weights, prompt, gen)
        if first is None:
            first = latents
        g = len(gen)
        scale = np.abs(rows).max()
        gap = (rows.max(-1) - rows[np.arange(g), gen]) / scale
        finite &= bool(np.isfinite(rows).all())
        log(f"[correct] request of {len(prompt)} prompt tokens: "
            f"{int((gap == 0).sum())}/{g} generated tokens equal the "
            f"reference argmax, {int((gap <= LOGIT_RTOL).sum())} within "
            f"{LOGIT_RTOL:.5f} of max|logit| {scale:.4f} of it; worst "
            f"{gap.max():.5f}, p90 {np.quantile(gap, 0.9):.5f}")
        gaps.append(gap)
        margins.append(margin)
    gaps, margins = np.concatenate(gaps), np.concatenate(margins, axis=1)
    close = float((gaps <= LOGIT_RTOL).mean())
    ties = float((margins < NEAR_TIE).mean())
    log(f"[correct] {len(gaps)} generated tokens of {len(records)} requests: "
        f"{close:.4f} within {LOGIT_RTOL:.5f} of the reference argmax (at "
        f"least {LOGIT_CLOSE_SHARE}); worst gap {gaps.max():.5f} (tol "
        f"{LOGIT_RTOL_WORST}); router near-ties, the reference's own margin "
        f"under {NEAR_TIE}: {ties:.4f} of the (token, expert layer) pairs "
        f"(bound {NEAR_TIE_SHARE}), {float((margins.min(0) < NEAR_TIE).mean()):.4f}"
        f" of the tokens in some layer; reference forward took "
        f"{time.perf_counter() - t0:.1f}s")
    ok = finite and close >= LOGIT_CLOSE_SHARE \
        and gaps.max() <= LOGIT_RTOL_WORST and ties <= NEAR_TIE_SHARE
    return ok, first


def latent_parity(run, weights, eng, rec, latents):
    """One request alone through the idle engine; the latent rows its slot
    holds when it has finished against the reference's (module docstring).
    `rec` is a finished lead-in request and `latents` the reference's rows
    over its sequence: they serve when the probe, which repeats its prompt,
    generates the same tokens, else the reference runs again."""
    t0 = time.perf_counter()
    prompt = rec["prompt"]
    stream = eng.submit(prompt, max_new_tokens=run.traffic["latent_probe"][
        "max_new_tokens"])
    gen = np.asarray(stream.result(timeout=600))
    got_c, got_kr = eng.slot_snapshot(stream.slot)[:2]
    n = len(prompt) + len(gen) - 1          # positions the slot holds
    if not np.array_equal(gen, np.asarray(rec["stream"].tokens)[:len(gen)]):
        latents = reference_forward(run, weights, prompt, gen)[1]
    first_k = run.config["first_k_dense_replace"]
    errs = []
    for i, want in enumerate(latents):
        want = np.asarray(want[:n], np.float32)
        got = np.concatenate([got_c[i, :n].astype(np.float32),
                              got_kr[i, :, :n].astype(np.float32).T], axis=1)
        errs.append(np.abs(got - want).max(-1) / np.abs(want).max())
    errs = np.asarray(errs)                                 # [layers, n]
    # the layers no routing precedes: the leading dense ones and the first
    # expert layer, whose attention reads only dense layers' outputs
    first = errs[:first_k + 1].max()
    medians = np.median(errs, axis=1)
    log(f"[correct] latent probe: {len(prompt)} prompt + {len(gen)} "
        f"generated tokens, {n} rows of {errs.shape[0]} layers against the "
        f"reference's normalised latent and rotated key, row difference "
        f"over the layer's max: layers 0-{first_k} worst {first:.5f} (tol "
        f"{LATENT_RTOL_FIRST}); median over positions by layer "
        f"{[round(float(m), 5) for m in medians]} (tol "
        f"{LATENT_RTOL_MEDIAN}); worst anywhere {errs.max():.5f}; "
        f"{time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(errs).all() and first <= LATENT_RTOL_FIRST
                and medians.max() <= LATENT_RTOL_MEDIAN)


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
                    rec["finished"] = now
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
        if rel > seconds + 240:
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
    lifetimes = [r["finished"] - r["submitted"] for r in records
                 if "finished" in r]
    also = {"ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p90_ms": harness.percentile(ttft, 90),
            "ttft_max_ms": float(ttft.max()) if len(ttft) else None,
            "itl_p50_ms": harness.percentile(gaps, 50),
            "itl_p99_ms": harness.percentile(gaps, 99),
            "serve_tokens_per_s": delivered / seconds,
            "request_lifetime_p50_s": harness.percentile(lifetimes, 50),
            "requests_submitted": len(records)}
    compiles = marks["compiles1"] - marks["compiles0"]
    log(f"[window] {len(win)} requests submitted in the window, "
        f"{len(errors)} in error, {failed} failed (error or first token "
        f"later than {limit_ms:.0f} ms); TTFT ms p50 "
        f"{also['ttft_p50_ms']:.1f} p90 {also['ttft_p90_ms']:.1f} max "
        f"{also['ttft_max_ms']:.1f}; ITL ms p50 {also['itl_p50_ms']:.2f} p90 "
        f"{harness.percentile(gaps, 90):.2f} p99 {also['itl_p99_ms']:.2f} "
        f"(n={len(gaps)}); {delivered} tokens delivered in {seconds}s = "
        f"{also['serve_tokens_per_s']:.1f}/s; request lifetime p50 "
        f"{also['request_lifetime_p50_s']:.1f}s; mean live slots "
        f"{live_slots_dt / sampled_dt:.1f}, mean live positions "
        f"{live_pos_dt / sampled_dt:.0f}; {len(records)} requests in all; "
        f"XLA compiles in the window: {compiles}")

    weights = published(params)
    parity = [r for r in records if r["phase"] == "lead_in"
              and r["error"] is None][:job["parity_requests"]]
    ok = len(parity) == job["parity_requests"]
    if ok:
        ok, latents = greedy_parity(run, weights, parity)
        ok &= latent_parity(run, weights, eng, parity[0], latents)
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
