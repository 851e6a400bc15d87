"""Runner `serve_swa_moe`: `mxnet_tpu.models.WindowMoELM` (window and full
attention layers mixed, a K/V cache whose window layers are rings, a
softmax-routed dropless expert layer) behind one `GenerationEngine`, in this
process, under the closed loop of `closed_loop.py`: `workers.count` workers,
each submitting its next request the moment its previous one has finished.
The loop, its phases and what is judged are `serve_closed_loop`'s and
`serve_latent_moe`'s (a lead-in of `workers.lead_in_s`, the window of
`--seconds`, a drain; `itl_p90_ms` over all gaps of the requests submitted
inside the window; such a request that errs, is refused, does not finish, or
whose first token comes later than `limits.ttft_s` counts as `failed`). Those
two files name their model, weight names, counters and limits and may not be
edited, so the loop is repeated here with the model's part as arguments
(`drive(run, job, eng, counters, check)`: PERF.md section 7).

`correct`, outside the window, against the plain reference
(`reference/mellum_swa_moe.py`, float32):

* the first `parity_requests` lead-in requests teacher-forced through the
  reference (`serve_latent_moe.reference_forward`). **Router near-ties decide
  how**, as PR 31 found: the program's bfloat16 hidden states and the
  reference's float32 ones may order the 8th and 9th router probability
  differently, the two then compute different experts for that token, and no
  rounding tolerance holds for it. So a share of at least LOGIT_CLOSE_SHARE
  of the generated tokens is held to LOGIT_RTOL (the reference argmax, or
  within a rounding tolerance of it), a share of LOGIT_NEAR_SHARE to
  LOGIT_NEAR_RTOL (what a few swapped experts move) and every token to
  LOGIT_RTOL_WORST: a minority of swapped experts cannot move the shares, a
  lower precision does. The (token,
  layer) pairs whose margin in the reference is under NEAR_TIE are counted —
  the program's routing is never shown to the reference — and their share is
  bounded by NEAR_TIE_SHARE (a router whose probabilities bunch up would show
  there);
* the K/V probe: one request of at least `kv_probe.min_prompt` tokens (longer
  than the window) alone through the idle engine's own prefill and
  `kv_probe.max_new_tokens` decode ticks, then the rows its slot holds — a
  full member's rows `[0, n)` and a window member's ring UNROLLED (row `p mod
  1024` is position `p`, the last 1,024 positions) — against the reference's
  rotated keys and values. Layer 0 (no routing upstream; a ring) is held at
  every position (KV_RTOL_FIRST); every layer of either kind is held in the
  median over positions (KV_RTOL_MEDIAN), which a minority of tokens with a
  swapped expert cannot move. A window of 1,023 or 1,025, a ring that a padded
  prefill wrapped, or YaRN's factor left out fail these
  (tests/python/unittest/test_window_moe_lm.py shows each);
* no compile inside the window.
"""
import time

import numpy as np

import closed_loop
import harness
from harness import log
from runners.serve_engine import MISSED_MS, POLL_S
from runners.serve_latent_moe import reference_forward

# The limits, each from two readings on the v5e (PERF.md section 6, PR 33: my
# chip runs, the embedding drawn at unit variance): the stated precision over
# eleven seeds | everything the configuration states as float32 that the
# program computes outside its kernels (norms, rotary angles, router
# probabilities) computed in bfloat16, two seeds. The lower precision fails
# by three of them (the first share and both K/V limits).
# Greedy parity, as serve_engine.LM_LOGIT_RTOL: two evaluation orders of a
# deep bfloat16 network agree to a few 2^-8 of the logit scale ...
LOGIT_RTOL = 2 ** -5
# ... which at least this share of the generated tokens must meet: read
# 0.9903-0.9953 | 0.8519, 0.8660 (0.9852-0.9964 | 0.5536, 0.6877 with the
# embedding at 1 / sqrt(hidden), when the limit was 0.85)
LOGIT_CLOSE_SHARE = 0.93
# what a few swapped experts (weight ~1/8 each of eight, in each of 8 layers)
# move: all but a hundredth of the tokens stay within 2^-3. Within it read
# 1.0 in all eleven | 0.99833, 0.99896 (0.9996-1.0 | 0.964 with the first
# embedding): it no longer tells the precisions apart and stays as the bound
# on what swapped experts may move
LOGIT_NEAR_RTOL = 2 ** -3
LOGIT_NEAR_SHARE = 0.99
# every token. The worst gap does NOT tell the two precisions apart — it read
# 0.048-0.124 | 0.148, 0.162 (0.064-0.166 | 0.234, 0.255 with the first
# embedding): one token whose experts were swapped in several layers moves
# nearly as far as the lower precision moves any — so no limit fits between
# its readings with room; it is held well above both, where a fault that
# breaks single tokens would read
LOGIT_RTOL_WORST = 0.4
# router probabilities closer than this are a near-tie: the 8th and 9th of 64
# softmax probabilities are each ~0.03, the reference's margins have a median
# of 0.0016 a layer, and bfloat16 hidden states move one by a few 1e-4. Pairs
# under it read 0.155-0.163 with or without the lower precision: a property
# of the probabilities
NEAR_TIE = 4e-4
NEAR_TIE_SHARE = 0.25       # of the (token, layer) pairs
# K/V rows, difference over the layer's max: layer 0 at every position read
# 0.0043-0.0066 | 1.61, 1.76; the median over positions, worst layer,
# 0.0059-0.0069 | 0.955, 0.964
KV_RTOL_FIRST = 2.0e-2
KV_RTOL_MEDIAN = 4.0e-2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "expert_assignments", "experts_hit",
            "expert_tokens_max", "kv_rows_live_full", "kv_rows_live_window")
HISTOGRAMS = ("tick_us", "prefill_us", "ttft_us")
TOP_NAMES = {"embed": "embed_tokens.weight", "head": "lm_head.weight",
             "norm_f": "norm.weight"}
LAYER_NAMES = {
    "norm1": "input_layernorm.weight",
    "norm2": "post_attention_layernorm.weight",
    "wo": "self_attn.o_proj.weight", "router": "mlp.gate.weight",
    "experts_in": "mlp.experts.gate_up_proj",
    "experts_out": "mlp.experts.down_proj"}


def published(params, config):
    """WindowMoELM's flat weights under the family's published names: a
    renaming, but for the fused `wqkv`, which is sliced into `q_proj`,
    `k_proj` and `v_proj` (the stacked experts and their fused gate|up stay
    as they are; reference/mellum_swa_moe.py takes them so)."""
    hd = config["head_dim"]
    q = config["num_attention_heads"] * hd
    k = config["num_key_value_heads"] * hd
    out = {}
    for name, arr in params.items():
        layer, _, leaf = name.rpartition(".")
        pre = f"layers.{layer[1:]}."
        if leaf == "wqkv":
            out[pre + "self_attn.q_proj.weight"] = arr[:, :q]
            out[pre + "self_attn.k_proj.weight"] = arr[:, q:q + k]
            out[pre + "self_attn.v_proj.weight"] = arr[:, q + k:]
        else:
            out[pre + LAYER_NAMES[leaf] if layer else TOP_NAMES[leaf]] = arr
    return out


def telemetry_mark(counters):
    from mxnet_tpu import telemetry

    pre = "serving.generation."
    mark = {c: telemetry.counter(pre + c).value for c in counters}
    for h in HISTOGRAMS:
        snap = telemetry.histogram(pre + h).snapshot()
        mark[h + ".sum"], mark[h + ".count"] = snap["sum"], snap["count"]
    return mark


def build_engine(run, dev):
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import WindowMoELM, WindowMoELMConfig
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    e = run.traffic["engine"]
    lm = WindowMoELM(
        WindowMoELMConfig.from_config(run.config, max_len=e["max_len"]),
        par.create_mesh(devices=[dev], dp=1))
    # a leaf at a time, on the device, in the served dtype
    params = lm.init_params(jax.random.PRNGKey(run.seed % 2 ** 31))
    # the embedding's rows at unit variance, not at 1 / hidden size (the
    # configuration file's `assumed.weights`): a row of norm 1 vanishes
    # under the first attention's output, the residual stream is then a
    # context average that the 32 streams share in part, and every router
    # sees that part — a skew of the seed's own (PERF.md section 6)
    params["embed"] = params["embed"] * float(lm.cfg.hidden_size) ** 0.5
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    n_bytes = sum(int(v.nbytes) for v in params.values())
    t1 = time.perf_counter()
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]),
                           prefix_cache=False, spec_k=0)
    warm = eng.warm()
    members = ", ".join("x".join(map(str, m.shape[1:])) for m in eng._kv)
    log(f"[setup] {n_params / 1e6:.1f}M params {lm.cfg.dtype} "
        f"({n_bytes / 1e9:.2f} GB) in {t1 - t0:.1f}s: {lm.cfg.experts_held} "
        f"of {lm.cfg.num_experts} experts from {lm.cfg.expert_first}, "
        f"{len(lm.window_layers)} window layers of {lm.cfg.sliding_window} + "
        f"{len(lm.full_layers)} full layers, {lm.cfg.vocab_size} vocabulary "
        f"rows; engine slots={eng.max_slots} max_len={eng.max_len} "
        f"buckets={list(eng.prefill_buckets)} cache "
        f"{eng.kv_slab_bytes() / 1e9:.2f} GB (a slot: {members}); decode "
        f"kernel block {eng._slab_block}; warm-up compiled or loaded "
        f"{warm['compiles']} programs in {warm['seconds']:.1f}s")
    return lm, params, eng


def greedy_parity(run, weights, records):
    """Teacher-forced greedy parity of finished requests (module docstring).
    Returns `(ok, [(record, the reference's K/V rows over its sequence)])`."""
    finite = True
    gaps, margins, kept = [], [], []
    t0 = time.perf_counter()
    for rec in records:
        prompt, gen = rec["prompt"], np.asarray(rec["stream"].tokens)
        rows, kv, margin = reference_forward(run, weights, prompt, gen)
        kept.append((rec, kv))
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
    near = float((gaps <= LOGIT_NEAR_RTOL).mean())
    ties = float((margins < NEAR_TIE).mean())
    log(f"[correct] {len(gaps)} generated tokens of {len(records)} requests: "
        f"{close:.4f} within {LOGIT_RTOL:.5f} of the reference argmax (at "
        f"least {LOGIT_CLOSE_SHARE}), {near:.5f} within {LOGIT_NEAR_RTOL} "
        f"(at least {LOGIT_NEAR_SHARE}); worst gap {gaps.max():.5f} (tol "
        f"{LOGIT_RTOL_WORST}); router near-ties, the reference's own margin "
        f"under {NEAR_TIE}: {ties:.4f} of the (token, layer) pairs (bound "
        f"{NEAR_TIE_SHARE}), {float((margins.min(0) < NEAR_TIE).mean()):.4f}"
        f" of the tokens in some layer, margin median "
        f"{float(np.median(margins)):.6f}; reference forward took "
        f"{time.perf_counter() - t0:.1f}s")
    ok = finite and close >= LOGIT_CLOSE_SHARE \
        and near >= LOGIT_NEAR_SHARE and gaps.max() <= LOGIT_RTOL_WORST \
        and ties <= NEAR_TIE_SHARE
    return ok, kept


def unrolled(member, page, n):
    """The rows a slot's `member` (K or V of one kind: `[layers, H, R, hd]`)
    holds of page `page` after `n` positions, in position order: `(rows [m,
    H, hd], first position)` — a full member's `[0, n)`; a ring's last `R`
    positions, row `p mod R` being position `p`."""
    rows = member.shape[2]
    first = max(0, n - rows)
    at = np.arange(first, n) % rows
    return member[page][:, at].transpose(1, 0, 2), first


def kv_parity(run, lm, weights, eng, kept):
    """One request alone through the idle engine; the K/V rows its slot holds
    when it has finished against the reference's (module docstring). `kept`
    are finished lead-in requests with the reference's rows over their
    sequences: one of at least `kv_probe.min_prompt` prompt tokens serves when
    the probe, which repeats its prompt, generates the same tokens; else the
    reference runs again (over a fresh prompt if none is long enough)."""
    t0 = time.perf_counter()
    probe = run.traffic["kv_probe"]
    rec, want = next(((r, kv) for r, kv in kept
                      if len(r["prompt"]) >= probe["min_prompt"]),
                     (None, None))
    if rec is None:
        prompt = np.random.default_rng([run.seed, 0x6b76]).integers(
            0, run.config["vocab_size"], probe["min_prompt"], dtype=np.int32)
    else:
        prompt = rec["prompt"]
    stream = eng.submit(prompt, max_new_tokens=probe["max_new_tokens"])
    gen = np.asarray(stream.result(timeout=600))
    k_full, v_full, k_ring, v_ring = [
        m.astype(np.float32) for m in eng.slot_snapshot(stream.slot)[:4]]
    n = len(prompt) + len(gen) - 1          # positions the slot holds
    if rec is None or not np.array_equal(
            gen, np.asarray(rec["stream"].tokens)[:len(gen)]):
        want = reference_forward(run, weights, prompt, gen)[1]
    errs, first_err = [], None
    for i, ref_kv in enumerate(want):
        if i in lm.full_layers:
            members, page = (k_full, v_full), lm.full_layers.index(i)
        else:
            members, page = (k_ring, v_ring), lm.window_layers.index(i)
        (k, first), (v, _) = (unrolled(m, page, n) for m in members)
        ref_kv = np.asarray(ref_kv[first:n], np.float32)    # [m, 2, H, hd]
        got = np.stack([k, v], axis=1)
        err = np.abs(got - ref_kv).reshape(n - first, -1).max(-1) \
            / np.abs(ref_kv).max()
        if i == 0:
            first_err = float(err.max())
        errs.append((float(np.median(err)), float(err.max())))
    medians = [e[0] for e in errs]
    log(f"[correct] K/V probe: {len(prompt)} prompt + {len(gen)} generated "
        f"tokens, {n} positions; the rows of {len(lm.full_layers)} full "
        f"members and the unrolled rings of {len(lm.window_layers)} window "
        f"members ({k_ring.shape[2]} rows) against the reference's rotated "
        f"keys and values, row difference over the layer's max: layer 0 "
        f"worst {first_err:.5f} (tol {KV_RTOL_FIRST}); median over positions "
        f"by layer {[round(m, 5) for m in medians]} (tol {KV_RTOL_MEDIAN}); "
        f"worst anywhere {max(e[1] for e in errs):.5f}; "
        f"{time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(errs).all() and first_err <= KV_RTOL_FIRST
                and max(medians) <= KV_RTOL_MEDIAN)


def run(run):
    from mxnet_tpu import telemetry

    if run.trace:
        telemetry.enable()      # counters and host histograms: traced run only
    lm, params, eng = build_engine(run, run.devices[0])

    def check(records):
        weights = published(params, run.config)
        ok, kept = greedy_parity(run, weights, records)
        return ok & kv_parity(run, lm, weights, eng, kept)

    try:
        return drive(run, run.traffic, eng, COUNTERS, check)
    finally:
        eng.close(timeout=30)


def drive(run, job, eng, counters, check):
    """The closed loop (module docstring). `counters` are the engine's
    counters the traced run marks; `check(records)` decides `correct` from
    the first `parity_requests` finished lead-in requests."""
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
                marks["tele0"] = telemetry_mark(counters)
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
                marks["trace0"] = telemetry_mark(counters)
            was_stopped = tracer.stopped_at is not None
            tracer.maybe_stop()
            if run.trace and not was_stopped \
                    and tracer.stopped_at is not None:
                marks["trace1"] = telemetry_mark(counters)
            if rel >= seconds and not tracer.active:
                closed = time.perf_counter()
                marks["compiles1"] = run.events.backend_compiles
                if run.trace:
                    marks["tele1"] = telemetry_mark(counters)
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
            "request_lifetime_max_s": max(lifetimes, default=None),
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
        f"{also['request_lifetime_p50_s']:.1f}s max "
        f"{also['request_lifetime_max_s']:.1f}s; mean live slots "
        f"{live_slots_dt / sampled_dt:.1f}, mean live positions "
        f"{live_pos_dt / sampled_dt:.0f}; {len(records)} requests in all; "
        f"XLA compiles in the window: {compiles}")

    parity = [r for r in records if r["phase"] == "lead_in"
              and r["error"] is None][:job["parity_requests"]]
    ok = len(parity) == job["parity_requests"] and check(parity)
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
