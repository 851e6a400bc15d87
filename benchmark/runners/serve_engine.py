"""Runner `serve_engine`: a `TransformerLM` behind one `GenerationEngine`, in
this process, under an open loop of requests that `traffic_gen.plan` draws from
the traffic file and the seed.

One thread besides the engine's own: it submits each request when it is due
and, between submissions, sweeps the live streams' public `tokens` lists every
half millisecond to stamp each new token with the client's clock. (No thread
per request sits in the blocking iterator: thirty pollers would contend for
the interpreter lock with the engine thread, and the cell would measure them.)
Time to first token runs from the instant the request was *due*, so a
generator that falls behind, or a queue, is charged to the system. A request
of the window that errs, is refused, does not finish, or whose first token
comes later than the traffic file's `limits.ttft_s` counts as `failed`.
"""
import collections
import time

import numpy as np

import harness
import traffic_gen
from harness import log

POLL_S = 0.0005
# a request that fails, is refused or never finishes misses every limit
MISSED_MS = 1e9
# Greedy parity: two evaluation orders of a deep bfloat16 network (decode
# through the cache vs the reference's float32 full forward on the same
# bfloat16 weights). bf16 keeps 8 bits and the residual stream is re-rounded
# after every op, so logits agree to about 2^-5 of their scale (PR 21 measured
# 8.7e-3 on 12 layers). A generated token may differ from the reference argmax
# only where the reference's own top-2 gap is below that.
LM_LOGIT_RTOL = 2 ** -5
TELEMETRY = {"counters": ("ticks", "tick_slots", "decode_tokens", "prefills",
                          "tokens"),
             "histograms": ("tick_us", "prefill_us", "ttft_us")}
PUBLISHED_NAMES = {
    "embed": "wte", "pos_embed": "wpe", "ln_f_scale": "ln_f.weight",
    "ln_f_bias": "ln_f.bias", "ln1_scale": "ln_1.weight",
    "ln1_bias": "ln_1.bias", "wqkv": "attn.c_attn.weight",
    "wo": "attn.c_proj.weight", "ln2_scale": "ln_2.weight",
    "ln2_bias": "ln_2.bias", "w1": "mlp.c_fc.weight", "b1": "mlp.c_fc.bias",
    "w2": "mlp.c_proj.weight", "b2": "mlp.c_proj.bias"}


def lm_config(config):
    from mxnet_tpu.models import TransformerLMConfig

    return TransformerLMConfig(
        vocab_size=config["vocab_size"], d_model=config["n_embd"],
        n_heads=config["n_head"], d_ff=config["n_inner"],
        n_layers=config["n_layer"], max_len=config["n_positions"],
        dtype=config["dtype"], tie_embeddings=config["tie_word_embeddings"])


def published(params):
    """The program's flat weights under the published GPT-2 names."""
    out = {}
    for name, arr in params.items():
        layer, _, leaf = name.rpartition(".")
        key = PUBLISHED_NAMES[leaf]
        out[f"h.{layer[1:]}.{key}" if layer else key] = arr
    return out


def telemetry_mark():
    from mxnet_tpu import telemetry

    pre = "serving.generation."
    mark = {c: telemetry.counter(pre + c).value for c in TELEMETRY["counters"]}
    for h in TELEMETRY["histograms"]:
        snap = telemetry.histogram(pre + h).snapshot()
        mark[h + ".sum"], mark[h + ".count"] = snap["sum"], snap["count"]
    return mark


def build_engine(run, dev):
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import TransformerLM
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    cfg = lm_config(run.config)
    lm = TransformerLM(cfg, par.create_mesh(devices=[dev], dp=1))
    # every weight in one jitted call, on the device, in the served dtype
    params = jax.jit(lm.init_params)(jax.random.PRNGKey(run.seed))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    t1 = time.perf_counter()
    e = run.traffic["engine"]
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]))
    warm = eng.warm()
    log(f"[setup] {n_params / 1e6:.1f}M params {cfg.dtype} in {t1 - t0:.1f}s;"
        f" engine slots={eng.max_slots} max_len={eng.max_len} buckets="
        f"{list(eng.prefill_buckets)} slab "
        f"{eng.kv_slab_bytes() / 2**30:.2f} GiB, prefix cache "
        f"{'on' if eng.prefix_cache is not None else 'off'}, spec_k "
        f"{eng.spec_k}; warm-up compiled or loaded {warm['compiles']} "
        f"programs in {warm['seconds']:.1f}s")
    return params, eng


def greedy_parity(run, params, records):
    """The first `parity_requests` lead-in requests, teacher-forced through
    the plain reference: every generated token is the reference argmax or
    lies within LM_LOGIT_RTOL of the logit scale of it."""
    ref = harness.load_plugin("reference", run.config["reference"])
    weights = published(params)
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
        tol = LM_LOGIT_RTOL * scale
        log(f"[correct] request of {n} prompt tokens: {exact}/{g} generated "
            f"tokens equal the reference argmax; worst reference-logit gap "
            f"{gaps.max():.4f} (tol {tol:.4f} = 2^-5 x max|logit| "
            f"{scale:.3f})")
        ok &= bool(np.isfinite(rows).all() and (gaps <= tol).all())
    log(f"[correct] reference forward of {len(records)} requests took "
        f"{time.perf_counter() - t0:.1f}s")
    return ok


def run(run):
    from mxnet_tpu import telemetry

    job = run.traffic
    if run.trace:
        telemetry.enable()      # counters and host histograms: traced run only
    params, eng = build_engine(run, run.devices[0])
    try:
        return drive(run, job, params, eng)
    finally:
        eng.close(timeout=30)


def drive(run, job, params, eng):
    tracer, seconds = run.tracer, run.seconds
    plan = traffic_gen.plan(job, run.config["vocab_size"], run.seed, seconds)
    n_window = sum(r["phase"] == "window" for r in plan)
    lead_in, tail = job["arrivals"]["lead_in_s"], job["arrivals"]["tail_s"]
    log(f"[traffic] {len(plan)} requests planned at "
        f"{job['arrivals']['rate_per_s']}/s: {n_window} due in the window, "
        f"prompts {min(len(r['prompt']) for r in plan)}-"
        f"{max(len(r['prompt']) for r in plan)} tokens, outputs "
        f"{min(r['max_new_tokens'] for r in plan)}-"
        f"{max(r['max_new_tokens'] for r in plan)}")
    pending = collections.deque(plan)
    active, records = [], []
    window_left = n_window
    opened = closed = None
    marks = {}
    live_pos_dt = live_slots_dt = sampled_dt = 0.0
    last_sample = None
    backlog = []        # (seconds into the window, requests with no token yet)
    t_open = time.monotonic() + lead_in
    while True:
        now = time.monotonic()
        rel = now - t_open
        while pending and pending[0]["due_s"] <= rel:
            r = pending.popleft()
            if r["phase"] == "tail" and window_left == 0:
                pending.clear()         # the window's requests are all done
                break
            rec = dict(r, due=t_open + r["due_s"], times=[], error=None)
            try:
                with tracer.annotate("submit"):
                    rec["stream"] = eng.submit(
                        r["prompt"], max_new_tokens=r["max_new_tokens"])
                active.append(rec)
            except Exception as e:  # noqa: BLE001 — a refusal is a failure
                rec["stream"], rec["error"] = None, repr(e)
                window_left -= r["phase"] == "window"
            rec["late"] = time.monotonic() - rec["due"]
            records.append(rec)
        # sweep the live streams
        still = []
        pos = slots = 0
        for rec in active:
            s = rec["stream"]
            n = len(s.tokens)
            rec["times"].extend([now] * (n - len(rec["times"])))
            if s.done and len(rec["times"]) == len(s.tokens):
                window_left -= rec["phase"] == "window"
                continue
            still.append(rec)
            if n:
                slots += 1
                pos += len(rec["prompt"]) + n
        active = still
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
            if not backlog or rel - backlog[-1][0] >= 0.05:
                backlog.append((rel, len(active) - slots))
            tracer.maybe_start(rel)
            tracer.maybe_stop()
            if rel >= seconds and not tracer.active:
                closed = time.perf_counter()
                marks["compiles1"] = run.events.backend_compiles
                if run.trace:
                    marks["tele1"] = telemetry_mark()
        if closed is not None and window_left == 0:
            break
        if rel > seconds + tail + 60:
            log(f"[traffic] gave up {rel:.0f}s after the window opened: "
                f"{window_left} window requests unfinished")
            break
        wake = POLL_S
        if pending:
            wake = min(wake, max(0.0, t_open + pending[0]["due_s"]
                                 - time.monotonic()))
        time.sleep(wake)
    tracer.maybe_stop(force=True)

    # let the rest drain so the parity requests are complete
    for rec in active:
        try:
            rec["stream"].result(timeout=120)
        except Exception as e:  # noqa: BLE001
            rec["error"] = repr(e)
    vocab = run.config["vocab_size"]
    for rec in records:
        s = rec["stream"]
        if rec["error"] is None and s is not None:
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
        log(f"[traffic] failed request due at {r['due_s']:.2f}s: {r['error']}")
    ttft = np.asarray([MISSED_MS if r["error"] else
                       (r["stream"].first_token_at - r["due"]) * 1e3
                       for r in win])
    limit_ms = job.get("limits", {}).get("ttft_s", float("inf")) * 1e3
    failed = sum(r["error"] is not None or t > limit_ms
                 for r, t in zip(win, ttft))
    gaps = [g * 1e3 for r in win if not r["error"]
            for g in np.diff(r["times"])]
    lo, hi = t_open, t_open + seconds
    delivered = sum(lo <= t < hi for r in records for t in r["times"])
    late = np.asarray([r["late"] for r in win]) * 1e3
    # what the client saw besides the judged metrics, in every run (the
    # result's `also`): no bound can hold them at tens of requests a window
    also = {"ttft_p50_ms": harness.percentile(ttft, 50),
            "ttft_p90_ms": harness.percentile(ttft, 90),
            "ttft_max_ms": float(ttft.max()),
            "serve_tokens_per_s": delivered / seconds}
    log(f"[window] {len(win)} requests due, {len(errors)} in error, {failed} "
        f"failed (error or first token later than {limit_ms:.0f} ms); TTFT ms"
        f" p50 {also['ttft_p50_ms']:.1f} p90 {also['ttft_p90_ms']:.1f} max "
        f"{also['ttft_max_ms']:.1f} (n={len(ttft)}); ITL ms p50 "
        f"{harness.percentile(gaps, 50):.1f} p90 "
        f"{harness.percentile(gaps, 90):.1f} (n={len(gaps)}); {delivered} "
        f"tokens delivered in {seconds}s = {also['serve_tokens_per_s']:.1f}/s;"
        f" generator late ms p50 {np.median(late):.2f} max {late.max():.2f}; "
        f"mean live slots {live_slots_dt / sampled_dt:.1f}, mean live "
        f"positions {live_pos_dt / sampled_dt:.0f}; XLA compiles in the "
        f"window: {marks['compiles1'] - marks['compiles0']}")

    parity = [r for r in records if r["phase"] == "lead_in"
              and r["error"] is None][:job["parity_requests"]]
    ok = len(parity) == job["parity_requests"] and \
        greedy_parity(run, params, parity)
    compiles = marks["compiles1"] - marks["compiles0"]
    obs = dict(correct=ok and compiles == 0, attempted=len(win),
               failed=failed, setup_s=opened - run.t_process_start,
               ttft_ms=ttft, itl_ms=gaps, also=also,
               window_s=seconds, compiles_in_window=compiles,
               mean_live_positions=live_pos_dt / sampled_dt,
               mean_live_slots=live_slots_dt / sampled_dt,
               max_slots=eng.max_slots, host_label="engine-thread",
               backlog=backlog)
    if run.trace:
        obs["telemetry"] = {k: marks["tele1"][k] - marks["tele0"][k]
                            for k in marks["tele0"]}
    return obs
