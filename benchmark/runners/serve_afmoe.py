"""Runner `serve_afmoe`: `mxnet_tpu.models.WindowMoELM` built as the `afmoe`
block (Trinity-Large-Preview: gated, QK-normed attention over rings and a full
member, four norms a layer, a leading dense layer, a sigmoid router with a
selection bias beside a shared expert; this chip's share of the experts and of
the vocabulary) behind one `GenerationEngine`, in this process, under the
closed loop of `serve_swa_moe.drive` — the loop, its phases and what is judged
are that file's, unrepeated. This file's own: the engine's build (the model
multiplies its embedding by sqrt(hidden) itself: `mup_enabled`; the post-norms'
gains at their depth-scaled start), the family's published weight names, the
limits of `correct` and the K/V probe over rings of 4,096.

`correct`, outside the window, against the plain reference
(`reference/trinity_afmoe.py`, float32), by `serve_swa_moe`'s scheme:

* the first `parity_requests` lead-in requests teacher-forced through the
  reference (`serve_latent_moe.reference_forward`). A share of at least
  LOGIT_CLOSE_SHARE of the generated tokens is held to LOGIT_RTOL (the
  reference argmax, or within a rounding tolerance of it), a share of
  LOGIT_NEAR_SHARE to LOGIT_NEAR_RTOL (what a swapped expert moves) and every
  token to LOGIT_RTOL_WORST. The (token, expert layer) pairs whose margin in
  the reference — the distance between the 4th and the 5th `sigmoid score +
  bias` of 256 — is under NEAR_TIE are counted and their share bounded by
  NEAR_TIE_SHARE; the program's routing is never shown to the reference;
* the K/V probe: one request of at least `kv_probe.min_prompt` tokens (longer
  than the window of 4,096) alone through the idle engine's own prefill and
  `kv_probe.max_new_tokens` decode ticks, then the rows its slot holds — the
  full member's rows `[0, n)` and every ring UNROLLED (`serve_swa_moe.
  unrolled`) — against the reference's keys (normalised a head; rotated in a
  window layer only) and values. The layers with no routing upstream (layer 0,
  and layer 1 behind the dense MLP) are held at every position (KV_RTOL_FIRST);
  every layer in the median over positions (KV_RTOL_MEDIAN), which a minority
  of tokens with a swapped expert cannot move;
* no compile inside the window.
"""
import time

import numpy as np

from harness import log
from runners.serve_latent_moe import reference_forward
from runners.serve_swa_moe import drive, unrolled

# The limits, each from two readings on the v5e (PERF.md section 6, PR 39: my
# chip runs): the stated precision over thirteen seeds of the cell as built |
# everything the configuration states as float32 that the program computes
# outside its kernels (the four norms a layer, the head norms, rotary angles,
# the gate's sigmoid, router scores) computed in bfloat16, two seeds. The
# lower precision fails by three of them (the first share and both K/V
# limits).
# Greedy parity, as serve_engine.LM_LOGIT_RTOL: two evaluation orders of a
# deep bfloat16 network agree to a few 2^-8 of the logit scale ...
LOGIT_RTOL = 2 ** -5
# ... which at least this share of the generated tokens must meet: read
# 0.9993-1.0 | 0.9680, 0.9785 (0.9962, 0.9974 with the first weights, whose
# routing was skewed)
LOGIT_CLOSE_SHARE = 0.985
# what a swapped expert (weight ~2.448 / 4 of the routed sum, in each of 4
# layers, under a post-norm gain of 0.129) moves: read 1.0 | 1.0 — it does not
# tell the precisions apart and stays as the bound on what swapped experts
# may move
LOGIT_NEAR_RTOL = 2 ** -3
LOGIT_NEAR_SHARE = 0.99
# every token: the worst gap read 0.016-0.044 | 0.082, 0.121 (0.075, 0.081
# with the first weights), so no limit fits between with room; held well
# above both, where a fault that breaks single tokens would read
# (serve_swa_moe.LOGIT_RTOL_WORST)
LOGIT_RTOL_WORST = 0.4
# `sigmoid score + bias` closer than this is a near-tie: the 4th and the 5th
# of 256 lie 0.0064-0.0069 apart at the median (mellum's 8th and 9th softmax
# probabilities of 64: 0.0016, hence its 4e-4), and bfloat16 hidden states
# move one by a few 1e-4. Pairs under it read 0.090-0.104 | 0.095, 0.101: a
# property of the scores
NEAR_TIE = 1e-3
NEAR_TIE_SHARE = 0.25       # of the (token, expert layer) pairs
# K/V rows, difference over the layer's max: layers 0-1 at every position read
# 0.0056-0.0084 | 1.66, 1.95; the median over positions, worst layer,
# 0.0040-0.0055 | 1.011, 0.996 (the full layer, which has no angle to lose:
# 0.0035-0.0039 | 0.057, 0.059)
KV_RTOL_FIRST = 2.0e-2
KV_RTOL_MEDIAN = 2.0e-2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "expert_assignments", "experts_hit",
            "expert_tokens_max", "kv_rows_live_full", "kv_rows_live_window")
TOP_NAMES = {"embed": "embed_tokens.weight", "head": "lm_head.weight",
             "norm_f": "norm.weight"}
LAYER_NAMES = {
    "norm1": "input_layernorm.weight",
    "norm1_post": "post_attention_layernorm.weight",
    "norm2": "pre_mlp_layernorm.weight",
    "norm2_post": "post_mlp_layernorm.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "wo": "self_attn.o_proj.weight",
    "w_in": "mlp.gate_up_proj.weight", "w_out": "mlp.down_proj.weight",
    "router": "mlp.router.gate.weight", "router_bias": "mlp.expert_bias",
    "experts_in": "mlp.experts.gate_up_proj",
    "experts_out": "mlp.experts.down_proj",
    "shared_in": "mlp.shared_experts.gate_up_proj.weight",
    "shared_out": "mlp.shared_experts.down_proj.weight"}


def published(params, config):
    """WindowMoELM's flat weights under the family's published names: a
    renaming, but for the fused `wqkv`, which is sliced into `q_proj`,
    `k_proj`, `v_proj` and `gate_proj` (the stacked experts and every fused
    gate|up stay as they are; reference/trinity_afmoe.py takes them so)."""
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
            out[pre + "self_attn.v_proj.weight"] = arr[:, q + k:q + 2 * k]
            out[pre + "self_attn.gate_proj.weight"] = arr[:, q + 2 * k:]
        else:
            out[pre + LAYER_NAMES[leaf] if layer else TOP_NAMES[leaf]] = arr
    return out


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
    # a leaf at a time, on the device, kept in the served dtype but DRAWN in
    # float32: jax's bfloat16 normal has a mean of -0.012 (128 values), and
    # matrices so drawn pass the all-ones direction on from layer to layer, a
    # part of the residual stream that every token of every stream shares.
    # The embedding is drawn at 1 / sqrt(hidden) and the model's own
    # sqrt(hidden) (`mup_enabled`) brings its rows to unit variance
    params = lm.init_params(jax.random.PRNGKey(run.seed % 2 ** 31),
                            draw_dtype="float32")
    # the norms of each sub-layer's OUTPUT at 1 / sqrt(published depth), not
    # at 1, and the selection bias at normal x 0.005, not x 0.02 (the
    # configuration file's `assumed.weights`, `assumed.expert_bias`): what is
    # left of a shared part in the routers' inputs, and a bias of the size of
    # the scores' own spacing, make the share of a tick's tokens that the 32
    # HELD experts draw the seed's own, and `itl_p90_ms` with it (PERF.md
    # section 6, PR 39: 18.6% of the held experts hit a tick, then 35.3%,
    # where uniform routing gives 39.6; spread over six seeds 3.5%)
    gain = float(run.config["published"]["num_hidden_layers"]) ** -0.5
    for name in params:
        if name.endswith("_post"):
            params[name] = params[name] * gain
        elif name.endswith("router_bias"):
            params[name] = params[name] * 0.25
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    n_bytes = sum(int(v.nbytes) for v in params.values())
    t1 = time.perf_counter()
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]),
                           prefix_cache=False, spec_k=0)
    warm = eng.warm()
    c = lm.cfg
    members = ", ".join("x".join(map(str, m.shape[1:])) for m in eng._kv)
    log(f"[setup] {n_params / 1e6:.1f}M params {c.dtype} "
        f"({n_bytes / 1e9:.2f} GB) in {t1 - t0:.1f}s: {c.experts_held} "
        f"of {c.num_experts} experts from {c.expert_first} in "
        f"{c.n_expert_layers} expert layers behind {c.num_dense_layers} "
        f"dense, {len(lm.window_layers)} window layers of "
        f"{c.sliding_window} + {len(lm.full_layers)} full layers, "
        f"{c.num_attention_heads // c.num_key_value_heads} query heads a "
        f"K/V head, {c.vocab_size} vocabulary rows; engine slots="
        f"{eng.max_slots} max_len={eng.max_len} "
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
        f"under {NEAR_TIE}: {ties:.4f} of the (token, expert layer) pairs "
        f"(bound {NEAR_TIE_SHARE}), "
        f"{float((margins.min(0) < NEAR_TIE).mean()):.4f} of the tokens in "
        f"some layer, margin median {float(np.median(margins)):.6f}; "
        f"reference forward took {time.perf_counter() - t0:.1f}s")
    ok = finite and close >= LOGIT_CLOSE_SHARE \
        and near >= LOGIT_NEAR_SHARE and gaps.max() <= LOGIT_RTOL_WORST \
        and ties <= NEAR_TIE_SHARE
    return ok, kept


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
    # layers whose K/V no routing has touched: held at every position
    unrouted = lm.cfg.num_dense_layers + 1
    medians, worst = [], []
    for i, ref_kv in enumerate(want):
        if i in lm.full_layers:
            members, page = (k_full, v_full), lm.full_layers.index(i)
        else:
            members, page = (k_ring, v_ring), lm.window_layers.index(i)
        (k, first), (v, _) = (unrolled(m, page, n) for m in members)
        ref_kv = np.asarray(ref_kv[first:n], np.float32)    # [m, 2, H, hd]
        err = np.abs(np.stack([k, v], axis=1) - ref_kv) \
            .reshape(n - first, -1).max(-1) / np.abs(ref_kv).max()
        medians.append(float(np.median(err)))
        worst.append(float(err.max()))
    first_err = max(worst[:unrouted])
    log(f"[correct] K/V probe: {len(prompt)} prompt + {len(gen)} generated "
        f"tokens, {n} positions; the rows of {len(lm.full_layers)} full "
        f"members and the unrolled rings of {len(lm.window_layers)} window "
        f"members ({k_ring.shape[2]} rows) against the reference's keys and "
        f"values, row difference over the layer's max: layers 0-"
        f"{unrouted - 1} (no routing upstream) worst {first_err:.5f} (tol "
        f"{KV_RTOL_FIRST}); median over positions by layer "
        f"{[round(m, 5) for m in medians]} (tol {KV_RTOL_MEDIAN}); worst "
        f"anywhere {max(worst):.5f}; {time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(worst).all() and first_err <= KV_RTOL_FIRST
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
