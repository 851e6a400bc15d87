"""Runner `serve_conv_moe`: `mxnet_tpu.models.HybridLM` built as the LFM2
expert block (gated short-convolution layers whose only cache is a window,
beside rotary grouped-query attention layers, under a sigmoid router with a
selection bias over a whole layer of experts) behind one `GenerationEngine`,
in this process, under the closed loop of `serve_swa_moe.drive` — the loop,
its phases and what is judged are that file's, unrepeated. This file's own:
the engine's build, the weights under the reference's names, the limits of
`correct` and the probe.

`correct`, outside the window, against the plain reference
(`reference/lfm2_moe.py`, float32), by `serve_afmoe`'s scheme:

* the first `parity_requests` lead-in requests teacher-forced through the
  reference (`serve_latent_moe.reference_forward`). A share of at least
  LOGIT_CLOSE_SHARE of the generated tokens is held to LOGIT_RTOL (the
  reference argmax, or within a rounding tolerance of it), a share of
  LOGIT_NEAR_SHARE to LOGIT_NEAR_RTOL (what a swapped expert moves) and every
  token to LOGIT_RTOL_WORST. The (token, expert layer) pairs whose margin in
  the reference — the distance between the 4th and the 5th `sigmoid score +
  bias` of 32 — is under NEAR_TIE are counted and their share bounded by
  NEAR_TIE_SHARE; the program's routing is never shown to the reference;
* the probe: one prompt of at least `probe.min_prompt` tokens alone through
  the idle engine's own prefill and `probe.max_new_tokens` decode ticks, then
  what its slot holds against the reference's full forward — every conv
  layer's window (the last two values of `B * x` a channel: WINDOW_RTOL_FIRST
  for the layers with no routing upstream, the largest difference over the
  layer's largest magnitude; a window is TWO tokens, so behind the expert
  layers one token whose experts were swapped is the whole reading, and
  every layer is held only to WINDOW_L2_WORST, the norm of the difference
  over the norm of the window, under what a window of the wrong tokens or a
  stale one reads) and the attention layers' K/V rows `[0, n)` (KV_RTOL_FIRST
  for the first attention layer — behind the dense MLPs only — at every
  position, KV_RTOL_MEDIAN for every layer in the median over positions);
* no compile inside the window.
"""
import collections.abc
import time

import numpy as np

from harness import log
from runners.serve_latent_moe import reference_forward
from runners.serve_swa_moe import drive

# The limits, each beside its two readings on the v5e (PERF.md section 6, PR
# 47: my chip runs, seeds 2147000111, ...114, 2147483715, 2147000116): the
# stated precision over four seeds | the lower one, seed 2147000112 —
# everything the configuration states as float32 that the program computes
# outside its kernels (norms, head norms, rotary angles, the taps' sum and the
# gate of the short convolution, router scores) computed in bfloat16 (the
# program has no option for it: the model was patched from a scratch script,
# PERF.md says how). The lower precision fails by both K/V limits; the others
# do not tell the two apart and stay as bounds on a gross fault.
# Greedy parity, as serve_engine.LM_LOGIT_RTOL: two evaluation orders of a
# deep bfloat16 network agree to a few 2^-8 of the logit scale ...
LOGIT_RTOL = 2 ** -5
# ... which at least this share of the generated tokens must meet: read
# 0.8742-0.8917 | 0.8336. The tied table's rows have norm 1, so the logits
# are of unit scale (max|logit| ~5) and a swapped expert moves a token
# further against it than in the cells whose head is untied; 0.02 on either
# side is no room, so this is a bound
LOGIT_CLOSE_SHARE = 0.80
# what a swapped expert (weight ~1/4 of the routed sum, in each of 10 layers)
# moves: read 0.9934-0.9963 | 0.9908; a bound
LOGIT_NEAR_RTOL = 2 ** -3
LOGIT_NEAR_SHARE = 0.97
# every token: the worst gap read 0.181-0.244 | 0.228; a token the program
# got wrong reads ~0.9 (a random token's logit against the largest of
# 65,536), so the limit lies between
LOGIT_RTOL_WORST = 0.6
# `sigmoid score + bias` closer than this is a near-tie (the 4th and the 5th
# of 32 lie 0.0194-0.0198 apart at the median): pairs under it read
# 0.0325-0.0340 | 0.0339, a property of the scores
NEAR_TIE = 1e-3
NEAR_TIE_SHARE = 0.25       # of the (token, expert layer) pairs
# conv windows, the largest difference over the layer's largest magnitude,
# the layers with no routing upstream (0 and 1, behind the dense MLPs): read
# 0.0070-0.0130 | 0.0086 (a window holds `B * x`, which both precisions form
# in bfloat16); a bound ...
WINDOW_RTOL_FIRST = 4.0e-2
# ... and every conv layer's, the norm of the difference over the norm of the
# window: a window is two tokens, so behind the expert layers one swapped
# expert is the whole reading (0.114-0.205 | 0.248); windows of the wrong
# tokens read 1.41, a stale or zero one 1.0
WINDOW_L2_WORST = 0.7
# K/V rows, row difference over the layer's max: the first attention layer
# (no routing upstream) at every position 0.0118-0.0126 | 1.484 ...
KV_RTOL_FIRST = 4.0e-2
# ... and every attention layer in the median over positions, by layer
# 0.0070-0.0075, 0.0158-0.0172, 0.0688-0.0745 | 0.701, 0.680, 0.730 (the third
# sits behind 8 expert layers: about half its positions have a swapped expert
# upstream, so its median is not the rounding's)
KV_RTOL_MEDIAN = 0.2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "state_slots_live", "state_bytes_touched",
            "expert_assignments", "experts_hit", "expert_tokens_max",
            "kv_rows_live_full")
TOP_NAMES = {"embed": "embed_tokens.weight", "norm_f": "embedding_norm.weight"}
LAYER_NAMES = {
    "norm1": "operator_norm.weight", "norm2": "ffn_norm.weight",
    "c_in": "conv.in_proj.weight", "c_out": "conv.out_proj.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.out_proj.weight",
    "q_norm": "self_attn.q_layernorm.weight",
    "k_norm": "self_attn.k_layernorm.weight",
    "w_out": "feed_forward.w2.weight", "router": "feed_forward.gate.weight",
    "router_bias": "feed_forward.expert_bias"}


class Published(collections.abc.Mapping):
    """HybridLM's flat weights under the reference's names, made on access:
    a renaming, but for what the model keeps fused or stacked — the dense
    MLP's gate | up (`w1` | `w3`), the experts' stacks `[expert, ...]` and
    their gate | up, each sliced, and the taps `[kernel, channels]`, which go
    back to `[channels, 1, kernel]`. The slices are copies on the device, so
    they are cut when the reference asks for an expert and dropped with it:
    whole, they would be a second 7 GB."""

    def __init__(self, params, config):
        self._params = params
        f, fe = config["intermediate_size"], config["moe_intermediate_size"]
        self._cut = {}          # reference name -> (param, how)
        for name, arr in params.items():
            layer, _, leaf = name.rpartition(".")
            if not layer:
                self._cut[TOP_NAMES[leaf]] = (name, None)
                continue
            pre = f"layers.{layer[1:]}."
            if leaf == "w_in":
                for j, at in ((1, 0), (3, f)):
                    self._cut[pre + f"feed_forward.w{j}.weight"] = (
                        name, (slice(None), slice(at, at + f)))
            elif leaf in ("experts_in", "experts_out"):
                for e in range(arr.shape[0]):
                    pe = pre + f"feed_forward.experts.{e}."
                    if leaf == "experts_out":
                        self._cut[pe + "w2.weight"] = (name, (e,))
                        continue
                    for j, at in ((1, 0), (3, fe)):
                        self._cut[pe + f"w{j}.weight"] = (
                            name, (e, slice(None), slice(at, at + fe)))
            elif leaf == "conv_w":
                self._cut[pre + "conv.conv.weight"] = (name, "taps")
            else:
                self._cut[pre + LAYER_NAMES[leaf]] = (name, None)

    def __getitem__(self, name):
        param, how = self._cut[name]
        arr = self._params[param]
        if how is None:
            return arr
        if how == "taps":
            return arr.T[:, None, :]
        return arr[how]

    def __iter__(self):
        return iter(self._cut)

    def __len__(self):
        return len(self._cut)


def build_engine(run, dev):
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu import telemetry
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    e = run.traffic["engine"]
    lm = HybridLM(HybridLMConfig.from_config(run.config, max_len=e["max_len"]),
                  par.create_mesh(devices=[dev], dp=1))
    # a leaf at a time, on the device, drawn in float32 and kept in the
    # served dtype (the router and its bias in float32). The tied table stays
    # at `init_params`' own 1 / sqrt(hidden): at unit variance (the other
    # expert cells' draw) a token's own row is a fifth of the final hidden
    # state and its logit twice every other's, so every stream repeats its
    # last prompt token and the comparison of logits compares nothing
    # (PERF.md section 6, PR 47: the first chip run)
    params = lm.init_params(jax.random.PRNGKey(run.seed % 2 ** 31))
    jax.block_until_ready(params)
    n_params = sum(int(np.prod(v.shape)) for v in params.values())
    n_bytes = sum(int(v.nbytes) for v in params.values())
    t1 = time.perf_counter()
    eng = GenerationEngine(lm, params, max_slots=e["max_slots"],
                           max_len=e["max_len"], buckets=tuple(e["buckets"]),
                           prefix_cache=False, spec_k=0)
    warm = eng.warm()
    c = lm.cfg
    went = {k: telemetry.counter("moe.grouped_product." + k).value
            for k in ("gmm", "ragged_dot")}
    members = ", ".join(f"{m} " + "x".join(map(str, a.shape[1:]))
                        for m, a in zip(lm.members, eng._kv))
    log(f"[setup] {n_params / 1e6:.1f}M params {c.dtype} "
        f"({n_bytes / 1e9:.2f} GB) in {t1 - t0:.1f}s: {lm.n_recurrent} conv "
        f"layers of {c.conv_L_cache} taps + {lm.n_attention} attention "
        f"layers of {c.num_attention_heads} queries over "
        f"{c.num_key_value_heads} K/V heads of {c.head_dim}, "
        f"{c.num_experts} experts of {c.moe_intermediate_size} (top "
        f"{c.num_experts_per_tok}) in {lm.n_expert_layers} expert layers "
        f"behind {c.num_dense_layers} dense, {c.vocab_size} vocabulary rows; "
        f"engine slots={eng.max_slots} max_len={eng.max_len} "
        f"buckets={list(eng.prefill_buckets)} cache "
        f"{eng.kv_slab_bytes() / 1e9:.2f} GB (a slot: {members}); decode "
        f"kernel block {eng._slab_block}; prefill attention blockwise at "
        f"{[b for b in eng.prefill_buckets if lm.prefill_blockwise(b)]}; "
        f"grouped products traced (counted with telemetry on): "
        f"{went['gmm']} gmm, {went['ragged_dot']} ragged_dot; "
        f"warm-up compiled or loaded {warm['compiles']} programs in "
        f"{warm['seconds']:.1f}s")
    return lm, params, eng


def greedy_parity(run, weights, records):
    """Teacher-forced greedy parity of finished requests (module docstring),
    as `serve_afmoe.greedy_parity` under this file's limits."""
    finite = True
    gaps, margins = [], []
    t0 = time.perf_counter()
    for rec in records:
        prompt, gen = rec["prompt"], np.asarray(rec["stream"].tokens)
        rows, _, margin = reference_forward(run, weights, prompt, gen)
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
    return bool(finite and close >= LOGIT_CLOSE_SHARE
                and near >= LOGIT_NEAR_SHARE
                and gaps.max() <= LOGIT_RTOL_WORST and ties <= NEAR_TIE_SHARE)


def probe_parity(run, lm, weights, eng, records):
    """One request alone through the idle engine; what its slot holds when it
    has finished — the conv layers' windows and the attention layers' K/V
    rows after the prompt and all but the last generated token — against the
    reference's full forward (module docstring). The prompt is the first
    parity request's of at least `probe.min_prompt` tokens, else a fresh one
    of that length."""
    t0 = time.perf_counter()
    probe = run.traffic["probe"]
    prompt = next((r["prompt"] for r in records
                   if len(r["prompt"]) >= probe["min_prompt"]), None)
    if prompt is None:
        prompt = np.random.default_rng([run.seed, 0x6c66]).integers(
            0, run.config["vocab_size"], probe["min_prompt"], dtype=np.int32)
    stream = eng.submit(prompt, max_new_tokens=probe["max_new_tokens"])
    gen = np.asarray(stream.result(timeout=600))
    held = dict(zip(lm.members, eng.slot_snapshot(stream.slot)))
    n = len(prompt) + len(gen) - 1          # positions the slot holds
    _, (windows, kv), _ = reference_forward(run, weights, prompt, gen)
    got = held["conv"].astype(np.float32)
    want = np.stack(windows)
    errs = np.asarray([np.abs(g - w).max() / np.abs(w).max()
                       for g, w in zip(got, want)])
    norms = np.asarray([np.linalg.norm(g - w) / np.linalg.norm(w)
                        for g, w in zip(got, want)])
    # conv layers whose input no routing has touched: those among the
    # leading dense layers and the first layer behind them
    kinds = run.config["layer_types"][:run.config["num_hidden_layers"]]
    unrouted = kinds[:lm.cfg.num_dense_layers + 1].count("conv")
    medians, worst = [], []
    for page, ref_kv in enumerate(kv):
        ref_kv = np.asarray(ref_kv, np.float32)             # [n, 2, H, hd]
        rows = np.stack([held[m][page][:, :n].astype(np.float32)
                         .transpose(1, 0, 2) for m in "kv"], axis=1)
        err = np.abs(rows - ref_kv).reshape(n, -1).max(-1) \
            / np.abs(ref_kv).max()
        medians.append(float(np.median(err)))
        worst.append(float(err.max()))
    log(f"[correct] probe: {len(prompt)} prompt + {len(gen)} generated "
        f"tokens, {n} positions; windows of {len(want)} conv layers, largest "
        f"difference over the layer's max|B x|: layers 0-{unrouted - 1} (no "
        f"routing upstream) {errs[:unrouted].max():.5f} (tol "
        f"{WINDOW_RTOL_FIRST}), by layer {[round(e, 4) for e in errs]}; "
        f"|difference| over |window| by layer "
        f"{[round(e, 4) for e in norms]}, worst {norms.max():.5f} (tol "
        f"{WINDOW_L2_WORST}); K/V rows of {len(kv)} attention layers, row "
        f"difference over the layer's max: first attention layer worst "
        f"{worst[0]:.5f} (tol {KV_RTOL_FIRST}), median over positions by "
        f"layer {[round(m, 5) for m in medians]} (tol {KV_RTOL_MEDIAN}), "
        f"worst anywhere {max(worst):.5f}; {time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(got).all() and np.isfinite(worst).all()
                and len(got) == len(want)
                and errs[:unrouted].max() <= WINDOW_RTOL_FIRST
                and norms.max() <= WINDOW_L2_WORST
                and worst[0] <= KV_RTOL_FIRST
                and max(medians) <= KV_RTOL_MEDIAN)


def run(run):
    from mxnet_tpu import telemetry

    if run.trace:
        telemetry.enable()      # counters and host histograms: traced run only
    lm, params, eng = build_engine(run, run.devices[0])

    def check(records):
        weights = Published(params, run.config)
        return greedy_parity(run, weights, records) \
            & probe_parity(run, lm, weights, eng, records)

    try:
        return drive(run, run.traffic, eng, COUNTERS, check)
    finally:
        eng.close(timeout=30)
