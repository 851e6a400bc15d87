"""Runner `serve_gdn_hybrid`: `mxnet_tpu.models.HybridLM` built as the
Olmo-Hybrid block (gated-delta-rule layers whose matrix state lives beside the
K/V rows of unrotated full-attention layers in one cache) behind one
`GenerationEngine`, in this process, under the closed loop of
`serve_swa_moe.drive` — the loop, its phases and what is judged are that
file's, unrepeated. This file's own: the engine's build, the weights under the
reference's names, the limits of `correct` and the two probes.

`correct`, outside the window, against the plain reference
(`reference/olmo_hybrid.py`, float32), by `serve_closed_loop`'s scheme:

* the first `parity_requests` lead-in requests teacher-forced through the
  reference: every generated token is the reference's argmax or within
  LOGIT_RTOL of the logit scale of it (no router: every token is held);
* the probe: one prompt alone through the idle engine's own prefill and
  `probe.max_new_tokens` decode ticks, then what its slot holds against the
  reference's full forward — each linear layer's state `S` (STATE_RTOL_FIRST
  for the first layer's relative error in norm, STATE_RTOL_MEDIAN for the
  median over the layers of the largest difference over the largest
  magnitude) and
  the full layers' K/V rows `[0, n)` (KV_RTOL_FIRST for the first full layer
  at every position, KV_RTOL_MEDIAN for every layer in the median over
  positions);
* no compile inside the window.
"""
import collections.abc
import time

import numpy as np

import harness
from harness import log
from runners.serve_swa_moe import drive

# The limits, each beside its two readings on the v5e (PERF.md section 6, PR
# 42: my chip runs): the stated precision over eleven seeds | the linear
# layers' state HELD in bfloat16 (`lax.reduce_precision` after the prefill and
# after every tick), two seeds. The lower precision fails by STATE_RTOL_FIRST
# alone: past the first layer the bfloat16 residual stream's own rounding,
# which builds up with depth, is as large as what a bfloat16 state adds, and
# the other limits stay as bounds on a gross fault, at about twice their
# readings.
# Greedy parity, every generated token: the reference's argmax, or within this
# share of the logit scale of it. The worst of ~2,200 tokens a run read
# 0.0134-0.0254 | 0.0159, 0.0161 (93-96% of the tokens are the argmax itself):
# serve_closed_loop's 2^-5 would leave 1.2x of room, so the limit is this
# file's own
LOGIT_RTOL = 2 ** -4
# The FIRST linear layer's state against the reference's, the norm of the
# difference over the norm of the state (over 553 thousand entries, so steady
# from seed to seed where the largest difference is the seed's own: 0.0051-
# 0.0075 | 0.0097, 0.0106): only the embedding, one norm, one projection and
# the convolution precede it. Read 0.00504, 0.00508, 0.00516 | 0.00822, 0.00882
STATE_RTOL_FIRST = 6.3e-3
# ... and every linear layer's, the largest difference over the largest
# magnitude, in the median over the layers: 0.0230-0.0340 | 0.0253, 0.0302
STATE_RTOL_MEDIAN = 6.0e-2
# K/V rows of the full layers, row difference over the layer's max: the first
# full layer (three linear layers upstream) at every position, 0.0098-0.0149 |
# 0.0109, 0.0159; every full layer in the median over positions, the worst
# layer 0.0164-0.0206 | 0.0196, 0.0208
KV_RTOL_FIRST = 3.0e-2
KV_RTOL_MEDIAN = 4.0e-2
COUNTERS = ("ticks", "tick_slots", "decode_tokens", "prefills", "tokens",
            "prefill_tokens", "state_slots_live", "state_bytes_touched",
            "kv_rows_live_full")
TOP_NAMES = {"embed": "embed_tokens.weight", "head": "lm_head.weight",
             "norm_f": "norm.weight"}
FULL_NAMES = {
    "norm1": "post_attention_layernorm.weight",
    "norm2": "post_feedforward_layernorm.weight",
    "wq": "self_attn.q_proj.weight", "wk": "self_attn.k_proj.weight",
    "wv": "self_attn.v_proj.weight", "wo": "self_attn.o_proj.weight",
    "q_norm": "self_attn.q_norm.weight", "k_norm": "self_attn.k_norm.weight",
    "w_out": "mlp.down_proj.weight"}
LINEAR_NAMES = {
    "norm1": "input_layernorm.weight",
    "norm2": "pre_feedforward_layernorm.weight",
    "dt_bias": "linear_attn.dt_bias", "A_log": "linear_attn.A_log",
    "g_norm": "linear_attn.o_norm.weight",
    "g_out": "linear_attn.o_proj.weight", "w_out": "mlp.down_proj.weight"}


class Published(collections.abc.Mapping):
    """HybridLM's flat weights under the reference's names, made on access:
    a renaming, but for what the model keeps fused — the MLP's gate|up, the
    linear layers' `g_in` (q | k | v | gate | b | a) and their three
    convolutions side by side `[kernel, channels]`, each sliced (a
    convolution goes back to `[channels, 1, kernel]`). The slices are
    copies on the device, so they are cut when the reference asks for a
    layer and dropped with it: whole, they would be a second 5 GB."""

    def __init__(self, params, config):
        self._params = params
        h = config["linear_num_value_heads"]
        qk = h * config["linear_key_head_dim"]
        v = h * config["linear_value_head_dim"]
        f = config["intermediate_size"]
        at = np.cumsum([0, qk, qk, v, v, h, h])
        self._cut = {}          # reference name -> (param, how)
        for name in params:
            layer, _, leaf = name.rpartition(".")
            if not layer:
                self._cut[TOP_NAMES[leaf]] = (name, None)
                continue
            pre = f"layers.{layer[1:]}."
            names = LINEAR_NAMES if f"{layer}.g_in" in params else FULL_NAMES
            if leaf == "w_in":
                for j, part in enumerate(("gate", "up")):
                    self._cut[pre + f"mlp.{part}_proj.weight"] = (
                        name, (slice(None), slice(j * f, (j + 1) * f)))
            elif leaf == "g_in":
                for j, part in enumerate("qkvgba"):
                    self._cut[pre + f"linear_attn.{part}_proj.weight"] = (
                        name, (slice(None), slice(at[j], at[j + 1])))
            elif leaf == "conv_w":
                for j, part in enumerate("qkv"):
                    self._cut[pre + f"linear_attn.{part}_conv1d.weight"] = (
                        name, ("conv", slice(at[j], at[j + 1])))
            else:
                self._cut[pre + names[leaf]] = (name, None)

    def __getitem__(self, name):
        param, how = self._cut[name]
        arr = self._params[param]
        if how is None:
            return arr
        if how[0] == "conv":
            return arr[:, how[1]].T[:, None, :]
        return arr[how]

    def __iter__(self):
        return iter(self._cut)

    def __len__(self):
        return len(self._cut)


def build_engine(run, dev):
    import jax

    from mxnet_tpu import parallel as par
    from mxnet_tpu.models import HybridLM, HybridLMConfig
    from mxnet_tpu.serving import GenerationEngine

    t0 = time.perf_counter()
    e = run.traffic["engine"]
    lm = HybridLM(HybridLMConfig.from_config(run.config, max_len=e["max_len"]),
                  par.create_mesh(devices=[dev], dp=1))
    # a leaf at a time, on the device, drawn in float32 and kept in the
    # served dtype; the untied embedding at unit variance (the configuration
    # file's `assumed.weights`)
    params = lm.init_params(jax.random.PRNGKey(run.seed % 2 ** 31))
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
        f"({n_bytes / 1e9:.2f} GB) in {t1 - t0:.1f}s: {lm.n_recurrent} "
        f"linear layers of {lm.mixer.heads} heads x {lm.mixer.dk} x "
        f"{lm.mixer.dv} + {lm.n_attention} full layers of "
        f"{lm.cfg.num_key_value_heads} K/V heads of {lm.cfg.head_dim}, "
        f"{lm.cfg.vocab_size} vocabulary rows; engine slots={eng.max_slots} "
        f"max_len={eng.max_len} buckets={list(eng.prefill_buckets)} cache "
        f"{eng.kv_slab_bytes() / 1e9:.2f} GB (a slot: {members}); decode "
        f"kernel block {eng._slab_block}, state kernel "
        f"{lm.state_kernel(eng._kv[2].shape, eng._kv[2].dtype)}; warm-up "
        f"compiled or loaded {warm['compiles']} programs in "
        f"{warm['seconds']:.1f}s")
    return lm, params, eng


def greedy_parity(run, weights, records):
    """Teacher-forced greedy parity of finished requests, as
    `serve_closed_loop.greedy_parity` under this file's limit."""
    ref = harness.load_plugin("reference", run.config["reference"])
    worst = 0.0
    t0 = time.perf_counter()
    for rec in records:
        prompt, gen = rec["prompt"], np.asarray(rec["stream"].tokens)
        n, g = len(prompt), len(gen)
        rows = np.asarray(ref.logits(
            run.config, weights, np.concatenate([prompt, gen[:-1]]),
            np.arange(n - 1, n - 1 + g)), np.float64)
        gap = (rows.max(-1) - rows[np.arange(g), gen]) / np.abs(rows).max()
        log(f"[correct] request of {n} prompt tokens: "
            f"{int((gap == 0).sum())}/{g} generated tokens equal the "
            f"reference argmax; worst reference-logit gap {gap.max():.5f} of "
            f"max|logit| {np.abs(rows).max():.4f} (tol {LOGIT_RTOL:.5f}), "
            f"p99 {np.quantile(gap, 0.99):.5f}")
        worst = max(worst, gap.max()) if np.isfinite(rows).all() else np.inf
    log(f"[correct] reference forward of {len(records)} requests took "
        f"{time.perf_counter() - t0:.1f}s; worst gap {worst:.5f}")
    return bool(worst <= LOGIT_RTOL)


def probe_parity(run, lm, weights, eng, prompt):
    """One request alone through the idle engine; what its slot holds when it
    has finished — the linear layers' states and the full layers' K/V rows
    after the prompt and all but the last generated token — against the
    reference's full forward (module docstring)."""
    ref = harness.load_plugin("reference", run.config["reference"])
    t0 = time.perf_counter()
    stream = eng.submit(prompt,
                        max_new_tokens=run.traffic["probe"]["max_new_tokens"])
    gen = np.asarray(stream.result(timeout=300))
    ck, cv, state, _ = eng.slot_snapshot(stream.slot)
    seq = np.concatenate([prompt, gen[:-1]])
    n = len(seq)
    _, states, kv = ref.forward(run.config, weights, seq, [n - 1])
    # the slot's pages [dk, H dv] as the reference's [H, dk, dv]
    h, dk, dv = lm.mixer.heads, lm.mixer.dk, lm.mixer.dv
    got = state.astype(np.float32).reshape(-1, dk, h, dv).transpose(0, 2, 1, 3)
    want = np.stack([np.asarray(s, np.float32) for s in states])
    errs = np.asarray([np.abs(g - w).max() / np.abs(w).max()
                       for g, w in zip(got, want)])
    first = float(np.linalg.norm(got[0] - want[0]) / np.linalg.norm(want[0]))
    medians, worst = [], []
    for page, ref_kv in enumerate(kv):
        ref_kv = np.asarray(ref_kv, np.float32)             # [n, 2, H, hd]
        rows = np.stack([m[page][:, :n].astype(np.float32).transpose(1, 0, 2)
                         for m in (ck, cv)], axis=1)
        err = np.abs(rows - ref_kv).reshape(n, -1).max(-1) \
            / np.abs(ref_kv).max()
        medians.append(float(np.median(err)))
        worst.append(float(err.max()))
    log(f"[correct] probe: {len(prompt)} prompt + {len(gen)} generated "
        f"tokens, {n} positions; state of {len(want)} linear layers, "
        f"first layer's |difference| over |S| {first:.5f} (tol "
        f"{STATE_RTOL_FIRST}); largest difference over the layer's max|S|: "
        f"first layer {errs[0]:.5f}, median {np.median(errs):.5f} (tol "
        f"{STATE_RTOL_MEDIAN}), worst {errs.max():.5f} (layer "
        f"{int(errs.argmax())}); K/V rows of {len(kv)} full layers, row "
        f"difference over the layer's max: first full layer worst "
        f"{worst[0]:.5f} (tol {KV_RTOL_FIRST}), median over positions by "
        f"layer {[round(m, 5) for m in medians]} (tol {KV_RTOL_MEDIAN}), "
        f"worst anywhere {max(worst):.5f}; {time.perf_counter() - t0:.1f}s")
    return bool(np.isfinite(got).all() and np.isfinite(worst).all()
                and len(got) == len(want)
                and first <= STATE_RTOL_FIRST
                and np.median(errs) <= STATE_RTOL_MEDIAN
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
            and probe_parity(run, lm, weights, eng, records[0]["prompt"])

    try:
        return drive(run, run.traffic, eng, COUNTERS, check)
    finally:
        eng.close(timeout=30)
