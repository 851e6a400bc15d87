"""Plain reference of the `sarvam_mla` block (sarvam-105b; the keys are the
DeepSeek-V2/V3 family's): `jax.numpy`, float32, matmul precision "highest",
the full forward over a whole sequence — no kernel, no cache, no sort, no
batching, independent of `mxnet_tpu`.

    h = embed[tokens]
    per layer:  h += attention(RMSNorm(h));  h += mlp(RMSNorm(h))
    logits = RMSNorm(h) @ lm_head

* attention — latent attention in its UNABSORBED form. `q = x W_q` is split
  per head into 128 `nope` + 64 `rope` entries after an RMSNorm over the
  head's 192 (assumed placement of `use_qk_norm`, see the configuration's
  `assumed`); `x W_dkv = c[512] | k_r[64]`; `c <- RMSNorm(c)`; `k_r` and
  `q_rope` are rotated (`deepseek_yarn` frequencies, half-split pairing),
  `k_r` once for all heads; `[k_nope, v] = c W_ukv` per head; scores
  `(q_nope.k_nope + q_rope.k_r) * 192^-1/2 * m^2` with `m = 0.1 *
  mscale_all_dim * ln(factor) + 1`; causal softmax; `out = (P v) W_o`.
* mlp — the first `first_k_dense_replace` layers a SiLU-gated MLP of width
  `intermediate_size`; every later layer `s = sigmoid(x W_g)`, the
  `num_experts_per_tok` experts with the largest `s + b` (the bias `b`
  enters the selection only), weights `s_e / sum_selected s *
  routed_scaling_factor`, `y = sum_e w_e E_e(x) + E_shared(x)`.

**The share.** The router is as wide as the published model (`published.
num_experts`); `weights` hold only the experts `[share.expert_first,
share.expert_first + num_experts)`. The sum runs over the chosen experts
that are held — a plain loop over the held experts, each applied to every
token and masked — and what the absent experts would add is left out. With
every expert held this is the uncut layer.

`forward` also returns what the serving cache must hold of every position —
`RMSNorm(c) | rotated k_r`, 576 numbers a layer — and the margin between the
8th and 9th biased router score of every (token, expert layer), from which
the benchmark counts routing near-ties.

Weights come under the family's published names with matrices input-major
(`x @ W`); gate and up projections are fused along the output axis (gate
first) and the held experts are stacked on a leading axis — departures of
storage that the configuration file lists. A float32 copy of the model does
not fit beside the serving cache: weights arrive in the served dtype and
are upcast one layer (one expert) at a time; a long sequence is attended in
blocks of heads and rows.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 2048           # sequences are padded to a multiple (of BLOCK too)
BLOCK = 512             # query rows attended at once
HEAD_BLOCK = 8          # heads attended at once


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def yarn_inv_freq(dim, theta, scaling):
    """`deepseek_yarn` inverse frequencies of the `dim` rotary entries: the
    published ones (`theta^(-2i/dim)`) where a frequency turns more than
    `beta_fast` times over the original context, those divided by `factor`
    where it turns fewer than `beta_slow` times, a linear blend between."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra
    orig = scaling["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / scaling["factor"] * ramp + extra * (1 - ramp)


def yarn_mscale(scaling, key):
    """`0.1 * scaling[key] * ln(factor) + 1` (1 without scaling)."""
    if not scaling or scaling["factor"] <= 1:
        return 1.0
    return 0.1 * scaling[key] * math.log(scaling["factor"]) + 1.0


def rotate(x, positions, inv_freq, amplitude):
    """Half-split rotary embedding of the last axis of `x` [L, ..., dim]."""
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) * inv_freq[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(angle) * amplitude).reshape(shape)
    sin = (jnp.sin(angle) * amplitude).reshape(shape)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(config):
    scaling = config.get("rope_scaling") or None
    inv = yarn_inv_freq(config["qk_rope_head_dim"], config["rope_theta"],
                        scaling)
    softmax_m = yarn_mscale(scaling, "mscale_all_dim")
    return (("eps", float(config["rms_norm_eps"])),
            ("heads", int(config["num_attention_heads"])),
            ("nope", int(config["qk_nope_head_dim"])),
            ("rope", int(config["qk_rope_head_dim"])),
            ("v_dim", int(config["v_head_dim"])),
            ("latent", int(config["kv_lora_rank"])),
            ("inv_freq", tuple(float(f) for f in inv)),
            # cos and sin carry mscale / mscale_all_dim (1 as published)
            ("amplitude", yarn_mscale(scaling, "mscale") / softmax_m),
            ("scale", float((config["qk_nope_head_dim"]
                             + config["qk_rope_head_dim"]) ** -0.5
                            * softmax_m ** 2)),
            ("top_k", int(config["num_experts_per_tok"])),
            ("routed_scale", float(config["routed_scaling_factor"])),
            ("first", int(config.get("share", {}).get("expert_first", 0))))


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _gated(x, w_in, w_out):
    g, u = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out


@functools.partial(jax.jit, static_argnames=("static",))
def attention_inputs(h, w, static):
    """`(x [L, D] = RMSNorm(h), latent [L, 576])`: the latent is `RMSNorm(c)
    | rotated k_r`, what a serving cache keeps of a position."""
    cfg = dict(static)
    w = _f32(w)
    pos = jnp.arange(h.shape[0])
    inv = jnp.asarray(cfg["inv_freq"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["input_layernorm.weight"], cfg["eps"])
        ckr = x @ w["self_attn.kv_a_proj_with_mqa.weight"]
        c = rms_norm(ckr[:, :cfg["latent"]],
                     w["self_attn.kv_a_layernorm.weight"], cfg["eps"])
        k_r = rotate(ckr[:, cfg["latent"]:], pos, inv, cfg["amplitude"])
        return x, jnp.concatenate([c, k_r], axis=-1)


@functools.partial(jax.jit, static_argnames=("static",))
def queries(x, w_q, q_norm, static):
    """A block of heads' queries `[L, Hb, 192]`: `w_q` [D, Hb, 192] (that
    block's columns of `q_proj`), RMSNorm over each head's 192 entries,
    then the rotary part rotated."""
    cfg = dict(static)
    pos = jnp.arange(x.shape[0])
    inv = jnp.asarray(cfg["inv_freq"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        q = jnp.einsum("ld,dhe->lhe", x, w_q.astype(jnp.float32))
        q = rms_norm(q, q_norm.astype(jnp.float32), cfg["eps"])
        return jnp.concatenate(
            [q[..., :cfg["nope"]],
             rotate(q[..., cfg["nope"]:], pos, inv, cfg["amplitude"])], -1)


@functools.partial(jax.jit, static_argnames=("static",))
def up_project(latent, w_ukv, static):
    """`[k_nope, v] = c W_ukv` for a block of heads: `w_ukv` [512, Hb, 256]
    (that block's columns of `kv_b_proj`) -> `(k_nope, v)` [L, Hb, 128]."""
    cfg = dict(static)
    with jax.default_matmul_precision("highest"):
        kv = jnp.einsum("lc,chd->lhd", latent[:, :cfg["latent"]],
                        w_ukv.astype(jnp.float32))
    return kv[..., :cfg["nope"]], kv[..., cfg["nope"]:]


@functools.partial(jax.jit, static_argnames=("static",))
def attend_block(q_rows, first_row, k_nope, v, k_r, static):
    """Causal attention of the query rows `[first_row, first_row + R)` of a
    block of heads over the whole sequence's keys, masked: `q_rows` [R, Hb,
    192], `k_nope` and `v` [L, Hb, 128], `k_r` [L, 64] (one for all heads).
    Returns [R, Hb, 128]."""
    cfg = dict(static)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("qhd,khd->hqk", q_rows[..., :cfg["nope"]], k_nope) \
            + jnp.einsum("qhd,kd->hqk", q_rows[..., cfg["nope"]:], k_r)
        rows = first_row + jnp.arange(q_rows.shape[0])
        seen = rows[:, None] >= jnp.arange(k_r.shape[0])[None, :]
        s = jnp.where(seen[None], s * cfg["scale"], -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)


@functools.partial(jax.jit, static_argnames=("static",))
def attention_out(h, a, w_o, static):
    with jax.default_matmul_precision("highest"):
        return h + a.reshape(a.shape[0], -1) @ w_o.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("static",))
def dense_mlp(h, w, static):
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["post_attention_layernorm.weight"], cfg["eps"])
        return h + _gated(x, w["mlp.gate_up_proj.weight"],
                          w["mlp.down_proj.weight"])


@functools.partial(jax.jit, static_argnames=("static",))
def route(h, w, static):
    """`(x, chosen [L, k] expert ids of the whole router, weights [L, k],
    margin [L])`: plain top-k of `s + b`; `margin` is the distance between
    the last chosen and the first rejected biased score."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["post_attention_layernorm.weight"], cfg["eps"])
        s = jax.nn.sigmoid(x @ w["mlp.gate.weight"])
    biased = s + w["mlp.gate.e_score_correction_bias"]
    order = jnp.argsort(-biased, axis=-1)
    chosen = order[:, :cfg["top_k"]]
    ranked = jnp.take_along_axis(biased, order[:, :cfg["top_k"] + 1], -1)
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    weights = picked / picked.sum(-1, keepdims=True) * cfg["routed_scale"]
    return x, chosen, weights, ranked[:, -2] - ranked[:, -1]


@jax.jit
def expert_add(y, x, weight_of_token, w_in, w_out):
    """`y + weight_of_token[:, None] * E(x)`: one expert over every token,
    weighted (0 for a token that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        return y + weight_of_token[:, None] * _gated(
            x, w_in.astype(jnp.float32), w_out.astype(jnp.float32))


def expert_mlp(h, w, static):
    """The expert layer: the shared expert once and the held experts in a
    plain loop. Returns `(h, margin)`."""
    cfg = dict(static)
    x, chosen, weights, margin = route(
        h, {k: w[k] for k in ("post_attention_layernorm.weight",
                              "mlp.gate.weight",
                              "mlp.gate.e_score_correction_bias")},
        static=static)
    y = expert_add(jnp.zeros_like(x), x, jnp.ones(x.shape[0], jnp.float32),
                   w["mlp.shared_experts.gate_up_proj.weight"],
                   w["mlp.shared_experts.down_proj.weight"])
    w_in, w_out = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]
    for e in range(w_in.shape[0]):
        of_token = jnp.where(chosen == cfg["first"] + e, weights, 0.0).sum(-1)
        y = expert_add(y, x, of_token, w_in[e], w_out[e])
    return h + y, margin


def attention(h, w, static):
    """`(h + attention(RMSNorm(h)), latent [L, 576])`, attended in blocks
    of heads and query rows so that a long sequence fits."""
    cfg = dict(static)
    L = h.shape[0]
    x, latent = attention_inputs(
        h, {k: w[k] for k in ("input_layernorm.weight",
                              "self_attn.kv_a_proj_with_mqa.weight",
                              "self_attn.kv_a_layernorm.weight")},
        static=static)
    w_q = w["self_attn.q_proj.weight"].reshape(
        -1, cfg["heads"], cfg["nope"] + cfg["rope"])
    w_ukv = w["self_attn.kv_b_proj.weight"].reshape(
        cfg["latent"], cfg["heads"], cfg["nope"] + cfg["v_dim"])
    heads = []
    for h0 in range(0, cfg["heads"], HEAD_BLOCK):
        hs = slice(h0, h0 + HEAD_BLOCK)
        q = queries(x, w_q[:, hs], w["self_attn.q_norm.weight"],
                    static=static)
        k_nope, v = up_project(latent, w_ukv[:, hs], static=static)
        heads.append(jnp.concatenate(
            [attend_block(q[r0:r0 + BLOCK], r0, k_nope, v,
                          latent[:, cfg["latent"]:], static=static)
             for r0 in range(0, L, BLOCK)], axis=0))
    a = jnp.concatenate(heads, axis=1)
    return attention_out(h, a, w["self_attn.o_proj.weight"],
                         static=static), latent


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h_rows, g, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h_rows, g.astype(jnp.float32), eps) \
            @ lm_head.astype(jnp.float32)


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, latents, margins)` of the full
    forward over `tokens` (1-D int array): the logits at positions `rows`;
    per layer the `[n, 576]` latent rows a cache must hold (numpy); per
    expert layer the `[n]` router margins (numpy)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end so that a few lengths share the compiled pieces
    # (and the persistent compile cache serves the next run's lengths too);
    # no earlier row sees the padding (causal attention, per-token MLPs)
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    static = _static(config)
    h = jnp.take(weights["embed_tokens.weight"], tokens, axis=0) \
        .astype(jnp.float32)
    latents, margins = [], []
    for i in range(config["num_hidden_layers"]):
        prefix = f"layers.{i}."
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h, latent = attention(h, w, static)
        latents.append(np.asarray(latent[:n]))
        if i < config["first_k_dense_replace"]:
            h = dense_mlp(h, {k: w[k] for k in (
                "post_attention_layernorm.weight", "mlp.gate_up_proj.weight",
                "mlp.down_proj.weight")}, static=static)
        else:
            h, margin = expert_mlp(h, w, static)
            margins.append(np.asarray(margin[:n]))
    out = head(h[np.asarray(rows)], weights["norm.weight"],
               weights["lm_head.weight"], eps=float(config["rms_norm_eps"]))
    return out, latents, margins


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: published name -> array, any float
    dtype."""
    return forward(config, weights, tokens, rows)[0]
