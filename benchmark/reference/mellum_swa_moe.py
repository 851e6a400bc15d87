"""Plain reference of the `mellum` block (Mellum2-12B-A2.5B: window and full
attention layers mixed over a softmax-routed expert MLP): `jax.numpy`,
float32, matmul precision "highest", the full forward over a whole sequence —
no kernel, no cache, no ring, no sort, no grouping, independent of
`mxnet_tpu`.

    h = embed[tokens]
    per layer i:  h += attn_i(RMSNorm(h));  h += moe_i(RMSNorm(h))
    logits = RMSNorm(h) @ lm_head

* attention — `q = x W_q -> [32, 128]`, `k = x W_k`, `v = x W_v -> [4, 128]`;
  `q` and `k` rotated over all 128 entries (half-split pairing; no norm on
  either: the configuration's `assumed`); query head `j` reads K/V head `j //
  8`; scores `q . k * 128^-1/2`; a softmax over the keys the layer's mask
  admits — `sliding_attention`: keys `(p - sliding_window, p]`, frequencies
  `theta^(-2d/128)`; `full_attention`: every key at or before `p`, YaRN's
  blended frequencies, cos and sin times `attention_factor` —; `out = (P v)
  W_o`. Window layers are computed as full attention under a mask.
* expert layer — `p = softmax(x W_g)` over all the experts, the
  `num_experts_per_tok` largest, weights `p_e / sum_chosen p`
  (`norm_topk_prob`), `y = sum_e w_e E_e(x)`, `E_e` SiLU-gated: a plain loop
  over the held experts, each applied to every token and weighted (0 for a
  token that did not choose it).

**The share.** The router is as wide as the published model (`published.
num_experts`, else `num_experts`); `weights` hold only the experts
`[share.expert_first, share.expert_first + num_experts)` and what the absent
experts would add is left out. With every expert held (the benchmark's
configuration) this is the uncut layer.

`forward` also returns what a serving cache must hold of every position — the
rotated keys and the values of every layer, 2 x 4 x 128 numbers a layer — and
the margin between the 8th and 9th router probability of every (token, layer),
from which the benchmark counts routing near-ties.

Weights come under the family's published names with matrices input-major (`x
@ W`); an expert's gate and up projections are fused along the output axis
(gate first) and the held experts are stacked on a leading axis — departures
of storage that the configuration file lists. A float32 copy of the model does
not fit beside the serving cache: weights arrive in the served dtype and are
upcast one layer (one expert) at a time; a long sequence is attended in blocks
of query rows, one K/V head's queries at a time.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 2048           # sequences are padded to a multiple (of BLOCK too)
BLOCK = 512             # query rows attended at once
FULL, WINDOW = "full_attention", "sliding_attention"


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def inv_freq(dim, rope):
    """Inverse frequencies of a head's `dim` rotary entries: `theta^(-2i /
    dim)` for `rope_type` default; for yarn those where a frequency turns more
    than `beta_fast` times over the original context, those divided by
    `factor` where fewer than `beta_slow`, a linear blend between the two
    correction dims (truncated to integers, the implementation's default)."""
    theta = rope["rope_theta"]
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if rope.get("rope_type", "default") != "yarn":
        return extra
    orig = rope["original_max_position_embeddings"]

    def correction_dim(rotations):
        return dim * math.log(orig / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(rope["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(rope["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / rope["factor"] * ramp + extra * (1 - ramp)


def amplitude(rope):
    """What cos and sin are multiplied by: yarn's `attention_factor` (`0.1
    ln(factor) + 1` where none is given), else 1."""
    if rope.get("rope_type", "default") != "yarn":
        return 1.0
    return float(rope.get("attention_factor")
                 or 0.1 * math.log(rope["factor"]) + 1.0)


def rotate(x, positions, freqs, amp):
    """Half-split rotary embedding of the last axis of `x` [L, H, dim]."""
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos = (jnp.cos(angle) * amp)[:, None, :]
    sin = (jnp.sin(angle) * amp)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(config, kind):
    """The numbers a layer of `kind` needs, hashable for `jit`."""
    rope = config["rope_parameters"][kind]
    return (("eps", float(config["rms_norm_eps"])),
            ("heads", int(config["num_attention_heads"])),
            ("kv_heads", int(config["num_key_value_heads"])),
            ("hd", int(config["head_dim"])),
            ("window", int(config["sliding_window"])
             if kind == WINDOW else None),
            ("inv_freq", tuple(float(f) for f in
                               inv_freq(config["head_dim"], rope))),
            ("amplitude", amplitude(rope)),
            ("top_k", int(config["num_experts_per_tok"])),
            ("norm_topk", bool(config.get("norm_topk_prob", True))),
            ("first", int(config.get("share", {}).get("expert_first", 0))))


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("static",))
def project(h, w, static):
    """`(q [L, 32, 128], k [L, 4, 128], v [L, 4, 128])` of `RMSNorm(h)`, `q`
    and `k` rotated: `k` and `v` are what a serving cache keeps of a
    position."""
    cfg = dict(static)
    w = _f32(w)
    L = h.shape[0]
    pos = jnp.arange(L)
    freqs = jnp.asarray(cfg["inv_freq"], jnp.float32)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["input_layernorm.weight"], cfg["eps"])
        q = (x @ w["self_attn.q_proj.weight"]).reshape(L, cfg["heads"], -1)
        k = (x @ w["self_attn.k_proj.weight"]).reshape(L, cfg["kv_heads"], -1)
        v = (x @ w["self_attn.v_proj.weight"]).reshape(L, cfg["kv_heads"], -1)
    return (rotate(q, pos, freqs, cfg["amplitude"]),
            rotate(k, pos, freqs, cfg["amplitude"]), v)


@functools.partial(jax.jit, static_argnames=("static",))
def attend_block(q_rows, first_row, k, v, static):
    """Attention of the query rows `[first_row, first_row + R)` of ONE K/V
    head's queries over the whole sequence's keys under the layer's mask:
    `q_rows` [R, G, 128], `k` and `v` [L, 128]. Returns [R, G, 128]."""
    cfg = dict(static)
    with jax.default_matmul_precision("highest"):
        s = jnp.einsum("qgd,kd->gqk", q_rows, k) * cfg["hd"] ** -0.5
        rows = first_row + jnp.arange(q_rows.shape[0])
        keys = jnp.arange(k.shape[0])
        seen = keys[None, :] <= rows[:, None]
        if cfg["window"] is not None:
            seen &= keys[None, :] > rows[:, None] - cfg["window"]
        s = jnp.where(seen[None], s, -jnp.inf)
        return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), v)


@jax.jit
def attention_out(h, a, w_o):
    with jax.default_matmul_precision("highest"):
        return h + a.reshape(a.shape[0], -1) @ w_o.astype(jnp.float32)


def attention(h, w, static):
    """`(h + attention(RMSNorm(h)), k [L, 4, 128], v [L, 4, 128])`, attended
    in blocks of query rows, one K/V head's queries at a time, so that a long
    sequence fits."""
    cfg = dict(static)
    L = h.shape[0]
    q, k, v = project(
        h, {n: w[n] for n in ("input_layernorm.weight",
                              "self_attn.q_proj.weight",
                              "self_attn.k_proj.weight",
                              "self_attn.v_proj.weight")}, static=static)
    group = cfg["heads"] // cfg["kv_heads"]
    heads = []
    for j in range(cfg["kv_heads"]):
        qj = q[:, j * group:(j + 1) * group]
        heads.append(jnp.concatenate(
            [attend_block(qj[r0:r0 + BLOCK], r0, k[:, j], v[:, j],
                          static=static) for r0 in range(0, L, BLOCK)],
            axis=0))
    a = jnp.concatenate(heads, axis=1)
    return attention_out(h, a, w["self_attn.o_proj.weight"]), k, v


@functools.partial(jax.jit, static_argnames=("static",))
def route(h, w, static):
    """`(x, chosen [L, k] expert ids of the whole router, weights [L, k],
    margin [L])`: plain top-k of the softmax probabilities; `margin` is the
    distance between the last chosen and the first rejected probability."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["post_attention_layernorm.weight"], cfg["eps"])
        p = jax.nn.softmax(x @ w["mlp.gate.weight"], axis=-1)
    order = jnp.argsort(-p, axis=-1)
    chosen = order[:, :cfg["top_k"]]
    ranked = jnp.take_along_axis(p, order[:, :cfg["top_k"] + 1], -1)
    weights = ranked[:, :-1]
    if cfg["norm_topk"]:
        weights = weights / weights.sum(-1, keepdims=True)
    return x, chosen, weights, ranked[:, -2] - ranked[:, -1]


@jax.jit
def expert_add(y, x, weight_of_token, w_in, w_out):
    """`y + weight_of_token[:, None] * E(x)`: one expert over every token,
    weighted (0 for a token that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        g, u = jnp.split(x @ w_in.astype(jnp.float32), 2, axis=-1)
        return y + weight_of_token[:, None] * (
            (jax.nn.silu(g) * u) @ w_out.astype(jnp.float32))


def expert_mlp(h, w, static):
    """The expert layer: the held experts in a plain loop. Returns `(h,
    margin)`."""
    cfg = dict(static)
    x, chosen, weights, margin = route(
        h, {n: w[n] for n in ("post_attention_layernorm.weight",
                              "mlp.gate.weight")}, static=static)
    y = jnp.zeros_like(x)
    w_in, w_out = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]
    for e in range(w_in.shape[0]):
        of_token = jnp.where(chosen == cfg["first"] + e, weights, 0.0).sum(-1)
        y = expert_add(y, x, of_token, w_in[e], w_out[e])
    return h + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h_rows, g, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h_rows, g.astype(jnp.float32), eps) \
            @ lm_head.astype(jnp.float32)


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, kv, margins)` of the full forward
    over `tokens` (1-D int array): the logits at positions `rows`; per layer
    the `[n, 2, 4, 128]` rotated keys and values a cache must hold (numpy);
    per layer the `[n]` router margins (numpy)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end so that a few lengths share the compiled pieces; no
    # earlier row sees the padding (causal attention, per-token MLPs)
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    h = jnp.take(weights["embed_tokens.weight"], tokens, axis=0) \
        .astype(jnp.float32)
    kv, margins = [], []
    for i in range(config["num_hidden_layers"]):
        static = _static(config, config["layer_types"][i])
        prefix = f"layers.{i}."
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h, k, v = attention(h, w, static)
        kv.append(np.stack([np.asarray(k[:n]), np.asarray(v[:n])], axis=1))
        h, margin = expert_mlp(h, w, static)
        margins.append(np.asarray(margin[:n]))
    out = head(h[np.asarray(rows)], weights["norm.weight"],
               weights["lm_head.weight"], eps=float(config["rms_norm_eps"]))
    return out, kv, margins


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: published name -> array, any float
    dtype."""
    return forward(config, weights, tokens, rows)[0]
