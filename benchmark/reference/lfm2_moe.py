"""Plain reference of the LFM2 expert block (`model_type` lfm2_moe, LiquidAI
LFM2-8B-A1B: gated short-convolution layers beside rotary grouped-query
attention, a sigmoid router with a selection bias over a whole layer of
experts): `jax.numpy`, float32, matmul precision "highest", the full forward
over a whole sequence — no kernel, no cache, no sort, no grouping, no
batching, independent of `mxnet_tpu`.

    h = embed[tokens]
    per layer i:  h = h + op_i(RMSNorm_operator(h))
                  h = h + ffn_i(RMSNorm_ffn(h))
    logits = RMSNorm_embedding(h) @ embed.T                     (tied head)

* `conv` — `[B, C, x] = u W_in` (three parts of `hidden_size`, in this
  order); `y_t = C_t * sum_k w_k (B x)_{t - (K - 1) + k}`, a causal depthwise
  convolution over time of `K = conv_L_cache` taps a channel, no bias, NO
  activation; `op = y W_out`. Computed a token at a time under `lax.scan`
  from a window of `K - 1` zeros: `window = [carried, (B x)_t]`, `y_t = C_t *
  sum_k window_k w_k`, carry `window[1:]`.
* `full_attention` — `q = u W_q -> [heads, hd]`, `k, v = u W_k, u W_v -> [kv
  heads, hd]`, `hd = hidden_size / num_attention_heads`; `q` and `k` take an
  RMSNorm over each head's `hd` entries (one weight of `hd` each), BEFORE the
  rotation; both are rotated over all `hd` entries (half-split pairing,
  frequencies `rope_theta^(-2d/hd)`); query head `j` reads K/V head `j //
  group`; scores `q . k * hd^-1/2`; a causal softmax; `op = concat_heads(P v)
  W_o`.
* ffn, layer `i < num_dense_layers` — `W_2(silu(W_1 x) * W_3 x)`.
* ffn, else — `s = sigmoid(x W_gate)` over all the experts; the
  `num_experts_per_tok` with the largest `s + expert_bias` (the bias enters
  the selection only, and only under `use_expert_bias`); weights `s_e /
  (sum_chosen s + 1e-6)` (`norm_topk_prob`) times `routed_scaling_factor`; `y =
  sum_e w_e E_e(x)`, every expert `W_2(silu(W_1 x) * W_3 x)`: a plain loop
  over the experts, each applied to every token and weighted (0 for a token
  that did not choose it). No shared expert.

`forward` also returns what a serving cache must hold: of every `conv`
layer the window after the last token (the last `K - 1` values of `B x`), of
every `full_attention` layer the normalised, rotated keys and the values of
every position, and the margin between the last chosen and the first rejected
`s + expert_bias` of every (token, expert layer), from which the benchmark
counts routing near-ties.

Weights come under published-style names (`embed_tokens.weight`,
`embedding_norm.weight`, `layers.<i>.operator_norm.weight`, `.ffn_norm.weight`,
`.conv.{in_proj,out_proj}.weight`, `.conv.conv.weight`,
`.self_attn.{q,k,v,out}_proj.weight`, `.self_attn.{q,k}_layernorm.weight`,
`.feed_forward.{w1,w2,w3}.weight`, `.feed_forward.gate.weight`,
`.feed_forward.expert_bias`, `.feed_forward.experts.<e>.{w1,w2,w3}.weight`: the
configuration file's `assumed.names`; a name is only a key). Matrices arrive
input-major (`x @ W`: the transpose of torch's `Linear.weight`); `conv.conv.
weight` is `[channels, 1, kernel]`. A float32 copy of the model does not fit
beside the serving cache: weights arrive in the served dtype and are upcast a
layer (an expert) at a time; a long sequence goes through attention in blocks
of rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 2048           # sequences are padded to a multiple (of BLOCK too)
BLOCK = 512             # query rows attended at once
ROUTER_EPS = 1e-6       # under the normalisation of the chosen scores
NORMS = ("operator_norm.weight", "ffn_norm.weight")
CONV = ("conv.in_proj.weight", "conv.conv.weight", "conv.out_proj.weight")
ATTENTION = tuple(f"self_attn.{p}_proj.weight" for p in ("q", "k", "v", "out")
                  ) + ("self_attn.q_layernorm.weight",
                       "self_attn.k_layernorm.weight")
DENSE = tuple(f"feed_forward.w{j}.weight" for j in (1, 2, 3))


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotate(x, positions, freqs):
    """Half-split rotary embedding of the last axis of `x` [L, H, dim]."""
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(config):
    """The numbers a layer needs, hashable for `jit`."""
    if config.get("conv_bias", False):
        raise ValueError("the lfm2_moe reference knows no conv_bias")
    hd = config["hidden_size"] // config["num_attention_heads"]
    return (("eps", float(config["norm_eps"])),
            ("heads", int(config["num_attention_heads"])),
            ("kv_heads", int(config["num_key_value_heads"])),
            ("hd", hd),
            ("inv_freq", tuple(
                float(f) for f in float(config["rope_theta"])
                ** (-np.arange(0, hd, 2, dtype=np.float64) / hd))),
            ("top_k", int(config.get("num_experts_per_tok", 0))),
            ("norm_topk", bool(config.get("norm_topk_prob", True))),
            ("scale", float(config.get("routed_scaling_factor", 1.0))),
            ("bias", bool(config.get("use_expert_bias", False))))


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("eps",))
def conv_operator(h, w, length, eps):
    """`(h + op(RMSNorm_operator(h)), window [K - 1, D])` of a `conv` layer:
    the convolution a token at a time; the window is what the scan carries
    after token `length - 1`."""
    w = _f32(w)
    taps = w["conv.conv.weight"][:, 0, :].T                     # [K, D]
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, w["operator_norm.weight"], eps)
        b, c, x = jnp.split(u @ w["conv.in_proj.weight"], 3, axis=-1)
        bx = b * x

        def token(carry, inp):
            window, kept = carry
            t, bx_t, c_t = inp
            window = jnp.concatenate([window, bx_t[None]], axis=0)
            y = c_t * (window * taps).sum(0)
            window = window[1:]
            return (window, jnp.where(t == length - 1, window, kept)), y

        zero = jnp.zeros((taps.shape[0] - 1, h.shape[1]), jnp.float32)
        (_, window), y = jax.lax.scan(
            token, (zero, zero), (jnp.arange(h.shape[0]), bx, c))
        return h + y @ w["conv.out_proj.weight"], window


def _heads(x, w, g, n, positions, cfg):
    """`rotate(RMSNorm_head(x W)) -> [L, n, hd]`."""
    y = rms_norm((x @ w).reshape(x.shape[0], n, -1), g, cfg["eps"])
    return rotate(y, positions, jnp.asarray(cfg["inv_freq"], jnp.float32))


@functools.partial(jax.jit, static_argnames=("static",))
def keys_values(h, w, static):
    """`(k [L, kv heads, hd], v)` of `RMSNorm_operator(h)`: what a serving
    cache keeps of a position (`k` normalised a head and rotated)."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["operator_norm.weight"], cfg["eps"])
        k = _heads(x, w["self_attn.k_proj.weight"],
                   w["self_attn.k_layernorm.weight"], cfg["kv_heads"],
                   jnp.arange(h.shape[0]), cfg)
        v = (x @ w["self_attn.v_proj.weight"]).reshape(
            h.shape[0], cfg["kv_heads"], -1)
    return k, v


@functools.partial(jax.jit, static_argnames=("static",))
def attend_rows(h_rows, first_row, k, v, w, static):
    """`h + attention` for the rows `[first_row, first_row + R)` of the
    sequence over ALL its keys and values, causal: `h_rows` [R, D], `k` and
    `v` [L, kv heads, hd]."""
    cfg = dict(static)
    w = _f32(w)
    r = h_rows.shape[0]
    group = cfg["heads"] // cfg["kv_heads"]
    rows = first_row + jnp.arange(r)
    seen = jnp.arange(k.shape[0])[None, :] <= rows[:, None]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h_rows, w["operator_norm.weight"], cfg["eps"])
        q = _heads(x, w["self_attn.q_proj.weight"],
                   w["self_attn.q_layernorm.weight"], cfg["heads"], rows, cfg)

        def one_kv_head(qkv):
            qj, kj, vj = qkv            # [R, G, hd], [L, hd], [L, hd]
            s = jnp.einsum("qgd,kd->gqk", qj, kj) * cfg["hd"] ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vj)

        a = jax.lax.map(one_kv_head, (
            q.reshape(r, cfg["kv_heads"], group, -1).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [kv, R, G, hd]
        a = a.transpose(1, 0, 2, 3).reshape(r, -1)
        return h_rows + a @ w["self_attn.out_proj.weight"]


def attention(h, w, static):
    """`(h + attn(RMSNorm_operator(h)), k, v)`, a block of query rows at a
    time so that a long sequence fits."""
    k, v = keys_values(h, w, static=static)
    h = jnp.concatenate(
        [attend_rows(h[r0:r0 + BLOCK], r0, k, v, w, static=static)
         for r0 in range(0, h.shape[0], BLOCK)], axis=0)
    return h, k, v


def _gated(x, w1, w2, w3):
    return (jax.nn.silu(x @ w1.astype(jnp.float32))
            * (x @ w3.astype(jnp.float32))) @ w2.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_ffn(h, w, eps):
    """`h + ffn(RMSNorm_ffn(h))` of a dense layer."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["ffn_norm.weight"].astype(jnp.float32), eps)
        return h + _gated(x, w["feed_forward.w1.weight"],
                          w["feed_forward.w2.weight"],
                          w["feed_forward.w3.weight"])


@functools.partial(jax.jit, static_argnames=("static",))
def route(h, w, static):
    """`(x, chosen [L, k] expert ids, weights [L, k], margin [L])`: sigmoid
    scores, selection by score + bias, weights from the scores; `margin` is
    the distance between the last chosen and the first rejected score +
    bias."""
    cfg = dict(static)
    w = _f32(w)
    k = cfg["top_k"]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["ffn_norm.weight"], cfg["eps"])
        s = jax.nn.sigmoid(x @ w["feed_forward.gate.weight"])
    biased = s + w["feed_forward.expert_bias"] if cfg["bias"] else s
    order = jnp.argsort(-biased, axis=-1)
    chosen = order[:, :k]
    ranked = jnp.take_along_axis(biased, order[:, :k + 1], -1)
    weights = jnp.take_along_axis(s, chosen, -1)
    if cfg["norm_topk"]:
        weights = weights / (weights.sum(-1, keepdims=True) + ROUTER_EPS)
    return x, chosen, weights * cfg["scale"], ranked[:, -2] - ranked[:, -1]


@jax.jit
def expert_add(y, x, weight_of_token, w1, w2, w3):
    """`y + weight_of_token[:, None] * E(x)`: one expert over every token,
    weighted (0 for a token that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        return y + weight_of_token[:, None] * _gated(x, w1, w2, w3)


def expert_ffn(h, weights, prefix, config, static):
    """The expert layer: every expert in a plain loop. Returns `(h,
    margin)`."""
    names = ["ffn_norm.weight", "feed_forward.gate.weight"] \
        + ["feed_forward.expert_bias"] * bool(dict(static)["bias"])
    x, chosen, gates, margin = route(
        h, {n: weights[prefix + n] for n in names}, static=static)
    y = jnp.zeros_like(x)
    for e in range(config["num_experts"]):
        of_token = jnp.where(chosen == e, gates, 0.0).sum(-1)
        y = expert_add(y, x, of_token, *(
            weights[f"{prefix}feed_forward.experts.{e}.w{j}.weight"]
            for j in (1, 2, 3)))
    return h + y, margin


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h_rows, g, embed, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h_rows, g.astype(jnp.float32), eps) \
            @ embed.astype(jnp.float32).T


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, (windows, kv), margins)` of the
    full forward over `tokens` (1-D int array): the logits at positions
    `rows`; what a cache must hold — per `conv` layer the `[K - 1, D]`
    window after the last token, per `full_attention` layer the `[n, 2, kv
    heads, hd]` keys and values; per EXPERT layer the `[n]` router margins
    (all numpy, in layer order). The first `num_hidden_layers` of
    `layer_types` are built."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end so that a few lengths share the compiled pieces; no
    # earlier row sees the padding (causal attention, a causal convolution,
    # per-token MLPs), and the window is taken at the last real token
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    static = _static(config)
    eps = float(config["norm_eps"])
    h = jnp.take(weights["embed_tokens.weight"], tokens, axis=0) \
        .astype(jnp.float32)
    windows, kv, margins = [], [], []
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        prefix = f"layers.{i}."
        if kind == "conv":
            h, window = conv_operator(
                h, {m: weights[prefix + m] for m in CONV + NORMS[:1]}, n,
                eps=eps)
            windows.append(np.asarray(window))
        elif kind == "full_attention":
            h, k, v = attention(
                h, {m: weights[prefix + m] for m in ATTENTION + NORMS[:1]},
                static)
            kv.append(np.stack([np.asarray(k[:n]), np.asarray(v[:n])],
                               axis=1))
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        if i < config["num_dense_layers"]:
            h = dense_ffn(h, {m: weights[prefix + m]
                              for m in DENSE + NORMS[1:]}, eps=eps)
        else:
            h, margin = expert_ffn(h, weights, prefix, config, static)
            margins.append(np.asarray(margin[:n]))
    out = head(h[np.asarray(rows)], weights["embedding_norm.weight"],
               weights["embed_tokens.weight"], eps=eps)
    return out, (windows, kv), margins


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: a mapping published name -> array, any
    float dtype, asked by key a layer (an expert) at a time (it may cut an
    array out of a fused one when asked)."""
    return forward(config, weights, tokens, rows)[0]
