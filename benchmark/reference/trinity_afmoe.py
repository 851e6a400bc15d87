"""Plain reference of the `afmoe` block (Arcee Trinity-Large-Preview: gated,
QK-normed grouped-query attention over window and full layers, four norms a
layer, leading dense layers, a sigmoid router with a selection bias beside a
shared expert): `jax.numpy`, float32, matmul precision "highest", the full
forward over a whole sequence — no kernel, no cache, no ring, no sort, no
grouping, independent of `mxnet_tpu`.

    h = embed[tokens] * sqrt(hidden_size)                       (mup_enabled)
    per layer i:  a = attn_i(RMSNorm_in(h));      h += RMSNorm_post_attn(a)
                  m = mlp_i(RMSNorm_pre_mlp(h));  h += RMSNorm_post_mlp(m)
    logits = RMSNorm(h) @ lm_head

* attention — `q = x W_q -> [48, 128]`, `k = x W_k`, `v = x W_v -> [8, 128]`,
  `g = x W_gate -> [48 x 128]`; `q` and `k` take an RMSNorm over each head's
  128 entries (one weight vector each); a `sliding_attention` layer then
  rotates `q` and `k` over all 128 entries (half-split pairing, frequencies
  `rope_theta^(-2d/128)`), a `full_attention` layer uses NO positions; query
  head `j` reads K/V head `j // 6`; scores `q . k * 128^-1/2`; a softmax over
  the keys the layer's mask admits — `sliding_attention`: keys `(p -
  sliding_window, p]`; `full_attention`: every key at or before `p` —; `out =
  (concat_heads(P v) * sigmoid(g)) W_o`. Window layers are computed as full
  attention under a mask.
* dense MLP (layer `i < num_dense_layers`) — `W_down(silu(x W_gate) * x
  W_up)`.
* expert layer — `s = sigmoid(x W_r)` over all the experts; the
  `num_experts_per_tok` with the largest `s + expert_bias` (the bias enters
  the selection only); weights `s_e / (sum_chosen s + 1e-20)` (`route_norm`)
  times `route_scale`; `y = sum_e w_e E_e(x) + E_shared(x)`, every expert
  SiLU-gated: a plain loop over the held experts, each applied to every token
  and weighted (0 for a token that did not choose it).

**The share.** The router is as wide as the published model (`published.
num_experts`, else `num_experts`); `weights` hold only the experts
`[share.expert_first, share.expert_first + num_experts)` and what the absent
experts would add is left out; the shared expert is whole on every chip. With
every expert held this is the uncut layer.

`forward` also returns what a serving cache must hold of every position — the
normalised (and, in a window layer, rotated) keys and the values of every
layer, 2 x 8 x 128 numbers a layer — and the margin between the last chosen
and the first rejected `s + expert_bias` of every (token, expert layer), from
which the benchmark counts routing near-ties.

Weights come under the family's published names with matrices input-major (`x
@ W`); a gated MLP's gate and up projections are fused along the output axis
(gate first) and the held experts are stacked on a leading axis — departures
of storage that the configuration file lists. A float32 copy of the model does
not fit beside the serving cache: weights arrive in the served dtype and are
upcast one layer (one expert) at a time, and a long sequence goes through
attention and the dense MLP in blocks of rows.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 2048           # sequences are padded to a multiple (of BLOCK too)
BLOCK = 512             # rows attended (or through the dense MLP) at once
FULL, WINDOW = "full_attention", "sliding_attention"


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def rotate(x, positions, freqs):
    """Half-split rotary embedding of the last axis of `x` [L, H, dim]."""
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) * freqs[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _static(config, kind):
    """The numbers a layer of `kind` needs, hashable for `jit`."""
    if config.get("rope_scaling") is not None:
        raise ValueError("the afmoe reference knows no rope_scaling")
    hd = int(config["head_dim"])
    return (("eps", float(config["rms_norm_eps"])),
            ("heads", int(config["num_attention_heads"])),
            ("kv_heads", int(config["num_key_value_heads"])),
            ("hd", hd),
            ("window", int(config["sliding_window"])
             if kind == WINDOW else None),
            ("inv_freq", tuple(
                float(f) for f in float(config["rope_theta"])
                ** (-np.arange(0, hd, 2, dtype=np.float64) / hd))
             if kind == WINDOW else None),
            ("top_k", int(config["num_experts_per_tok"])),
            ("route_norm", bool(config.get("route_norm", True))),
            ("route_scale", float(config.get("route_scale", 1.0))),
            ("first", int(config.get("share", {}).get("expert_first", 0))))


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def _heads(x, w, g, n, positions, cfg):
    """`RMSNorm_head(x W) -> [L, n, 128]`, rotated where the layer does."""
    y = rms_norm((x @ w).reshape(x.shape[0], n, -1), g, cfg["eps"])
    if cfg["inv_freq"] is None:
        return y
    return rotate(y, positions, jnp.asarray(cfg["inv_freq"], jnp.float32))


@functools.partial(jax.jit, static_argnames=("static",))
def keys_values(h, w, static):
    """`(k [L, 8, 128], v [L, 8, 128])` of `RMSNorm_in(h)`: what a serving
    cache keeps of a position (`k` normalised a head, rotated in a window
    layer)."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["input_layernorm.weight"], cfg["eps"])
        k = _heads(x, w["self_attn.k_proj.weight"],
                   w["self_attn.k_norm.weight"], cfg["kv_heads"],
                   jnp.arange(h.shape[0]), cfg)
        v = (x @ w["self_attn.v_proj.weight"]).reshape(
            h.shape[0], cfg["kv_heads"], -1)
    return k, v


@functools.partial(jax.jit, static_argnames=("static",))
def attend_rows(h_rows, first_row, k, v, w, static):
    """`h + RMSNorm_post_attn(attention)` for the rows `[first_row,
    first_row + R)` of the sequence over ALL its keys and values under the
    layer's mask: `h_rows` [R, D], `k` and `v` [L, 8, 128]."""
    cfg = dict(static)
    w = _f32(w)
    r = h_rows.shape[0]
    group = cfg["heads"] // cfg["kv_heads"]
    rows = first_row + jnp.arange(r)
    keys = jnp.arange(k.shape[0])
    seen = keys[None, :] <= rows[:, None]
    if cfg["window"] is not None:
        seen &= keys[None, :] > rows[:, None] - cfg["window"]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h_rows, w["input_layernorm.weight"], cfg["eps"])
        q = _heads(x, w["self_attn.q_proj.weight"],
                   w["self_attn.q_norm.weight"], cfg["heads"], rows, cfg)

        def one_kv_head(qkv):
            qj, kj, vj = qkv            # [R, G, 128], [L, 128], [L, 128]
            s = jnp.einsum("qgd,kd->gqk", qj, kj) * cfg["hd"] ** -0.5
            s = jnp.where(seen[None], s, -jnp.inf)
            return jnp.einsum("gqk,kd->qgd", jax.nn.softmax(s, axis=-1), vj)

        a = jax.lax.map(one_kv_head, (
            q.reshape(r, cfg["kv_heads"], group, -1).transpose(1, 0, 2, 3),
            k.transpose(1, 0, 2), v.transpose(1, 0, 2)))    # [8, R, G, 128]
        a = a.transpose(1, 0, 2, 3).reshape(r, -1)
        a = a * jax.nn.sigmoid(x @ w["self_attn.gate_proj.weight"])
        out = a @ w["self_attn.o_proj.weight"]
        return h_rows + rms_norm(out, w["post_attention_layernorm.weight"],
                                 cfg["eps"])


ATTENTION = ("input_layernorm.weight", "post_attention_layernorm.weight",
             "self_attn.q_proj.weight", "self_attn.k_proj.weight",
             "self_attn.v_proj.weight", "self_attn.gate_proj.weight",
             "self_attn.o_proj.weight", "self_attn.q_norm.weight",
             "self_attn.k_norm.weight")


def attention(h, w, static):
    """`(h + RMSNorm_post_attn(attention(RMSNorm_in(h))), k [L, 8, 128], v
    [L, 8, 128])`, a block of query rows at a time so that a long sequence
    fits."""
    w = {n: w[n] for n in ATTENTION}
    k, v = keys_values(h, w, static=static)
    h = jnp.concatenate(
        [attend_rows(h[r0:r0 + BLOCK], r0, k, v, w, static=static)
         for r0 in range(0, h.shape[0], BLOCK)], axis=0)
    return h, k, v


def _gated(x, w_in, w_out):
    g, u = jnp.split(x @ w_in.astype(jnp.float32), 2, axis=-1)
    return (jax.nn.silu(g) * u) @ w_out.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps",))
def dense_rows(h_rows, w, eps):
    """`h + RMSNorm_post_mlp(mlp(RMSNorm_pre_mlp(h)))` of a dense layer."""
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h_rows, w["pre_mlp_layernorm.weight"]
                     .astype(jnp.float32), eps)
        y = _gated(x, w["mlp.gate_up_proj.weight"], w["mlp.down_proj.weight"])
        return h_rows + rms_norm(
            y, w["post_mlp_layernorm.weight"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("static",))
def route(h, w, static):
    """`(x, chosen [L, k] expert ids of the whole router, weights [L, k],
    margin [L])`: sigmoid scores, selection by score + bias, weights from the
    scores; `margin` is the distance between the last chosen and the first
    rejected score + bias."""
    cfg = dict(static)
    w = _f32(w)
    k = cfg["top_k"]
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h, w["pre_mlp_layernorm.weight"], cfg["eps"])
        s = jax.nn.sigmoid(x @ w["mlp.router.gate.weight"])
    biased = s + w["mlp.expert_bias"]
    order = jnp.argsort(-biased, axis=-1)
    chosen = order[:, :k]
    ranked = jnp.take_along_axis(biased, order[:, :k + 1], -1)
    weights = jnp.take_along_axis(s, chosen, -1)
    if cfg["route_norm"]:
        weights = weights / (weights.sum(-1, keepdims=True) + 1e-20)
    return x, chosen, weights * cfg["route_scale"], \
        ranked[:, -2] - ranked[:, -1]


@jax.jit
def expert_add(y, x, weight_of_token, w_in, w_out):
    """`y + weight_of_token[:, None] * E(x)`: one expert over every token,
    weighted (0 for a token that did not choose it)."""
    with jax.default_matmul_precision("highest"):
        return y + weight_of_token[:, None] * _gated(x, w_in, w_out)


@functools.partial(jax.jit, static_argnames=("eps",))
def add_post_mlp(h, y, g, eps):
    return h + rms_norm(y, g.astype(jnp.float32), eps)


def expert_mlp(h, w, static):
    """The expert layer: the held experts in a plain loop, the shared expert
    once with weight 1. Returns `(h, margin)`."""
    cfg = dict(static)
    x, chosen, weights, margin = route(
        h, {n: w[n] for n in ("pre_mlp_layernorm.weight",
                              "mlp.router.gate.weight", "mlp.expert_bias")},
        static=static)
    y = jnp.zeros_like(x)
    w_in, w_out = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]
    for e in range(w_in.shape[0]):
        of_token = jnp.where(chosen == cfg["first"] + e, weights, 0.0).sum(-1)
        y = expert_add(y, x, of_token, w_in[e], w_out[e])
    if "mlp.shared_experts.gate_up_proj.weight" in w:
        y = expert_add(y, x, jnp.ones(x.shape[0], jnp.float32),
                       w["mlp.shared_experts.gate_up_proj.weight"],
                       w["mlp.shared_experts.down_proj.weight"])
    return add_post_mlp(h, y, w["post_mlp_layernorm.weight"],
                        eps=cfg["eps"]), margin


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h_rows, g, lm_head, eps):
    with jax.default_matmul_precision("highest"):
        return rms_norm(h_rows, g.astype(jnp.float32), eps) \
            @ lm_head.astype(jnp.float32)


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, kv, margins)` of the full forward
    over `tokens` (1-D int array): the logits at positions `rows`; per layer
    the `[n, 2, 8, 128]` keys and values a cache must hold (numpy); per
    EXPERT layer the `[n]` router margins (numpy)."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end so that a few lengths share the compiled pieces; no
    # earlier row sees the padding (causal attention, per-token MLPs)
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    eps = float(config["rms_norm_eps"])
    h = jnp.take(weights["embed_tokens.weight"], tokens, axis=0) \
        .astype(jnp.float32)
    if config.get("mup_enabled", False):
        h = h * float(config["hidden_size"]) ** 0.5
    kv, margins = [], []
    for i in range(config["num_hidden_layers"]):
        static = _static(config, config["layer_types"][i])
        prefix = f"layers.{i}."
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h, k, v = attention(h, w, static)
        kv.append(np.stack([np.asarray(k[:n]), np.asarray(v[:n])], axis=1))
        if i < config.get("num_dense_layers", 0):
            mlp = {m: w[m] for m in (
                "pre_mlp_layernorm.weight", "post_mlp_layernorm.weight",
                "mlp.gate_up_proj.weight", "mlp.down_proj.weight")}
            h = jnp.concatenate([dense_rows(h[r0:r0 + BLOCK], mlp, eps=eps)
                                 for r0 in range(0, L, BLOCK)], axis=0)
        else:
            h, margin = expert_mlp(h, w, static)
            margins.append(np.asarray(margin[:n]))
    out = head(h[np.asarray(rows)], weights["norm.weight"],
               weights["lm_head.weight"], eps=eps)
    return out, kv, margins


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: published name -> array, any float
    dtype."""
    return forward(config, weights, tokens, rows)[0]
