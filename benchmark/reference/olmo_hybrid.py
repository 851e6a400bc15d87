"""Plain reference of the Olmo-Hybrid block (`model_type` olmo_hybrid):
`jax.numpy`, float32, matmul precision "highest", the full forward over a
whole sequence — no kernels, no cache, no chunks, no batching, independent
of `mxnet_tpu`.

    h = embed[tokens]                               (no multiplier, no positions)
    full_attention layer:    h = h + RMSNorm_post_attn(attn(h))
                             h = h + RMSNorm_post_ff(mlp(h))
    linear_attention layer:  h = h + gdn(RMSNorm_attn(h))
                             h = h + mlp(RMSNorm_ff(h))
    logits = RMSNorm_f(h) @ head                    (untied)
    mlp(x) = W_down(silu(x W_gate) * x W_up)

* `full_attention` — `q, k, v = x W_q, x W_k, x W_v`; `q` and `k` through an
  RMSNorm over the WHOLE projected vector (one weight of `hidden_size`
  each), then `num_attention_heads` heads; NO rotary (`rope_theta` null);
  causal softmax, scores times `head_dim ** -0.5`; `concat(P v) W_o`.
* `linear_attention` — the gated delta rule (Gated DeltaNet,
  arXiv:2412.06464): `q, k -> [H, dk]`, `v -> [H, dv]`, each stream through
  its OWN causal depthwise convolution of width `linear_conv_kernel_dim` (no
  bias) and SiLU; `beta = 2 sigmoid(x W_b)` a head (`linear_allow_neg_eigval`;
  else `sigmoid`); `alpha = exp(-exp(A_log) softplus(x W_a + dt_bias))` a
  head; `q <- q / |q| * dk ** -0.5`, `k <- k / |k|` a head. A head's state `S
  [dk, dv]`, zero at the start, moves a token at a time under `lax.scan`:

      S' = alpha_t S;  u_t = v_t - S'^T k_t;  S = S' + beta_t k_t u_t^T;
      o_t = S^T q_t

  and `out = concat(RMSNorm_o(o_t) * silu(x W_g)) W_o`, `RMSNorm_o` over
  each head's `dv` with one weight of `dv`.

Weights come under published-style names (`embed_tokens.weight`,
`lm_head.weight`, `layers.<i>.linear_attn.q_proj.weight`, ...: the
configuration file's `assumed.names`). Matrices arrive input-major (`x @ W`:
the transpose of torch's `Linear.weight`); a `conv1d.weight` is `[channels,
1, kernel]`. A float32 copy of the model does not fit beside the serving
cache, so weights arrive in the dtype they are served in and are upcast one
layer at a time, inside a jitted layer function that all layers of a kind
share; the head is multiplied a block of the vocabulary at a time.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 512
MLP_WEIGHTS = tuple(f"mlp.{p}_proj.weight" for p in ("gate", "up", "down"))
# a layer's weights by its kind: what `forward` asks `weights` for, by key
LAYER_WEIGHTS = {
    "full_attention": MLP_WEIGHTS + (
        "post_attention_layernorm.weight",
        "post_feedforward_layernorm.weight", "self_attn.q_norm.weight",
        "self_attn.k_norm.weight") + tuple(
            f"self_attn.{p}_proj.weight" for p in "qkvo"),
    "linear_attention": MLP_WEIGHTS + (
        "input_layernorm.weight", "pre_feedforward_layernorm.weight",
        "linear_attn.A_log", "linear_attn.dt_bias",
        "linear_attn.o_norm.weight") + tuple(
            f"linear_attn.{p}_proj.weight" for p in "qkvgbao") + tuple(
            f"linear_attn.{p}_conv1d.weight" for p in "qkv")}
HEAD_BLOCKS = 8         # the head's columns are upcast an eighth at a time
L2_EPS = 1e-6           # under the root of q's and k's norm a head


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def l2_norm(x):
    return x * jax.lax.rsqrt((x * x).sum(-1, keepdims=True) + L2_EPS)


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def mlp(x, w):
    return (jax.nn.silu(x @ w["mlp.gate_proj.weight"])
            * (x @ w["mlp.up_proj.weight"])) @ w["mlp.down_proj.weight"]


def attention_mixer(x, w, cfg):
    """Returns `(out [L, D], kv [L, 2, H, hd])`: the keys (normalised) and
    values a cache would keep."""
    L = x.shape[0]
    nh = cfg["heads"]
    q = rms_norm(x @ w["self_attn.q_proj.weight"],
                 w["self_attn.q_norm.weight"], cfg["eps"])
    k = rms_norm(x @ w["self_attn.k_proj.weight"],
                 w["self_attn.k_norm.weight"], cfg["eps"])
    v = x @ w["self_attn.v_proj.weight"]
    hd = q.shape[1] // nh
    q, k, v = (t.reshape(L, nh, hd) for t in (q, k, v))
    s = jnp.einsum("qhd,khd->hqk", q, k) * hd ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(L, nh * hd) @ w["self_attn.o_proj.weight"], \
        jnp.stack([k, v], axis=1)


def _conv_silu(x, w):
    """Causal depthwise convolution, no bias, then SiLU: `x` [L, C], `w`
    `[C, 1, K]`; `out[t] = sum_j w[c, j] x[t - (K - 1) + j]`."""
    kc = w.shape[-1]
    padded = jnp.pad(x, ((kc - 1, 0), (0, 0)))
    return jax.nn.silu(sum(padded[j:j + x.shape[0]] * w[:, 0, j]
                           for j in range(kc)))


def delta_mixer(x, w, cfg, length):
    """Returns `(out [L, D], S [H, dk, dv])`: `S` is the state after token
    `length - 1` (rows past it are padding)."""
    L = x.shape[0]
    nh, dk, dv = cfg["linear_heads"], cfg["dk"], cfg["dv"]
    pre = "linear_attn."
    q, k, v = (_conv_silu(x @ w[pre + f"{s}_proj.weight"],
                          w[pre + f"{s}_conv1d.weight"]) for s in "qkv")
    q = l2_norm(q.reshape(L, nh, dk)) * dk ** -0.5
    k = l2_norm(k.reshape(L, nh, dk))
    v = v.reshape(L, nh, dv)
    beta = jax.nn.sigmoid(x @ w[pre + "b_proj.weight"])         # [L, nh]
    if cfg["neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(w[pre + "A_log"]) * jax.nn.softplus(
        x @ w[pre + "a_proj.weight"] + w[pre + "dt_bias"]))     # [L, nh]

    def step(state, inp):
        t, a_t, b_t, q_t, k_t, v_t = inp
        kept = a_t[:, None, None] * state                       # [nh,dk,dv]
        u_t = v_t - jnp.einsum("hkv,hk->hv", kept, k_t)
        new = kept + (b_t[:, None] * k_t)[:, :, None] * u_t[:, None, :]
        o_t = jnp.einsum("hkv,hk->hv", new, q_t)
        return jnp.where(t < length, new, state), o_t

    state, o = jax.lax.scan(
        step, jnp.zeros((nh, dk, dv), jnp.float32),
        (jnp.arange(L), alpha, beta, q, k, v))
    o = rms_norm(o, w[pre + "o_norm.weight"], cfg["eps"])       # a head
    o = o.reshape(L, nh * dv) * jax.nn.silu(x @ w[pre + "g_proj.weight"])
    return o @ w[pre + "o_proj.weight"], state


def _static(config):
    return (("eps", float(config["rms_norm_eps"])),
            ("heads", int(config["num_attention_heads"])),
            ("linear_heads", int(config["linear_num_value_heads"])),
            ("dk", int(config["linear_key_head_dim"])),
            ("dv", int(config["linear_value_head_dim"])),
            ("neg_eigval", bool(config["linear_allow_neg_eigval"])))


@functools.partial(jax.jit, static_argnames=("kind", "static"))
def layer(h, w, length, kind, static):
    """One layer of kind `kind` on `h` [L, D] float32; `w` maps the layer's
    weight names (without the `layers.<i>.` prefix) to arrays. Returns `(h,
    kept)`: `kept` is the recurrent state of a `linear_attention` layer, the
    K/V rows `[L, 2, H, hd]` of a `full_attention` layer."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        if kind == "full_attention":
            mixed, kept = attention_mixer(h, w, cfg)
            h = h + rms_norm(mixed, w["post_attention_layernorm.weight"],
                             cfg["eps"])
            return h + rms_norm(mlp(h, w),
                                w["post_feedforward_layernorm.weight"],
                                cfg["eps"]), kept
        if kind == "linear_attention":
            mixed, kept = delta_mixer(
                rms_norm(h, w["input_layernorm.weight"], cfg["eps"]), w, cfg,
                length)
            h = h + mixed
            return h + mlp(rms_norm(h, w["pre_feedforward_layernorm.weight"],
                                    cfg["eps"]), w), kept
        raise ValueError(f"unknown layer type {kind!r}")


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(h_rows, g, eps):
    return rms_norm(h_rows, g.astype(jnp.float32), eps)


@jax.jit
def _head_block(x, w):
    with jax.default_matmul_precision("highest"):
        return x @ w.astype(jnp.float32)


def head(h_rows, g, w, eps):
    x = _normed(h_rows, g, eps)
    vocab = w.shape[1]
    step = -(-vocab // HEAD_BLOCKS)
    return jnp.concatenate([_head_block(x, w[:, at:at + step])
                            for at in range(0, vocab, step)], axis=1)


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, states, kv)` of the full forward
    over `tokens` (1-D int array): the logits at positions `rows`; the
    recurrent state `[H, dk, dv]` of every `linear_attention` layer, in
    layer order, after the last token; the K/V rows `[len(tokens), 2, H,
    hd]` of every `full_attention` layer, in layer order. The first
    `num_hidden_layers` entries of `layer_types` are built."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end to a multiple of PAD_TO so that a few lengths share
    # one compiled layer; no earlier row sees the padding (causal attention,
    # causal convolution, a forward recurrence), and the state is taken at
    # the last real token
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    static = _static(config)
    h = jnp.take(weights["embed_tokens.weight"], tokens,
                 axis=0).astype(jnp.float32)
    states, kv = [], []
    kinds = config["layer_types"][:config["num_hidden_layers"]]
    for i, kind in enumerate(kinds):
        w = {name: weights[f"layers.{i}.{name}"]
             for name in LAYER_WEIGHTS[kind]}
        h, kept = layer(h, w, n, kind=kind, static=static)
        if kind == "linear_attention":
            states.append(kept)
        else:
            kv.append(kept[:n])
    out = head(h[np.asarray(rows)], weights["norm.weight"],
               weights["lm_head.weight"], eps=float(config["rms_norm_eps"]))
    return out, states, kv


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: a mapping name -> array, any float
    dtype, asked by key a layer at a time (it may cut a layer's arrays out of
    fused ones when asked)."""
    return forward(config, weights, tokens, rows)[0]
