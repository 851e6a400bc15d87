"""Plain reference of the Granite 4.0-H block (`model_type` granitemoehybrid
without experts): `jax.numpy`, float32, matmul precision "highest", the full
forward over a whole sequence — no kernels, no cache, no chunks, no batching,
independent of `mxnet_tpu`.

Follows the published model (the `transformers` GraniteMoeHybridModel and the
configuration's keys): `h = embed[tokens] * embedding_multiplier`; every layer
is `h += residual_multiplier * mixer(RMSNorm(h))` then
`h += residual_multiplier * mlp(RMSNorm(h))` with the SiLU-gated "shared" MLP;
`layer_types` says which mixer a layer has:

* `attention` — grouped-query causal softmax attention, no position encoding
  (`position_embedding_type` "nope"), scores times `attention_multiplier`;
* `mamba` — the Mamba-2 mixer: input projection to `[z, xBC, dt]`, causal
  depthwise convolution and SiLU on `xBC = [x, B, C]`, the selective
  state-space recurrence `S_t = exp(dt_t A) S_{t-1} + dt_t x_t (x) B_t`,
  `y_t = S_t C_t + D x_t` per head as a plain sequential `lax.scan` over the
  tokens, gated RMSNorm `RMSNorm(y * silu(z))`, output projection.

Last: `logits = RMSNorm(h) @ embed.T / logits_scaling`.

Weights come under the published names (`embed_tokens.weight`,
`layers.<i>.mamba.in_proj.weight`, ...). Matrices arrive input-major
(`x @ W`: the transpose of torch's `Linear.weight`); `conv1d.weight` is
`[channels, 1, kernel]` as published. A float32 copy of the model does not
fit beside the serving cache, so weights arrive in the dtype they are served
in and are upcast one layer at a time, inside a jitted layer function that
all layers of a kind share.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np

PAD_TO = 512


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt((x * x).mean(-1, keepdims=True) + eps) * g


def _f32(w):
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def mlp(h, w, cfg):
    """The gated MLP sub-layer with its norm and residual."""
    u = rms_norm(h, w["post_attention_layernorm.weight"], cfg["eps"])
    g, v = jnp.split(u @ w["shared_mlp.input_linear.weight"], 2, axis=-1)
    return h + cfg["residual"] * (
        (jax.nn.silu(g) * v) @ w["shared_mlp.output_linear.weight"])


def attention_mixer(u, w, cfg):
    L = u.shape[0]
    nq, nkv = cfg["heads"], cfg["kv_heads"]
    hd = w["self_attn.q_proj.weight"].shape[1] // nq
    q = (u @ w["self_attn.q_proj.weight"]).reshape(L, nq, hd)
    k = (u @ w["self_attn.k_proj.weight"]).reshape(L, nkv, hd)
    v = (u @ w["self_attn.v_proj.weight"]).reshape(L, nkv, hd)
    # query head i reads K/V head i // (nq // nkv)
    k = jnp.repeat(k, nq // nkv, axis=1)
    v = jnp.repeat(v, nq // nkv, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) * cfg["attention_multiplier"]
    s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
    a = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v)
    return a.reshape(L, nq * hd) @ w["self_attn.o_proj.weight"]


def mamba_mixer(u, w, cfg, length):
    """Returns `(out [L, D], S [heads, head_dim, d_state])`: `S` is the
    state after token `length - 1` (rows past it are padding)."""
    L = u.shape[0]
    nh, hp, ds, kc = (cfg["mamba_heads"], cfg["mamba_head_dim"],
                      cfg["d_state"], cfg["d_conv"])
    inner = nh * hp
    zxbcdt = u @ w["mamba.in_proj.weight"]
    z, xbc, dt = jnp.split(zxbcdt, [inner, 2 * inner + 2 * ds], axis=-1)
    # causal depthwise convolution: out[t] = b + sum_k w[c, k] x[t - (K-1) + k]
    cw = w["mamba.conv1d.weight"][:, 0, :]                     # [C, K]
    padded = jnp.pad(xbc, ((kc - 1, 0), (0, 0)))
    conv = w["mamba.conv1d.bias"] + sum(
        padded[k:k + L] * cw[:, k] for k in range(kc))
    xbc = jax.nn.silu(conv)
    x, b, c = jnp.split(xbc, [inner, inner + ds], axis=-1)
    x = x.reshape(L, nh, hp)
    dt = jax.nn.softplus(dt + w["mamba.dt_bias"])              # [L, nh]
    decay = jnp.exp(dt * -jnp.exp(w["mamba.A_log"]))           # [L, nh]

    def step(state, inp):
        t, a_t, dt_t, x_t, b_t, c_t = inp
        new = a_t[:, None, None] * state + (
            (dt_t[:, None] * x_t)[:, :, None] * b_t[None, None, :])
        y_t = new @ c_t                                         # [nh, hp]
        return jnp.where(t < length, new, state), y_t

    state, y = jax.lax.scan(
        step, jnp.zeros((nh, hp, ds), jnp.float32),
        (jnp.arange(L), decay, dt, x, b, c))
    y = y + w["mamba.D"][None, :, None] * x
    y = y.reshape(L, inner) * jax.nn.silu(z)
    y = rms_norm(y, w["mamba.norm.weight"], cfg["eps"])
    return y @ w["mamba.out_proj.weight"], state


def _static(config):
    return (("eps", float(config["rms_norm_eps"])),
            ("residual", float(config["residual_multiplier"])),
            ("attention_multiplier", float(config["attention_multiplier"])),
            ("heads", int(config["num_attention_heads"])),
            ("kv_heads", int(config["num_key_value_heads"])),
            ("mamba_heads", int(config["mamba_n_heads"])),
            ("mamba_head_dim", int(config["mamba_d_head"])),
            ("d_state", int(config["mamba_d_state"])),
            ("d_conv", int(config["mamba_d_conv"])))


@functools.partial(jax.jit, static_argnames=("kind", "static"))
def layer(h, w, length, kind, static):
    """One layer of kind `kind` on `h` [L, D] float32; `w` maps the layer's
    published weight names (without the `layers.<i>.` prefix) to arrays.
    Returns `(h, state)`; `state` is None for an attention layer."""
    cfg = dict(static)
    w = _f32(w)
    with jax.default_matmul_precision("highest"):
        u = rms_norm(h, w["input_layernorm.weight"], cfg["eps"])
        if kind == "attention":
            mixed, state = attention_mixer(u, w, cfg), None
        elif kind == "mamba":
            mixed, state = mamba_mixer(u, w, cfg, length)
        else:
            raise ValueError(f"unknown layer type {kind!r}")
        return mlp(h + cfg["residual"] * mixed, w, cfg), state


@functools.partial(jax.jit, static_argnames=("eps", "scaling"))
def head(h_rows, g, embed, eps, scaling):
    with jax.default_matmul_precision("highest"):
        x = rms_norm(h_rows, g.astype(jnp.float32), eps)
        return x @ embed.astype(jnp.float32).T / scaling


def forward(config, weights, tokens, rows):
    """`(logits [len(rows), vocab] float32, states)` of the full forward
    over `tokens` (1-D int array): the logits at positions `rows`, and the
    recurrent state of every `mamba` layer, in layer order, after the last
    token."""
    tokens = np.asarray(tokens, np.int32)
    n = len(tokens)
    if n > config["max_position_embeddings"]:
        raise ValueError(f"{n} tokens exceed max_position_embeddings")
    # padded at the end to a multiple of 512 so that a few lengths share one
    # compiled layer (and the persistent compile cache serves the next run's
    # lengths too); no earlier row sees the padding (causal attention,
    # causal convolution, a forward recurrence), and the state is taken at
    # the last real token
    L = -(-n // PAD_TO) * PAD_TO
    tokens = np.pad(tokens, (0, L - n))
    static = _static(config)
    h = (jnp.take(weights["embed_tokens.weight"], tokens, axis=0)
         .astype(jnp.float32) * float(config["embedding_multiplier"]))
    states = []
    for i, kind in enumerate(config["layer_types"]):
        prefix = f"layers.{i}."
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h, state = layer(h, w, n, kind=kind, static=static)
        if state is not None:
            states.append(state)
    out = head(h[np.asarray(rows)], weights["norm.weight"],
               weights["embed_tokens.weight"],
               eps=float(config["rms_norm_eps"]),
               scaling=float(config["logits_scaling"]))
    return out, states


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens`. `weights`: published name -> array, any float
    dtype."""
    return forward(config, weights, tokens, rows)[0]
