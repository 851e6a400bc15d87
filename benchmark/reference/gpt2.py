"""Plain reference of the GPT-2 block: `jax.numpy`, float32, matmul precision
"highest", the full forward over a whole sequence — no kernels, no cache, no
batching, independent of `mxnet_tpu`.

Follows the published model (Radford et al. 2019; the `transformers` GPT2Model):
learned positions, pre-LayerNorm (eps from the config), fused QKV, causal
softmax attention scaled by 1/sqrt(head), tanh-approximated GELU (`gelu_new`),
tied output head. Weights come under the published names (`wte`, `wpe`,
`h.<i>.attn.c_attn.weight`, ...); a bias that the caller does not pass is
taken as absent (configs/gpt2_xl.json, departures).

A float32 copy of GPT-2 XL does not fit beside the serving slab, so weights
arrive in the dtype they are served in and are upcast one layer at a time,
inside one jitted layer function that every layer shares.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


@functools.partial(jax.jit, static_argnames=("n_head", "eps"))
def block(h, w, n_head, eps):
    """One transformer block on `h` [L, D] float32; `w` maps the block's
    published weight names (without the `h.<i>.` prefix) to arrays."""
    w = {k: v.astype(jnp.float32) for k, v in w.items()}
    with jax.default_matmul_precision("highest"):
        L, D = h.shape
        x = layer_norm(h, w["ln_1.weight"], w["ln_1.bias"], eps)
        qkv = x @ w["attn.c_attn.weight"] + w.get("attn.c_attn.bias", 0.0)
        q, k, v = (t.reshape(L, n_head, D // n_head).transpose(1, 0, 2)
                   for t in jnp.split(qkv, 3, axis=-1))
        s = q @ k.transpose(0, 2, 1) / math.sqrt(D // n_head)
        s = jnp.where(jnp.tril(jnp.ones((L, L), bool)), s, -jnp.inf)
        a = jax.nn.softmax(s, axis=-1) @ v
        a = a.transpose(1, 0, 2).reshape(L, D)
        h = h + a @ w["attn.c_proj.weight"] + w.get("attn.c_proj.bias", 0.0)
        x = layer_norm(h, w["ln_2.weight"], w["ln_2.bias"], eps)
        x = gelu_new(x @ w["mlp.c_fc.weight"] + w["mlp.c_fc.bias"])
        return h + x @ w["mlp.c_proj.weight"] + w["mlp.c_proj.bias"]


@functools.partial(jax.jit, static_argnames=("eps",))
def head(h_rows, g, b, wte, eps):
    with jax.default_matmul_precision("highest"):
        x = layer_norm(h_rows, g.astype(jnp.float32), b.astype(jnp.float32),
                       eps)
        return x @ wte.astype(jnp.float32).T


def logits(config, weights, tokens, rows):
    """Float32 logits [len(rows), vocab] at positions `rows` of the full
    forward over `tokens` (1-D int array). `weights`: published name ->
    array, any float dtype."""
    eps = float(config["layer_norm_epsilon"])
    tokens = np.asarray(tokens, np.int32)
    if len(tokens) > config["n_positions"]:
        raise ValueError(f"{len(tokens)} tokens exceed n_positions")
    # padded at the end to a multiple of 128 so that a few lengths share one
    # compiled block; under the causal mask no earlier row sees the padding
    L = min(config["n_positions"], -(-len(tokens) // 128) * 128)
    tokens = np.pad(tokens, (0, L - len(tokens)))
    h = (jnp.take(weights["wte"], tokens, axis=0).astype(jnp.float32)
         + weights["wpe"][:L].astype(jnp.float32))
    for i in range(config["n_layer"]):
        prefix = f"h.{i}."
        w = {k[len(prefix):]: v for k, v in weights.items()
             if k.startswith(prefix)}
        h = block(h, w, n_head=config["n_head"], eps=eps)
    return head(h[np.asarray(rows)], weights["ln_f.weight"],
                weights["ln_f.bias"], weights["wte"], eps=eps)
