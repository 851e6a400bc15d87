"""Hybrid state-space / attention language model (the Granite 4.0-H block,
`model_type` granitemoehybrid without experts): Mamba-2 layers interleaved
with grouped-query attention layers as the configuration's `layer_types`
says, RMSNorm, a SiLU-gated MLP, and the family's four scalar multipliers.

    h = embed[tokens] * embedding_multiplier
    per layer:  h += residual_multiplier * mixer(RMSNorm(h))
                h += residual_multiplier * mlp(RMSNorm(h))
    logits = RMSNorm(h) @ embed.T / logits_scaling

* attention mixer — `num_attention_heads` queries over `num_key_value_heads`
  K/V heads, NO position encoding, scores times `attention_multiplier`.
* Mamba-2 mixer — `[z, xBC, dt] = u @ W_in`; `xBC = silu(causal depthwise
  conv(xBC))`; `[x, B, C] = xBC`; per head `S_t = exp(dt_t A) S_{t-1} + dt_t
  x_t (x) B_t`, `y_t = S_t C_t + D x_t`; `RMSNorm(y * silu(z)) @ W_out`. One
  group: B and C are shared by all heads. `dt`, the decay and `S` are
  float32 whatever the compute dtype.

Serving (`GenerationEngine`) sees the model through the cache protocol
(docs/faq/perf.md, "The cache protocol"): `init_cache` returns a TUPLE of
arrays, each with the slot as its leading axis, and `prefill` /
`decode_step` take its members in order after `params` and return them in
order after their result. Here the members are

    K, V   [slots, attention layers, kv heads, max_len, head_dim]   dtype
    ssm    [slots, mamba layers, heads, head_dim, d_state]          float32
    conv   [slots, mamba layers, d_conv - 1, conv channels]         dtype

so the Mamba layers pay no rows, and their state does not grow. Prefill
computes the recurrence in chunks of `mamba_chunk_size` (the "SSD" form:
inside a chunk a masked-decay matmul, between chunks the carried state);
a ragged last chunk and the padding up to the prefill bucket are steps of
`dt = 0`, which leave the state where the prompt's true last token put it.
Decode is one step from the stored state and the stored last `d_conv - 1`
convolution inputs. A recurrent state cannot be rewound or extended from an
offset, so the model offers no `prefill_at` / `verify_step` and
`cache_traits` says so: the engine refuses the prefix cache and speculation.
"""
from __future__ import annotations

import dataclasses

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..parallel.spmd import model_mesh
from .transformer import _attend_rows, _table_rows, _write_rows

__all__ = ["HybridLMConfig", "HybridLM"]


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    """The published configuration's keys, under their published names
    (`from_config` reads a `config.json`-shaped dict), plus what serving
    adds: `max_len` (positions a cache may be asked for) and `dtype`."""
    vocab_size: int = 1024
    hidden_size: int = 64
    shared_intermediate_size: int = 128
    layer_types: tuple = ("mamba", "mamba", "attention", "mamba")
    num_attention_heads: int = 8
    num_key_value_heads: int = 2
    attention_multiplier: float = 0.125
    embedding_multiplier: float = 12.0
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    mamba_n_heads: int = 4
    mamba_d_head: int = 16
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 8
    max_len: int = 2048
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, config, max_len=None, dtype=None):
        """From a published `config.json` (a dict). What the block cannot
        express is refused by name rather than ignored (experts live in
        `models/experts.py`, which `LatentMoELM` and `WindowMoELM` call: a
        state-space model with experts would call it from here)."""
        for key, want in (("mamba_n_groups", 1), ("num_local_experts", 0),
                          ("position_embedding_type", "nope"),
                          ("attention_bias", False),
                          ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True),
                          ("hidden_act", "silu"),
                          ("tie_word_embeddings", True)):
            if key in config and config[key] != want:
                raise ValueError(f"HybridLM: {key}={config[key]!r} is not "
                                 f"supported (only {want!r})")
        if config["mamba_expand"] * config["hidden_size"] != \
                config["mamba_n_heads"] * config["mamba_d_head"]:
            raise ValueError("HybridLM: mamba_expand * hidden_size must be "
                             "mamba_n_heads * mamba_d_head")
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            shared_intermediate_size=config["shared_intermediate_size"],
            layer_types=tuple(config["layer_types"]),
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            attention_multiplier=config["attention_multiplier"],
            embedding_multiplier=config["embedding_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            logits_scaling=config["logits_scaling"],
            rms_norm_eps=config["rms_norm_eps"],
            mamba_n_heads=config["mamba_n_heads"],
            mamba_d_head=config["mamba_d_head"],
            mamba_d_state=config["mamba_d_state"],
            mamba_d_conv=config["mamba_d_conv"],
            mamba_chunk_size=config["mamba_chunk_size"],
            max_len=int(config["max_position_embeddings"]
                        if max_len is None else max_len),
            dtype=config.get("dtype", "bfloat16") if dtype is None else dtype)

    # derived sizes
    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self):
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_channels(self):
        return self.mamba_inner + 2 * self.mamba_d_state


class HybridLM:
    """Functional hybrid LM bound to a mesh; `params` is a flat dict name ->
    jax.Array. All methods are pure. Weights are replicated: the block has
    no sharding plan yet, and its kernels run on one device."""

    def __init__(self, config, mesh=None):
        kinds = set(config.layer_types)
        if not kinds <= {"mamba", "attention"}:
            raise ValueError(f"HybridLM: unknown layer types "
                             f"{sorted(kinds - {'mamba', 'attention'})}")
        if config.num_attention_heads % config.num_key_value_heads:
            raise ValueError("HybridLM: query heads must group evenly over "
                             "the K/V heads")
        self.cfg = config
        self.mesh = mesh or model_mesh()
        # a layer's index among the layers of its own kind: its page of the
        # K/V slabs or of the state slabs
        self._page = []
        seen = {"mamba": 0, "attention": 0}
        for kind in config.layer_types:
            self._page.append(seen[kind])
            seen[kind] += 1
        self.n_attention, self.n_mamba = seen["attention"], seen["mamba"]

    # -- parameters ---------------------------------------------------------

    def _shapes(self):
        c = self.cfg
        d, f = c.hidden_size, c.shared_intermediate_size
        shapes = {"embed": (c.vocab_size, d), "norm_f": (d,)}
        for i, kind in enumerate(c.layer_types):
            shapes.update({f"l{i}.norm1": (d,), f"l{i}.norm2": (d,),
                           f"l{i}.w_in": (d, 2 * f), f"l{i}.w_out": (f, d)})
            if kind == "attention":
                kv = c.num_key_value_heads * c.head_dim
                shapes.update({f"l{i}.wq": (d, d), f"l{i}.wk": (d, kv),
                               f"l{i}.wv": (d, kv), f"l{i}.wo": (d, d)})
            else:
                shapes.update({
                    f"l{i}.m_in": (d, 2 * c.mamba_inner
                                   + 2 * c.mamba_d_state + c.mamba_n_heads),
                    f"l{i}.conv_w": (c.mamba_d_conv, c.conv_channels),
                    f"l{i}.conv_b": (c.conv_channels,),
                    f"l{i}.dt_bias": (c.mamba_n_heads,),
                    f"l{i}.A_log": (c.mamba_n_heads,),
                    f"l{i}.D": (c.mamba_n_heads,),
                    f"l{i}.m_norm": (c.mamba_inner,),
                    f"l{i}.m_out": (c.mamba_inner, d)})
        return shapes

    def param_specs(self):
        repl = NamedSharding(self.mesh, P())
        return {name: repl for name in self._shapes()}

    def init_params(self, key):
        """Random weights: normal / sqrt(fan_in) for matrices (the
        embedding's fan-in is the hidden size, which its use as the output
        head contracts), ones for the norms and `D`, and the Mamba-2
        reference initialisation for what decides the decays — `A_log =
        log U[1, 16]`, `dt_bias = softplus^-1(log-uniform[1e-3, 1e-1])` — a
        normal draw there gives degenerate decays."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = self._shapes()
        specs = self.param_specs()
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            leaf = name.rpartition(".")[2]
            if leaf in ("norm1", "norm2", "norm_f", "m_norm", "D"):
                val = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                val = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                 1.0, 16.0))
            elif leaf == "dt_bias":
                step = jnp.exp(jax.random.uniform(
                    k, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
                val = step + jnp.log(-jnp.expm1(-step))
            else:
                fan_in = (c.hidden_size if leaf == "embed" else
                          c.mamba_d_conv if leaf in ("conv_w", "conv_b")
                          else shape[0])
                val = jax.random.normal(k, shape, jnp.float32) \
                    / np.sqrt(fan_in)
            params[name] = jax.device_put(val.astype(dt), specs[name])
        return params

    # -- pieces -------------------------------------------------------------

    # Device-side scopes (`jax.named_scope`: in every instruction's op_name,
    # read by benchmark/program_scopes.py): `embed`, `norm`, `mlp`, `head`,
    # `attn.project`, `attn.prefill` | `attn.decode`, `attn.out`,
    # `mamba.project`, `mamba.conv`, `mamba.gates`, `mamba.ssd` |
    # `mamba.state_update`, `mamba.out`, `cache.write`.

    def _rms(self, x, g):
        with jax.named_scope("norm"):
            x32 = x.astype(jnp.float32)
            out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                  + self.cfg.rms_norm_eps)
            return (out * g.astype(jnp.float32)).astype(x.dtype)

    def _mlp(self, params, i, h):
        """The gated MLP sub-layer with its norm and residual."""
        u = self._rms(h, params[f"l{i}.norm2"])
        with jax.named_scope("mlp"):
            g, v = jnp.split(u @ params[f"l{i}.w_in"], 2, axis=-1)
            return h + self.cfg.residual_multiplier * (
                (jax.nn.silu(g) * v) @ params[f"l{i}.w_out"])

    def _qkv(self, params, i, u):
        """`u` [T, D] -> q [T, Hq, hd], k and v [T, Hkv, hd]."""
        c = self.cfg
        t = u.shape[0]
        with jax.named_scope("attn.project"):
            return ((u @ params[f"l{i}.wq"]).reshape(
                        t, c.num_attention_heads, c.head_dim),
                    (u @ params[f"l{i}.wk"]).reshape(
                        t, c.num_key_value_heads, c.head_dim),
                    (u @ params[f"l{i}.wv"]).reshape(
                        t, c.num_key_value_heads, c.head_dim))

    def _attention_seq(self, params, i, u):
        """The attention mixer over one whole sequence `u` [L, D]: `(out
        [L, D], k, v [L, Hkv, hd])`. Plain XLA: 4 of 40 layers."""
        c = self.cfg
        L = u.shape[0]
        q, k, v = self._qkv(params, i, u)
        group = c.num_attention_heads // c.num_key_value_heads
        with jax.named_scope("attn.prefill"):
            q = q.reshape(L, c.num_key_value_heads, group, c.head_dim)
            s = jnp.einsum("qhgd,khd->hgqk", q, k,
                           preferred_element_type=jnp.float32) \
                * c.attention_multiplier
            ar = jnp.arange(L)
            # large-negative, not -inf: see TransformerLM.prefill
            s = s + jnp.where(ar[:, None] >= ar[None, :], 0.0, -1e9)
            p = jax.nn.softmax(s, axis=-1).astype(u.dtype)
            a = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(L, c.hidden_size)
        with jax.named_scope("attn.out"):
            return a @ params[f"l{i}.wo"], k, v

    def _mamba_project(self, params, i, u):
        """`u` [T, D] -> z [T, inner], xBC [T, C], dt_raw [T, H]."""
        c = self.cfg
        with jax.named_scope("mamba.project"):
            return jnp.split(u @ params[f"l{i}.m_in"],
                             [c.mamba_inner, c.mamba_inner + c.conv_channels],
                             axis=-1)

    def _mamba_gates(self, params, i, dt_raw):
        """float32 step sizes `dt` [T, H] and log-decays `dt * A`."""
        with jax.named_scope("mamba.gates"):
            step = jax.nn.softplus(
                dt_raw.astype(jnp.float32)
                + params[f"l{i}.dt_bias"].astype(jnp.float32))
            return step, step * -jnp.exp(params[f"l{i}.A_log"]
                                         .astype(jnp.float32))

    def _mamba_out(self, params, i, y, x, z):
        """`y` [T, H, P] float32 (the recurrence's output) -> the mixer's
        output [T, D]: the skip `D x`, the gate, the gated RMSNorm, the
        output projection."""
        c = self.cfg
        t = y.shape[0]
        with jax.named_scope("mamba.out"):      # its norm nests: the
            # outermost scope names the work
            y = y + params[f"l{i}.D"].astype(jnp.float32)[None, :, None] \
                * x.astype(jnp.float32)
            y = y.reshape(t, c.mamba_inner) \
                * jax.nn.silu(z.astype(jnp.float32))
            return self._rms(y, params[f"l{i}.m_norm"]).astype(z.dtype) \
                @ params[f"l{i}.m_out"]

    def _split_xbc(self, xbc):
        c = self.cfg
        x, b, cc = jnp.split(xbc, [c.mamba_inner,
                                   c.mamba_inner + c.mamba_d_state], axis=-1)
        return (x.reshape(x.shape[0], c.mamba_n_heads, c.mamba_d_head), b, cc)

    def _mamba_seq(self, params, i, u, length):
        """The Mamba-2 mixer over one whole sequence `u` [L, D] of which the
        first `length` tokens are real. Returns `(out [L, D], state [H, P,
        N] float32, conv_tail [d_conv - 1, C])`: the recurrent state after
        token `length - 1` and the last `d_conv - 1` convolution inputs up
        to it (zeros before the sequence's start). Rows at and past
        `length` are steps of `dt = 0`: they neither move the state nor
        enter the tail, and their outputs are garbage nobody reads."""
        c = self.cfg
        L, dt_ = u.shape[0], u.dtype
        k = c.mamba_d_conv
        z, xbc, dt_raw = self._mamba_project(params, i, u)
        with jax.named_scope("mamba.conv"):
            padded = jnp.concatenate(
                [jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc], axis=0)
            # padded row t + k - 1 is token t: the tail is tokens
            # [length - (k - 1), length)
            tail = lax.dynamic_slice_in_dim(padded, length, k - 1, axis=0)
            w = params[f"l{i}.conv_w"].astype(jnp.float32)
            conv = params[f"l{i}.conv_b"].astype(jnp.float32) + sum(
                padded[j:j + L].astype(jnp.float32) * w[j] for j in range(k))
            x, b, cc = self._split_xbc(jax.nn.silu(conv).astype(dt_))
        step, log_a = self._mamba_gates(params, i, dt_raw)
        with jax.named_scope("mamba.gates"):
            real = (jnp.arange(L) < length)[:, None]
            step = jnp.where(real, step, 0.0)
            log_a = jnp.where(real, log_a, 0.0)
        with jax.named_scope("mamba.ssd"):
            y, state = self._ssd(x, b, cc, step, log_a)
        return self._mamba_out(params, i, y, x, z), state, tail

    def _ssd(self, x, b, c, step, log_a):
        """The recurrence over a whole sequence from a zero state, in chunks
        of `mamba_chunk_size`: x [L, H, P], b and c [L, N], step and log_a
        [L, H] float32. Returns `(y [L, H, P] float32, state [H, P, N]
        float32)`. A ragged last chunk is padded with steps of `dt = 0`.

        Inside a chunk, with `cs` the running sum of `log_a`: `y_t = sum_{s
        <= t} exp(cs_t - cs_s) dt_s (C_t . B_s) x_s + exp(cs_t) (S_in C_t)`
        and `S_out = exp(cs_end) S_in + sum_s exp(cs_end - cs_s) dt_s x_s
        (x) B_s`. What touches the carried state runs at matmul precision
        `highest`: the state is float32 and stays so."""
        q = self.cfg.mamba_chunk_size
        L, nh, hp = x.shape
        n = b.shape[1]
        pad = -L % q
        if pad:
            x, b, c, step, log_a = (jnp.pad(t, ((0, pad),) + ((0, 0),)
                                            * (t.ndim - 1))
                                    for t in (x, b, c, step, log_a))
        nc = (L + pad) // q
        f32 = jnp.float32
        chunks = (x.reshape(nc, q, nh, hp), b.reshape(nc, q, n),
                  c.reshape(nc, q, n), step.reshape(nc, q, nh),
                  log_a.reshape(nc, q, nh))
        causal = jnp.tril(jnp.ones((q, q), bool))[:, :, None]

        def chunk(state, xs):
            x_c, b_c, c_c, dt_c, la_c = xs
            cs = jnp.cumsum(la_c, axis=0)                        # [q, H]
            # exp(cs_t - cs_s) for s <= t; masked before the exp
            decay = jnp.exp(jnp.where(causal, cs[:, None, :] - cs[None, :, :],
                                      -jnp.inf))                 # [t, s, H]
            g = jnp.einsum("tn,sn->ts", c_c, b_c,
                           preferred_element_type=f32)
            m = g[:, :, None] * decay * dt_c[None, :, :]
            y = jnp.einsum("tsh,shp->thp", m, x_c.astype(f32))
            y = y + jnp.exp(cs)[:, :, None] * jnp.einsum(
                "tn,hpn->thp", c_c.astype(f32), state,
                precision=lax.Precision.HIGHEST)
            to_end = jnp.exp(cs[-1][None, :] - cs) * dt_c        # [s, H]
            state = jnp.exp(cs[-1])[:, None, None] * state + jnp.einsum(
                "shp,sn->hpn", to_end[:, :, None] * x_c.astype(f32),
                b_c.astype(f32), precision=lax.Precision.HIGHEST)
            return state, y

        state, y = lax.scan(chunk, jnp.zeros((nh, hp, n), f32), chunks)
        return y.reshape(nc * q, nh, hp)[:L], state

    # -- forward ------------------------------------------------------------

    def _embed(self, rows):
        return (rows * self.cfg.embedding_multiplier).astype(
            jnp.dtype(self.cfg.dtype))

    def _logits(self, params, h):
        h = self._rms(h, params["norm_f"])
        with jax.named_scope("head"):
            return (h @ params["embed"].T).astype(jnp.float32) \
                / self.cfg.logits_scaling

    def _sequence(self, params, tokens, length):
        """One whole sequence `tokens` [L]: the hidden states [L, D] after
        the last layer and, per layer, what a cache keeps of it: `(k, v)` of
        an attention layer, `(state, conv_tail)` of a Mamba layer."""
        c = self.cfg
        with jax.named_scope("embed"):
            h = self._embed(jnp.take(params["embed"], tokens, axis=0))
        kept = []
        for i, kind in enumerate(c.layer_types):
            u = self._rms(h, params[f"l{i}.norm1"])
            if kind == "attention":
                mixed, *keep = self._attention_seq(params, i, u)
            else:
                mixed, *keep = self._mamba_seq(params, i, u, length)
            kept.append(keep)
            h = self._mlp(params, i, h + c.residual_multiplier * mixed)
        return h, kept

    def forward(self, params, tokens):
        """tokens [B, L] int32 -> logits [B, L, V] float32: the full forward,
        no cache."""
        def one(seq):
            h, _ = self._sequence(params, seq, seq.shape[0])
            return self._logits(params, h)

        return jax.vmap(one)(tokens)

    # -- the cache protocol (serving/generation) ------------------------------

    def init_cache(self, max_slots, max_len=None):
        """The serving cache: `(K, V, ssm, conv)`, zeroed, each with the slot
        as its leading axis (module docstring). K/V rows are paid by the
        attention layers only; the state of the Mamba layers has one size
        whatever `max_len`."""
        c = self.cfg
        max_len = c.max_len if max_len is None else int(max_len)
        if max_len > c.max_len:
            raise ValueError(f"cache max_len {max_len} exceeds the model's "
                             f"positional range {c.max_len}")
        s, dt = int(max_slots), jnp.dtype(c.dtype)
        sh = NamedSharding(self.mesh, P())
        kv = (s, self.n_attention, c.num_key_value_heads, max_len, c.head_dim)
        shapes = ((kv, dt), (kv, dt),
                  ((s, self.n_mamba, c.mamba_n_heads, c.mamba_d_head,
                    c.mamba_d_state), jnp.float32),
                  ((s, self.n_mamba, c.mamba_d_conv - 1, c.conv_channels),
                   dt))
        return tuple(jax.device_put(jnp.zeros(shape, t), sh)
                     for shape, t in shapes)

    def decode_block(self, slab_shape, dtype):
        """As `TransformerLM.decode_block`: the Pallas decode kernel's block
        over the K/V slab's rows, or None for the XLA formulation."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_decode as pd

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        return pd.decode_block(slab_shape, dtype)

    def state_kernel(self, slab_shape, dtype):
        """Whether :meth:`decode_step` advances a recurrent-state slab of
        this shape through the Pallas kernel (``ops/pallas_ssm.py``: each
        live slot's state read once and written once, where it lies) or in
        XLA (which reads it twice). Decided as :meth:`decode_block` is;
        which way a layer's trace went is counted
        (`mamba.state_update.kernel` / `.xla`, once a trace, telemetry
        on)."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_ssm

        return (self.mesh.size == 1 and pa.pallas_enabled()
                and pallas_ssm.state_update_applies(slab_shape, dtype))

    def cache_traits(self, cache):
        """What the engine may ask about a cache it otherwise only carries
        (docs/faq/perf.md, "The cache protocol")."""
        _, _, ssm, conv = cache
        slots = ssm.shape[0]
        return {
            "block": self.decode_block(cache[0].shape, cache[0].dtype),
            "state_bytes_per_slot": (int(ssm.nbytes) + int(conv.nbytes))
            // slots,
            "rewindable": False,
            "why_not_rewindable":
                "the Mamba layers' recurrent and convolution state holds "
                "only the last token's value: it cannot be extended from a "
                "row offset (prefix reuse) nor rolled back (speculation) "
                "without snapshots, which this cache does not keep"}

    def prefill(self, params, ck, cv, ssm, conv, tokens, length, slot):
        """Full-prompt forward for ONE session into slot `slot`: writes the
        K/V rows `[0, Lb)` of the attention layers and REPLACES the slot's
        recurrent and convolution state with those of the prompt's true
        last token, computed from zero — nothing of what the previous
        occupant left is read. Returns `(logits [V] fp32 at position length
        - 1, ck, cv, ssm, conv)`. `tokens` [Lb] is the prompt padded (with
        anything) to the bucket; `length` and `slot` are traced."""
        h, kept = self._sequence(params, tokens, length)
        with jax.named_scope("cache.write"):
            for kind, page, keep in zip(self.cfg.layer_types, self._page,
                                        kept):
                at = (slot, page, 0, 0, 0)
                if kind == "attention":
                    k, v = keep
                    ck = lax.dynamic_update_slice(
                        ck, k.transpose(1, 0, 2)[None, None].astype(ck.dtype),
                        at)
                    cv = lax.dynamic_update_slice(
                        cv, v.transpose(1, 0, 2)[None, None].astype(cv.dtype),
                        at)
                else:
                    state, tail = keep
                    ssm = lax.dynamic_update_slice(
                        ssm, state[None, None].astype(ssm.dtype), at)
                    conv = lax.dynamic_update_slice(
                        conv, tail[None, None].astype(conv.dtype), at[:4])
            last = lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)
        return self._logits(params, last)[0], ck, cv, ssm, conv

    def _mamba_step(self, params, i, u, ssm, conv, page, alive):
        """One token for every slot through Mamba layer `i`: `u` [S, D],
        the slot-major state slabs, `alive` [S]. The state update happens on
        the layer's page of the slab where it lies; a dead slot's state and
        convolution window stay bit-for-bit what they were."""
        z, xbc, dt_raw = self._mamba_project(params, i, u)
        with jax.named_scope("mamba.conv"):
            window = jnp.concatenate([conv[:, page], xbc[:, None, :]], axis=1)
            w = params[f"l{i}.conv_w"].astype(jnp.float32)
            out = params[f"l{i}.conv_b"].astype(jnp.float32) + jnp.einsum(
                "skc,kc->sc", window.astype(jnp.float32), w)
            conv = conv.at[:, page].set(jnp.where(
                alive[:, None, None], window[:, 1:], conv[:, page]))
            x, b, cc = self._split_xbc(jax.nn.silu(out).astype(u.dtype))
        step, log_a = self._mamba_gates(params, i, dt_raw)
        with jax.named_scope("mamba.state_update"):
            f32 = jnp.float32
            decay, dtx = jnp.exp(log_a), step[:, :, None] * x.astype(f32)
            kernel = self.state_kernel(ssm.shape, ssm.dtype)
            if telemetry._enabled:
                telemetry.counter("mamba.state_update."
                                  + ("kernel" if kernel else "xla")).inc()
            if kernel:
                from ..ops import pallas_attention as pa
                from ..ops import pallas_ssm

                y, ssm = pallas_ssm.state_update(
                    ssm, jnp.int32(page), decay, dtx, b, cc, alive,
                    interpret=pa.pallas_interpret())
            else:
                old = ssm[:, page]                              # [S,H,P,N]
                new = decay[:, :, None, None] * old + (
                    dtx[:, :, :, None] * b.astype(f32)[:, None, None, :])
                y = jnp.sum(new * cc.astype(f32)[:, None, None, :], axis=-1)
                ssm = ssm.at[:, page].set(
                    jnp.where(alive[:, None, None, None], new, old))
        return self._mamba_out(params, i, y, x, z), ssm, conv

    def decode_step(self, params, ck, cv, ssm, conv, tokens, positions):
        """One fused incremental step over every slot: a live slot consumes
        one token, writes its K/V row at `positions[s]` in each attention
        layer, attends rows `[0, positions[s]]`, and advances the state of
        each Mamba layer by one step. A NEGATIVE position marks a dead slot:
        nothing of it is written, attended or advanced. Returns `(logits
        [S, V] fp32, ck, cv, ssm, conv)`; jit with the cache donated. How
        the cache is touched is decided from shapes, policy and mesh before
        the call (:meth:`decode_block`, :meth:`state_kernel`): the Pallas
        kernels on one TPU chip, else the same mathematics in XLA."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_decode as pd

        c = self.cfg
        block = self.decode_block(ck.shape, ck.dtype)
        positions = jnp.minimum(positions, ck.shape[3] - 1)
        alive = positions >= 0
        with jax.named_scope("embed"):
            h = self._embed(_table_rows(params["embed"], tokens))
        for i, (kind, page) in enumerate(zip(c.layer_types, self._page)):
            u = self._rms(h, params[f"l{i}.norm1"])
            if kind == "attention":
                q, k, v = self._qkv(params, i, u)
                with jax.named_scope("attn.decode"):
                    k, v = k.astype(ck.dtype), v.astype(cv.dtype)
                    if block is not None:
                        a, ck, cv = pd.decode_update_attend(
                            q, k, v, ck, cv, jnp.int32(page), positions,
                            block=block, scale=c.attention_multiplier,
                            interpret=pa.pallas_interpret())
                    else:
                        ck = _write_rows(ck, page, positions, k)
                        cv = _write_rows(cv, page, positions, v)
                        a = _attend_rows(q, ck, cv, page, positions,
                                         scale=c.attention_multiplier)
                with jax.named_scope("attn.out"):
                    mixed = a.astype(h.dtype).reshape(-1, c.hidden_size) \
                        @ params[f"l{i}.wo"]
            else:
                mixed, ssm, conv = self._mamba_step(params, i, u, ssm, conv,
                                                    page, alive)
            h = self._mlp(params, i, h + c.residual_multiplier * mixed)
        return self._logits(params, h), ck, cv, ssm, conv
