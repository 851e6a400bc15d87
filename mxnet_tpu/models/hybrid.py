"""Hybrid recurrent / attention language model: layers with a recurrent
mixer interleaved with attention layers as the configuration's `layer_types`
says, RMSNorm, a SiLU-gated MLP or a layer of routed experts. Three published
blocks are built from it.

**The Granite 4.0-H block** (`model_type` granitemoehybrid without experts;
`layer_types` of "mamba" and "attention"), with the family's four scalar
multipliers:

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

**The Olmo-Hybrid block** (`model_type` olmo_hybrid; `layer_types` of
"linear_attention" and "full_attention"): no multipliers, an untied head,
the norms AFTER the sub-layer in a full-attention layer and before it in a
linear one:

    h = embed[tokens]
    full_attention:    h += RMSNorm(attn(h));      h += RMSNorm(mlp(h))
    linear_attention:  h += gdn(RMSNorm(h));       h += mlp(RMSNorm(h))
    logits = RMSNorm(h) @ head

* attention mixer — as above with `head_dim ** -0.5` for the multiplier,
  and `q` and `k` through an RMSNorm over the WHOLE projected vector before
  the heads are cut; still no position encoding.
* gated-delta-rule mixer — `models/recurrent.py`, `GatedDeltaMixer`: a
  head's matrix state `S [dk, dv]` moves by `S' = a S; u = v - S'^T k; S = S'
  + b k u^T; o = S^T q`.

**The LFM2 expert block** (`model_type` lfm2_moe; `layer_types` of "conv"
and "full_attention"): no multipliers, a tied head, every norm before its
sub-layer, positions, and experts after the leading dense layers:

    h = embed[tokens]
    per layer i:  h += op_i(RMSNorm(h));  h += ffn_i(RMSNorm(h))
    logits = RMSNorm(h) @ embed.T

* attention mixer — as above with `head_dim ** -0.5`; `q` and `k` through
  an RMSNorm over EACH head's `head_dim` entries (one weight of `head_dim`
  each), then rotated over the whole head (`models/rotary.py`, `rope_theta`,
  half-split pairing).
* short-convolution mixer — `models/recurrent.py`, `ShortConvMixer`: `[B,
  C, x] = u W_in`; `y = C * conv(B * x)`, `conv_L_cache` taps a channel
  over time, no bias, no activation; `y W_out`. It keeps no recurrent
  state, only the window.
* ffn — layer `i < num_dense_layers`: the gated MLP of `intermediate_size`.
  Else `models/experts.py`: `s = sigmoid(x W_r)` in float32 over all
  `num_experts`; the `num_experts_per_tok` largest `s + expert_bias` (the
  bias enters the selection only); `w_e = s_e / (sum_chosen s + 1e-6) *
  routed_scaling_factor`; `y = sum_e w_e E_e(x)`, every expert SiLU-gated
  of `moe_intermediate_size`, all of them held here; no shared expert.

What a later block adds is read from the configuration (`from_config`) and
compiles to nothing for an earlier one: granite's and Olmo-Hybrid's programs
are the same operations as before the next existed
(tests/python/unittest/test_hybrid_lm.py and test_hybrid_lm_olmo.py hold
their lowered text).

Serving (`GenerationEngine`) sees the model through the cache protocol
(docs/faq/perf.md, "The cache protocol"): `init_cache` returns a TUPLE of
arrays, each with the slot as its leading axis, and `prefill` /
`decode_step` take its members in order after `params` and return them in
order after their result. The members follow from the block
(`HybridLM.members`): K and V always; `state` where the mixer keeps one
(`mixer.state_shape`); `conv`; `routed` where the block has experts —
`(K, V, state, conv)` for granite and Olmo-Hybrid, `(K, V, conv, routed)`
for LFM2:

    K, V    [slots, attention layers, kv heads, max_len, head_dim]   dtype
    state   [slots, recurrent layers, *mixer.state_shape]            float32
            Mamba-2: [heads, head_dim, d_state]; gated delta rule: [dk,
            heads * dv] (the heads side by side on the lanes)
    conv    [slots, recurrent layers, kernel - 1, conv channels]     dtype
            Mamba-2: x | B | C; gated delta rule: q | k | v, three streams;
            short convolution: B * x
    routed  [slots, expert layers, experts a token]                  int32
            what the last decode step chose for the slot (`latent_moe`'s
            member)

so the recurrent layers pay no rows, and their state does not grow. Prefill
computes the recurrence in chunks (Mamba-2: the "SSD" form, inside a chunk a
masked-decay matmul, between chunks the carried state; the delta rule: the
WY form, a unit-lower-triangular solve a chunk); a ragged last chunk and the
padding up to the prefill bucket are steps that leave the state where the
prompt's true last token put it (`dt = 0`; `g = 0, beta = 0`; the window is
cut at the prompt's length). Decode is one step from the stored state and
the stored last `kernel - 1` convolution inputs. Neither a recurrent state
nor a window can be rewound or extended from an offset, so the model offers
no `prefill_at` / `verify_step` and `cache_traits` says so: the engine
refuses the prefix cache and speculation.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.spmd import model_mesh
from . import experts, rotary, window_moe
from .transformer import _attend_rows, _table_rows, _write_rows

__all__ = ["HybridLMConfig", "HybridLM"]

# `layer_types` by what a layer's mixer is
ATTENTION_KINDS = ("attention", "full_attention")
RECURRENT_KINDS = ("mamba", "linear_attention", "conv")
# bytes of one `[query heads, L, L]` float32 score matrix past which a
# prefill's attention in XLA goes blockwise (`HybridLM.prefill_blockwise`)
_SCORES_BUDGET = 1 << 30


def _known_kinds(kinds):
    """`kinds`, or a refusal that names the layer types no mixer builds."""
    unknown = set(kinds) - set(ATTENTION_KINDS + RECURRENT_KINDS)
    if unknown:
        raise ValueError(f"HybridLM: unknown layer types {sorted(unknown)}")
    return kinds


def _need(config, key, kind):
    """`config[key]`, or a refusal that names the key and the kind of layer
    that needs it."""
    if key not in config:
        raise ValueError(f"HybridLM: the configuration lacks {key!r}, which "
                         f"its {kind!r} layers need")
    return config[key]


@dataclasses.dataclass(frozen=True)
class HybridLMConfig:
    """The published configuration's keys, under their published names
    (`from_config` reads a `config.json`-shaped dict), plus what serving
    adds: `max_len` (positions a cache may be asked for) and `dtype`. The
    keys of a mixer matter only where `layer_types` names it."""
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
    # the Olmo-Hybrid block (module docstring); the defaults are granite's
    tie_word_embeddings: bool = True
    qk_norm: bool = False               # over the whole projected q and k
    post_norm_kinds: tuple = ()         # kinds whose norms follow a sub-layer
    linear_num_heads: int = 4
    linear_key_head_dim: int = 8
    linear_value_head_dim: int = 16
    linear_conv_kernel_dim: int = 4
    linear_allow_neg_eigval: bool = True
    gdn_chunk_size: int = 64
    # the LFM2 expert block (module docstring); the defaults build none of it
    conv_L_cache: int = 3               # the short convolution's taps
    qk_norm_heads: bool = False         # `qk_norm` over each head's entries
    rope_theta: float | None = None     # None: no positions
    num_experts: int = 0                # 0: every layer's MLP is dense
    num_dense_layers: int = 0           # leading layers with a dense MLP
    moe_intermediate_size: int = 32
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    use_expert_bias: bool = True

    @classmethod
    def from_config(cls, config, max_len=None, dtype=None):
        """From a published `config.json` (a dict): the first
        `num_hidden_layers` of its `layer_types`. Each mixer's keys are read
        only where `layer_types` names it; what the block cannot express is
        refused by name rather than ignored."""
        kinds = tuple(config["layer_types"])
        kinds = _known_kinds(
            kinds[:config.get("num_hidden_layers", len(kinds))])
        for key, want in (("num_local_experts", 0), ("attention_bias", False),
                          ("hidden_act", "silu")):
            if key in config and config[key] != want:
                raise ValueError(f"HybridLM: {key}={config[key]!r} is not "
                                 f"supported (only {want!r})")
        fields = dict(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"], layer_types=kinds,
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            max_len=int(config["max_position_embeddings"]
                        if max_len is None else max_len),
            dtype=config.get("dtype", "bfloat16") if dtype is None else dtype)
        block = {"olmo_hybrid": cls._olmo_fields,
                 "lfm2_moe": cls._lfm2_fields}.get(
                     config.get("model_type"), cls._granite_fields)
        fields.update(block(config, kinds))
        return cls(**fields)

    @staticmethod
    def _granite_fields(config, kinds):
        for key, want in (("mamba_n_groups", 1),
                          ("position_embedding_type", "nope"),
                          ("mamba_proj_bias", False),
                          ("mamba_conv_bias", True),
                          ("tie_word_embeddings", True)):
            if key in config and config[key] != want:
                raise ValueError(f"HybridLM: {key}={config[key]!r} is not "
                                 f"supported (only {want!r})")
        fields = dict(
            rms_norm_eps=config["rms_norm_eps"],
            shared_intermediate_size=config["shared_intermediate_size"],
            attention_multiplier=config["attention_multiplier"],
            embedding_multiplier=config["embedding_multiplier"],
            residual_multiplier=config["residual_multiplier"],
            logits_scaling=config["logits_scaling"])
        if "mamba" in kinds:
            if _need(config, "mamba_expand", "mamba") \
                    * config["hidden_size"] \
                    != _need(config, "mamba_n_heads", "mamba") \
                    * _need(config, "mamba_d_head", "mamba"):
                raise ValueError("HybridLM: mamba_expand * hidden_size must "
                                 "be mamba_n_heads * mamba_d_head")
            fields.update({key: _need(config, key, "mamba") for key in (
                "mamba_n_heads", "mamba_d_head", "mamba_d_state",
                "mamba_d_conv", "mamba_chunk_size")})
        return fields

    @staticmethod
    def _olmo_fields(config, kinds):
        if (config.get("rope_parameters") or {}).get("rope_theta") is not None:
            raise ValueError("HybridLM: rope_parameters.rope_theta="
                             f"{config['rope_parameters']['rope_theta']!r} is "
                             "not supported (only None: the block has no "
                             "rotary)")
        if config.get("tie_word_embeddings", False):
            raise ValueError("HybridLM: tie_word_embeddings=True is not "
                             "supported for olmo_hybrid (only False: the "
                             "block has an lm_head)")
        hd = config["hidden_size"] // config["num_attention_heads"]
        fields = dict(
            rms_norm_eps=config["rms_norm_eps"],
            shared_intermediate_size=config["intermediate_size"],
            attention_multiplier=hd ** -0.5, embedding_multiplier=1.0,
            residual_multiplier=1.0, logits_scaling=1.0,
            tie_word_embeddings=False, qk_norm=True,
            post_norm_kinds=("full_attention",))
        if "linear_attention" in kinds:
            kind = "linear_attention"
            heads = _need(config, "linear_num_value_heads", kind)
            if _need(config, "linear_num_key_heads", kind) != heads:
                raise ValueError(
                    "HybridLM: linear_num_key_heads="
                    f"{config['linear_num_key_heads']!r} is not supported "
                    f"(only linear_num_value_heads, {heads})")
            fields.update(
                linear_num_heads=heads,
                linear_allow_neg_eigval=bool(
                    config.get("linear_allow_neg_eigval", False)),
                gdn_chunk_size=config.get("gdn_chunk_size", 64),
                **{key: _need(config, key, kind) for key in (
                    "linear_key_head_dim", "linear_value_head_dim",
                    "linear_conv_kernel_dim")})
        return fields

    @staticmethod
    def _lfm2_fields(config, kinds):
        """The LFM2 expert block: the keys of `lfm2_moe`'s `config.json`
        under their published names. What no key states (the order `B | C |
        x`, no activation in the operator, per-head norms before the
        rotation, the tied head, the router's 1e-6) is the block's own
        (module docstring; a benchmark configuration lists each under
        `assumed`)."""
        for key, want in (("conv_bias", False),
                          ("tie_word_embeddings", True),
                          ("rope_scaling", None)):
            if config.get(key, want) != want:
                raise ValueError(f"HybridLM: {key}={config[key]!r} is not "
                                 f"supported for lfm2_moe (only {want!r})")
        n, dense = len(kinds), int(config.get("num_dense_layers", 0))
        if not 0 <= dense <= n:
            raise ValueError(f"HybridLM: num_dense_layers={dense} of {n} "
                             f"layers")
        hd = config["hidden_size"] // config["num_attention_heads"]
        fields = dict(
            rms_norm_eps=config["norm_eps"],
            shared_intermediate_size=config["intermediate_size"],
            attention_multiplier=hd ** -0.5, embedding_multiplier=1.0,
            residual_multiplier=1.0, logits_scaling=1.0,
            qk_norm=True, qk_norm_heads=True,
            rope_theta=float(config["rope_theta"]))
        if "conv" in kinds:
            fields["conv_L_cache"] = _need(config, "conv_L_cache", "conv")
        if dense < n:
            fields.update(
                num_dense_layers=dense,
                norm_topk_prob=bool(config.get("norm_topk_prob", True)),
                routed_scaling_factor=float(
                    config.get("routed_scaling_factor", 1.0)),
                use_expert_bias=bool(config.get("use_expert_bias", False)),
                **{key: _need(config, key, "expert") for key in (
                    "num_experts", "moe_intermediate_size",
                    "num_experts_per_tok")})
        return fields

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
        from . import recurrent

        kinds = set(_known_kinds(config.layer_types))
        if len(kinds & set(RECURRENT_KINDS)) > 1:
            raise ValueError("HybridLM: one kind of recurrent layer a model "
                             "(the cache has one state member), not "
                             f"{sorted(kinds & set(RECURRENT_KINDS))}")
        if config.num_attention_heads % config.num_key_value_heads:
            raise ValueError("HybridLM: query heads must group evenly over "
                             "the K/V heads")
        if config.rope_theta is not None and config.head_dim % 2:
            raise ValueError("HybridLM: a rotated head must be even")
        if config.num_experts_per_tok > config.num_experts > 0:
            raise ValueError("HybridLM: more experts a token than experts")
        self.cfg = config
        self.mesh = mesh or model_mesh()
        # the recurrent layers' mixer (models/recurrent.py); a model with
        # none keeps Mamba-2's shapes for its empty state members
        self.mixer = recurrent.MIXERS[next(
            (k for k in RECURRENT_KINDS if k in kinds), "mamba")](
                config, self.mesh, self._rms)
        # a layer's index among the layers that share its member of the
        # cache: its page of the K/V slabs or of the state slabs
        self._page = []
        seen = {False: 0, True: 0}
        for kind in config.layer_types:
            self._page.append(seen[kind in ATTENTION_KINDS])
            seen[kind in ATTENTION_KINDS] += 1
        self.n_attention, self.n_recurrent = seen[True], seen[False]
        # the layers with routed experts: those after the leading dense
        # ones, a page of `routed` each
        self.n_expert_layers = len(config.layer_types) \
            - config.num_dense_layers if config.num_experts else 0
        # the cache's members, in order (module docstring)
        self.members = ("k", "v") \
            + (("state",) if self.mixer.state_shape is not None else ()) \
            + ("conv",) + (("routed",) if self.n_expert_layers else ())
        # what `tick_counters` counts, in its order (`WindowMoELM`'s names):
        # `experts.routing_counters`' three where the block has experts, the
        # live K/V rows where it has `full_attention` layers
        self.tick_counter_names = (
            ("expert_assignments", "experts_hit", "expert_tokens_max")
            if self.n_expert_layers else ()) + (
                ("kv_rows_live_full",)
                if "full_attention" in config.layer_types else ())

    def _is_dense(self, i):
        return not self.cfg.num_experts or i < self.cfg.num_dense_layers

    # -- parameters ---------------------------------------------------------

    def _shapes(self):
        c = self.cfg
        d, f = c.hidden_size, c.shared_intermediate_size
        shapes = {"embed": (c.vocab_size, d), "norm_f": (d,)}
        if not c.tie_word_embeddings:
            shapes["head"] = (d, c.vocab_size)
        for i, kind in enumerate(c.layer_types):
            shapes.update({f"l{i}.norm1": (d,), f"l{i}.norm2": (d,)})
            if self._is_dense(i):
                shapes.update({f"l{i}.w_in": (d, 2 * f),
                               f"l{i}.w_out": (f, d)})
            else:
                fe = c.moe_intermediate_size
                shapes.update({
                    f"l{i}.router": (d, c.num_experts),
                    f"l{i}.experts_in": (c.num_experts, d, 2 * fe),
                    f"l{i}.experts_out": (c.num_experts, fe, d)})
                if c.use_expert_bias:
                    shapes[f"l{i}.router_bias"] = (c.num_experts,)
            if kind in ATTENTION_KINDS:
                kv = c.num_key_value_heads * c.head_dim
                shapes.update({f"l{i}.wq": (d, d), f"l{i}.wk": (d, kv),
                               f"l{i}.wv": (d, kv), f"l{i}.wo": (d, d)})
                if c.qk_norm:
                    shapes.update(
                        {f"l{i}.q_norm": (c.head_dim,),
                         f"l{i}.k_norm": (c.head_dim,)}
                        if c.qk_norm_heads else
                        {f"l{i}.q_norm": (d,), f"l{i}.k_norm": (kv,)})
            else:
                shapes.update(self.mixer.shapes(i))
        return shapes

    def param_specs(self):
        repl = NamedSharding(self.mesh, P())
        return {name: repl for name in self._shapes()}

    def init_params(self, key):
        """Random weights, drawn in float32 and kept in `dtype`: normal /
        sqrt(fan_in) for matrices (a tied embedding's fan-in is the hidden
        size, which its use as the output head contracts; an untied one is
        only looked up and is drawn at unit variance), ones for the norms
        and `D`, and the Mamba-2 reference initialisation for what decides
        the decays of either mixer — `A_log = log U[1, 16]`, `dt_bias =
        softplus^-1(log-uniform[1e-3, 1e-1])` — a normal draw there gives
        degenerate decays. A router and its selection bias stay float32
        whatever the dtype; the bias is normal * 0.02, large enough that
        selection by `s + b` differs from selection by `s` for some tokens
        and not for all (`WindowMoELM.init_params`)."""
        from .recurrent import _softplus_inverse_steps

        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = self._shapes()
        specs = self.param_specs()
        ones = ("norm1", "norm2", "norm_f", "q_norm", "k_norm") \
            + self.mixer.ONES
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            leaf = name.rpartition(".")[2]
            if leaf in ones:
                val = jnp.ones(shape, jnp.float32)
            elif leaf == "A_log":
                val = jnp.log(jax.random.uniform(k, shape, jnp.float32,
                                                 1.0, 16.0))
            elif leaf == "dt_bias":
                val = _softplus_inverse_steps(k, shape)
            elif leaf == "router_bias":
                val = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                if leaf == "embed":
                    fan_in = c.hidden_size if c.tie_word_embeddings else 1
                elif leaf in ("conv_w", "conv_b"):  # its kernel
                    fan_in = self.mixer.conv_shape[0] + 1
                else:                   # a stacked expert's own input width
                    fan_in = shape[-2]
                val = jax.random.normal(k, shape, jnp.float32) \
                    / fan_in ** 0.5
            kept = jnp.float32 if leaf in ("router", "router_bias") else dt
            params[name] = jax.device_put(val.astype(kept), specs[name])
        return params

    # -- pieces -------------------------------------------------------------

    # Device-side scopes (`jax.named_scope`: in every instruction's op_name,
    # read by benchmark/program_scopes.py): `embed`, `norm`, `mlp`, `head`,
    # `attn.project`, `attn.qknorm`, `attn.prefill` | `attn.decode`,
    # `attn.out`, `cache.write`, and the mixer's (models/recurrent.py):
    # `mamba.project`, `mamba.conv`, `mamba.gates`, `mamba.ssd` |
    # `mamba.state_update`, `mamba.out`; `gdn.project`, `gdn.conv`,
    # `gdn.gates`, `gdn.chunk` | `gdn.state_update`, `gdn.out`;
    # `shortconv.project`, `shortconv.conv`, `shortconv.out`. With
    # positions `attn.rotary`; with experts `moe.route`, `moe.group`,
    # `moe.experts` (models/experts.py).

    def _rms(self, x, g, scope="norm"):
        with jax.named_scope(scope):
            x32 = x.astype(jnp.float32)
            out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                  + self.cfg.rms_norm_eps)
            return (out * g.astype(jnp.float32)).astype(x.dtype)

    def _post(self, kind):
        return kind in self.cfg.post_norm_kinds

    def _add(self, h, x):
        """The residual: `h + residual_multiplier * x`."""
        m = self.cfg.residual_multiplier
        return h + (x if m == 1 else m * x)

    def _mixed(self, params, i, kind, h, mixed):
        """The mixer's output into the residual stream, through the layer's
        first norm where that follows the sub-layer."""
        if self._post(kind):
            mixed = self._rms(mixed, params[f"l{i}.norm1"])
        return self._add(h, mixed)

    def _mixer_input(self, params, i, kind, h):
        return h if self._post(kind) \
            else self._rms(h, params[f"l{i}.norm1"])

    def _mlp(self, params, i, kind, h, real=None):
        """The MLP sub-layer with its norm and residual: `(h, local)` —
        `local` [T, k] is what an expert layer routed (`experts.
        expert_layer`; `real` [T] marks the tokens that exist), None for a
        dense layer."""
        post = self._post(kind)
        u = h if post else self._rms(h, params[f"l{i}.norm2"])
        if not self._is_dense(i):
            out, local = experts.expert_layer(
                u, real, lambda xs: self._route(params, i, xs),
                params[f"l{i}.experts_in"], params[f"l{i}.experts_out"],
                expert_first=0, mesh=self.mesh)
            return self._add(h, out), local
        with jax.named_scope("mlp"):
            g, v = jnp.split(u @ params[f"l{i}.w_in"], 2, axis=-1)
            out = (jax.nn.silu(g) * v) @ params[f"l{i}.w_out"]
            if not post:
                return self._add(h, out), None
        return self._add(h, self._rms(out, params[f"l{i}.norm2"])), None

    def _route(self, params, i, x):
        """`x` [T, D] -> `(chosen [T, k] expert ids, weights [T, k]
        float32)`: the sigmoid router with its selection bias (zero without
        `use_expert_bias`) and the published 1e-6 under the
        normalisation."""
        c = self.cfg
        return experts.sigmoid_route(
            x, params[f"l{i}.router"],
            params[f"l{i}.router_bias"] if c.use_expert_bias else 0.0,
            c.num_experts_per_tok, c.routed_scaling_factor,
            c.norm_topk_prob, eps=1e-6)

    def _qkv(self, params, i, u):
        """`u` [T, D] -> q [T, Hq, hd], k and v [T, Hkv, hd]; `q` and `k`
        through their norms where the block has them — over the whole
        vector, or over each head's entries (`qk_norm_heads`)."""
        c = self.cfg
        t = u.shape[0]
        heads = (c.num_attention_heads, c.num_key_value_heads,
                 c.num_key_value_heads)
        with jax.named_scope("attn.project"):
            q, k, v = ((u @ params[f"l{i}.w{s}"]).reshape(t, n, c.head_dim)
                       for s, n in zip("qkv", heads))
        if c.qk_norm_heads:
            q, k = (self._rms(x, params[f"l{i}.{s}_norm"], "attn.qknorm")
                    for s, x in (("q", q), ("k", k)))
        elif c.qk_norm:
            q, k = (self._rms(x.reshape(t, -1), params[f"l{i}.{s}_norm"],
                              "attn.qknorm").reshape(x.shape)
                    for s, x in (("q", q), ("k", k)))
        return q, k, v

    def _rotate(self, q, k, positions):
        """`q` and `k` [T, heads, hd] rotated over the whole head at
        `positions` [T] (a callable: they are made only where the block has
        positions); as they came where it has none."""
        c = self.cfg
        if c.rope_theta is None:
            return q, k
        with jax.named_scope("attn.rotary"):
            freq = rotary.yarn_inv_freq(c.head_dim, c.rope_theta, None)
            at = positions()
            return (rotary.rotate_half(q, at, freq),
                    rotary.rotate_half(k, at, freq))

    def _attention_seq(self, params, i, u):
        """The attention mixer over one whole sequence `u` [L, D]: `(out
        [L, D], k, v [L, Hkv, hd])`. Heads of 128 go through the prefill
        kernel of `ops/pallas_window.py` where :meth:`prefill_block` says
        so; else plain XLA: one score matrix (granite: 4 of 40 layers), or
        blockwise where :meth:`prefill_blockwise` says that one would not
        fit."""
        c = self.cfg
        L = u.shape[0]
        q, k, v = self._qkv(params, i, u)
        q, k = self._rotate(q, k, lambda: jnp.arange(L))
        group = c.num_attention_heads // c.num_key_value_heads
        block = self.prefill_block(L)
        with jax.named_scope("attn.prefill"):
            if block is not None:
                from ..ops import pallas_attention as pa
                from ..ops import pallas_window as pw

                a = pw.band_prefill_attend(
                    q.transpose(1, 0, 2), k.transpose(1, 0, 2),
                    v.transpose(1, 0, 2), block=block,
                    scale=c.attention_multiplier,
                    interpret=pa.pallas_interpret()) \
                    .transpose(1, 0, 2).reshape(L, c.hidden_size)
            elif self.prefill_blockwise(L):
                a = window_moe._band_attention(
                    q, k.transpose(1, 0, 2), v.transpose(1, 0, 2),
                    c.attention_multiplier, None).reshape(L, c.hidden_size)
            else:
                q = q.reshape(L, c.num_key_value_heads, group, c.head_dim)
                s = jnp.einsum("qhgd,khd->hgqk", q, k,
                               preferred_element_type=jnp.float32) \
                    * c.attention_multiplier
                ar = jnp.arange(L)
                # large-negative, not -inf: see TransformerLM.prefill
                s = s + jnp.where(ar[:, None] >= ar[None, :], 0.0, -1e9)
                p = jax.nn.softmax(s, axis=-1).astype(u.dtype)
                a = jnp.einsum("hgqk,khd->qhgd", p, v).reshape(
                    L, c.hidden_size)
        with jax.named_scope("attn.out"):
            return a @ params[f"l{i}.wo"], k, v

    # -- forward ------------------------------------------------------------

    def _embed(self, rows):
        m = self.cfg.embedding_multiplier
        return (rows if m == 1 else rows * m).astype(
            jnp.dtype(self.cfg.dtype))

    def _logits(self, params, h):
        c = self.cfg
        h = self._rms(h, params["norm_f"])
        with jax.named_scope("head"):
            if not c.tie_word_embeddings:
                return (h @ params["head"]).astype(jnp.float32)
            return (h @ params["embed"].T).astype(jnp.float32) \
                / c.logits_scaling

    def _sequence(self, params, tokens, length):
        """One whole sequence `tokens` [L]: the hidden states [L, D] after
        the last layer and, per layer, what a cache keeps of it: `(k, v)` of
        an attention layer, `(state, conv_tail)` of a recurrent layer."""
        c = self.cfg
        with jax.named_scope("embed"):
            h = self._embed(jnp.take(params["embed"], tokens, axis=0))
        # padding is routed to no expert
        real = jnp.arange(tokens.shape[0]) < length \
            if self.n_expert_layers else None
        kept = []
        for i, kind in enumerate(c.layer_types):
            u = self._mixer_input(params, i, kind, h)
            if kind in ATTENTION_KINDS:
                mixed, *keep = self._attention_seq(params, i, u)
            else:
                mixed, *keep = self.mixer.seq(params, i, u, length)
            kept.append(keep)
            h, _ = self._mlp(params, i, kind,
                             self._mixed(params, i, kind, h, mixed), real)
        return h, kept

    def forward(self, params, tokens):
        """tokens [B, L] int32 -> logits [B, L, V] float32: the full forward,
        no cache."""
        def one(seq):
            h, _ = self._sequence(params, seq, seq.shape[0])
            return self._logits(params, h)

        if self.n_expert_layers:    # the grouped product is not batched
            return jnp.stack([one(seq) for seq in tokens])
        return jax.vmap(one)(tokens)

    # -- the cache protocol (serving/generation) ------------------------------

    def init_cache(self, max_slots, max_len=None):
        """The serving cache: its :attr:`members` — `(K, V, state, conv)`, or
        `(K, V, conv, routed)` for a block whose mixer keeps no state over
        experts — zeroed, each with the slot as its leading axis (module
        docstring). K/V rows are paid by the attention layers only; what the
        recurrent layers keep has one size whatever `max_len`."""
        c = self.cfg
        max_len = c.max_len if max_len is None else int(max_len)
        if max_len > c.max_len:
            raise ValueError(f"cache max_len {max_len} exceeds the model's "
                             f"positional range {c.max_len}")
        s, dt = int(max_slots), jnp.dtype(c.dtype)
        sh = NamedSharding(self.mesh, P())
        kv = (s, self.n_attention, c.num_key_value_heads, max_len, c.head_dim)
        shapes = {"k": (kv, dt), "v": (kv, dt),
                  "conv": ((s, self.n_recurrent) + self.mixer.conv_shape,
                           dt)}
        if "state" in self.members:
            shapes["state"] = ((s, self.n_recurrent)
                               + self.mixer.state_shape, jnp.float32)
        if "routed" in self.members:
            shapes["routed"] = ((s, self.n_expert_layers,
                                 c.num_experts_per_tok), jnp.int32)
        return tuple(jax.device_put(jnp.zeros(*shapes[m]), sh)
                     for m in self.members)

    def decode_block(self, slab_shape, dtype):
        """As `TransformerLM.decode_block`: the Pallas decode kernel's block
        over the K/V slab's rows, or None for the XLA formulation. A slab
        whose heads are the lane width lies `hd`-minor on the chip and goes
        to `ops/pallas_window.py`'s kernel, any other to
        `ops/pallas_decode.py`'s (:meth:`decode_step` tells them apart the
        same way, and counts which body of either a layer's trace took:
        `attn.decode.kv128.one_query` / `.grouped`, `attn.decode.slab.
        one_query` / `.grouped`, once a trace, telemetry on)."""
        from ..ops import pallas_attention as pa

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        if slab_shape[-1] == pa._LANES:
            from ..ops import pallas_window as pw

            return pw.kv_block(slab_shape, dtype)
        from ..ops import pallas_decode as pd

        return pd.decode_block(slab_shape, dtype)

    def prefill_block(self, length):
        """The prefill attention kernel's block over a sequence of `length`
        positions (`ops/pallas_window.band_prefill_attend`, heads of 128
        only), or None for the XLA formulation; decided as
        :meth:`decode_block` is."""
        from ..ops import pallas_attention as pa

        if self.mesh.size > 1 or not pa.pallas_enabled() \
                or self.cfg.head_dim != pa._LANES:
            return None
        from ..ops import pallas_window as pw

        return pw.band_block(length)

    def prefill_blockwise(self, length):
        """Whether a prefill's attention in XLA over `length` positions runs
        blockwise with a running softmax (`window_moe._band_attention`: a
        query block meets only the key blocks of its triangle) rather than
        as one `[query heads, L, L]` float32 score matrix: where that matrix
        would pass `_SCORES_BUDGET` and whole blocks divide the sequence (32
        heads at 8,192 positions: 8.6 GB). From shapes alone, before the
        call."""
        return length % window_moe._ATTN_BLOCK == 0 and 4 * length * length \
            * self.cfg.num_attention_heads > _SCORES_BUDGET

    def state_kernel(self, slab_shape, dtype):
        """Whether :meth:`decode_step` advances a recurrent-state slab of
        this shape through the mixer's Pallas kernel (``ops/pallas_ssm.py``:
        each live slot's state read once and written once, where it lies)
        or in XLA (which reads it more than once). Decided as
        :meth:`decode_block` is; which way a layer's trace went is counted
        (`mamba.state_update.kernel` / `.xla`, `gdn.state_update.kernel` /
        `.xla`, once a trace, telemetry on)."""
        return self.mixer.kernel(slab_shape, dtype)

    def cache_traits(self, cache):
        """What the engine may ask about a cache it otherwise only carries
        (docs/faq/perf.md, "The cache protocol"). `state_bytes_per_slot` is
        what a live slot's recurrent layers read and write whole a tick: the
        state where the mixer keeps one, and the convolution window."""
        held = dict(zip(self.members, cache))
        slots = held["conv"].shape[0]
        traits = {
            "block": self.decode_block(cache[0].shape, cache[0].dtype),
            "state_bytes_per_slot": sum(
                int(held[m].nbytes) for m in ("state", "conv") if m in held)
            // slots,
            "rewindable": False,
            "why_not_rewindable":
                "the recurrent layers' state and convolution window hold "
                "only the last token's value: it cannot be extended from a "
                "row offset (prefix reuse) nor rolled back (speculation) "
                "without snapshots, which this cache does not keep"}
        if self.tick_counter_names:
            traits["tick_counters"] = self.tick_counter_names
        return traits

    def tick_counters(self, *cache_positions):
        """int32 `[len(tick_counter_names)]` of ONE decode step, from what
        that step left in the cache (`routed`) and its positions:
        `experts.routing_counters`' three, and the K/V rows the live slots
        attend, summed over the attention layers."""
        *cache, positions = cache_positions
        held = dict(zip(self.members, cache))
        alive = positions >= 0
        counted = []
        if self.n_expert_layers:
            counted.append(experts.routing_counters(
                held["routed"], alive, self.cfg.num_experts))
        if "kv_rows_live_full" in self.tick_counter_names:
            rows = jnp.where(alive, jnp.minimum(positions + 1,
                                                held["k"].shape[3]), 0)
            counted.append((rows.sum(dtype=jnp.int32)
                            * self.n_attention)[None])
        return jnp.concatenate(counted)

    def prefill(self, params, *cache_tokens_length_slot):
        """`prefill(params, *cache, tokens, length, slot)`: the full-prompt
        forward for ONE session into slot `slot`. Writes the K/V rows `[0,
        Lb)` of the attention layers and REPLACES what the slot's recurrent
        layers keep (state, convolution window) with that of the prompt's
        true last token, computed from zero — nothing of what the previous
        occupant left is read; `routed` stays. Returns `(logits [V] fp32 at
        position length - 1, *cache)`. `tokens` [Lb] is the prompt padded
        (with anything) to the bucket; `length` and `slot` are traced."""
        *cache, tokens, length, slot = cache_tokens_length_slot
        held = dict(zip(self.members, cache))
        h, kept = self._sequence(params, tokens, length)
        with jax.named_scope("cache.write"):
            for kind, page, keep in zip(self.cfg.layer_types, self._page,
                                        kept):
                at = (slot, page, 0, 0, 0)
                if kind in ATTENTION_KINDS:
                    for m, x in zip("kv", keep):
                        held[m] = lax.dynamic_update_slice(
                            held[m], x.transpose(1, 0, 2)[None, None]
                            .astype(held[m].dtype), at)
                else:
                    for m, x in zip(("state", "conv"), keep):
                        if x is not None:
                            held[m] = lax.dynamic_update_slice(
                                held[m], x[None, None].astype(held[m].dtype),
                                at[:held[m].ndim])
            last = lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)
        return (self._logits(params, last)[0],
                *(held[m] for m in self.members))

    def decode_step(self, params, *cache_tokens_positions):
        """`decode_step(params, *cache, tokens, positions)`: one fused
        incremental step over every slot. A live slot consumes one token,
        writes its K/V row at `positions[s]` in each attention layer,
        attends rows `[0, positions[s]]`, advances what each recurrent
        layer keeps by one step, and leaves its choice of experts in
        `routed`. A NEGATIVE position marks a dead slot: nothing of it is
        written, attended or advanced. Returns `(logits [S, V] fp32,
        *cache)`; jit with the cache donated. How the cache is touched is
        decided from shapes, policy and mesh before the call
        (:meth:`decode_block`, :meth:`state_kernel`): the Pallas kernels on
        one TPU chip, else the same mathematics in XLA."""
        from ..ops import pallas_attention as pa

        c = self.cfg
        *cache, tokens, positions = cache_tokens_positions
        held = dict(zip(self.members, cache))
        ck, cv, ssm, conv = (held.get(m) for m in ("k", "v", "state",
                                                    "conv"))
        block = self.decode_block(ck.shape, ck.dtype)
        positions = jnp.minimum(positions, ck.shape[3] - 1)
        alive = positions >= 0
        with jax.named_scope("embed"):
            h = self._embed(_table_rows(params["embed"], tokens))
        chose = []
        for i, (kind, page) in enumerate(zip(c.layer_types, self._page)):
            u = self._mixer_input(params, i, kind, h)
            if kind in ATTENTION_KINDS:
                q, k, v = self._qkv(params, i, u)
                q, k = self._rotate(q, k, lambda: jnp.maximum(positions, 0))
                with jax.named_scope("attn.decode"):
                    k, v = k.astype(ck.dtype), v.astype(cv.dtype)
                    if block is None:
                        ck = _write_rows(ck, page, positions, k)
                        cv = _write_rows(cv, page, positions, v)
                        a = _attend_rows(q, ck, cv, page, positions,
                                         scale=c.attention_multiplier)
                    elif c.head_dim == pa._LANES:
                        from ..ops import pallas_window as pw

                        pw.count_body(q, ck)
                        a, ck, cv = pw.kv_update_attend(
                            q, k, v, ck, cv, jnp.int32(page), positions,
                            block=block, scale=c.attention_multiplier,
                            interpret=pa.pallas_interpret())
                    else:
                        from ..ops import pallas_decode as pd

                        pd.count_body(q, ck)
                        a, ck, cv = pd.decode_update_attend(
                            q, k, v, ck, cv, jnp.int32(page), positions,
                            block=block, scale=c.attention_multiplier,
                            interpret=pa.pallas_interpret())
                with jax.named_scope("attn.out"):
                    mixed = a.astype(h.dtype).reshape(-1, c.hidden_size) \
                        @ params[f"l{i}.wo"]
            else:
                mixed, ssm, conv = self.mixer.step(params, i, u, ssm, conv,
                                                   page, alive)
            h, local = self._mlp(params, i, kind,
                                 self._mixed(params, i, kind, h, mixed),
                                 alive)
            if local is not None:
                chose.append(local)
        held.update(k=ck, v=cv, state=ssm, conv=conv)
        if chose:
            with jax.named_scope("cache.write"):
                held["routed"] = jnp.where(
                    alive[:, None, None], jnp.stack(chose, axis=1),
                    held["routed"])
        return (self._logits(params, h), *(held[m] for m in self.members))
