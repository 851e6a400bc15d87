"""Latent-attention language model with a dropless expert layer (the
DeepSeek-V2/V3 family's block; `model_type` sarvam_mla is one): rotary
positions with the YaRN correction, RMSNorm, multi-head latent attention, a
sigmoid router with a selection bias over top-k SiLU-gated experts beside a
shared expert, leading dense layers, an untied head.

    h = embed[tokens]
    per layer:  h += attention(RMSNorm(h));  h += mlp(RMSNorm(h))
    logits = RMSNorm(h) @ head

* attention — `q = x W_q -> [H, nope + rope]`, RMSNorm over each head's
  entries, the rope part rotated. `x W_dkv = c[R] | k_r[rope]`; `c <-
  RMSNorm(c)`; `k_r` rotated, one for all heads. `[k_nope, v] = c W_ukv`
  per head. Scores `(q_nope . k_nope + q_rope . k_r) * softmax_scale`,
  causal softmax in float32, `(P v) W_o`.
* expert layer — `s = sigmoid(x W_g)` over ALL the model's experts; the
  `top_k` with the largest `s + b` (the bias `b` enters the selection
  only); weights `s_e / sum_selected s * routed_scaling_factor`; `y = sum_e
  w_e E_e(x) + E_shared(x)`. **Dropless**: there is no capacity; the (token,
  expert) assignments are sorted by expert and the experts' matmuls are
  grouped products over the sorted rows (a Pallas grouped matmul on the
  TPU, `lax.ragged_dot` elsewhere), so the work follows the assignments. **The layer is told which experts it holds**
  (`expert_first`, `experts_held` of `num_experts`): it routes over all of
  them and sums over the chosen experts it holds; what the absent experts
  would add is left out (they live on other chips; nothing here stands in
  for them or for the exchange).

Serving (`GenerationEngine`) sees the model through the cache protocol
(docs/faq/perf.md, "The cache protocol"). The cache holds of every position
the LATENT, not keys and values — `RMSNorm(c)` and the rotated `k_r`,
`R + rope` numbers a layer whatever the number of heads:

    c       [slots, layers, max_len, R]        dtype
    k_r     [slots, layers, rope, max_len]     dtype (positions on lanes)
    routed  [slots, expert layers, top_k]      int32

(two members so that neither is padded to whole lane rows; `routed` is what
the last decode step chose for the slot — a held expert's local index, -1
for an expert held elsewhere — from which `tick_counters` counts inside the
decode's own program). Prefill attends in the form above (up-project,
attend). Decode uses the ABSORBED form: `qc_h = q_nope,h W_uk,h^T`, scores
`qc . c + q_rope . k_r` against the latent rows, the weighted sum of `c`
rows, then `W_uv,h` — each cached row is read once for all heads
(`ops/pallas_latent.py` on one TPU chip: the kernel also takes the tick's
new row and puts it into the slabs; else the same mathematics in XLA, the
row written by a `dynamic_update_slice` a slot).
The model offers no `prefill_at` / `verify_step`, and `cache_traits` says
so.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from .. import telemetry
from ..parallel.spmd import model_mesh
from . import experts, rotary
from .transformer import _table_rows

__all__ = ["LatentMoELMConfig", "LatentMoELM"]

# rows of one blockwise-attention step in a prefill
_ATTN_BLOCK = 1024


@dataclasses.dataclass(frozen=True)
class LatentMoELMConfig:
    """The published configuration's keys under their published names
    (`from_config` reads a `config.json`-shaped dict), what says which part
    of a layer this chip holds (`experts_held`, `expert_first`), and what
    serving adds (`max_len`, `dtype`)."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_hidden_layers: int = 3
    num_attention_heads: int = 4
    kv_lora_rank: int = 32
    qk_nope_head_dim: int = 16
    qk_rope_head_dim: int = 8
    v_head_dim: int = 16
    intermediate_size: int = 128
    moe_intermediate_size: int = 32
    first_k_dense_replace: int = 1
    num_experts: int = 16           # the router's width: ALL the experts
    experts_held: int = 16          # ... of which this chip holds these
    expert_first: int = 0
    num_experts_per_tok: int = 4
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: tuple = ()        # sorted (key, value) pairs, or empty
    max_len: int = 2048
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, config, max_len=None, dtype=None):
        """From a published `config.json` (a dict). `num_experts` there
        counts the experts HELD when the file is a chip's share of a
        deployment (`published.num_experts` then gives the router's width
        and `share.expert_first` the first held expert). What the block
        cannot express is refused by name rather than ignored."""
        for key, want in (("hidden_act", "silu"),
                          ("tie_word_embeddings", False),
                          ("use_qk_norm", True),
                          ("moe_router_enable_expert_bias", True)):
            if key in config and config[key] != want:
                raise ValueError(f"LatentMoELM: {key}={config[key]!r} is "
                                 f"not supported (only {want!r})")
        scaling = config.get("rope_scaling") or {}
        if scaling and scaling.get("type") != "deepseek_yarn":
            raise ValueError(f"LatentMoELM: rope_scaling type "
                             f"{scaling.get('type')!r} is not supported")
        held = config["num_experts"]
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_hidden_layers=config["num_hidden_layers"],
            num_attention_heads=config["num_attention_heads"],
            kv_lora_rank=config["kv_lora_rank"],
            qk_nope_head_dim=config["qk_nope_head_dim"],
            qk_rope_head_dim=config["qk_rope_head_dim"],
            v_head_dim=config["v_head_dim"],
            intermediate_size=config["intermediate_size"],
            moe_intermediate_size=config["moe_intermediate_size"],
            first_k_dense_replace=config["first_k_dense_replace"],
            num_experts=config.get("published", {}).get("num_experts", held),
            experts_held=held,
            expert_first=config.get("share", {}).get("expert_first", 0),
            num_experts_per_tok=config["num_experts_per_tok"],
            num_shared_experts=config["num_shared_experts"],
            routed_scaling_factor=config["routed_scaling_factor"],
            rms_norm_eps=config["rms_norm_eps"],
            rope_theta=config["rope_theta"],
            rope_scaling=tuple(sorted(scaling.items())),
            max_len=int(config["max_position_embeddings"]
                        if max_len is None else max_len),
            dtype=config.get("dtype", "bfloat16") if dtype is None else dtype)

    # derived sizes
    @property
    def q_head_dim(self):
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def n_expert_layers(self):
        return self.num_hidden_layers - self.first_k_dense_replace

    def _mscale(self, key):
        s = dict(self.rope_scaling)
        if not s or s["factor"] <= 1:
            return 1.0
        return 0.1 * s[key] * math.log(s["factor"]) + 1.0

    @property
    def softmax_scale(self):
        """`q_head_dim^-1/2 * m^2`, `m` the YaRN attention factor."""
        return self.q_head_dim ** -0.5 * self._mscale("mscale_all_dim") ** 2

    @property
    def rope_amplitude(self):
        """What cos and sin are multiplied by (`mscale / mscale_all_dim`)."""
        return self._mscale("mscale") / self._mscale("mscale_all_dim")

    def inv_freq(self):
        """`deepseek_yarn` inverse frequencies of the rotary entries
        (`rotary.yarn_inv_freq`)."""
        return rotary.yarn_inv_freq(self.qk_rope_head_dim, self.rope_theta,
                                    dict(self.rope_scaling))


class LatentMoELM:
    """Functional latent-attention expert LM bound to a mesh; `params` is a
    flat dict name -> jax.Array. All methods are pure. Weights are
    replicated over the mesh: the share of a layer this chip holds is a
    property of the configuration, not a sharding."""

    def __init__(self, config, mesh=None):
        c = config
        if not 0 <= c.expert_first <= c.expert_first + c.experts_held \
                <= c.num_experts:
            raise ValueError(
                f"LatentMoELM: held experts [{c.expert_first}, "
                f"{c.expert_first + c.experts_held}) are not among the "
                f"router's {c.num_experts}")
        if c.num_experts_per_tok > c.num_experts:
            raise ValueError("LatentMoELM: more experts a token than experts")
        if c.qk_rope_head_dim % 2:
            raise ValueError("LatentMoELM: the rotary width must be even")
        self.cfg = c
        self.mesh = mesh or model_mesh()

    def _is_dense(self, i):
        return i < self.cfg.first_k_dense_replace

    # -- parameters ---------------------------------------------------------

    def _shapes(self):
        c = self.cfg
        d, h = c.hidden_size, c.num_attention_heads
        f = c.moe_intermediate_size
        shapes = {"embed": (c.vocab_size, d), "head": (d, c.vocab_size),
                  "norm_f": (d,)}
        for i in range(c.num_hidden_layers):
            shapes.update({
                f"l{i}.norm1": (d,), f"l{i}.norm2": (d,),
                f"l{i}.wq": (d, h * c.q_head_dim),
                f"l{i}.q_norm": (c.q_head_dim,),
                f"l{i}.w_dkv": (d, c.kv_lora_rank + c.qk_rope_head_dim),
                f"l{i}.kv_norm": (c.kv_lora_rank,),
                f"l{i}.w_ukv": (c.kv_lora_rank,
                                h * (c.qk_nope_head_dim + c.v_head_dim)),
                f"l{i}.wo": (h * c.v_head_dim, d)})
            if self._is_dense(i):
                shapes.update({
                    f"l{i}.w_in": (d, 2 * c.intermediate_size),
                    f"l{i}.w_out": (c.intermediate_size, d)})
            else:
                fs = f * c.num_shared_experts
                shapes.update({
                    f"l{i}.router": (d, c.num_experts),
                    f"l{i}.router_bias": (c.num_experts,),
                    f"l{i}.experts_in": (c.experts_held, d, 2 * f),
                    f"l{i}.experts_out": (c.experts_held, f, d),
                    f"l{i}.shared_in": (d, 2 * fs),
                    f"l{i}.shared_out": (fs, d)})
        return shapes

    def param_specs(self):
        repl = NamedSharding(self.mesh, P())
        return {name: repl for name in self._shapes()}

    def init_params(self, key):
        """Random weights: matrices normal / sqrt(fan_in) (an expert's
        fan-in is its own input width, the embedding's the hidden size),
        norm weights 1, the router's selection bias normal * 0.02 — large
        enough that selection by `s + b` differs from selection by `s`.
        The router and its bias stay float32 whatever the dtype."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = self._shapes()
        specs = self.param_specs()
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            leaf = name.rpartition(".")[2]
            if leaf in ("norm1", "norm2", "norm_f", "q_norm", "kv_norm"):
                val = jnp.ones(shape, dt)
            elif leaf == "router_bias":
                val = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = c.hidden_size if leaf == "embed" else shape[-2]
                # drawn in the served dtype: a float32 draw of the stacked
                # experts (1 GiB a layer in bfloat16) would double it
                val = jax.random.normal(
                    k, shape, jnp.float32 if leaf == "router" else dt) \
                    * float(fan_in) ** -0.5     # a python float: dtype kept
            params[name] = jax.device_put(val, specs[name])
        return params

    # -- pieces -------------------------------------------------------------

    # Device-side scopes (`jax.named_scope`: in every instruction's op_name,
    # read by benchmark/program_scopes.py; where they nest, the outermost
    # names the work): `embed`, `norm`, `mlp`, `head`, `mla.project`,
    # `mla.absorb`, `mla.attend`, `attn.out`, `cache.write`, `moe.route`,
    # `moe.group`, `moe.experts`, `moe.shared`.

    def _rms(self, x, g):
        with jax.named_scope("norm"):
            x32 = x.astype(jnp.float32)
            out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                  + self.cfg.rms_norm_eps)
            return (out * g.astype(jnp.float32)).astype(x.dtype)

    def _rotate(self, x, positions):
        """Half-split rotary embedding of the last axis of `x` [T, ...,
        rope] at `positions` [T], computed in float32."""
        return rotary.rotate_half(x, positions, self.cfg.inv_freq(),
                                  self.cfg.rope_amplitude)

    def _project(self, params, i, u, positions):
        """`u` [T, D] at `positions` [T] -> `(q_nope [T, H, nope], q_rope
        [T, H, rope] rotated, c [T, R] normalised, k_r [T, rope]
        rotated)`: what attention needs of a token, and (`c`, `k_r`) what
        the cache keeps of it."""
        c = self.cfg
        with jax.named_scope("mla.project"):
            t = u.shape[0]
            q = (u @ params[f"l{i}.wq"]).reshape(t, c.num_attention_heads,
                                                 c.q_head_dim)
            q = self._rms(q, params[f"l{i}.q_norm"])
            q_nope = q[..., :c.qk_nope_head_dim]
            q_rope = self._rotate(q[..., c.qk_nope_head_dim:], positions)
            ckr = u @ params[f"l{i}.w_dkv"]
            lat = self._rms(ckr[:, :c.kv_lora_rank], params[f"l{i}.kv_norm"])
            k_r = self._rotate(ckr[:, c.kv_lora_rank:], positions)
        return q_nope, q_rope, lat, k_r

    def _w_ukv(self, params, i):
        """`W_ukv` as `(W_uk [R, H, nope], W_uv [R, H, v])`."""
        c = self.cfg
        w = params[f"l{i}.w_ukv"].reshape(
            c.kv_lora_rank, c.num_attention_heads,
            c.qk_nope_head_dim + c.v_head_dim)
        return w[..., :c.qk_nope_head_dim], w[..., c.qk_nope_head_dim:]

    def _attention_seq(self, params, i, u):
        """Latent attention over one whole sequence `u` [L, D] in the
        UNABSORBED form (keys and values up-projected, then attended):
        `(out [L, D], c [L, R], k_r [L, rope])`."""
        c = self.cfg
        L = u.shape[0]
        q_nope, q_rope, lat, k_r = self._project(params, i, u, jnp.arange(L))
        block = self.prefill_block(L)
        with jax.named_scope("mla.attend"):
            w_uk, w_uv = self._w_ukv(params, i)
            if block is not None:
                from ..ops import pallas_attention as pa
                from ..ops import pallas_latent

                # head-major, straight out of the up-projection
                a = pallas_latent.prefill_attend(
                    q_nope.transpose(1, 0, 2), q_rope.transpose(1, 0, 2),
                    jnp.einsum("lr,rhd->hld", lat, w_uk), k_r,
                    jnp.einsum("lr,rhd->hld", lat, w_uv), block=block,
                    scale=c.softmax_scale,
                    interpret=pa.pallas_interpret()).transpose(1, 0, 2)
            else:
                k_nope = jnp.einsum("lr,rhd->lhd", lat, w_uk)
                v = jnp.einsum("lr,rhd->lhd", lat, w_uv)
                q = jnp.concatenate([q_nope, q_rope], axis=-1)
                k = jnp.concatenate(
                    [k_nope, jnp.broadcast_to(k_r[:, None, :],
                                              (L,) + q_rope.shape[1:])],
                    axis=-1)
                a = _causal_attention(q, k, v, c.softmax_scale)
        with jax.named_scope("attn.out"):
            return a.reshape(L, -1).astype(u.dtype) @ params[f"l{i}.wo"], \
                lat, k_r

    def _attention_step(self, params, i, u, cache_c, cache_kr, page,
                        positions, block):
        """One token a slot through layer `i`'s attention in the ABSORBED
        form: `u` [S, D]; each live slot's latent row goes in at its
        position and the slot attends rows `[0, position]`. With a `block`
        the kernel does both (`pallas_latent.latent_attend` takes the row,
        merges it into the block that holds the position and sends one tile
        back to each slab); without one (`block is None`: a mesh, no TPU
        kernels, a shape the kernel refuses) XLA writes the row
        (`_write_rows`) and attends every row under a mask — the reference
        the kernel is held to, slabs bit for bit. Which way a layer's trace
        went is counted (`mla.attend.kernel` / `.xla`, once a trace,
        telemetry on). Returns `(out [S, D], cache_c, cache_kr)`."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_latent

        c = self.cfg
        q_nope, q_rope, lat, k_r = self._project(
            params, i, u, jnp.maximum(positions, 0))
        w_uk, w_uv = self._w_ukv(params, i)
        with jax.named_scope("mla.absorb"):
            qc = jnp.einsum("shd,rhd->shr", q_nope, w_uk)
        if telemetry._enabled:
            telemetry.counter("mla.attend."
                              + ("xla" if block is None else "kernel")).inc()
        if block is not None:
            with jax.named_scope("mla.attend"):
                o, cache_c, cache_kr = pallas_latent.latent_attend(
                    qc, q_rope, lat, k_r, cache_c, cache_kr, jnp.int32(page),
                    positions, block=block, scale=c.softmax_scale,
                    interpret=pa.pallas_interpret())
        else:
            with jax.named_scope("cache.write"):
                cache_c = _write_rows(cache_c, page, positions,
                                      lat[:, None, :], 2)
                cache_kr = _write_rows(cache_kr, page, positions,
                                       k_r[:, :, None], 3)
            with jax.named_scope("mla.attend"):
                o = _attend_latent(qc, q_rope, cache_c[:, page],
                                   cache_kr[:, page], positions,
                                   c.softmax_scale)
        with jax.named_scope("mla.absorb"):
            a = jnp.einsum("shr,rhd->shd", o.astype(u.dtype), w_uv)
        with jax.named_scope("attn.out"):
            return a.reshape(u.shape[0], -1) @ params[f"l{i}.wo"], \
                cache_c, cache_kr

    _gated = staticmethod(experts.gated_mlp)

    def _route(self, params, i, x):
        """`x` [T, D] -> `(chosen [T, k] expert ids of the whole router,
        weights [T, k] float32)`: `experts.sigmoid_route` — sigmoid scores
        in float32, selection by `s + b`, weights from `s` normalised over
        the selection."""
        c = self.cfg
        return experts.sigmoid_route(
            x, params[f"l{i}.router"], params[f"l{i}.router_bias"],
            c.num_experts_per_tok, c.routed_scaling_factor)

    def _experts(self, params, i, x, real):
        """The held experts' part of the expert layer for `x` [T, D]:
        `(y [T, D], local [T, k])`; `real` [T] marks the tokens that exist.
        The dropless grouping and the grouped products are
        `experts.expert_layer`'s, under this model's router."""
        return experts.expert_layer(
            x, real, lambda xs: self._route(params, i, xs),
            params[f"l{i}.experts_in"], params[f"l{i}.experts_out"],
            expert_first=self.cfg.expert_first, mesh=self.mesh)

    def _mlp(self, params, i, h, real=None):
        """The MLP sub-layer with its norm and residual: `(h, local)`;
        `local` [T, k] is the routing of an expert layer (a held expert's
        local index, -1 elsewhere), None for a dense layer."""
        x = self._rms(h, params[f"l{i}.norm2"])
        if self._is_dense(i):
            with jax.named_scope("mlp"):
                return h + self._gated(x, params[f"l{i}.w_in"],
                                       params[f"l{i}.w_out"]), None
        real = jnp.ones(x.shape[0], bool) if real is None else real
        y, local = self._experts(params, i, x, real)
        with jax.named_scope("moe.shared"):
            y = y + self._gated(x, params[f"l{i}.shared_in"],
                                params[f"l{i}.shared_out"])
        return h + y, local

    # -- forward ------------------------------------------------------------

    def _logits(self, params, h):
        h = self._rms(h, params["norm_f"])
        with jax.named_scope("head"):
            return (h @ params["head"]).astype(jnp.float32)

    def _sequence(self, params, tokens, length):
        """One whole sequence `tokens` [L] of which the first `length` are
        real: the hidden states [L, D] after the last layer and, per layer,
        the latent rows `(c [L, R], k_r [L, rope])` a cache keeps."""
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0) \
                .astype(jnp.dtype(self.cfg.dtype))
        real = jnp.arange(tokens.shape[0]) < length
        kept = []
        for i in range(self.cfg.num_hidden_layers):
            mixed, lat, k_r = self._attention_seq(
                params, i, self._rms(h, params[f"l{i}.norm1"]))
            kept.append((lat, k_r))
            h, _ = self._mlp(params, i, h + mixed, real)
        return h, kept

    def forward(self, params, tokens):
        """tokens [B, L] int32 -> logits [B, L, V] float32: the full forward,
        no cache."""
        def one(seq):
            h, _ = self._sequence(params, seq, seq.shape[0])
            return self._logits(params, h)

        return jnp.stack([one(seq) for seq in tokens])

    # -- the cache protocol (serving/generation) ------------------------------

    def init_cache(self, max_slots, max_len=None):
        """The serving cache: `(c, k_r, routed)`, zeroed, each with the slot
        as its leading axis (module docstring)."""
        c = self.cfg
        max_len = c.max_len if max_len is None else int(max_len)
        if max_len > c.max_len:
            raise ValueError(f"cache max_len {max_len} exceeds the model's "
                             f"positional range {c.max_len}")
        s, n, dt = int(max_slots), c.num_hidden_layers, jnp.dtype(c.dtype)
        sh = NamedSharding(self.mesh, P())
        shapes = (((s, n, max_len, c.kv_lora_rank), dt),
                  ((s, n, c.qk_rope_head_dim, max_len), dt),
                  ((s, max(c.n_expert_layers, 1), c.num_experts_per_tok),
                   jnp.int32))
        return tuple(jax.device_put(jnp.zeros(shape, t), sh)
                     for shape, t in shapes)

    def decode_block(self, slab_shape, dtype):
        """The latent decode kernel's block over the slab's rows, or None
        for the XLA formulation; decided from shapes, policy and mesh before
        the call, as `TransformerLM.decode_block`."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_latent

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        return pallas_latent.latent_block(slab_shape, dtype)

    def prefill_block(self, length):
        """The prefill attention kernel's block over a sequence of `length`
        positions, or None for the XLA formulation; decided as
        :meth:`decode_block` is."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_latent

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        return pallas_latent.prefill_block(length)

    TICK_COUNTERS = ("expert_assignments", "experts_hit",
                     "expert_tokens_max", "latent_rows_live")

    def cache_traits(self, cache):
        """What the engine may ask about a cache it otherwise only carries
        (docs/faq/perf.md, "The cache protocol"). Every member that grows
        is a range of rows, so a slot COULD be extended from an offset and
        rolled back; the model does not offer the methods that would
        (`prefill_at`, `verify_step`), and says so."""
        return {
            "block": self.decode_block(cache[0].shape, cache[0].dtype),
            "state_bytes_per_slot": 0,
            "rewindable": False,
            "why_not_rewindable":
                "the latent-attention model offers no prefill_at / "
                "verify_step yet: its cache is rows only and could be "
                "extended or rolled back, but no program does",
            "tick_counters": self.TICK_COUNTERS}

    def tick_counters(self, cache_c, cache_kr, routed, positions):
        """int32 `[len(TICK_COUNTERS)]` of ONE decode step, computed from
        what that step left in the cache (`routed`) and its positions:
        (token, expert) pairs computed here; held experts with at least one
        token, summed over the expert layers; the fullest expert's tokens,
        summed over the expert layers; latent rows the live slots attend
        (a layer)."""
        del cache_c, cache_kr
        alive = positions >= 0
        return jnp.concatenate([
            experts.routing_counters(routed, alive, self.cfg.experts_held),
            jnp.where(alive, positions + 1, 0).sum(dtype=jnp.int32)[None]])

    def prefill(self, params, cache_c, cache_kr, routed, tokens, length,
                slot):
        """Full-prompt forward for ONE session into slot `slot`: writes the
        latent rows `[0, Lb)` of every layer (rows at and past `length` are
        the padding's, which nothing attends). Returns `(logits [V] fp32 at
        position length - 1, cache_c, cache_kr, routed)`. `tokens` [Lb] is
        the prompt padded (with anything) to the bucket; `length` and
        `slot` are traced."""
        h, kept = self._sequence(params, tokens, length)
        with jax.named_scope("cache.write"):
            for i, (lat, k_r) in enumerate(kept):
                cache_c = lax.dynamic_update_slice(
                    cache_c, lat[None, None].astype(cache_c.dtype),
                    (slot, i, 0, 0))
                cache_kr = lax.dynamic_update_slice(
                    cache_kr, k_r.T[None, None].astype(cache_kr.dtype),
                    (slot, i, 0, 0))
            last = lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)
        return self._logits(params, last)[0], cache_c, cache_kr, routed

    def decode_step(self, params, cache_c, cache_kr, routed, tokens,
                    positions):
        """One fused incremental step over every slot: a live slot consumes
        one token, writes its latent row at `positions[s]` in every layer
        and attends rows `[0, positions[s]]`. A NEGATIVE position marks a
        dead slot: nothing of it is written or attended, and its `routed`
        stays what it was. Returns `(logits [S, V] fp32, cache_c, cache_kr,
        routed)`; jit with the cache donated."""
        c = self.cfg
        block = self.decode_block(cache_c.shape, cache_c.dtype)
        positions = jnp.minimum(positions, cache_c.shape[2] - 1)
        alive = positions >= 0
        with jax.named_scope("embed"):
            h = _table_rows(params["embed"], tokens).astype(
                jnp.dtype(c.dtype))
        chose = []
        for i in range(c.num_hidden_layers):
            mixed, cache_c, cache_kr = self._attention_step(
                params, i, self._rms(h, params[f"l{i}.norm1"]), cache_c,
                cache_kr, i, positions, block)
            h, local = self._mlp(params, i, h + mixed, alive)
            if local is not None:
                chose.append(local)
        if chose:
            with jax.named_scope("cache.write"):
                routed = jnp.where(alive[:, None, None],
                                   jnp.stack(chose, axis=1), routed)
        return self._logits(params, h), cache_c, cache_kr, routed


def _write_rows(slab, layer, positions, rows, axis):
    """The XLA formulation's row write (on one TPU chip the decode kernel
    takes the row itself: `_attention_step`): `slab[s, layer, ...]` takes
    `rows[s]` at index `positions[s]` of `axis` for every slot with a
    position >= 0, in place on a donated slab: one dynamic_update_slice a
    slot, which XLA performs in the slab's own layout
    (`transformer._write_rows`). A dead slot writes back what its index 0
    held."""
    for s in range(slab.shape[0]):
        at = [s, layer, 0, 0]
        at[axis] = jnp.maximum(positions[s], 0)
        new = rows[s][None, None].astype(slab.dtype)
        old = lax.dynamic_slice(slab, at, new.shape)
        slab = lax.dynamic_update_slice(
            slab, jnp.where(positions[s] >= 0, new, old), at)
    return slab


def _attend_latent(qc, qr, page_c, page_kr, positions, scale):
    """The absorbed decode attention in plain XLA: `qc` [S, H, R], `qr`
    [S, H, rope] against a layer's pages `page_c` [S, L, R] and `page_kr`
    [S, rope, L], all `L` rows of every slot, masked to `l <=
    positions[s]`. Rows past the position are selected away, not multiplied
    by a zero weight, so whatever a previous occupant left there cannot
    reach the output; a dead slot's result is 0. Returns [S, H, R] fp32."""
    dt = page_c.dtype
    L = page_c.shape[1]
    live = jnp.arange(L)[None, :] <= positions[:, None]              # [S, L]
    s = jnp.einsum("shr,slr->shl", qc.astype(dt), page_c,
                   preferred_element_type=jnp.float32) \
        + jnp.einsum("shd,sdl->shl", qr.astype(dt),
                     jnp.where(live[:, None, :], page_kr, 0),
                     preferred_element_type=jnp.float32)
    p = jax.nn.softmax(jnp.where(live[:, None, :], s * scale, -1e9), axis=-1)
    rows = jnp.where(live[:, :, None], page_c, 0)
    out = jnp.einsum("shl,slr->shr", p.astype(dt), rows,
                     preferred_element_type=jnp.float32)
    return jnp.where((positions >= 0)[:, None, None], out, 0.0)


def _causal_attention(q, k, v, scale):
    """Causal softmax attention of one sequence: `q`, `k` [L, H, dk], `v`
    [L, H, dv] -> [L, H, dv]. Blockwise with a running softmax (float32)
    once the sequence is longer than one block: a query block meets only
    the key blocks at or before it."""
    L = q.shape[0]
    b = _ATTN_BLOCK
    if L <= b or L % b:
        s = jnp.einsum("qhd,khd->hqk", q, k,
                       preferred_element_type=jnp.float32) * scale
        ar = jnp.arange(L)
        # large-negative, not -inf: see TransformerLM.prefill
        s = s + jnp.where(ar[:, None] >= ar[None, :], 0.0, -1e9)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("hqk,khd->qhd", p, v)
    n, heads = L // b, q.shape[1]
    ar = jnp.arange(b)

    def query_block(i):
        qi = lax.dynamic_slice_in_dim(q, i * b, b, axis=0)

        def key_block(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(k, j * b, b, axis=0)
            vj = lax.dynamic_slice_in_dim(v, j * b, b, axis=0)
            s = jnp.einsum("qhd,khd->hqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where((i * b + ar[:, None] >= j * b + ar[None, :])[None],
                          s, -1e9)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = alpha[..., None] * acc + jnp.einsum(
                "hqk,khd->hqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + p.sum(-1), acc

        m, l, acc = lax.fori_loop(
            0, i + 1, key_block,
            (jnp.full((heads, b), -1e9, jnp.float32),
             jnp.zeros((heads, b), jnp.float32),
             jnp.zeros((heads, b, v.shape[-1]), jnp.float32)))
        return (acc / l[..., None]).astype(v.dtype).transpose(1, 0, 2)

    return lax.map(query_block, jnp.arange(n)).reshape(L, heads, -1)
