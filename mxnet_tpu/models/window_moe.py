"""Language model whose attention layers are of two kinds by the cache they
keep — full layers that see every earlier position, window layers that see
the last `sliding_window` — over a dropless softmax-routed expert MLP in
every layer (`model_type` mellum is one: grouped-query attention with heads
of 128, rotary positions, RMSNorm, an untied head).

    h = embed[tokens]
    per layer i:  h += attn_i(RMSNorm(h));  h += moe_i(RMSNorm(h))
    logits = RMSNorm(h) @ head

* attention — `q = x W_q -> [Hq, hd]`, `k, v = x W_k, x W_v -> [H, hd]`;
  `q, k` rotated over all `hd` entries (half-split pairing); query head `j`
  reads K/V head `j // (Hq / H)`; scores `q . k * hd^-1/2`, softmax in
  float32 over the keys the layer's mask admits. `layer_types[i]` decides
  mask and rotary table: `sliding_attention` — a query at `p` sees keys `(p
  - sliding_window, p]`, the plain frequencies `theta^(-2d/hd)`;
  `full_attention` — causal over everything, YaRN's blended frequencies with
  cos and sin times `attention_factor` (so a score grows by its square).
* expert layer — `p = softmax(x W_g)` over ALL the experts in float32, the
  `top_k` largest, weights `p_e / sum_chosen p` (`norm_topk_prob`); `y =
  sum_e w_e E_e(x)`, SiLU-gated experts; dropless, through
  `experts.expert_layer` (told which experts it holds: `expert_first`,
  `experts_held` of `num_experts`).

Serving (`GenerationEngine`) sees the model through the cache protocol
(docs/faq/perf.md, "The cache protocol"). The cache's members have
DIFFERENT LENGTHS along the position axis:

    k_full, v_full  [slots, full layers,   H, max_len,        hd]   dtype
    k_ring, v_ring  [slots, window layers, H, sliding_window, hd]   dtype
    routed          [slots, layers, top_k]                          int32

A full layer keeps every position's K and V: position `p` at row `p`. A
window layer keeps a RING of the last `sliding_window`: position `p` at row
`p mod sliding_window`; the new row overwrites the position that has just
left the window, and once `p >= sliding_window - 1` every row is live. One
rule serves both — row `p mod R`, the first `min(p + 1, R)` rows live, `R`
the member's own length — so one decode kernel does (`ops/pallas_window.py`
on one TPU chip, else the same mathematics in XLA). A prefill of a
bucket-padded prompt writes into a ring only the rows of the positions
`[max(0, length - R), length)`: the padding's rows never wrap over real
ones. `routed` is what the last decode step chose for the slot
(`latent_moe`'s member). A ring overwrites what a roll-back would need, so
the cache is not rewindable and the model offers no `prefill_at` /
`verify_step`; `cache_traits` says so.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.spmd import model_mesh
from . import experts, rotary
from .transformer import _table_rows, _write_rows

__all__ = ["WindowMoELMConfig", "WindowMoELM"]

# rows of one blockwise-attention step of a prefill in XLA
_ATTN_BLOCK = 1024
FULL, WINDOW = "full_attention", "sliding_attention"


@dataclasses.dataclass(frozen=True)
class WindowMoELMConfig:
    """The published configuration's keys under their published names
    (`from_config` reads a `config.json`-shaped dict), what says which
    experts this chip holds (`experts_held`, `expert_first`), and what
    serving adds (`max_len`, `dtype`). `rope_full` / `rope_window` are the
    two entries of `rope_parameters` as sorted (key, value) pairs."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    moe_intermediate_size: int = 32
    num_experts: int = 8            # the router's width: ALL the experts
    experts_held: int = 8           # ... of which this chip holds these
    expert_first: int = 0
    num_experts_per_tok: int = 2
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    sliding_window: int = 8
    layer_types: tuple = (WINDOW, WINDOW, WINDOW, FULL)
    rope_full: tuple = (("rope_theta", 10000.0), ("rope_type", "default"))
    rope_window: tuple = (("rope_theta", 10000.0), ("rope_type", "default"))
    max_len: int = 2048
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, config, max_len=None, dtype=None):
        """From a published `config.json` (a dict). `num_experts` there
        counts the experts HELD when the file is a chip's share of a
        deployment (`published.num_experts` then gives the router's width
        and `share.expert_first` the first held expert). What the block
        cannot express is refused by name rather than ignored."""
        for key, want in (("hidden_act", "silu"), ("attention_bias", False),
                          ("tie_word_embeddings", False),
                          ("use_sliding_window", True)):
            if key in config and config[key] != want:
                raise ValueError(f"WindowMoELM: {key}={config[key]!r} is "
                                 f"not supported (only {want!r})")
        # a depth cut below the published one builds the first layers of
        # the published pattern
        n = config["num_hidden_layers"]
        kinds = tuple(config["layer_types"][:n])
        if len(kinds) != n or set(kinds) - {FULL, WINDOW}:
            raise ValueError(f"WindowMoELM: layer_types must name {n} "
                             f"layers, each {FULL!r} or {WINDOW!r}")
        mlps = config.get("mlp_layer_types", ["sparse"] * n)[:n]
        if len(mlps) != n or set(mlps) != {"sparse"}:
            raise ValueError("WindowMoELM: mlp_layer_types must be 'sparse' "
                             "for every layer (no dense MLP is built)")
        rope = config["rope_parameters"]
        for kind in (FULL, WINDOW):
            if rope[kind].get("rope_type", "default") not in ("default",
                                                              "yarn"):
                raise ValueError(f"WindowMoELM: rope_type "
                                 f"{rope[kind]['rope_type']!r} is not "
                                 f"supported")
        held = config["num_experts"]
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_hidden_layers=n,
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            moe_intermediate_size=config["moe_intermediate_size"],
            num_experts=config.get("published", {}).get("num_experts", held),
            experts_held=held,
            expert_first=config.get("share", {}).get("expert_first", 0),
            num_experts_per_tok=config["num_experts_per_tok"],
            norm_topk_prob=bool(config.get("norm_topk_prob", True)),
            rms_norm_eps=config["rms_norm_eps"],
            sliding_window=config["sliding_window"],
            layer_types=kinds,
            rope_full=tuple(sorted(rope[FULL].items())),
            rope_window=tuple(sorted(rope[WINDOW].items())),
            max_len=int(config["max_position_embeddings"]
                        if max_len is None else max_len),
            dtype=config.get("dtype", "bfloat16") if dtype is None else dtype)

    def layers_of(self, kind):
        """The indices of the layers of one kind, in order: a layer's page
        in its member of the cache is its place in this list."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    def rope(self, kind):
        return dict(self.rope_full if kind == FULL else self.rope_window)

    def inv_freq(self, kind):
        """The rotary inverse frequencies of the layers of one kind: plain,
        or YaRN's blend (`rotary.yarn_inv_freq`)."""
        r = self.rope(kind)
        return rotary.yarn_inv_freq(
            self.head_dim, r["rope_theta"],
            r if r.get("rope_type", "default") == "yarn" else None)

    def rope_amplitude(self, kind):
        """What cos and sin are multiplied by: YaRN's `attention_factor`
        (`0.1 ln(factor) + 1` where the config gives none), 1 without."""
        r = self.rope(kind)
        if r.get("rope_type", "default") != "yarn":
            return 1.0
        return float(r.get("attention_factor")
                     or 0.1 * math.log(r["factor"]) + 1.0)


class WindowMoELM:
    """Functional window/full-attention expert LM bound to a mesh; `params`
    is a flat dict name -> jax.Array. All methods are pure. Weights are
    replicated over the mesh."""

    def __init__(self, config, mesh=None):
        c = config
        if not 0 <= c.expert_first <= c.expert_first + c.experts_held \
                <= c.num_experts:
            raise ValueError(
                f"WindowMoELM: held experts [{c.expert_first}, "
                f"{c.expert_first + c.experts_held}) are not among the "
                f"router's {c.num_experts}")
        if c.num_experts_per_tok > c.num_experts:
            raise ValueError("WindowMoELM: more experts a token than experts")
        if c.head_dim % 2 or c.num_attention_heads % c.num_key_value_heads:
            raise ValueError("WindowMoELM: the head size must be even and "
                             "the query heads a multiple of the K/V heads")
        self.cfg = c
        self.mesh = mesh or model_mesh()
        self.full_layers = c.layers_of(FULL)
        self.window_layers = c.layers_of(WINDOW)

    # -- parameters ---------------------------------------------------------

    def _shapes(self):
        c = self.cfg
        d, f, hd = c.hidden_size, c.moe_intermediate_size, c.head_dim
        hq, hk = c.num_attention_heads, c.num_key_value_heads
        shapes = {"embed": (c.vocab_size, d), "head": (d, c.vocab_size),
                  "norm_f": (d,)}
        for i in range(c.num_hidden_layers):
            shapes.update({
                f"l{i}.norm1": (d,), f"l{i}.norm2": (d,),
                f"l{i}.wqkv": (d, (hq + 2 * hk) * hd),
                f"l{i}.wo": (hq * hd, d),
                f"l{i}.router": (d, c.num_experts),
                f"l{i}.experts_in": (c.experts_held, d, 2 * f),
                f"l{i}.experts_out": (c.experts_held, f, d)})
        return shapes

    def param_specs(self):
        repl = NamedSharding(self.mesh, P())
        return {name: repl for name in self._shapes()}

    def init_params(self, key):
        """Random weights: matrices normal / sqrt(fan_in) (an expert's
        fan-in is its own input width, the embedding's the hidden size),
        norm weights 1. The router stays float32 whatever the dtype. A leaf
        at a time, on the device, in the served dtype."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = self._shapes()
        specs = self.param_specs()
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            leaf = name.rpartition(".")[2]
            if leaf in ("norm1", "norm2", "norm_f"):
                val = jnp.ones(shape, dt)
            else:
                fan_in = c.hidden_size if leaf == "embed" else shape[-2]
                val = jax.random.normal(
                    k, shape, jnp.float32 if leaf == "router" else dt) \
                    * float(fan_in) ** -0.5     # a python float: dtype kept
            params[name] = jax.device_put(val, specs[name])
        return params

    # -- pieces -------------------------------------------------------------

    # Device-side scopes (`jax.named_scope`: in every instruction's op_name,
    # read by benchmark/program_scopes.py): `embed`, `norm`, `head`,
    # `attn.project`, `attn.rotary`, `attn.prefill` | `attn.decode` |
    # `attn.window`, `attn.out`, `cache.write`, `moe.route`, `moe.group`,
    # `moe.experts`.

    def _rms(self, x, g):
        with jax.named_scope("norm"):
            x32 = x.astype(jnp.float32)
            out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                  + self.cfg.rms_norm_eps)
            return (out * g.astype(jnp.float32)).astype(x.dtype)

    def _window(self, i):
        """Layer `i`'s window, None for a full layer."""
        return self.cfg.sliding_window \
            if self.cfg.layer_types[i] == WINDOW else None

    def _project(self, params, i, u, positions):
        """`u` [T, D] at `positions` [T] -> `(q [T, Hq, hd], k [T, H, hd],
        v [T, H, hd])`, `q` and `k` rotated by the layer's own table."""
        c = self.cfg
        hq, hk, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        kind = c.layer_types[i]
        with jax.named_scope("attn.project"):
            qkv = (u @ params[f"l{i}.wqkv"]).reshape(-1, hq + 2 * hk, hd)
        with jax.named_scope("attn.rotary"):
            qk = rotary.rotate_half(qkv[:, :hq + hk], positions,
                                    c.inv_freq(kind), c.rope_amplitude(kind))
        return qk[:, :hq], qk[:, hq:], qkv[:, hq + hk:]

    def _attention_seq(self, params, i, u):
        """Layer `i`'s attention over one whole sequence `u` [L, D]: `(out
        [L, D], k [H, L, hd], v [H, L, hd])` — the keys and values
        head-major, as a member of the cache keeps them."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_window as pw

        c = self.cfg
        L = u.shape[0]
        window = self._window(i)
        q, k, v = self._project(params, i, u, jnp.arange(L))
        k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
        block = self.prefill_block(L, window)
        with jax.named_scope("attn.prefill"):
            if block is not None:
                a = pw.band_prefill_attend(
                    q.transpose(1, 0, 2), k, v, block=block,
                    scale=c.head_dim ** -0.5, window=window,
                    interpret=pa.pallas_interpret()).transpose(1, 0, 2)
            else:
                a = _band_attention(q, k, v, c.head_dim ** -0.5, window)
        with jax.named_scope("attn.out"):
            return a.reshape(L, -1).astype(u.dtype) @ params[f"l{i}.wo"], \
                k, v

    def _attention_step(self, params, i, u, slab_k, slab_v, page, positions,
                        block):
        """One token a slot through layer `i`'s attention: `u` [S, D];
        writes each live slot's K/V row at `positions[s] mod R` of page
        `page` of the layer's member (`R` its rows) and attends the slot's
        first `min(positions[s] + 1, R)` rows. Returns `(out [S, D],
        slab_k, slab_v)`."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_window as pw

        c = self.cfg
        q, k, v = self._project(params, i, u, jnp.maximum(positions, 0))
        # granite's name for the full members, a name of its own for a ring
        with jax.named_scope("attn.window" if self._window(i)
                             else "attn.decode"):
            if block is not None:
                a, slab_k, slab_v = pw.kv_update_attend(
                    q, k, v, slab_k, slab_v, jnp.int32(page), positions,
                    block=block, scale=c.head_dim ** -0.5,
                    interpret=pa.pallas_interpret())
            else:
                rows = slab_k.shape[3]
                at = jnp.where(positions >= 0, positions % rows, -1)
                slab_k = _write_rows(slab_k, page, at, k.astype(slab_k.dtype))
                slab_v = _write_rows(slab_v, page, at, v.astype(slab_v.dtype))
                a = _attend_member(q, slab_k[:, page], slab_v[:, page],
                                   positions, c.head_dim ** -0.5)
        with jax.named_scope("attn.out"):
            return a.reshape(u.shape[0], -1).astype(u.dtype) \
                @ params[f"l{i}.wo"], slab_k, slab_v

    def _route(self, params, i, x):
        """`x` [T, D] -> `(chosen [T, k] expert ids of the whole router,
        weights [T, k] float32)`: softmax probabilities in float32 over all
        the experts, the `k` largest, normalised over the selection when
        `norm_topk_prob`."""
        c = self.cfg
        with jax.named_scope("moe.route"):
            p = jax.nn.softmax(jnp.dot(
                x.astype(jnp.float32), params[f"l{i}.router"],
                precision=lax.Precision.HIGHEST), axis=-1)
            weights, chosen = lax.top_k(p, c.num_experts_per_tok)
            if c.norm_topk_prob:
                weights = weights / weights.sum(-1, keepdims=True)
        return chosen, weights

    def _mlp(self, params, i, h, real=None):
        """The expert sub-layer with its norm and residual: `(h, local)`;
        `local` [T, k] is the routing (a held expert's local index, -1
        elsewhere)."""
        x = self._rms(h, params[f"l{i}.norm2"])
        real = jnp.ones(x.shape[0], bool) if real is None else real
        y, local = experts.expert_layer(
            x, real, lambda xs: self._route(params, i, xs),
            params[f"l{i}.experts_in"], params[f"l{i}.experts_out"],
            expert_first=self.cfg.expert_first, mesh=self.mesh)
        return h + y, local

    # -- forward ------------------------------------------------------------

    def _logits(self, params, h):
        h = self._rms(h, params["norm_f"])
        with jax.named_scope("head"):
            return (h @ params["head"]).astype(jnp.float32)

    def _sequence(self, params, tokens, length):
        """One whole sequence `tokens` [L] of which the first `length` are
        real: the hidden states [L, D] after the last layer and, per layer,
        the keys and values `(k, v)` [H, L, hd] a cache keeps."""
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0) \
                .astype(jnp.dtype(self.cfg.dtype))
        real = jnp.arange(tokens.shape[0]) < length
        kept = []
        for i in range(self.cfg.num_hidden_layers):
            mixed, k, v = self._attention_seq(
                params, i, self._rms(h, params[f"l{i}.norm1"]))
            kept.append((k, v))
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
        """The serving cache: `(k_full, v_full, k_ring, v_ring, routed)`,
        zeroed, each with the slot as its leading axis (module docstring).
        A kind with no layer keeps one unused page."""
        c = self.cfg
        max_len = c.max_len if max_len is None else int(max_len)
        if max_len > c.max_len:
            raise ValueError(f"cache max_len {max_len} exceeds the model's "
                             f"positional range {c.max_len}")
        s, dt = int(max_slots), jnp.dtype(c.dtype)
        hk, hd = c.num_key_value_heads, c.head_dim
        full = (s, max(len(self.full_layers), 1), hk, max_len, hd)
        # a ring longer than the cache would hold rows no position reaches
        ring = (s, max(len(self.window_layers), 1), hk,
                min(c.sliding_window, max_len), hd)
        sh = NamedSharding(self.mesh, P())
        shapes = ((full, dt), (full, dt), (ring, dt), (ring, dt),
                  ((s, c.num_hidden_layers, c.num_experts_per_tok),
                   jnp.int32))
        return tuple(jax.device_put(jnp.zeros(shape, t), sh)
                     for shape, t in shapes)

    def decode_block(self, slab_shape, dtype):
        """The decode kernel's block over the rows of a member of this
        shape, or None for the XLA formulation; decided from shapes, policy
        and mesh before the call, as `TransformerLM.decode_block`."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_window as pw

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        return pw.kv_block(slab_shape, dtype)

    def prefill_block(self, length, window=None):
        """The prefill attention kernel's block over a sequence of `length`
        positions, or None for the XLA formulation; decided as
        :meth:`decode_block` is."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_window as pw

        if self.mesh.size > 1 or not pa.pallas_enabled() \
                or self.cfg.head_dim % 128:
            return None
        return pw.band_block(length, window)

    TICK_COUNTERS = ("expert_assignments", "experts_hit", "expert_tokens_max",
                     "kv_rows_live_full", "kv_rows_live_window")

    def cache_traits(self, cache):
        """What the engine may ask about a cache it otherwise only carries
        (docs/faq/perf.md, "The cache protocol"). `block` is the decode
        kernel's over the FULL members (the engine's slab-block counters
        count those; a ring is read whole once it has wrapped)."""
        return {
            "block": self.decode_block(cache[0].shape, cache[0].dtype),
            "state_bytes_per_slot": 0,
            "rewindable": False,
            "why_not_rewindable":
                "a window layer's cache is a ring of sliding_window rows "
                "that overwrites what a roll-back or an extension from an "
                "offset would need, and the model offers no prefill_at / "
                "verify_step",
            "tick_counters": self.TICK_COUNTERS}

    def tick_counters(self, k_full, v_full, k_ring, v_ring, routed,
                      positions):
        """int32 `[len(TICK_COUNTERS)]` of ONE decode step, computed from
        what that step left in the cache (`routed`) and its positions:
        `experts.routing_counters`' three, and the K/V rows the live slots
        attend summed over the full layers and over the window layers."""
        del v_full, v_ring
        alive = positions >= 0
        rows = [jnp.where(alive, jnp.minimum(positions + 1, m.shape[3]), 0)
                .sum(dtype=jnp.int32) * n
                for m, n in ((k_full, len(self.full_layers)),
                             (k_ring, len(self.window_layers)))]
        return jnp.concatenate([
            experts.routing_counters(routed, alive, self.cfg.experts_held),
            jnp.stack(rows)])

    def prefill(self, params, k_full, v_full, k_ring, v_ring, routed, tokens,
                length, slot):
        """Full-prompt forward for ONE session into slot `slot`: a full
        layer's page takes the rows `[0, Lb)` (rows at and past `length`
        are the padding's, which nothing attends); a window layer's ring
        takes, at row `r`, the LAST real position `p < length` with `p mod
        R = r` — the positions `[max(0, length - R), length)`, so the
        padding never wraps over a real row (a row no real position
        reaches keeps garbage that a decode selects away). Returns `(logits
        [V] fp32 at position length - 1, *cache)`. `tokens` [Lb] is the
        prompt padded (with anything) to the bucket; `length` and `slot`
        are traced."""
        h, kept = self._sequence(params, tokens, length)
        lb, rows = tokens.shape[0], k_ring.shape[3]

        def put(slab, page, x):
            return lax.dynamic_update_slice(
                slab, x[None, None].astype(slab.dtype), (slot, page, 0, 0, 0))

        with jax.named_scope("cache.write"):
            newest = jnp.clip(
                length - 1 - (length - 1 - jnp.arange(rows)) % rows,
                0, lb - 1)
            for page, i in enumerate(self.full_layers):
                k_full = put(k_full, page, kept[i][0])
                v_full = put(v_full, page, kept[i][1])
            for page, i in enumerate(self.window_layers):
                k_ring = put(k_ring, page,
                             jnp.take(kept[i][0], newest, axis=1))
                v_ring = put(v_ring, page,
                             jnp.take(kept[i][1], newest, axis=1))
            last = lax.dynamic_slice_in_dim(h, length - 1, 1, axis=0)
        return (self._logits(params, last)[0], k_full, v_full, k_ring,
                v_ring, routed)

    def decode_step(self, params, k_full, v_full, k_ring, v_ring, routed,
                    tokens, positions):
        """One fused incremental step over every slot: a live slot consumes
        one token, writes its K/V row in every layer (module docstring) and
        attends what the layer's mask admits. A NEGATIVE position marks a
        dead slot: nothing of it is written or attended, and its `routed`
        stays what it was. Returns `(logits [S, V] fp32, *cache)`; jit with
        the cache donated."""
        c = self.cfg
        # a layer's member of the cache and its page in it, by its kind
        slabs = {FULL: (k_full, v_full), WINDOW: (k_ring, v_ring)}
        pages = {FULL: self.full_layers, WINDOW: self.window_layers}
        blocks = {kind: self.decode_block(k.shape, k.dtype)
                  for kind, (k, _) in slabs.items()}
        positions = jnp.minimum(positions, k_full.shape[3] - 1)
        alive = positions >= 0
        with jax.named_scope("embed"):
            h = _table_rows(params["embed"], tokens).astype(
                jnp.dtype(c.dtype))
        chose = []
        for i, kind in enumerate(c.layer_types):
            mixed, *slabs[kind] = self._attention_step(
                params, i, self._rms(h, params[f"l{i}.norm1"]), *slabs[kind],
                pages[kind].index(i), positions, blocks[kind])
            h, local = self._mlp(params, i, h + mixed, alive)
            chose.append(local)
        (k_full, v_full), (k_ring, v_ring) = slabs[FULL], slabs[WINDOW]
        with jax.named_scope("cache.write"):
            routed = jnp.where(alive[:, None, None],
                               jnp.stack(chose, axis=1), routed)
        return (self._logits(params, h), k_full, v_full, k_ring, v_ring,
                routed)


def _attend_member(q, page_k, page_v, positions, scale):
    """One layer's decode attention in plain XLA on its page of a member:
    `q` [S, Hq, hd] against `page_k`, `page_v` [S, H, R, hd], each slot's
    first `min(positions[s] + 1, R)` rows (every row of a ring that has
    wrapped). Rows past them are selected away, not multiplied by a zero
    weight, so whatever a previous occupant left there cannot reach the
    output; a dead slot's result is 0. Returns [S, Hq, hd] fp32."""
    dt = page_k.dtype
    s, heads, rows, hd = page_k.shape
    live = jnp.arange(rows)[None, :] \
        < jnp.minimum(positions + 1, rows)[:, None]                  # [S, R]
    qg = q.astype(dt).reshape(s, heads, -1, hd)
    sc = jnp.einsum("shgd,shrd->shgr", qg,
                    jnp.where(live[:, None, :, None], page_k, 0),
                    preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(live[:, None, None, :], sc, -1e9), axis=-1)
    out = jnp.einsum("shgr,shrd->shgd", p.astype(dt),
                     jnp.where(live[:, None, :, None], page_v, 0),
                     preferred_element_type=jnp.float32)
    return jnp.where((positions >= 0)[:, None, None],
                     out.reshape(s, -1, hd), 0.0)


def _band_attention(q, k, v, scale, window):
    """Causal softmax attention of one sequence with an optional window in
    plain XLA: `q` [L, Hq, hd], `k`, `v` [H, L, hd] (grouped queries) ->
    [L, Hq, hd]; a query at `p` sees the keys `(p - window, p]`. Blockwise
    with a running softmax (float32) once the sequence is longer than one
    block: a query block meets only the key blocks of its band."""
    L, hq, hd = q.shape
    heads = k.shape[0]
    qg = q.reshape(L, heads, hq // heads, hd)
    b = _ATTN_BLOCK

    def seen(ahead):
        """`ahead`: key position minus query position."""
        ok = ahead <= 0
        return ok if window is None else ok & (ahead > -window)

    if L <= b or L % b:
        s = jnp.einsum("qhgd,hkd->hgqk", qg, k,
                       preferred_element_type=jnp.float32) * scale
        ar = jnp.arange(L)
        # large-negative, not -inf: see TransformerLM.prefill
        s = jnp.where(seen(ar[None, :] - ar[:, None]), s, -1e9)
        p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
        return jnp.einsum("hgqk,hkd->qhgd", p, v).reshape(L, hq, hd)
    n, group = L // b, hq // heads
    reach = n if window is None else -(-(window - 1) // b)
    ar = jnp.arange(b)

    def query_block(i):
        qi = lax.dynamic_slice_in_dim(qg, i * b, b, axis=0)

        def key_block(j, carry):
            m, l, acc = carry
            kj = lax.dynamic_slice_in_dim(k, j * b, b, axis=1)
            vj = lax.dynamic_slice_in_dim(v, j * b, b, axis=1)
            s = jnp.einsum("qhgd,hkd->hgqk", qi, kj,
                           preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen((j - i) * b + ar[None, :] - ar[:, None]),
                          s, -1e9)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = alpha[..., None] * acc + jnp.einsum(
                "hgqk,hkd->hgqd", p.astype(v.dtype), vj,
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + p.sum(-1), acc

        m, l, acc = lax.fori_loop(
            jnp.maximum(i - reach, 0), i + 1, key_block,
            (jnp.full((heads, group, b), -1e9, jnp.float32),
             jnp.zeros((heads, group, b), jnp.float32),
             jnp.zeros((heads, group, b, hd), jnp.float32)))
        return (acc / l[..., None]).astype(v.dtype).transpose(2, 0, 1, 3)

    return lax.map(query_block, jnp.arange(n)).reshape(L, hq, hd)
