"""Language model whose attention layers are of two kinds by the cache they
keep — full layers that see every earlier position, window layers that see
the last `sliding_window` — over a dropless expert MLP (`model_type` mellum
is one: grouped-query attention with heads of 128, rotary positions,
RMSNorm, a softmax router, an untied head; `model_type` afmoe another: the
same cache under a block with an output gate, per-head norms, a second pair
of norms, unrotated full layers, leading dense layers and a sigmoid router
beside a shared expert).

    h = embed[tokens]                               (* sqrt(D): mup_enabled)
    per layer i:  h += post1_i(attn_i(RMSNorm(h)))
                  h += post2_i(mlp_i(RMSNorm(h)))
    logits = RMSNorm(h) @ head

`post1`, `post2` are RMSNorms of the sub-layer's OUTPUT where the block has
them (`post_norms`), else nothing. What a block has beyond mellum's is
stated by the configuration (`WindowMoELMConfig`); what it lacks adds no
parameter and no operation to its programs.

* attention — `q = x W_q -> [Hq, hd]`, `k, v = x W_k, x W_v -> [H, hd]`, `G
  = Hq / H` query heads a K/V head: query head `j` reads K/V head `j // G`.
  With `qk_norm`, `q` and `k` take an RMSNorm over each head's `hd` entries
  (one weight vector each), before the rotation. A layer whose kind has a
  rotary table rotates `q, k` over all `hd` entries (half-split pairing); a
  kind without one (`rope_full` None: afmoe's full layers) uses no
  positions at all. Scores `q . k * hd^-1/2`, softmax in float32 over the
  keys the layer's mask admits. `layer_types[i]` decides mask and table:
  `sliding_attention` — a query at `p` sees keys `(p - sliding_window, p]`;
  `full_attention` — causal over everything (mellum: YaRN's blended
  frequencies with cos and sin times `attention_factor`). With
  `attention_gate`, `g = x W_gate -> [Hq hd]` (its columns fused behind
  `W_v`'s in `wqkv`) and `out = (concat_heads(P v) * sigmoid(g)) W_o`.
* MLP — layer `i < num_dense_layers`: the SiLU-gated `experts.gated_mlp` of
  width `intermediate_size`. Else the expert layer: the router of
  `score_func` (`experts.softmax_route`: `p = softmax(x W_r)` over ALL the
  experts in float32, the `top_k` largest, `p_e / sum_chosen p`;
  `experts.sigmoid_route`: `s = sigmoid(x W_r)`, the `top_k` largest `s +
  b`, `s_e / sum_chosen s * route_scale`), `y = sum_e w_e E_e(x)` (+
  `E_shared(x)` with a shared expert), SiLU-gated experts; dropless,
  through `experts.expert_layer` (told which experts it holds:
  `expert_first`, `experts_held` of `num_experts`).

Serving (`GenerationEngine`) sees the model through the cache protocol
(docs/faq/perf.md, "The cache protocol"). The cache's members have
DIFFERENT LENGTHS along the position axis:

    k_full, v_full  [slots, full layers,   H, max_len,        hd]   dtype
    k_ring, v_ring  [slots, window layers, H, sliding_window, hd]   dtype
    routed          [slots, expert layers, top_k]                   int32

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
import functools
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


# What a published block has that no key of its `config.json` states, by
# `model_type` (read from the family's published implementation; a
# benchmark configuration lists each under `assumed`). afmoe: an output
# gate, RMSNorm of each query and key head, RMSNorm of each sub-layer's
# output, and full layers that use no positions.
_BLOCKS = {"afmoe": dict(attention_gate=True, qk_norm=True, post_norms=True,
                         rotate_full=False)}


def _plain_rope(theta):
    return (("rope_theta", float(theta)), ("rope_type", "default"))


@dataclasses.dataclass(frozen=True)
class WindowMoELMConfig:
    """The published configuration's keys under their published names
    (`from_config` reads a `config.json`-shaped dict), what says which
    experts this chip holds (`experts_held`, `expert_first`), and what
    serving adds (`max_len`, `dtype`). `rope_full` / `rope_window` are the
    rotary parameters of a kind of layer as sorted (key, value) pairs, None
    for a kind that uses no positions. The defaults are mellum's block."""
    vocab_size: int = 512
    hidden_size: int = 64
    num_hidden_layers: int = 4
    num_attention_heads: int = 4
    num_key_value_heads: int = 2
    head_dim: int = 16
    intermediate_size: int = 128    # the dense layers' width
    moe_intermediate_size: int = 32
    num_dense_layers: int = 0       # leading layers with a dense MLP
    num_experts: int = 8            # the router's width: ALL the experts
    experts_held: int = 8           # ... of which this chip holds these
    expert_first: int = 0
    num_experts_per_tok: int = 2
    num_shared_experts: int = 0     # 0 | 1, of `moe_intermediate_size`
    score_func: str = "softmax"     # | "sigmoid" (with a selection bias)
    norm_topk_prob: bool = True     # afmoe's `route_norm`
    route_scale: float = 1.0
    rms_norm_eps: float = 1e-6
    sliding_window: int = 8
    layer_types: tuple = (WINDOW, WINDOW, WINDOW, FULL)
    rope_full: tuple | None = _plain_rope(10000.0)
    rope_window: tuple | None = _plain_rope(10000.0)
    attention_gate: bool = False    # out = (P v * sigmoid(x W_gate)) W_o
    qk_norm: bool = False           # RMSNorm of each q and k head
    post_norms: bool = False        # RMSNorm of each sub-layer's output
    mup_enabled: bool = False       # h = embed[tokens] * sqrt(hidden_size)
    max_len: int = 2048
    dtype: str = "bfloat16"

    @classmethod
    def from_config(cls, config, max_len=None, dtype=None):
        """From a published `config.json` (a dict). `num_experts` there
        counts the experts HELD when the file is a chip's share of a
        deployment (`published.num_experts` then gives the router's width
        and `share.expert_first` the first held expert). Rotary parameters
        come per kind of layer (`rope_parameters`, mellum) or as one
        `rope_theta` (afmoe). What the block cannot express is refused by
        name rather than ignored."""
        wanted = [("hidden_act", "silu"), ("attention_bias", False),
                  ("tie_word_embeddings", False),
                  ("use_sliding_window", True)]
        # no limit of the selection to groups of experts is built
        wanted += [(key, 1) for key in ("n_group", "topk_group",
                                        "num_expert_groups",
                                        "num_limited_groups")]
        for key, want in wanted:
            if key in config and config[key] != want:
                raise ValueError(f"WindowMoELM: {key}={config[key]!r} is "
                                 f"not supported (only {want!r})")
        block = _BLOCKS.get(config.get("model_type"), {})
        # a depth cut below the published one builds the first layers of
        # the published pattern
        n = config["num_hidden_layers"]
        kinds = tuple(config["layer_types"][:n])
        if len(kinds) != n or set(kinds) - {FULL, WINDOW}:
            raise ValueError(f"WindowMoELM: layer_types must name {n} "
                             f"layers, each {FULL!r} or {WINDOW!r}")
        dense = int(config.get("num_dense_layers", 0))
        if not 0 <= dense <= n:
            raise ValueError(f"WindowMoELM: num_dense_layers={dense} of "
                             f"{n} layers")
        mlps = list(config.get("mlp_layer_types", []))[:n]
        if "mlp_layer_types" in config \
                and mlps != ["dense"] * dense + ["sparse"] * (n - dense):
            raise ValueError(
                f"WindowMoELM: mlp_layer_types must be 'dense' for the "
                f"first num_dense_layers={dense} layers and 'sparse' for "
                f"the rest (no other placement of a dense MLP is built)")
        if config.get("num_shared_experts", 0) not in (0, 1):
            raise ValueError(
                f"WindowMoELM: num_shared_experts="
                f"{config['num_shared_experts']!r} is not supported (only "
                f"0 or 1)")
        score = config.get("score_func", "softmax")
        if score not in ("softmax", "sigmoid"):
            raise ValueError(f"WindowMoELM: score_func={score!r} is not "
                             f"supported (only 'softmax' or 'sigmoid')")
        if "rope_parameters" in config:
            rope = {kind: tuple(sorted(config["rope_parameters"][kind]
                                       .items()))
                    for kind in (FULL, WINDOW)}
        else:
            if config.get("rope_scaling") is not None:
                raise ValueError(
                    f"WindowMoELM: rope_scaling="
                    f"{config['rope_scaling']!r} is not supported beside "
                    f"rope_theta (only None)")
            rope = {kind: _plain_rope(config["rope_theta"])
                    for kind in (FULL, WINDOW)}
        if not block.get("rotate_full", True):
            rope[FULL] = None
        for r in filter(None, rope.values()):
            if dict(r).get("rope_type", "default") not in ("default",
                                                           "yarn"):
                raise ValueError(f"WindowMoELM: rope_type "
                                 f"{dict(r)['rope_type']!r} is not "
                                 f"supported")
        held = config["num_experts"]
        return cls(
            vocab_size=config["vocab_size"],
            hidden_size=config["hidden_size"],
            num_hidden_layers=n,
            num_attention_heads=config["num_attention_heads"],
            num_key_value_heads=config["num_key_value_heads"],
            head_dim=config["head_dim"],
            intermediate_size=config.get("intermediate_size", 0),
            moe_intermediate_size=config["moe_intermediate_size"],
            num_dense_layers=dense,
            num_experts=config.get("published", {}).get("num_experts", held),
            experts_held=held,
            expert_first=config.get("share", {}).get("expert_first", 0),
            num_experts_per_tok=config["num_experts_per_tok"],
            num_shared_experts=config.get("num_shared_experts", 0),
            score_func=score,
            norm_topk_prob=bool(config.get(
                "norm_topk_prob", config.get("route_norm", True))),
            route_scale=float(config.get("route_scale", 1.0)),
            rms_norm_eps=config["rms_norm_eps"],
            sliding_window=config["sliding_window"],
            layer_types=kinds,
            rope_full=rope[FULL],
            rope_window=rope[WINDOW],
            attention_gate=block.get("attention_gate", False),
            qk_norm=block.get("qk_norm", False),
            post_norms=block.get("post_norms", False),
            mup_enabled=bool(config.get("mup_enabled", False)),
            max_len=int(config["max_position_embeddings"]
                        if max_len is None else max_len),
            dtype=config.get("dtype", "bfloat16") if dtype is None else dtype)

    @property
    def n_expert_layers(self):
        return self.num_hidden_layers - self.num_dense_layers

    def layers_of(self, kind):
        """The indices of the layers of one kind, in order: a layer's page
        in its member of the cache is its place in this list."""
        return tuple(i for i, k in enumerate(self.layer_types) if k == kind)

    def rope(self, kind):
        """The rotary parameters of the layers of one kind, None where they
        use no positions."""
        r = self.rope_full if kind == FULL else self.rope_window
        return None if r is None else dict(r)

    def inv_freq(self, kind):
        """The rotary inverse frequencies of the layers of one kind that
        rotates: plain, or YaRN's blend (`rotary.yarn_inv_freq`)."""
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

    def _is_dense(self, i):
        return i < self.cfg.num_dense_layers

    # -- parameters ---------------------------------------------------------

    def _shapes(self):
        c = self.cfg
        d, f, hd = c.hidden_size, c.moe_intermediate_size, c.head_dim
        hq, hk = c.num_attention_heads, c.num_key_value_heads
        shapes = {"embed": (c.vocab_size, d), "head": (d, c.vocab_size),
                  "norm_f": (d,)}
        # the gate's columns ride behind W_v's: one product a layer
        gate = hq if c.attention_gate else 0
        for i in range(c.num_hidden_layers):
            shapes.update({
                f"l{i}.norm1": (d,), f"l{i}.norm2": (d,),
                f"l{i}.wqkv": (d, (hq + 2 * hk + gate) * hd),
                f"l{i}.wo": (hq * hd, d)})
            if c.qk_norm:
                shapes.update({f"l{i}.q_norm": (hd,), f"l{i}.k_norm": (hd,)})
            if c.post_norms:
                shapes.update({f"l{i}.norm1_post": (d,),
                               f"l{i}.norm2_post": (d,)})
            if self._is_dense(i):
                shapes.update({
                    f"l{i}.w_in": (d, 2 * c.intermediate_size),
                    f"l{i}.w_out": (c.intermediate_size, d)})
                continue
            shapes.update({
                f"l{i}.router": (d, c.num_experts),
                f"l{i}.experts_in": (c.experts_held, d, 2 * f),
                f"l{i}.experts_out": (c.experts_held, f, d)})
            if c.score_func == "sigmoid":
                shapes[f"l{i}.router_bias"] = (c.num_experts,)
            if c.num_shared_experts:
                shapes.update({f"l{i}.shared_in": (d, 2 * f),
                               f"l{i}.shared_out": (f, d)})
        return shapes

    def param_specs(self):
        repl = NamedSharding(self.mesh, P())
        return {name: repl for name in self._shapes()}

    def init_params(self, key, draw_dtype=None):
        """Random weights: matrices normal / sqrt(fan_in) (an expert's
        fan-in is its own input width, the embedding's the hidden size),
        norm weights 1, a sigmoid router's selection bias normal * 0.02 —
        large enough that selection by `s + b` differs from selection by
        `s`. The router and its bias stay float32 whatever the dtype. A
        leaf at a time, on the device, in the served dtype.

        `draw_dtype` is the dtype the normal draws are MADE in (None: the
        served dtype, as ever). jax's bfloat16 normal takes 128 values and
        has a mean of -0.012: every matrix so drawn carries a rank-one part
        along the all-ones direction that every token shares and no
        averaging removes (PERF.md section 6, PR 39). "float32" draws a
        leaf in float32 inside one jitted program that writes the served
        dtype, so no float32 copy of a stack of experts is kept."""
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = self._shapes()
        specs = self.param_specs()
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            leaf = name.rpartition(".")[2]
            if "norm" in leaf:
                val = jnp.ones(shape, dt)
            elif leaf == "router_bias":
                val = 0.02 * jax.random.normal(k, shape, jnp.float32)
            else:
                fan_in = c.hidden_size if leaf == "embed" else shape[-2]
                kept = jnp.float32 if leaf == "router" else dt
                if draw_dtype is None or jnp.dtype(draw_dtype) == kept:
                    val = jax.random.normal(k, shape, kept) \
                        * float(fan_in) ** -0.5     # a python float: dtype kept
                else:
                    val = _drawn(k, shape, jnp.dtype(draw_dtype), kept,
                                 float(fan_in) ** -0.5)
            params[name] = jax.device_put(val, specs[name])
        return params

    # -- pieces -------------------------------------------------------------

    # Device-side scopes (`jax.named_scope`: in every instruction's op_name,
    # read by benchmark/program_scopes.py): `embed`, `norm`, `head`,
    # `attn.project`, `attn.qknorm`, `attn.rotary`, `attn.prefill` |
    # `attn.decode` | `attn.window`, `attn.gate`, `attn.out`, `cache.write`,
    # `mlp`, `moe.route`, `moe.group`, `moe.experts`, `moe.shared`.

    def _rms(self, x, g, scope="norm"):
        with jax.named_scope(scope):
            x32 = x.astype(jnp.float32)
            out = x32 * lax.rsqrt((x32 * x32).mean(-1, keepdims=True)
                                  + self.cfg.rms_norm_eps)
            return (out * g.astype(jnp.float32)).astype(x.dtype)

    def _post(self, params, name, y):
        """The RMSNorm of a sub-layer's output where the block has one."""
        return self._rms(y, params[name]) if self.cfg.post_norms else y

    def _window(self, i):
        """Layer `i`'s window, None for a full layer."""
        return self.cfg.sliding_window \
            if self.cfg.layer_types[i] == WINDOW else None

    def _project(self, params, i, u, positions):
        """`u` [T, D] at `positions` [T] -> `(q [T, Hq, hd], k [T, H, hd],
        v [T, H, hd], gate [T, Hq hd] or None)`: `q` and `k` normalised a
        head where the block says so, then rotated by the layer's own
        table where its kind has one."""
        c = self.cfg
        hq, hk, hd = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        kind = c.layer_types[i]
        with jax.named_scope("attn.project"):
            out = u @ params[f"l{i}.wqkv"]
            qkv = out[:, :(hq + 2 * hk) * hd].reshape(-1, hq + 2 * hk, hd)
            gate = out[:, (hq + 2 * hk) * hd:] if c.attention_gate else None
            qk, v = qkv[:, :hq + hk], qkv[:, hq + hk:]
        if c.qk_norm:
            # a pass each: one pass over both under a stacked weight keeps a
            # float32 copy of a 16,384-token prefill's heads (+0.47 GB)
            qk = jnp.concatenate([
                self._rms(qk[:, :hq], params[f"l{i}.q_norm"], "attn.qknorm"),
                self._rms(qk[:, hq:], params[f"l{i}.k_norm"], "attn.qknorm")],
                axis=1)
        if c.rope(kind) is not None:
            with jax.named_scope("attn.rotary"):
                qk = rotary.rotate_half(qk, positions, c.inv_freq(kind),
                                        c.rope_amplitude(kind))
        return qk[:, :hq], qk[:, hq:], v, gate

    def _attention_out(self, params, i, a, gate, dtype):
        """`(a * sigmoid(gate)) W_o` for the attended heads `a` [T, Hq,
        hd]; no gate, no product."""
        a = a.reshape(a.shape[0], -1)
        if gate is not None:
            with jax.named_scope("attn.gate"):
                a = a.astype(jnp.float32) \
                    * jax.nn.sigmoid(gate.astype(jnp.float32))
        with jax.named_scope("attn.out"):
            return a.astype(dtype) @ params[f"l{i}.wo"]

    def _attention_seq(self, params, i, u):
        """Layer `i`'s attention over one whole sequence `u` [L, D]: `(out
        [L, D], k [H, L, hd], v [H, L, hd])` — the keys and values
        head-major, as a member of the cache keeps them."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_window as pw

        c = self.cfg
        L = u.shape[0]
        window = self._window(i)
        q, k, v, gate = self._project(params, i, u, jnp.arange(L))
        block = self.prefill_block(L, window)
        with jax.named_scope("attn.prefill"):
            k, v = k.transpose(1, 0, 2), v.transpose(1, 0, 2)
            if block is not None:
                a = pw.band_prefill_attend(
                    q.transpose(1, 0, 2), k, v, block=block,
                    scale=c.head_dim ** -0.5, window=window,
                    interpret=pa.pallas_interpret()).transpose(1, 0, 2)
            else:
                a = _band_attention(q, k, v, c.head_dim ** -0.5, window)
        return self._attention_out(params, i, a, gate, u.dtype), k, v

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
        q, k, v, gate = self._project(params, i, u,
                                      jnp.maximum(positions, 0))
        # granite's name for the full members, a name of its own for a ring
        with jax.named_scope("attn.window" if self._window(i)
                             else "attn.decode"):
            if block is not None:
                pw.count_body(q, slab_k)
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
        return self._attention_out(params, i, a, gate, u.dtype), \
            slab_k, slab_v

    def _route(self, params, i, x):
        """`x` [T, D] -> `(chosen [T, k] expert ids of the whole router,
        weights [T, k] float32)` by the router `score_func` names
        (`experts.softmax_route` | `experts.sigmoid_route`)."""
        c = self.cfg
        if c.score_func == "sigmoid":
            # 1e-20: the published guard of an all-zero selection
            return experts.sigmoid_route(
                x, params[f"l{i}.router"], params[f"l{i}.router_bias"],
                c.num_experts_per_tok, c.route_scale, c.norm_topk_prob,
                eps=1e-20)
        return experts.softmax_route(
            x, params[f"l{i}.router"], c.num_experts_per_tok,
            c.norm_topk_prob, c.route_scale)

    def _mlp_out(self, params, i, x, real):
        """Layer `i`'s MLP of the normed rows `x` [T, D]: `(y, local)`;
        `local` [T, k] is the routing of an expert layer (a held expert's
        local index, -1 elsewhere), None for a dense layer. An expert
        layer's `y` is the held experts' part plus the shared expert,
        which every chip computes whole."""
        if self._is_dense(i):
            with jax.named_scope("mlp"):
                return _by_chunks(lambda xs: experts.gated_mlp(
                    xs, params[f"l{i}.w_in"], params[f"l{i}.w_out"]), x), None
        y, local = experts.expert_layer(
            x, real, lambda xs: self._route(params, i, xs),
            params[f"l{i}.experts_in"], params[f"l{i}.experts_out"],
            expert_first=self.cfg.expert_first, mesh=self.mesh)
        if self.cfg.num_shared_experts:
            with jax.named_scope("moe.shared"):
                y = y + _by_chunks(lambda xs: experts.gated_mlp(
                    xs, params[f"l{i}.shared_in"],
                    params[f"l{i}.shared_out"]), x)
        return y, local

    def _mlp(self, params, i, h, real=None):
        """The MLP sub-layer with its norm(s) and residual: `(h, local)`
        (`_mlp_out`'s routing)."""
        x = self._rms(h, params[f"l{i}.norm2"])
        real = jnp.ones(x.shape[0], bool) if real is None else real
        y, local = self._mlp_out(params, i, x, real)
        return h + self._post(params, f"l{i}.norm2_post", y), local

    # -- forward ------------------------------------------------------------

    def _embedded(self, rows):
        """The embedding's rows as the first hidden states: in the served
        dtype, times `sqrt(hidden_size)` under `mup_enabled`."""
        c = self.cfg
        if c.mup_enabled:
            rows = rows.astype(jnp.float32) * float(c.hidden_size) ** 0.5
        return rows.astype(jnp.dtype(c.dtype))

    def _logits(self, params, h):
        h = self._rms(h, params["norm_f"])
        with jax.named_scope("head"):
            return (h @ params["head"]).astype(jnp.float32)

    def _sequence(self, params, tokens, length):
        """One whole sequence `tokens` [L] of which the first `length` are
        real: the hidden states [L, D] after the last layer and, per layer,
        the keys and values `(k, v)` [H, L, hd] a cache keeps."""
        with jax.named_scope("embed"):
            h = self._embedded(jnp.take(params["embed"], tokens, axis=0))
        real = jnp.arange(tokens.shape[0]) < length
        kept = []
        for i in range(self.cfg.num_hidden_layers):
            mixed, k, v = self._attention_seq(
                params, i, self._rms(h, params[f"l{i}.norm1"]))
            kept.append((k, v))
            h, _ = self._mlp(
                params, i,
                h + self._post(params, f"l{i}.norm1_post", mixed), real)
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
                  ((s, max(c.n_expert_layers, 1), c.num_experts_per_tok),
                   jnp.int32))
        return tuple(jax.device_put(jnp.zeros(shape, t), sh)
                     for shape, t in shapes)

    def decode_block(self, slab_shape, dtype):
        """The decode kernel's block over the rows of a member of this
        shape, or None for the XLA formulation; decided from shapes, policy
        and mesh before the call, as `TransformerLM.decode_block`. Which
        body of the kernel a layer's trace took is counted
        (`attn.decode.kv128.grouped` / `.one_query`, once a trace, telemetry
        on)."""
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
            h = self._embedded(_table_rows(params["embed"], tokens))
        chose = []
        for i, kind in enumerate(c.layer_types):
            mixed, *slabs[kind] = self._attention_step(
                params, i, self._rms(h, params[f"l{i}.norm1"]), *slabs[kind],
                pages[kind].index(i), positions, blocks[kind])
            h, local = self._mlp(
                params, i,
                h + self._post(params, f"l{i}.norm1_post", mixed), alive)
            if local is not None:
                chose.append(local)
        (k_full, v_full), (k_ring, v_ring) = slabs[FULL], slabs[WINDOW]
        if chose:
            with jax.named_scope("cache.write"):
                routed = jnp.where(alive[:, None, None],
                                   jnp.stack(chose, axis=1), routed)
        return (self._logits(params, h), k_full, v_full, k_ring, v_ring,
                routed)


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4))
def _drawn(key, shape, draw, kept, scale):
    """`normal(shape) * scale` drawn in `draw`, kept in `kept`: one program,
    so the wider draw is not kept beside the result."""
    return (jax.random.normal(key, shape, draw) * scale).astype(kept)


def _by_chunks(fn, x):
    """`fn(x)` for a row-wise `fn`, `experts.EXPERT_CHUNK` rows at a time
    once `x` [T, D] has more: a 16,384-token prefill's `[T, 2 F]` hidden
    rows of a dense MLP are then a chunk's, not the bucket's."""
    t, chunk = x.shape[0], experts.EXPERT_CHUNK
    if t > chunk and t % chunk == 0:
        return lax.map(fn, x.reshape(t // chunk, chunk, -1)) \
            .reshape(t, -1)
    return fn(x)


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
