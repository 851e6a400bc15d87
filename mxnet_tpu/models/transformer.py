"""SPMD Transformer language model — the distributed/long-context flagship.

Mapping to the reference: its sequence-model story is the fused cuDNN RNN +
BucketingModule (`src/operator/rnn-inl.h`, `module/bucketing_module.py:36`;
SURVEY.md §5 "long-context: none"). The TPU-native replacement is a
transformer whose training step is ONE jitted SPMD program over a
dp×sp×tp(+fsdp) mesh:

* batch over 'dp', sequence over 'sp' (ring attention — exact attention
  with K/V circulating the ICI ring, `parallel/ring_attention.py`),
* Megatron-style tensor parallelism over 'tp' expressed as GSPMD sharding
  annotations (column-parallel in-proj, row-parallel out-proj — XLA inserts
  the psum),
* optional 'fsdp' parameter sharding.

Everything is bfloat16 on the MXU with fp32 master params and fp32 softmax.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.collectives import sharding_constraint
from ..parallel.mesh import default_mesh
from ..parallel.ring_attention import ring_attention
from ..parallel.spmd import model_mesh


@dataclasses.dataclass(frozen=True)
class TransformerLMConfig:
    vocab_size: int = 32768
    d_model: int = 512
    n_heads: int = 8
    d_ff: int = 2048
    n_layers: int = 4
    max_len: int = 2048
    dtype: str = "bfloat16"
    causal: bool = True
    tie_embeddings: bool = True
    # Mixture-of-Experts (beyond-parity; the GShard/Switch recipe):
    # moe_experts > 0 turns every `moe_every`-th FFN into a top-1-routed
    # expert layer whose expert dim shards over the 'ep' mesh axis (or the
    # 'dp' axis when no dedicated ep axis exists — the standard deployment:
    # all-to-all rides the data-parallel group).
    moe_experts: int = 0
    moe_every: int = 2
    moe_capacity_factor: float = 1.5
    moe_aux_loss: float = 0.01


def _spec(mesh, *axes):
    return NamedSharding(mesh, P(*[a if (a in mesh.shape and mesh.shape[a] > 1) else None
                                   for a in axes]))


def _table_rows(table, rows):
    """``table[rows]`` as one dynamic_slice a row. The tied embedding lies
    vocabulary-minor on a TPU (less tile padding; the head's matmul reads
    it so), and a gather makes the compiler copy the whole table into the
    other layout every call; a slice reads it where it lies."""
    return jnp.concatenate([lax.dynamic_slice_in_dim(table, rows[s], 1, 0)
                            for s in range(rows.shape[0])], axis=0)


def _write_rows(slab, layer, positions, rows):
    """``slab[s, layer, :, positions[s], :] = rows[s]`` for every slot with
    a position >= 0, in place on a donated slab: one dynamic_update_slice
    of ``[H, 1, hd]`` per slot, which XLA performs in the slab's own layout
    (a scatter makes the TPU compiler copy the whole slab into the layout
    it prefers and back). A dead slot (negative position) writes back what
    its row 0 held."""
    for s in range(slab.shape[0]):
        at = (s, layer, 0, jnp.maximum(positions[s], 0), 0)
        new = rows[s][None, None, :, None, :]
        old = lax.dynamic_slice(slab, at, new.shape)
        slab = lax.dynamic_update_slice(
            slab, jnp.where(positions[s] >= 0, new, old), at)
    return slab


def _attend_rows(q, cache_k, cache_v, layer, positions, scale=None):
    """Layer ``layer``'s decode attention in plain XLA: the layer's page is
    read where it lies in the slab (the slice fuses into the two
    reductions), all ``L`` rows of every slot, masked to
    ``j <= positions[s]`` (<=: the token just written attends itself).
    Scores are masked large-negative, not -inf: a dead slot masks every row
    and must give finite garbage, not NaN. V rows past the position are
    selected away, not multiplied by a zero weight, so whatever a previous
    occupant left there (inf, nan) cannot reach the output; a dead slot's
    attention is 0. ``q`` may carry a multiple of the slab's heads
    (grouped-query attention: query head ``i`` reads slab head ``i //
    group``); ``scale`` multiplies the scores (None: ``1/sqrt(hd)``)."""
    dt = q.dtype
    heads, L, hd = cache_k.shape[2:]
    scale = 1.0 / np.sqrt(hd) if scale is None else scale
    live = jnp.arange(L)[None, :] <= positions[:, None]              # [S,L]
    if q.shape[1] != heads:
        q = q.reshape(q.shape[0], heads, q.shape[1] // heads, hd)
        s = jnp.einsum("shgd,shld->shgl", q, cache_k[:, layer].astype(dt),
                       preferred_element_type=jnp.float32) * scale
        p = jax.nn.softmax(jnp.where(live[:, None, None, :], s, -1e9),
                           axis=-1).astype(dt)
        v = jnp.where(live[:, None, :, None], cache_v[:, layer].astype(dt), 0)
        return jnp.einsum("shgl,shld->shgd", p, v).reshape(
            q.shape[0], -1, hd)
    s = jnp.einsum("shd,shld->shl", q, cache_k[:, layer].astype(dt),
                   preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(jnp.where(live[:, None, :], s, -1e9),
                       axis=-1).astype(dt)
    v = jnp.where(live[:, None, :, None], cache_v[:, layer].astype(dt), 0)
    return jnp.einsum("shl,shld->shd", p, v)


class TransformerLM:
    """Functional transformer LM bound to a mesh.

    params is a flat dict name -> jax.Array (sharded). All methods are
    pure; `init_params` places every weight with its partition spec.
    """

    def __init__(self, config, mesh=None):
        self.cfg = config
        # model_mesh: the MXNET_SPMD mesh when that gate is on (serving/
        # generation weights and the KV slab shard without plumbing),
        # else the ambient/default mesh — `default_mesh` semantics
        self.mesh = mesh or model_mesh()

    def _is_moe(self, i):
        c = self.cfg
        return c.moe_experts > 0 and (i % max(c.moe_every, 1)) == \
            max(c.moe_every, 1) - 1

    @property
    def _ep_axis(self):
        # dedicated 'ep' axis when the mesh has one, else experts shard
        # over the data-parallel group (GShard deployment)
        return "ep" if "ep" in self.mesh.shape else "dp"

    # -- parameters ---------------------------------------------------------

    def param_specs(self):
        c, mesh = self.cfg, self.mesh
        specs = {
            "embed": _spec(mesh, "tp", None),            # [V, D] vocab-sharded
            "pos_embed": _spec(mesh, None, None),        # [max_len, D]
            "ln_f_scale": _spec(mesh, None),
            "ln_f_bias": _spec(mesh, None),
        }
        ep = self._ep_axis
        for i in range(c.n_layers):
            specs.update({
                f"l{i}.ln1_scale": _spec(mesh, None),
                f"l{i}.ln1_bias": _spec(mesh, None),
                f"l{i}.wqkv": _spec(mesh, None, "tp"),   # [D, 3D] col-parallel
                f"l{i}.wo": _spec(mesh, "tp", None),     # [D, D] row-parallel
                f"l{i}.ln2_scale": _spec(mesh, None),
                f"l{i}.ln2_bias": _spec(mesh, None),
            })
            if self._is_moe(i):
                specs.update({
                    f"l{i}.router": _spec(mesh, None, None),       # [D, E]
                    f"l{i}.we1": _spec(mesh, ep, None, "tp"),      # [E, D, F]
                    f"l{i}.be1": _spec(mesh, ep, "tp"),            # [E, F]
                    f"l{i}.we2": _spec(mesh, ep, "tp", None),      # [E, F, D]
                    f"l{i}.be2": _spec(mesh, ep, None),            # [E, D]
                })
            else:
                specs.update({
                    f"l{i}.w1": _spec(mesh, None, "tp"),  # [D, F] col-parallel
                    f"l{i}.b1": _spec(mesh, "tp"),
                    f"l{i}.w2": _spec(mesh, "tp", None),  # [F, D] row-parallel
                    f"l{i}.b2": _spec(mesh, None),
                })
        if not c.tie_embeddings:
            specs["lm_head"] = _spec(mesh, None, "tp")
        return specs

    def init_params(self, key):
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        shapes = {
            "embed": (c.vocab_size, c.d_model),
            "pos_embed": (c.max_len, c.d_model),
            "ln_f_scale": (c.d_model,),
            "ln_f_bias": (c.d_model,),
        }
        for i in range(c.n_layers):
            shapes.update({
                f"l{i}.ln1_scale": (c.d_model,), f"l{i}.ln1_bias": (c.d_model,),
                f"l{i}.wqkv": (c.d_model, 3 * c.d_model),
                f"l{i}.wo": (c.d_model, c.d_model),
                f"l{i}.ln2_scale": (c.d_model,), f"l{i}.ln2_bias": (c.d_model,),
            })
            if self._is_moe(i):
                e = c.moe_experts
                shapes.update({
                    f"l{i}.router": (c.d_model, e),
                    f"l{i}.we1": (e, c.d_model, c.d_ff),
                    f"l{i}.be1": (e, c.d_ff),
                    f"l{i}.we2": (e, c.d_ff, c.d_model),
                    f"l{i}.be2": (e, c.d_model),
                })
            else:
                shapes.update({
                    f"l{i}.w1": (c.d_model, c.d_ff), f"l{i}.b1": (c.d_ff,),
                    f"l{i}.w2": (c.d_ff, c.d_model), f"l{i}.b2": (c.d_model,),
                })
        if not c.tie_embeddings:
            shapes["lm_head"] = (c.d_model, c.vocab_size)

        specs = self.param_specs()
        params = {}
        keys = jax.random.split(key, len(shapes))
        for (name, shape), k in zip(sorted(shapes.items()), keys):
            if name.endswith(("_scale",)):
                val = jnp.ones(shape, dt)
            elif name.endswith(("_bias", ".b1", ".b2", ".be1", ".be2")):
                val = jnp.zeros(shape, dt)
            else:
                # 3-D expert weights are per-expert matrices: fan over the
                # contracted dim, not the expert dim
                fan_in = shape[-2] if len(shape) == 3 else shape[0]
                val = (jax.random.normal(k, shape, jnp.float32) / np.sqrt(fan_in)).astype(dt)
            params[name] = jax.device_put(val, specs[name])
        return params

    # -- forward ------------------------------------------------------------

    def _ln(self, x, scale, bias):
        with jax.named_scope("norm"):
            x32 = x.astype(jnp.float32)
            mu = x32.mean(-1, keepdims=True)
            var = x32.var(-1, keepdims=True)
            out = (x32 - mu) * jax.lax.rsqrt(var + 1e-5)
            return (out * scale.astype(jnp.float32)
                    + bias.astype(jnp.float32)).astype(x.dtype)

    def _local_attention(self, q, k, v, blocks):
        """One device's attention over [B, L, H, D]: the fused flash kernel
        (ops/pallas_attention.py: QK^T -> streaming softmax -> PV without
        the HBM round trip) with the `(block_q, block_k)` the caller's shape
        test chose, else (`blocks` None) the XLA blockwise path. The choice
        is made from shapes before the call, so a kernel the compiler
        refuses raises instead of hiding."""
        c = self.cfg
        if blocks is not None:
            from ..ops import pallas_attention as pa

            return pa.flash_attention(q, k, v, causal=c.causal,
                                      block_q=blocks[0], block_k=blocks[1],
                                      interpret=pa.pallas_interpret())
        from ..parallel.ring_attention import _block_attn, _bhql_to_bqhl, _full_causal_bias
        bias = _full_causal_bias(q.shape[1], k.shape[1]) if c.causal else None
        o, m, l = _block_attn(q, k, v, bias)
        return o / _bhql_to_bqhl(l)

    def _attention(self, q, k, v):
        """Dispatch: ring attention if 'sp' is a real mesh axis, else local
        attention (same math, zero hops). A Mosaic kernel cannot be
        partitioned by GSPMD, so on a mesh of several devices the fused
        kernel runs per shard inside `shard_map` (batch over dp/fsdp, heads
        over tp — attention is independent across both)."""
        from ..ops import pallas_attention as pa

        mesh, c = self.mesh, self.cfg
        sp = mesh.shape.get("sp", 1)
        # blocks depend on L and D only, which dp/tp sharding leaves whole
        blocks = None
        if (sp == 1 and pa.pallas_enabled()
                and c.n_heads % mesh.shape.get("tp", 1) == 0):
            blocks = pa.flash_blocks(q.shape, k.shape, q.dtype, c.causal)
        if sp == 1 and (blocks is None or mesh.size == 1):
            return self._local_attention(q, k, v, blocks)
        from ..parallel.collectives import shard_map
        spec = P(("dp", "fsdp") if "fsdp" in mesh.shape else "dp", "sp", "tp", None)
        spec = P(*[a if (isinstance(a, tuple) or (a in mesh.shape and mesh.shape[a] > 1)) else None
                   for a in spec])

        def body(q, k, v):
            if sp > 1:
                return ring_attention(q, k, v, "sp", sp, causal=c.causal)
            return self._local_attention(q, k, v, blocks)

        fn = shard_map(body, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec, check_vma=False)
        return fn(q, k, v)

    def _moe_ffn(self, i, params, x):
        """Top-1 ("Switch") expert FFN — the GShard GROUPED dispatch/
        combine einsum recipe with STATIC per-group capacity: tokens are
        grouped by batch row (G=B), each group routes at most C =
        ceil(cf·L/E) tokens to an expert, dispatch (G, L, E, C) one-hots
        move kept tokens into expert buffers (the all-to-all when experts
        shard over ep/dp), experts batch-apply their FFN, combine scales
        by the router gate. Grouping keeps dispatch memory O(S·E·C) with
        C ∝ L/E instead of the ungrouped O(S²). Returns (out, aux)."""
        c = self.cfg
        dt = x.dtype
        B, L, D = x.shape
        E = c.moe_experts
        C = max(1, int(np.ceil(c.moe_capacity_factor * L / E)))

        logits = (x.astype(jnp.float32) @
                  params[f"l{i}.router"].astype(jnp.float32))     # (B, L, E)
        probs = jax.nn.softmax(logits, axis=-1)
        expert = jnp.argmax(probs, axis=-1)                       # (B, L)
        gate = jnp.max(probs, axis=-1)                            # (B, L)

        mask = jax.nn.one_hot(expert, E, dtype=jnp.float32)       # (B, L, E)
        # position of each token within its expert's PER-GROUP buffer
        pos = (jnp.cumsum(mask, axis=1) - 1.0) * mask             # (B, L, E)
        keep = mask * (pos < C)
        # load-balancing aux loss (Switch eq. 4) from the PRE-capacity
        # assignment — post-capacity f saturates at cf/E exactly when
        # routing collapses, killing the balance gradient
        f = mask.mean(axis=(0, 1))
        pmean = probs.mean(axis=(0, 1))
        aux = E * jnp.sum(f * pmean)

        slot = jax.nn.one_hot(jnp.sum(pos * keep, axis=2).astype(jnp.int32),
                              C, dtype=jnp.float32)               # (B, L, C)
        dispatch = keep[:, :, :, None] * slot[:, :, None, :]      # (B, L, E, C)
        combine = dispatch * gate[:, :, None, None]

        xe = jnp.einsum("glec,gld->gecd", dispatch.astype(dt), x)  # (B,E,C,D)
        h1 = jax.nn.gelu(
            jnp.einsum("gecd,edf->gecf", xe, params[f"l{i}.we1"]) +
            params[f"l{i}.be1"].astype(dt)[None, :, None, :])
        h2 = jnp.einsum("gecf,efd->gecd", h1, params[f"l{i}.we2"]) + \
            params[f"l{i}.be2"].astype(dt)[None, :, None, :]
        out = jnp.einsum("glec,gecd->gld", combine.astype(dt), h2)
        return out, aux

    def forward(self, params, tokens, return_aux=False):
        """tokens [B, L] int32 → logits [B, L, V] (compute dtype, fp32 at
        loss); with return_aux also the summed MoE load-balance loss."""
        c, mesh = self.cfg, self.mesh
        dt = jnp.dtype(c.dtype)
        B, L = tokens.shape
        act = P(*[a if (a in mesh.shape and mesh.shape[a] > 1) else None
                  for a in ("dp", "sp", None)])

        h = jnp.take(params["embed"], tokens, axis=0).astype(dt)
        h = h + params["pos_embed"][None, :L].astype(dt)
        h = lax.with_sharding_constraint(h, NamedSharding(mesh, act))
        aux_total = jnp.asarray(0.0, jnp.float32)

        for i in range(c.n_layers):
            ln1 = self._ln(h, params[f"l{i}.ln1_scale"], params[f"l{i}.ln1_bias"])
            qkv = ln1 @ params[f"l{i}.wqkv"]              # [B,L,3D] heads on tp
            q, k, v = jnp.split(qkv, 3, axis=-1)
            hd = c.d_model // c.n_heads
            q = q.reshape(B, L, c.n_heads, hd)
            k = k.reshape(B, L, c.n_heads, hd)
            v = v.reshape(B, L, c.n_heads, hd)
            attn = self._attention(q, k, v).reshape(B, L, c.d_model)
            h = h + attn @ params[f"l{i}.wo"]              # row-parallel: XLA psums over tp
            h = lax.with_sharding_constraint(h, NamedSharding(mesh, act))
            ln2 = self._ln(h, params[f"l{i}.ln2_scale"], params[f"l{i}.ln2_bias"])
            if self._is_moe(i):
                ff, aux = self._moe_ffn(i, params, ln2)
                aux_total = aux_total + aux
                h = h + ff
            else:
                ff = jax.nn.gelu(ln2 @ params[f"l{i}.w1"] + params[f"l{i}.b1"].astype(dt))
                h = h + ff @ params[f"l{i}.w2"] + params[f"l{i}.b2"].astype(dt)
            h = lax.with_sharding_constraint(h, NamedSharding(mesh, act))

        h = self._ln(h, params["ln_f_scale"], params["ln_f_bias"])
        head = params["embed"].T if c.tie_embeddings else params["lm_head"]
        logits = h @ head.astype(dt)
        if return_aux:
            return logits, aux_total
        return logits

    # -- incremental decoding (serving/generation) ---------------------------
    #
    # The O(1)-per-token cache discipline of arXiv:2603.09555: one
    # preallocated KV slab of FIXED shape holds every live session's keys
    # and values, `prefill` fills a slot's rows [0, L) from the prompt in
    # one full-length pass, and `decode_step` extends every live slot by
    # exactly one token — one K/V row written in place plus attention over
    # the slot's own live rows, never a recompile, never O(T) recomputation.
    # Both are pure functions of (params, cache, ...) so the serving engine
    # can jit them once per shape with the cache buffers donated.

    def _slab_sharding(self):
        """The KV slab's layout: heads axis over 'tp' when the mesh has a
        real tp axis that divides n_heads (the serving twin of the SPMD
        weight sharding — per-head attention is independent, so the slab
        shards cleanly on heads and decode K/V writes stay local), else
        replicated. Every slab allocation AND every cache-returning
        method pins this layout, so the donated decode/prefill buffers
        alias across ticks."""
        c = self.cfg
        tp = self.mesh.shape.get("tp", 1)
        if tp > 1 and c.n_heads % tp == 0:
            return NamedSharding(self.mesh, P(None, None, "tp", None, None))
        return NamedSharding(self.mesh, P())

    def init_cache(self, max_slots, max_len=None):
        """Allocate the slot-based KV slab: two arrays (keys, values) of
        shape ``[max_slots, n_layers, n_heads, max_len, head_dim]`` in the
        compute dtype, zeroed, laid out per :meth:`_slab_sharding` on the
        model's mesh (heads over 'tp' when present — the slab stops being
        replicated under `MXNET_SPMD=tp=K`). Slot contents are garbage
        until a `prefill` claims the slot; reads are always masked by the
        slot's current length, so stale rows from a previous occupant are
        never attended."""
        c = self.cfg
        max_len = c.max_len if max_len is None else int(max_len)
        if max_len > c.max_len:
            raise ValueError(f"cache max_len {max_len} exceeds the model's "
                             f"positional range {c.max_len}")
        hd = c.d_model // c.n_heads
        shape = (int(max_slots), c.n_layers, c.n_heads, max_len, hd)
        sh = self._slab_sharding()
        dt = jnp.dtype(c.dtype)
        return (jax.device_put(jnp.zeros(shape, dt), sh),
                jax.device_put(jnp.zeros(shape, dt), sh))

    def _head(self, params):
        return (params["embed"].T if self.cfg.tie_embeddings
                else params["lm_head"])

    # The serving programs' device-side scopes (`jax.named_scope`: in every
    # instruction's op_name, read by benchmark/program_scopes.py): `embed`,
    # `norm`, `attn.project`, `attn.prefill` | `attn.decode`, `attn.out`,
    # `mlp`, `head` — the names the other three models use for the same work.

    def _project_qkv(self, params, i, h, rows):
        """Layer ``i``'s q, k, v ``[rows, H, hd]`` of the normed ``h``."""
        c = self.cfg
        ln1 = self._ln(h, params[f"l{i}.ln1_scale"], params[f"l{i}.ln1_bias"])
        with jax.named_scope("attn.project"):
            q, k, v = jnp.split(ln1 @ params[f"l{i}.wqkv"], 3, axis=-1)
            shape = (rows, c.n_heads, c.d_model // c.n_heads)
            return q.reshape(shape), k.reshape(shape), v.reshape(shape)

    def _attn_out(self, params, i, h, attn):
        with jax.named_scope("attn.out"):
            return h + attn @ params[f"l{i}.wo"]

    def _mlp(self, params, i, h, group_axis):
        """``h`` plus layer ``i``'s feed-forward half. An expert layer
        routes the rows as groups along ``group_axis``: 0 is one group of
        all rows (a prefill), 1 a group a row (a decode)."""
        dt = h.dtype
        ln2 = self._ln(h, params[f"l{i}.ln2_scale"], params[f"l{i}.ln2_bias"])
        with jax.named_scope("mlp"):
            if self._is_moe(i):
                ff, _ = self._moe_ffn(i, params,
                                      jnp.expand_dims(ln2, group_axis))
                return h + jnp.squeeze(ff, group_axis)
            ff = jax.nn.gelu(ln2 @ params[f"l{i}.w1"]
                             + params[f"l{i}.b1"].astype(dt))
            return h + ff @ params[f"l{i}.w2"] + params[f"l{i}.b2"].astype(dt)

    def _logits(self, params, h, row=None):
        """fp32 logits of the final-normed rows ``h`` [T, D]; with ``row``,
        of that one row alone [1, V] — every row is normed and then the one
        is cut, the order the prefill programs have always had."""
        h = self._ln(h, params["ln_f_scale"], params["ln_f_bias"])
        if row is not None:
            h = lax.dynamic_slice_in_dim(h, row, 1, axis=0)         # [1,D]
        with jax.named_scope("head"):
            return (h @ self._head(params).astype(h.dtype)).astype(
                jnp.float32)

    def prefill(self, params, cache_k, cache_v, tokens, length, slot):
        """Full-prompt forward for ONE session, writing its K/V into slot
        ``slot`` rows ``[0, Lb)`` of the slab and returning the logits at
        the last REAL token (position ``length - 1``) — the distribution
        the first generated token is sampled from.

        tokens : int32 [Lb]   prompt padded (with anything) up to the
                              compile bucket; padded positions produce
                              garbage K/V that the length mask keeps
                              unread forever.
        length : int32 scalar real prompt length (1 <= length <= Lb)
        slot   : int32 scalar slab row to fill (traced — one executable
                              serves every slot)

        Returns ``(logits [V] fp32, cache_k, cache_v)``. Pure; jit with
        the two cache operands donated.
        """
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        Lb = tokens.shape[0]
        hd = c.d_model // c.n_heads
        scale = 1.0 / np.sqrt(hd)
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0).astype(dt)  # [Lb,D]
            h = h + params["pos_embed"][:Lb].astype(dt)
        # additive causal mask, large-negative (not -inf: a fully-masked
        # row must softmax to harmless garbage, not NaN)
        ar = jnp.arange(Lb)
        causal = jnp.where(ar[:, None] >= ar[None, :], 0.0, -1e9)   # [Lb,Lb]
        for i in range(c.n_layers):
            q, k, v = self._project_qkv(params, i, h, Lb)
            with jax.named_scope("attn.prefill"):
                # slab write: [1, 1, H, Lb, hd] block at (slot, layer, 0, 0,
                # 0)
                cache_k = lax.dynamic_update_slice(
                    cache_k,
                    k.transpose(1, 0, 2)[None, None].astype(cache_k.dtype),
                    (slot, i, 0, 0, 0))
                cache_v = lax.dynamic_update_slice(
                    cache_v,
                    v.transpose(1, 0, 2)[None, None].astype(cache_v.dtype),
                    (slot, i, 0, 0, 0))
                s = jnp.einsum("qhd,khd->hqk", q, k,
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(s + causal[None], axis=-1).astype(dt)
                attn = jnp.einsum("hqk,khd->qhd", p, v).reshape(Lb,
                                                                c.d_model)
            h = self._attn_out(params, i, h, attn)
            # an expert layer dispatches the prompt as one group; note:
            # capacity is computed at the BUCKET length, so under heavy
            # routing imbalance a bucket-padded prefill can keep tokens a
            # shorter forward would have dropped (decode_step always keeps:
            # C=1, L=1)
            h = self._mlp(params, i, h, 0)
        logits = self._logits(params, h, row=length - 1)
        sh = self._slab_sharding()
        return (logits[0], sharding_constraint(cache_k, sh),
                sharding_constraint(cache_v, sh))

    def prefill_at(self, params, cache_k, cache_v, tokens, length, slot,
                   offset):
        """Suffix prefill: the prompt's UNMATCHED tail after a prefix-cache
        fork. The slot's rows ``[0, offset)`` already hold the K/V of the
        prompt's first ``offset`` tokens (copied slot-to-slot from a cached
        entry by the fork executable); this forward consumes only the
        remaining ``length`` tokens, writes their K/V into rows
        ``[offset, offset + Lb)`` and returns the logits at the last REAL
        suffix token — so a cache hit pays O(suffix), not O(prompt).

        tokens : int32 [Lb]   suffix padded up to the compile bucket
        length : int32 scalar real suffix length (1 <= length <= Lb)
        slot   : int32 scalar slab row (traced)
        offset : int32 scalar matched-prefix length (traced — ONE
                              executable per bucket serves every split
                              point, the compile-once discipline)

        Unlike :meth:`prefill` (whose attention is the Lb x Lb causal
        block), the suffix block must also attend the cached rows, so each
        layer scores the suffix queries against the slot's FULL slab row
        masked to ``j <= offset + i`` — the decode-step mask family, at
        Lb x slab_len cost. Returns ``(logits [V] fp32, cache_k,
        cache_v)``. Pure; jit with the cache operands donated.
        """
        c = self.cfg
        dt = jnp.dtype(c.dtype)
        Lb = tokens.shape[0]
        L = cache_k.shape[3]
        hd = c.d_model // c.n_heads
        scale = 1.0 / np.sqrt(hd)
        pos = offset + jnp.arange(Lb)
        with jax.named_scope("embed"):
            h = jnp.take(params["embed"], tokens, axis=0).astype(dt)  # [Lb,D]
            # jnp.take clips out-of-range positions (pad rows past the
            # model's positional range read row max_len-1 — garbage the mask
            # hides)
            h = h + jnp.take(params["pos_embed"], pos, axis=0).astype(dt)
        # suffix token i attends slab rows j <= offset + i: the cached
        # prefix plus causal-within-suffix, one mask over the whole row.
        # Large-negative, not -inf (finite garbage for fully-masked rows)
        mask = jnp.where(jnp.arange(L)[None, None, :]
                         <= pos[None, :, None], 0.0, -1e9)     # [1,Lb,L]
        for i in range(c.n_layers):
            q, k, v = self._project_qkv(params, i, h, Lb)
            with jax.named_scope("attn.prefill"):
                # slab write: [1, 1, H, Lb, hd] block at (slot, layer, 0,
                # offset, 0) — rows [0, offset) stay the forked prefix
                cache_k = lax.dynamic_update_slice(
                    cache_k,
                    k.transpose(1, 0, 2)[None, None].astype(cache_k.dtype),
                    (slot, i, 0, offset, 0))
                cache_v = lax.dynamic_update_slice(
                    cache_v,
                    v.transpose(1, 0, 2)[None, None].astype(cache_v.dtype),
                    (slot, i, 0, offset, 0))
                ck_i = lax.dynamic_slice(
                    cache_k, (slot, i, 0, 0, 0),
                    (1, 1, c.n_heads, L, hd))[0, 0]                # [H,L,hd]
                cv_i = lax.dynamic_slice(
                    cache_v, (slot, i, 0, 0, 0),
                    (1, 1, c.n_heads, L, hd))[0, 0]
                s = jnp.einsum("qhd,hkd->hqk", q, ck_i.astype(dt),
                               preferred_element_type=jnp.float32) * scale
                p = jax.nn.softmax(s + mask, axis=-1).astype(dt)
                attn = jnp.einsum("hqk,hkd->qhd", p,
                                  cv_i.astype(dt)).reshape(Lb, c.d_model)
            h = self._attn_out(params, i, h, attn)
            h = self._mlp(params, i, h, 0)      # one group, as in prefill
        logits = self._logits(params, h, row=length - 1)
        sh = self._slab_sharding()
        return (logits[0], sharding_constraint(cache_k, sh),
                sharding_constraint(cache_v, sh))

    def decode_block(self, slab_shape, dtype):
        """How :meth:`decode_step` touches a slab of this shape: the block
        over ``L`` of the Pallas decode kernel (``ops/pallas_decode.py``:
        rows are written and only each live slot's live blocks are read,
        where the slab lies), or None for the XLA formulation, which reads
        every row of every slot. Decided from what can be seen before the
        call, as `flash_blocks` is: the kernel policy, the slab's shape and
        dtype, and the mesh — a Mosaic kernel is not partitioned by GSPMD,
        so a model on several devices keeps the XLA formulation, which
        is."""
        from ..ops import pallas_attention as pa
        from ..ops import pallas_decode as pd

        if self.mesh.size > 1 or not pa.pallas_enabled():
            return None
        return pd.decode_block(slab_shape, dtype)

    def cache_traits(self, cache):
        """What a serving engine may ask about a cache it otherwise only
        carries (docs/faq/perf.md, "The cache protocol"): the decode
        kernel's ``block`` over the slab's rows (None: the XLA formulation),
        the per-slot bytes of state that is not rows (none here), and
        whether every member is a range of rows, so that a slot can be
        extended from an offset (:meth:`prefill_at`) and rolled back by not
        advancing its position (:meth:`verify_step`)."""
        return {"block": self.decode_block(cache[0].shape, cache[0].dtype),
                "state_bytes_per_slot": 0, "rewindable": True}

    def decode_step(self, params, cache_k, cache_v, tokens, positions):
        """One fused incremental step over the WHOLE slot slab: each live
        slot consumes one token, writes its K/V at ``positions[s]`` — one
        row, in place on the donated slab — and attends over rows
        ``[0, positions[s]]``: O(1) work per token in generated length,
        every slot in one XLA program.

        tokens    : int32 [S] the token extending each slot (dead slots:
                    anything — their output is discarded by the engine)
        positions : int32 [S] the index each token occupies (== the slot's
                    current length). A NEGATIVE position marks a dead
                    slot: nothing of it is written or attended and its
                    logits are garbage, so the slab rows of a slot nobody
                    decodes (free, parked, holding a cached prefix, or of
                    another weight cohort) stay exactly as they were. A
                    position past the slab is clamped to its last row.

        Returns ``(logits [S, V] fp32, cache_k, cache_v)``. Pure; jit with
        the cache operands donated. One executable serves every admission/
        eviction pattern — continuous batching never recompiles. How the
        slab is touched (:meth:`decode_block`): the Pallas kernel where it
        applies, else per-slot dynamic_update_slice writes and attention
        over the whole page in XLA — same rows written, same mathematics.
        """
        from ..ops import pallas_attention as pa
        from ..ops import pallas_decode as pd

        c = self.cfg
        dt = jnp.dtype(c.dtype)
        S = tokens.shape[0]
        block = self.decode_block(cache_k.shape, cache_k.dtype)
        positions = jnp.minimum(positions, cache_k.shape[3] - 1)
        with jax.named_scope("embed"):
            h = _table_rows(params["embed"], tokens).astype(dt)      # [S,D]
            h = h + jnp.take(params["pos_embed"], jnp.maximum(positions, 0),
                             axis=0).astype(dt)
        for i in range(c.n_layers):
            q, k, v = self._project_qkv(params, i, h, S)
            with jax.named_scope("attn.decode"):
                k, v = k.astype(cache_k.dtype), v.astype(cache_v.dtype)
                if block is not None:
                    pd.count_body(q, cache_k)
                    attn, cache_k, cache_v = pd.decode_update_attend(
                        q, k, v, cache_k, cache_v, jnp.int32(i), positions,
                        block=block, interpret=pa.pallas_interpret())
                else:
                    cache_k = _write_rows(cache_k, i, positions, k)
                    cache_v = _write_rows(cache_v, i, positions, v)
                    attn = _attend_rows(q, cache_k, cache_v, i, positions)
                attn = attn.astype(dt).reshape(S, c.d_model)
            h = self._attn_out(params, i, h, attn)
            # [S, 1, D]: every slot is its own routing group of one token
            # with capacity 1, so a decoded token is ALWAYS routed (never
            # capacity-dropped, unlike training forward)
            h = self._mlp(params, i, h, 1)
        logits = self._logits(params, h)
        sh = self._slab_sharding()
        return (logits, sharding_constraint(cache_k, sh),
                sharding_constraint(cache_v, sh))

    def verify_step(self, params, cache_k, cache_v, tokens, positions):
        """Speculative-decoding verify: advance every slot by ``K = k + 1``
        tokens in ONE executable. ``tokens[:, 0]`` is each slot's last
        committed token, ``tokens[:, 1:]`` the draft's k proposals; the
        returned logits row ``i`` is the model's next-token distribution
        after consuming ``tokens[:, :i+1]`` — the engine accepts the
        longest draft prefix whose proposals match the greedy argmaxes and
        rolls the rest back by NOT advancing ``positions`` past it (the
        rejected rows beyond the new frontier are never attended and are
        overwritten sequentially before they could be).

        tokens    : int32 [S, K]  fed block per slot (dead slots: anything)
        positions : int32 [S]     row the block starts at (== slot length)

        Returns ``(logits [S, K, V] fp32, cache_k, cache_v)``.

        Structure is deliberately K *unrolled* :meth:`decode_step` graphs
        chained through the slab — NOT a batched K-query attention block.
        The per-token math is then structurally identical to the
        non-speculative decode executable, which is what makes speculative
        greedy output BIT-EXACT with the plain path (a batched
        formulation reassociates the attention reductions and can flip an
        argmax by a ulp — the PR 6/8 FMA precedent). On accelerators the
        unrolled chain still amortizes K dispatches and K HBM round-trips
        of host scheduling into one program launch, which is where the
        speculative win lives at decode batch sizes. Pure; jit with the
        cache operands donated.
        """
        steps = []
        for i in range(tokens.shape[1]):
            # a dead slot (negative position) stays dead through the block
            logits, cache_k, cache_v = self.decode_step(
                params, cache_k, cache_v, tokens[:, i],
                jnp.where(positions < 0, positions, positions + i))
            steps.append(logits)
        return jnp.stack(steps, axis=1), cache_k, cache_v

    # -- training -----------------------------------------------------------

    def loss(self, params, tokens, targets):
        logits, aux = self.forward(params, tokens, return_aux=True)
        logits = logits.astype(jnp.float32)
        logp = jax.nn.log_softmax(logits, axis=-1)
        nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)
        return nll.mean() + self.cfg.moe_aux_loss * aux

    def make_train_step(self, optimizer=None, lr=1e-3):
        """Return jitted (params, opt_state, tokens, targets) -> (params,
        opt_state, loss): Adam in fp32 master precision."""
        mesh = self.mesh
        b1, b2, eps = 0.9, 0.999, 1e-8

        def init_opt(params):
            return {k: (jnp.zeros(v.shape, jnp.float32),
                        jnp.zeros(v.shape, jnp.float32)) for k, v in params.items()}

        def step(params, opt_state, tokens, targets, step_no):
            loss, grads = jax.value_and_grad(self.loss)(params, tokens, targets)
            new_p, new_s = {}, {}
            t = step_no.astype(jnp.float32) + 1
            for name, p in params.items():
                g = grads[name].astype(jnp.float32)
                m, v = opt_state[name]
                m = b1 * m + (1 - b1) * g
                v = b2 * v + (1 - b2) * g * g
                mhat = m / (1 - b1 ** t)
                vhat = v / (1 - b2 ** t)
                new_p[name] = (p.astype(jnp.float32) -
                               lr * mhat / (jnp.sqrt(vhat) + eps)).astype(p.dtype)
                new_s[name] = (m, v)
            return new_p, new_s, loss

        specs = self.param_specs()
        state_specs = {k: (s, s) for k, s in specs.items()}
        data_spec = NamedSharding(mesh, P(*[a if (a in mesh.shape and mesh.shape[a] > 1) else None
                                            for a in ("dp", "sp")]))
        repl = NamedSharding(mesh, P())
        fn = jax.jit(step,
                     in_shardings=(specs, state_specs, data_spec, data_spec, repl),
                     out_shardings=(specs, state_specs, repl))
        return fn, init_opt

    def shard_tokens(self, tokens):
        mesh = self.mesh
        spec = NamedSharding(mesh, P(*[a if (a in mesh.shape and mesh.shape[a] > 1) else None
                                       for a in ("dp", "sp")]))
        return jax.device_put(jnp.asarray(tokens, jnp.int32), spec)
