"""Ring attention: exact attention over sequences sharded across chips.

The reference has NO sequence parallelism (SURVEY.md §5 — its long-sequence
tools are bucketing + truncated BPTT); this is the TPU-first extension the
ICI torus makes natural. Algorithm (Liu et al., blockwise ring attention):
shard the sequence over the 'sp' mesh axis; each device holds its Q block
permanently and passes its K/V block around the ring with `ppermute`
(one ICI hop per step), accumulating attention with the numerically-stable
streaming-softmax update. Peak memory O(seq/n) per chip, compute overlaps
communication (XLA pipelines the ppermute with the matmuls).

Used inside `shard_map` over a mesh with an 'sp' axis; `ring_self_attention`
is the eager/sharded convenience wrapper.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..compile_cache import CompileCache
from . import mesh as mesh_mod
from .mesh import AXIS_SP, default_mesh

# one jitted shard_map program per (mesh, axis, size, causal, scale) —
# named so `compile_cache.named_stats("ring_attention")` answers "did a
# long-sequence step recompile?" (this was an anonymous lru_cache, the
# exact silent-recompile class tpulint's executable-cache rule now flags)
_ring_cache = CompileCache("ring_attention")


def _block_attn(q, k, v, bias=None, scale=None):
    """One Q-block × K/V-block partial attention.

    Returns (numerator, row max, row sum-exp) for streaming combination.
    q: [B, Lq, H, D], k/v: [B, Lk, H, D].
    """
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    # fp32 softmax: scores, max and sum-exp accumulate in float32 even when
    # q/k/v are bfloat16 (matches the module's stated design; avoids
    # precision loss accumulating l over many K blocks)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)                    # [B,H,Lq,1]
    p = jnp.exp(s - lax.stop_gradient(m))
    l = jnp.sum(p, axis=-1, keepdims=True)                    # [B,H,Lq,1]
    o = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)   # [B,Lq,H,D]
    return o, m, l


def _combine(o1, m1, l1, o2, m2, l2):
    """Merge two streaming-softmax partials (flash-attention rescale).

    The max-shift must be gradient-inert everywhere: _block_attn computes
    p = exp(s - stop_gradient(m)), so the rescale factors here must also be
    stop-gradiented or spurious gradients flow through each block's argmax
    (the shift cancels exactly in the true softmax, so killing its gradient
    is exact, same as standard flash/ring attention backward).
    """
    m = jnp.maximum(m1, m2)
    a1 = jnp.exp(lax.stop_gradient(m1) - lax.stop_gradient(m))
    a2 = jnp.exp(lax.stop_gradient(m2) - lax.stop_gradient(m))
    l = l1 * a1 + l2 * a2
    o = o1 * _bhql_to_bqhl(a1).astype(o1.dtype) + o2 * _bhql_to_bqhl(a2).astype(o2.dtype)
    return o, m, l


def _bhql_to_bqhl(x):
    # [B,H,Lq,1] scaling factor applied to [B,Lq,H,D]
    return jnp.transpose(x, (0, 2, 1, 3))


def _hop_fn(scale):
    """Per-hop block attention: the fused Pallas kernel where the policy
    enables it (TPU backend by default; MXNET_PALLAS_ATTENTION /
    MXNET_PALLAS_INTERPRET as for the transformer's local attention) AND the
    hop's shapes pass the kernel's shape test — VMEM-resident QK^T/softmax/PV
    while K/V ride the ICI ring, exact recomputed backward; the XLA blockwise
    path otherwise. Decided from shapes before the call: a kernel the
    compiler refuses raises."""
    from ..ops import pallas_attention as pa

    if not pa.pallas_enabled():
        return lambda q, k, v, bias: _block_attn(q, k, v, bias, scale)
    interpret = pa.pallas_interpret()

    def hop(q, k, v, bias):
        if pa.partial_blocks(q.shape, k.shape, q.dtype) is None:
            return _block_attn(q, k, v, bias, scale)
        return pa.block_partials_pallas(q, k, v, bias, scale,
                                        interpret=interpret)

    return hop


def ring_attention(q, k, v, axis_name, axis_size, causal=False, scale=None,
                   q_offset=None):
    """Exact attention where K/V circulate the 'sp' ring.

    All inputs are the LOCAL sequence shards: q [B, Lq, H, D], k/v
    [B, Lk, H, D]. Must run inside `shard_map` with mesh axis `axis_name`.
    ``causal`` masks with GLOBAL positions (shard i owns rows
    [i*Lq, (i+1)*Lq)).
    """
    my_idx = lax.axis_index(axis_name)
    lq = q.shape[1]
    lk = k.shape[1]
    scale = scale if scale is not None else 1.0 / np.sqrt(q.shape[-1])
    q_pos_base = (my_idx if q_offset is None else q_offset) * lq

    def bias_for(kv_idx):
        if not causal:
            return None
        q_pos = q_pos_base + jnp.arange(lq)[:, None]          # [Lq,1]
        k_pos = kv_idx * lk + jnp.arange(lk)[None, :]          # [1,Lk]
        mask = q_pos >= k_pos
        # finite mask constant: -inf breaks the streaming combine when a
        # whole K/V block is masked (max would be -inf ⇒ inf-inf = nan);
        # -1e30 makes fully-masked blocks drop out with weight exp(-1e30-m)=0
        return jnp.where(mask, 0.0, -1e30)[None, None]         # [1,1,Lq,Lk]

    from .collectives import ring_shift

    block = _hop_fn(scale)

    o, m, l = block(q, k, v, bias_for(my_idx))

    def body(i, carry):
        o, m, l, k, v = carry
        # one ICI hop: the shared ring primitive (collectives.ring_shift),
        # not a privately-built permutation table
        k = ring_shift(k, axis_name, axis_size)
        v = ring_shift(v, axis_name, axis_size)
        kv_idx = (my_idx - i - 1) % axis_size
        o2, m2, l2 = block(q, k, v, bias_for(kv_idx))
        o, m, l = _combine(o, m, l, o2, m2, l2)
        return o, m, l, k, v

    o, m, l, _, _ = lax.fori_loop(0, axis_size - 1, body, (o, m, l, k, v))
    return (o / _bhql_to_bqhl(l).astype(o.dtype)).astype(q.dtype)


def ring_self_attention(q, k, v, mesh=None, axis_name=AXIS_SP, causal=False,
                        scale=None):
    """Sharded entry point: q/k/v are GLOBAL [B, L, H, D] arrays (or numpy);
    the sequence dim is sharded over `axis_name` and ring attention runs as
    one jitted SPMD program.

    Mesh resolution goes through the shared substrate (`mesh.default_mesh`
    honors `use_mesh` and `MXNET_MESH_SHAPE`, so e.g. 'dp=2,sp=4' composes
    the same way zero1/pipeline resolve their axes); the degenerate-axis
    check uses `mesh.axis_size` — absent axis == size 1 == replicated."""
    from .collectives import shard_map

    mesh = mesh or default_mesh()
    n = mesh_mod.axis_size(mesh, axis_name)
    if n == 1:
        # no (or size-1) sequence axis — plain attention
        qj = jnp.asarray(q)
        o, m, l = _block_attn(qj, jnp.asarray(k), jnp.asarray(v),
                              _full_causal_bias(q.shape[1], k.shape[1]) if causal else None,
                              scale)
        return (o / _bhql_to_bqhl(l).astype(o.dtype)).astype(qj.dtype)

    fn = _sharded_ring_fn(mesh, axis_name, n, causal, scale)
    spec = NamedSharding(mesh, P(None, axis_name))
    q = jax.device_put(jnp.asarray(q), spec)
    k = jax.device_put(jnp.asarray(k), spec)
    v = jax.device_put(jnp.asarray(v), spec)
    with mesh:
        return fn(q, k, v)


def _full_causal_bias(lq, lk):
    mask = jnp.arange(lq)[:, None] >= jnp.arange(lk)[None, :]
    return jnp.where(mask, 0.0, -1e30)[None, None]


def _sharded_ring_fn(mesh, axis_name, axis_size, causal, scale):
    def build():
        from .collectives import shard_map

        spec = P(None, axis_name)

        def body(q, k, v):
            return ring_attention(q, k, v, axis_name, axis_size, causal,
                                  scale)

        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(spec, spec, spec),
                                 out_specs=spec))

    return _ring_cache.get_or_build(
        (mesh, axis_name, axis_size, causal, scale), build)
