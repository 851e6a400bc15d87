"""Fused flash-attention Pallas kernel (beyond-parity TPU perf item).

The transformer's local attention (`models/transformer.py._attention` →
`parallel/ring_attention._block_attn`) is already streaming-softmax at the
XLA level, but the S = QK^T logits still round-trip HBM between the two
einsums. This kernel keeps the whole Q-block pipeline — QK^T, running
max/sum-exp, PV accumulation — resident in VMEM (the flash-attention
schedule; see /opt/skills/guides/pallas_guide.md), one grid step per
(batch*head, q-block).

Backward: `jax.custom_vjp` whose pullback is the vjp of the plain-XLA
reference attention (recompute; exact same math, so gradients agree with
the fused forward bit-for-bit up to reassociation). That is the standard
"fast forward, recomputed backward" pattern — the backward stays one fused
XLA program.

Availability: TPU (or `interpret=True` anywhere — the CPU test path).
Callers decide BEFORE the call, from the shapes alone, whether the kernel
applies (`flash_blocks` / `partial_blocks`: blocks the Mosaic tiling accepts
and K/V that fit the kernel's fast memory) and take the XLA blockwise path
otherwise; once a caller has chosen the kernel, a compiler refusal raises.
"""
from __future__ import annotations

import functools
import math
import os

import jax
import jax.numpy as jnp
from jax import lax

from jax.experimental import pallas as pl

from ..compile_cache import CompileCache

__all__ = ["flash_attention", "reference_attention", "flash_blocks",
           "partial_blocks", "pallas_enabled", "pallas_interpret"]

# one custom_vjp-wrapped kernel per (config) — named so
# `compile_cache.named_stats("pallas")` attributes kernel rebuilds the
# way every other executable cache does (these were anonymous lru_caches).
# track_memory=False: entries are custom_vjp callables with no .lower(),
# so aval recording could never yield a memory row anyway
_pallas_cache = CompileCache("pallas", track_memory=False)

_NEG_INF = -1e30

_LANES = 128
# Mosaic's default scoped-VMEM limit for one kernel on a TPU v5e is 16 MiB
# (the compiler refuses "scoped vmem ... limit 16.00M"). Both kernels keep a
# (batch*head)'s whole K and V resident per grid step, double-buffered by the
# pipeline; 3/4 of the limit goes to them, the rest to the Q/O blocks and the
# fp32 temporaries of one K step.
_RESIDENT_BUDGET_BYTES = 12 * 2 ** 20


def pallas_enabled():
    """Fused-kernel policy: ON by default on the TPU backend, OFF elsewhere
    (the interpret path is a debugging tool, not a CPU win);
    MXNET_PALLAS_ATTENTION=0/1 overrides either way."""
    flag = os.environ.get("MXNET_PALLAS_ATTENTION")
    if flag is not None:
        return flag == "1"
    return jax.default_backend() == "tpu"


def pallas_interpret():
    """MXNET_PALLAS_INTERPRET=1 runs the kernels in the Pallas interpreter
    (the CPU test path); otherwise they are compiled."""
    return os.environ.get("MXNET_PALLAS_INTERPRET") == "1"


def _divisor_block(n, target=128, multiple=8):
    """Largest block <= target that divides n AND that the TPU tiling
    accepts: a multiple of ``multiple`` (8 sublanes for a second-to-last
    block dim, 128 lanes for a last one), or the full dimension. None when
    no such block exists — the caller keeps the XLA path."""
    if n <= target:
        return n
    for b in range(target - target % multiple, 0, -multiple):
        if n % b == 0:
            return b
    return None


def _resident_fits(lk, d, dtype, bias_lanes=0):
    """Whether whole K and V (and the partials kernel's [Lk, block_q] bias
    block) fit the fast-memory budget: the last dim pads to 128 lanes and
    the pipeline holds two buffers of each."""
    lanes = -(-d // _LANES) * _LANES
    per_row = 2 * lanes * jnp.dtype(dtype).itemsize + 4 * bias_lanes
    return 2 * lk * per_row <= _RESIDENT_BUDGET_BYTES


def flash_blocks(q_shape, k_shape, dtype, causal, block_q=128, block_k=128):
    """The shape test for :func:`flash_attention`: ``(block_q, block_k)``
    when the fused forward applies to [B, L, H, D] operands of these shapes,
    None when the caller should take the XLA path (cross-length causal
    attention, lengths no accepted block divides, K/V beyond the resident
    budget)."""
    lq, lk, d = q_shape[1], k_shape[1], q_shape[-1]
    if causal and lq != lk:
        return None
    bq, bk = _divisor_block(lq, block_q), _divisor_block(lk, block_k)
    if bq is None or bk is None or not _resident_fits(lk, d, dtype):
        return None
    return bq, bk


def partial_blocks(q_shape, k_shape, dtype, block_q=128, block_k=128):
    """The shape test for :func:`block_partials_pallas` (the ring hop). The
    q block is also the LAST dim of the kernel's transposed bias block, so
    it tiles by 128 lanes, not 8 sublanes."""
    lq, lk, d = q_shape[1], k_shape[1], q_shape[-1]
    bq = _divisor_block(lq, block_q, multiple=_LANES)
    bk = _divisor_block(lk, block_k)
    if bq is None or bk is None or not _resident_fits(
            lk, d, dtype, bias_lanes=-(-bq // _LANES) * _LANES):
        return None
    return bq, bk


def reference_attention(q, k, v, causal=False, scale=None):
    """Plain-XLA exact attention, fp32 softmax — the numerics contract the
    kernel must reproduce (and the recomputed backward). Layout
    [B, L, H, D] (the transformer's)."""
    scale = scale if scale is not None else 1.0 / math.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        lq, lk = q.shape[1], k.shape[1]
        mask = jnp.tril(jnp.ones((lq, lk), bool), k=lk - lq)
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, block_q,
                block_k, seq_k):
    """One (batch*head, q-block) grid step: stream every K/V block through
    VMEM with the running-softmax update."""
    qb = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    nk = seq_k // block_k

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = qb * block_q + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = i * block_k + lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(k_pos <= q_pos, s, _NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    d = q.shape[-1]
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m0 = jnp.full((block_q, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q, 1), jnp.float32)
    acc, _, l = lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[0] = (acc / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


def _pallas_forward(q, k, v, scale, causal, block_q, block_k, interpret):
    b, lq, h, d = q.shape
    lk = k.shape[1]
    # [B, L, H, D] -> [B*H, L, D]
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k, seq_k=lk)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, lq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, lk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, lk, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, lq, d).transpose(0, 2, 1, 3)


def _make_fa(scale, causal, block_q, block_k, interpret):
    def build():
        @jax.custom_vjp
        def fa(q, k, v):
            return _pallas_forward(q, k, v, scale, causal, block_q,
                                   block_k, interpret)

        def fwd(q, k, v):
            return fa(q, k, v), (q, k, v)

        def bwd(res, do):
            q, k, v = res
            _, vjp = jax.vjp(
                lambda q_, k_, v_: reference_attention(
                    q_, k_, v_, causal=causal, scale=scale), q, k, v)
            return vjp(do)

        fa.defvjp(fwd, bwd)
        return fa

    return _pallas_cache.get_or_build(
        ("fa", scale, causal, block_q, block_k, interpret), build)


def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=False):
    """Fused attention over [B, L, H, D] tensors.

    block sizes clamp to the sequence lengths; raises ValueError when the
    lengths are not divisible by the (clamped) blocks — callers ask
    :func:`flash_blocks` first and keep the XLA blockwise path for such
    shapes."""
    lq, lk = q.shape[1], k.shape[1]
    if causal and lq != lk:
        # the kernel's causal mask assumes aligned self-attention
        # positions; the XLA reference aligns sequence ENDS for lq != lk —
        # callers keep the XLA path for cross-length causal attention
        raise ValueError(
            f"flash_attention: causal requires lq == lk, got ({lq}, {lk})")
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"flash_attention: seq lengths ({lq}, {lk}) not divisible by "
            f"blocks ({block_q}, {block_k})")
    scale = float(scale if scale is not None else 1.0 / math.sqrt(q.shape[-1]))
    fn = _make_fa(scale, bool(causal), int(block_q), int(block_k),
                  bool(interpret))
    return fn(q, k, v)


# ---------------------------------------------------------------------------
# Streaming-partial variant for RING attention (`parallel/ring_attention.py`):
# one ring hop computes this Q-block x local-K/V-block partial — the fused
# kernel returns the UNNORMALIZED (o, m, l) triple the ring's streaming
# combine consumes, so each hop's QK^T/softmax/PV stays in VMEM while K/V
# circulate the ICI ring around it.
# ---------------------------------------------------------------------------


def _partial_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, m_ref, l_ref, *,
                    scale, block_k, seq_k):
    q = q_ref[0].astype(jnp.float32)  # (block_q, D)
    nk = seq_k // block_k

    def body(i, carry):
        acc, m, l = carry
        k = k_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.dslice(i * block_k, block_k), :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        s = s + bias_ref[0, pl.dslice(i * block_k, block_k)].astype(
            jnp.float32).T
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_new = acc * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return acc_new, m_new, l_new

    d = q.shape[-1]
    acc0 = jnp.zeros((q.shape[0], d), jnp.float32)
    m0 = jnp.full((q.shape[0], 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((q.shape[0], 1), jnp.float32)
    acc, m, l = lax.fori_loop(0, nk, body, (acc0, m0, l0))
    o_ref[0] = acc.astype(o_ref.dtype)
    m_ref[0] = m
    l_ref[0] = l


def flash_block_partials(q, k, v, bias=None, scale=None, block_q=128,
                         block_k=128, interpret=False):
    """Fused partial attention over [B, L, H, D]: returns the
    `(o, m, l)` triple with `_block_attn`'s exact contract
    (o = exp(s - m) @ v UNNORMALIZED, m row max, l row sum-exp; all
    fp32 stats, o in q.dtype; `bias` is the ring's additive [*, *, Lq, Lk]
    mask). Raises ValueError on shapes the kernel does not tile."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    block_q = min(block_q, lq)
    block_k = min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(f"flash_block_partials: ({lq}, {lk}) not divisible "
                         f"by blocks ({block_q}, {block_k})")
    scale = float(scale if scale is not None else 1.0 / math.sqrt(d))
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, lq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * h, lk, d)
    if bias is None:
        bias_f = jnp.zeros((1, lq, lk), jnp.float32)
    else:
        bias = jnp.asarray(bias, jnp.float32)
        if bias.size != lq * lk:
            # the kernel shares ONE (Lq, Lk) bias across batch/heads (the
            # ring's mask shape); silently collapsing a per-head bias
            # would be wrong — callers fall back to the XLA path instead
            raise ValueError(
                f"flash_block_partials: bias shape {bias.shape} is not a "
                f"broadcastable ({lq}, {lk}) mask")
        bias_f = bias.reshape(1, lq, lk)
    kernel = functools.partial(_partial_kernel, scale=scale,
                               block_k=block_k, seq_k=lk)
    o, m, l = pl.pallas_call(
        kernel,
        grid=(b * h, lq // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, lk, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, lk, d), lambda i, j: (i, 0, 0)),
            # bias blocked over q rows, transposed inside ((Lk, bq) slices)
            pl.BlockSpec((1, lk, block_q),
                         lambda i, j: (0, 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, lq, d), q.dtype),
            jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * h, lq, 1), jnp.float32),
        ],
        interpret=interpret,
    )(qf, kf, vf, jnp.swapaxes(bias_f, 1, 2))
    o = o.reshape(b, h, lq, d).transpose(0, 2, 1, 3)
    m = m.reshape(b, h, lq, 1)
    l = l.reshape(b, h, lq, 1)
    return o, m, l


def _make_partials_vjp(scale, block_q, block_k, interpret):
    """Differentiable partials: forward is the fused kernel, backward is
    the vjp of the plain-XLA `_block_attn` (same math recomputed) — the
    ring loop stays end-to-end differentiable with the kernel inside."""
    def build():
        from ..parallel.ring_attention import _block_attn

        @jax.custom_vjp
        def partials(q, k, v, bias):
            return flash_block_partials(q, k, v, bias=bias, scale=scale,
                                        block_q=block_q, block_k=block_k,
                                        interpret=interpret)

        def fwd(q, k, v, bias):
            return partials(q, k, v, bias), (q, k, v, bias)

        def bwd(res, cts):
            q, k, v, bias = res
            _, vjp = jax.vjp(
                lambda q_, k_, v_: _block_attn(q_, k_, v_, bias, scale),
                q, k, v)
            dq, dk, dv = vjp(cts)
            return dq, dk, dv, jnp.zeros_like(bias)

        partials.defvjp(fwd, bwd)
        return partials

    return _pallas_cache.get_or_build(
        ("partials", scale, block_q, block_k, interpret), build)


def block_partials_pallas(q, k, v, bias, scale, block_q=128, block_k=128,
                          interpret=False):
    """Ring-hop entry point: `_block_attn`'s contract with the fused
    kernel forward and an exact recomputed backward. `bias` may be None.
    Raises ValueError on shapes :func:`partial_blocks` refuses."""
    blocks = partial_blocks(q.shape, k.shape, q.dtype, block_q, block_k)
    if blocks is None:
        raise ValueError(
            f"block_partials_pallas: no accepted tiling for q {q.shape} / "
            f"k {k.shape} ({q.dtype}); use the XLA path")
    if bias is None:
        bias = jnp.zeros((1, 1, q.shape[1], k.shape[1]), jnp.float32)
    fn = _make_partials_vjp(float(scale), *blocks, bool(interpret))
    return fn(q, k, v, bias)
