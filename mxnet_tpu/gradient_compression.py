"""2-bit gradient compression with error-feedback residual.

Parity: `src/kvstore/gradient_compression.cc:45-113` (`SetParams`,
`SetTwoBitCompression`, `Quantize`/`Dequantize`) and the element kernel
`quantize_2bit` in `src/kvstore/gradient_compression-inl.h:40-80`:

    residual += grad
    if residual >=  threshold: emit code 11, residual -= threshold
    if residual <= -threshold: emit code 10, residual += threshold
    else:                      emit code 00 (value dropped, kept in residual)

Sixteen 2-bit codes pack into one 32-bit word (the reference packs into a
float32's bytes, MSB-first within each byte; we pack LSB-first into a
uint32 — the wire format is ours, the arithmetic is bit-for-bit the same
and is what the tests pin down, reproducing the reference's own expected-
value simulation `tests/nightly/test_kvstore.py:33`
``compute_expected_2bit_quantization``).

TPU-native design: quantize/dequantize are pure jitted functions (fused by
XLA into the push program) plus a Pallas kernel for the quantize hot path
(`quantize_2bit_pallas`) — grid over lane-aligned [16, words] blocks, pack
via a 16-step shift-or in registers. Dequantize(sum-over-workers) runs as one fused XLA
program on the allgathered packed words (`parallel/dist.py`).
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .base import MXNetError

__all__ = ["GradientCompression", "quantize_2bit", "dequantize_2bit",
           "quantize_2bit_pallas"]

_VALS_PER_WORD = 16  # 32 bits / 2 bits per value (GetCompressionFactor, gradient_compression.cc:86)


def compressed_size(n):
    """Number of uint32 words for n values (`GetCompressedSize`,
    gradient_compression.cc:94-99)."""
    return (n + _VALS_PER_WORD - 1) // _VALS_PER_WORD


@functools.partial(jax.jit, static_argnames=("threshold",))
def quantize_2bit(grad, residual, threshold):
    """Error-feedback 2-bit quantization.

    Returns ``(packed uint32[ceil(n/16)], new_residual)``. Gradient + residual
    maps to {-threshold, 0, +threshold}; the rounding error stays in the
    residual (`gradient_compression-inl.h:66-79`).
    """
    r = residual + grad.astype(residual.dtype)
    pos = r >= threshold
    neg = r <= -threshold
    new_residual = jnp.where(pos, r - threshold, jnp.where(neg, r + threshold, r))
    codes = jnp.where(pos, jnp.uint32(3), jnp.where(neg, jnp.uint32(2), jnp.uint32(0)))
    flat = codes.reshape(-1)
    pad = (-flat.shape[0]) % _VALS_PER_WORD
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.uint32)])
    blocks = flat.reshape(-1, _VALS_PER_WORD)
    shifts = (jnp.arange(_VALS_PER_WORD, dtype=jnp.uint32) * 2)[None, :]
    packed = jnp.bitwise_or.reduce(blocks << shifts, axis=1)
    return packed, new_residual


@functools.partial(jax.jit, static_argnames=("shape", "threshold", "dtype"))
def dequantize_2bit(packed, shape, threshold, dtype=jnp.float32):
    """Inverse map: code 11 → +threshold, 10 → -threshold, else 0
    (`Dequantize2BitImpl`, gradient_compression-inl.h:83-...)."""
    n = int(np.prod(shape))
    shifts = (jnp.arange(_VALS_PER_WORD, dtype=jnp.uint32) * 2)[None, :]
    codes = (packed[:, None] >> shifts) & jnp.uint32(3)
    flat = codes.reshape(-1)[:n]
    out = jnp.where(flat == 3, jnp.asarray(threshold, dtype),
                    jnp.where(flat == 2, jnp.asarray(-threshold, dtype),
                              jnp.asarray(0, dtype)))
    return out.reshape(shape)


_LANES = 128          # TPU vector lane count: the block's last dim tiles by it
_MAX_WORD_BLOCK = 1024  # words per grid step (a 16 x 1024 f32 block = 64 KiB)


def _quantize_kernel(g_ref, r_ref, packed_ref, res_ref, *, threshold):
    """One (16, words) block: row j holds value j of every word, so packing is
    a 16-step shift-or down the sublane axis and every load/store is a full
    lane-aligned 32-bit vector."""
    r = r_ref[...] + g_ref[...]
    pos = r >= threshold
    neg = r <= -threshold
    res_ref[...] = jnp.where(pos, r - threshold,
                             jnp.where(neg, r + threshold, r))
    codes = jnp.where(pos, jnp.uint32(3),
                      jnp.where(neg, jnp.uint32(2), jnp.uint32(0)))
    packed = codes[0:1, :]
    for j in range(1, _VALS_PER_WORD):
        packed = packed | (codes[j:j + 1, :] << jnp.uint32(2 * j))
    packed_ref[...] = packed


def quantize_2bit_pallas(grad, residual, threshold, interpret=False):
    """Pallas TPU kernel for the quantize hot path (SURVEY §7's showcase),
    bit-equal to :func:`quantize_2bit`.

    The flat values are laid out ``[16, words]`` (value j of word w at
    ``[j, w]``) so one grid step packs up to 1024 words from 2-D,
    lane-aligned 32-bit blocks — the shape Mosaic tiles. The kernel is
    compiled for the TPU unless the caller asks for ``interpret=True`` (the
    CPU test path).
    """
    from jax.experimental import pallas as pl

    n = grad.size
    words = compressed_size(n)
    block = min(_MAX_WORD_BLOCK, -(-words // _LANES) * _LANES)
    padded_words = -(-words // block) * block

    def lay_out(x):
        flat = x.reshape(-1).astype(jnp.float32)
        flat = jnp.pad(flat, (0, padded_words * _VALS_PER_WORD - n))
        return flat.reshape(padded_words, _VALS_PER_WORD).T

    vals = pl.BlockSpec((_VALS_PER_WORD, block), lambda i: (0, i))
    packed, new_res = pl.pallas_call(
        functools.partial(_quantize_kernel, threshold=float(threshold)),
        grid=(padded_words // block,),
        in_specs=[vals, vals],
        out_specs=[pl.BlockSpec((1, block), lambda i: (0, i)), vals],
        out_shape=[
            jax.ShapeDtypeStruct((1, padded_words), jnp.uint32),
            jax.ShapeDtypeStruct((_VALS_PER_WORD, padded_words), jnp.float32)],
        interpret=interpret,
    )(lay_out(grad), lay_out(residual))
    new_res = new_res.T.reshape(-1)[:n].reshape(residual.shape)
    return packed[0, :words], new_res.astype(residual.dtype)


class GradientCompression:
    """Per-kvstore compression state (`GradientCompression`,
    gradient_compression.h / .cc:40-63). Holds the per-key error-feedback
    residuals — one per worker, exactly like the reference keeps a residual
    NDArray per compressed key on the worker (`kvstore_dist.h` comm buffers).
    """

    def __init__(self):
        self.type = None
        self.threshold = 0.5
        self._residuals = {}

    def set_params(self, compression_params):
        params = dict(compression_params)
        ctype = params.pop("type", None)
        threshold = float(params.pop("threshold", 0.5))
        if params:
            raise MXNetError(f"unknown gradient compression params {sorted(params)}")
        if ctype != "2bit":
            raise MXNetError(f"Unknown type for gradient compression {ctype}")
        if threshold <= 0:
            raise MXNetError("threshold must be greater than 0")
        self.type = "2bit"
        self.threshold = threshold

    @property
    def active(self):
        return self.type == "2bit"

    def quantize(self, key, grad):
        """Quantize ``grad`` for ``key``, folding in and updating the
        residual. Returns packed uint32 words."""
        res = self._residuals.get(key)
        if res is None or res.shape != grad.shape:
            res = jnp.zeros(grad.shape, jnp.float32)
        packed, new_res = quantize_2bit(jnp.asarray(grad), res, self.threshold)
        self._residuals[key] = new_res
        return packed

    def dequantize(self, packed, shape, dtype=jnp.float32):
        return dequantize_2bit(packed, tuple(shape), self.threshold, dtype)
