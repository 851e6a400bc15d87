"""Rotary position embedding as the models with positions share it
(`latent_moe.LatentMoELM`, `window_moe.WindowMoELM`): the half-split pairing
(`rotate_half`) and YaRN's blended frequencies."""
from __future__ import annotations

import math

import numpy as np

import jax.numpy as jnp

__all__ = ["yarn_inv_freq", "rotate_half"]


def yarn_inv_freq(dim, theta, scaling):
    """Inverse frequencies of the `dim` rotary entries of a head: the
    published `theta^(-2i/dim)` without `scaling`; with it (YaRN: `factor`,
    `original_max_position_embeddings`, `beta_fast`, `beta_slow`) as
    published where a frequency turns more than `beta_fast` times over the
    original context, divided by `factor` where fewer than `beta_slow`, a
    linear blend between (the correction dims truncated to integers)."""
    extra = theta ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return extra

    def correction_dim(rotations):
        return dim * math.log(scaling["original_max_position_embeddings"]
                              / (rotations * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    ramp = np.clip((np.arange(dim // 2) - low) / max(high - low, 1e-3), 0, 1)
    return extra / scaling["factor"] * ramp + extra * (1 - ramp)


def rotate_half(x, positions, inv_freq, amplitude=1.0):
    """Half-split rotary embedding of the last axis of `x` [T, ..., dim] at
    `positions` [T], computed in float32: entry `i` pairs with entry `i +
    dim/2`; cos and sin are multiplied by `amplitude`."""
    half = x.shape[-1] // 2
    angle = positions[:, None].astype(jnp.float32) \
        * jnp.asarray(inv_freq, jnp.float32)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (half,)
    cos = (jnp.cos(angle) * amplitude).reshape(shape)
    sin = (jnp.sin(angle) * amplitude).reshape(shape)
    a, b = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)
