"""The dropless expert layer that the expert models share
(`latent_moe.LatentMoELM`, `window_moe.WindowMoELM`): the model routes —
its own router, passed in — and this module computes the chosen experts it
is told it holds. The two published routers (`softmax_route`,
`sigmoid_route`) and the SiLU-gated MLP of a dense layer or a shared expert
(`gated_mlp`) are here too, once, for the models that use them.

**Dropless**: there is no capacity. The (token, expert) assignments are
sorted by expert and the experts' two matmuls are grouped products over the
sorted rows (a Pallas grouped matmul on one TPU chip, `lax.ragged_dot`
elsewhere), so the work follows the assignments. **The layer is told which
experts it holds** (`expert_first`, `experts_held` of the router's width):
the router chooses among all of them, the sum runs over the chosen experts
that are held, and what the absent experts would add is left out (they live
on other chips; nothing here stands in for them or for the exchange). With
every expert held (`expert_first` 0, `experts_held` the router's width)
this is the uncut layer.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from .. import telemetry

__all__ = ["expert_layer", "grouped_product", "gmm_tiling",
           "routing_counters", "sigmoid_route", "softmax_route",
           "gated_mlp"]

# tokens an expert layer groups at once in a prefill: bounds the sorted
# copies (top_k rows a token) whatever the bucket
EXPERT_CHUNK = 4096
_LANES = 128


def gmm_tiling(m, k, n, itemsize):
    """The grouped matmul's `(row tile, K tile, N tile)` for `[m, k]` rows
    against `[groups, k, n]` weights, or None when no row tile divides `m`
    (the caller keeps `lax.ragged_dot`). From the shapes:

    * few rows (a decode tick) take the smallest row tile, 128, and the
      widest weight tile: at a few rows a group the product is the weights'
      bytes, and a `[K tile, N tile]` of a weight is one DMA;
    * many rows (a prefill chunk) take a row tile of 256, which reuses a
      weight tile over more rows, and a narrower weight tile;
    * the K and N tiles are whole lane rows that DIVIDE `k` and `n` (a
      remainder tile is masked inside the kernel and moves its full bytes),
      the largest whose `[K tile, N tile]` stays in the budget that keeps
      the double buffers and the accumulator under Mosaic's 16 MiB of
      scoped VMEM: 4 MiB at a row tile of 128, 2 MiB at 256.

    K = 4096 / 2048 with N = 4096 give (128, 512, 4096) and (256, 1024,
    1024), the constants PR 31 measured; K = 2304 / 896 with N = 1792 /
    2304 give (128, 1152, 1792), (128, 896, 2304), (256, 1152, 896) and
    (256, 896, 768)."""
    tm = 256 if m > 1024 and m % 256 == 0 else 128
    if m % tm:
        return None
    budget, widest = ((4 << 20), 4096) if tm == 128 else ((2 << 20), 1024)

    def divisor(x, most):
        """The largest whole-lane-row divisor of `x` at most `most`, `x`
        itself when it is small enough or has none."""
        if x <= most:
            return x
        for t in range(most - most % _LANES, 0, -_LANES):
            if x % t == 0:
                return t
        return x

    tn = divisor(n, widest)
    tk = divisor(k, max(budget // (tn * itemsize), _LANES))
    return tm, tk, tn


def gated_mlp(x, w_in, w_out):
    """The SiLU-gated MLP `(silu(x W_gate) * x W_up) W_down` of a dense
    layer or a shared expert: `w_in` [D, 2 F] is gate | up fused along the
    output axis, `w_out` [F, D]."""
    g, v = jnp.split(x @ w_in, 2, axis=-1)
    return (jax.nn.silu(g) * v) @ w_out


def softmax_route(x, router, top_k, normalise=True, scale=1.0):
    """`x` [T, D] -> `(chosen [T, k] expert ids of the whole router, weights
    [T, k] float32)`: softmax probabilities in float32 over all the experts,
    the `top_k` largest, normalised over the selection when `normalise`
    (`norm_topk_prob`), times `scale` where it is not 1."""
    with jax.named_scope("moe.route"):
        p = jax.nn.softmax(jnp.dot(
            x.astype(jnp.float32), router,
            precision=lax.Precision.HIGHEST), axis=-1)
        weights, chosen = lax.top_k(p, top_k)
        if normalise:
            weights = weights / weights.sum(-1, keepdims=True)
        if scale != 1.0:
            weights = weights * scale
    return chosen, weights


def sigmoid_route(x, router, bias, top_k, scale, normalise=True, eps=0.0):
    """`x` [T, D] -> `(chosen [T, k] expert ids of the whole router, weights
    [T, k] float32)`: sigmoid scores `s` in float32 over all the experts;
    the `top_k` with the largest `s + bias` (the bias enters the selection
    only); weights `s_e / (sum_chosen s + eps) * scale`, the division left
    out without `normalise`."""
    with jax.named_scope("moe.route"):
        s = jax.nn.sigmoid(jnp.dot(
            x.astype(jnp.float32), router,
            precision=lax.Precision.HIGHEST))
        _, chosen = lax.top_k(s + bias, top_k)
        weights = jnp.take_along_axis(s, chosen, axis=-1)
        if normalise:
            total = weights.sum(-1, keepdims=True)
            weights = weights / (total + eps if eps else total)
        weights = weights * scale
    return chosen, weights


def grouped_product(rows, weights, sizes, mesh):
    """The grouped product `rows[group g] @ weights[g]`: `rows` [M, K]
    sorted by group, `weights` [G, K, N], `sizes` [G]; rows past the
    groups' total hold nothing that was computed. On one TPU chip the
    Pallas grouped matmul (jax's megablox `gmm`: empty groups cost nothing
    — XLA's own lowering of `lax.ragged_dot` moved the weights in 512 x
    512 tiles at 52% of the HBM rate where this reads 86%: PERF.md section
    6, PR 31), tiled by :func:`gmm_tiling`; elsewhere `lax.ragged_dot`.
    Decided from shapes, policy and mesh before the call, as a model's
    `decode_block` is; which way a trace went is counted
    (`moe.grouped_product.gmm` / `.ragged_dot`, once a trace, telemetry
    on)."""
    from ..ops import pallas_attention as pa

    tiling = None
    if mesh.size == 1 and pa.pallas_enabled():
        tiling = gmm_tiling(rows.shape[0], rows.shape[1], weights.shape[2],
                            rows.dtype.itemsize)
    if telemetry._enabled:
        telemetry.counter("moe.grouped_product."
                          + ("gmm" if tiling else "ragged_dot")).inc()
    if tiling is None:
        return lax.ragged_dot(rows, weights, sizes)
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    return gmm(rows, weights, sizes, preferred_element_type=rows.dtype,
               tiling=tiling, interpret=pa.pallas_interpret())


def _experts(x, real, route, w_in, w_out, expert_first, mesh):
    """The held experts' part of the layer for `x` [T, D]: `(y [T, D],
    local [T, k])`."""
    held_n = w_in.shape[0]
    chosen, weights = route(x)
    t, k = chosen.shape
    with jax.named_scope("moe.group"):
        local = chosen - expert_first
        held = (local >= 0) & (local < held_n) & real[:, None]
        local = jnp.where(held, local, -1)
        # the pairs held elsewhere sort past the last group
        key = jnp.where(held, local, held_n).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.sum(key[:, None] == jnp.arange(held_n), axis=0,
                        dtype=jnp.int32)
        rows = jnp.take(x, order // k, axis=0)                  # [T k, D]
    with jax.named_scope("moe.experts"):
        hid = grouped_product(rows, w_in, sizes, mesh)
        g, v = jnp.split(hid, 2, axis=-1)
        out = grouped_product(jax.nn.silu(g) * v, w_out, sizes, mesh)
    with jax.named_scope("moe.group"):
        # back to (token, choice) order; a row past the groups holds
        # nothing that was computed
        w = jnp.where(held, weights, 0.0).reshape(-1)
        back = jnp.argsort(order)
        out = jnp.where((jnp.arange(t * k) < sizes.sum())[:, None], out, 0)
        y = (jnp.take(out, back, axis=0).astype(jnp.float32)
             * w[:, None]).reshape(t, k, -1).sum(1)
    return y.astype(x.dtype), local


def expert_layer(x, real, route, w_in, w_out, *, expert_first, mesh):
    """`sum_e w_e E_e(x)` over the chosen experts that are held, for `x`
    [T, D]: `(y [T, D], local [T, k])`.

    * `route(x) -> (chosen [T, k] expert ids of the whole router, weights
      [T, k] float32)` is the model's own router (scope `moe.route`);
    * `w_in` [held, D, 2 F] (gate | up) and `w_out` [held, F, D] are the
      stacked SiLU-gated experts `[expert_first, expert_first + held)`;
    * `real` [T] marks the tokens that exist (padding is routed nowhere).

    `local` is each choice as a held expert's local index, -1 for an expert
    held elsewhere (and for padding). Every (token, chosen held expert)
    pair is computed, grouped by expert; more than `EXPERT_CHUNK` tokens
    are grouped a chunk at a time."""
    def one(xs, rs):
        return _experts(xs, rs, route, w_in, w_out, expert_first, mesh)

    t = x.shape[0]
    if t > EXPERT_CHUNK and t % EXPERT_CHUNK == 0:
        n = t // EXPERT_CHUNK
        y, local = lax.map(lambda xr: one(*xr),
                           (x.reshape(n, EXPERT_CHUNK, -1),
                            real.reshape(n, EXPERT_CHUNK)))
        return y.reshape(x.shape), local.reshape(t, -1)
    return one(x, real)


def routing_counters(routed, alive, experts_held):
    """int32 `[3]` of ONE decode step from what it left in the cache member
    `routed` [S, expert layers, k] (a held expert's local index, -1
    elsewhere) and the live slots `alive` [S]: (token, expert) pairs
    computed here; held experts with at least one token, summed over the
    expert layers; the fullest expert's tokens, summed over the expert
    layers."""
    hit = (routed[..., None] == jnp.arange(experts_held)) \
        & alive[:, None, None, None]                        # [S, Lx, k, E]
    per = hit.sum((0, 2), dtype=jnp.int32)                  # [Lx, E]
    return jnp.stack([per.sum(), (per > 0).sum(dtype=jnp.int32),
                      per.max(-1).sum()])
