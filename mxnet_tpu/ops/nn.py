"""Neural-network ops.

Parity: `src/operator/nn/` — fully_connected.cc, convolution.cc,
deconvolution.cc, pooling.cc, activation.cc, leaky_relu.cc (leaky/prelu/elu/
selu/gelu/rrelu), batch_norm.cc, layer_norm.cc, dropout.cc, softmax.cc,
log_softmax, softmax_activation.cc, upsampling.cc, lrn.cc;
`src/operator/softmax_output.cc`; `src/operator/instance_norm.cc`.

TPU-first design notes:
- Convs/matmuls call `lax.conv_general_dilated`/`lax.dot_general` with
  fp32 accumulation (`preferred_element_type`) so bf16 weights ride the MXU
  at full rate — the reference's pseudo-fp16 path needed explicit casts.
- Data layout stays NCHW at the API (reference default); XLA's layout
  assignment re-tiles for the TPU's (8,128) registers internally, so no
  NHWC rewrite is forced on users.
- Everything is a pure function: BatchNorm returns updated moving stats as
  extra outputs (mutate_aux), replacing in-kernel aux mutation
  (reference batch_norm.cc writes moving_mean in-place).
"""
from __future__ import annotations

import math
from functools import partial as _partial

import jax
import jax.numpy as jnp
import numpy as _np
from jax import lax

from .. import telemetry
from .registry import register
from ._utils import as_tuple, parse_bool


def _acc(x):
    return jnp.float32 if x.dtype in (jnp.bfloat16, jnp.float16) else None


@_partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6, 7))
def _conv_accum32(data, weight, stride, padding, lhs_dilation, rhs_dilation,
                  dim_spec, groups):
    """conv_general_dilated with explicit fp32 accumulation for half-dtype
    inputs. jax 0.9's conv transpose rule cannot mix a fp32 cotangent with
    half-dtype residuals (it rejects the dtype pair), so the backward here
    re-derives the gradient convs at the INPUT dtype — gradients are linear
    in the cotangent, and the MXU accumulates partial products in fp32 in
    hardware either way."""
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dim_spec)
    return lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=padding,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dn, feature_group_count=groups,
        preferred_element_type=jnp.float32,
    ).astype(data.dtype)


def _conv_accum32_fwd(data, weight, stride, padding, lhs_dilation, rhs_dilation,
                      dim_spec, groups):
    out = _conv_accum32(data, weight, stride, padding, lhs_dilation,
                        rhs_dilation, dim_spec, groups)
    return out, (data, weight)


def _conv_accum32_bwd(stride, padding, lhs_dilation, rhs_dilation, dim_spec,
                      groups, res, ct):
    data, weight = res

    def same_dtype_conv(d, w):
        dn = lax.conv_dimension_numbers(d.shape, w.shape, dim_spec)
        return lax.conv_general_dilated(
            d, w, window_strides=stride, padding=padding,
            lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
            dimension_numbers=dn, feature_group_count=groups)

    _, vjp = jax.vjp(same_dtype_conv, data, weight)
    return vjp(ct.astype(data.dtype))


_conv_accum32.defvjp(_conv_accum32_fwd, _conv_accum32_bwd)


def _conv_any(data, weight, stride, padding, lhs_dilation, rhs_dilation,
              dim_spec, groups):
    """Dispatch: fp32-accumulating custom-vjp path for half dtypes, plain
    conv otherwise."""
    if _acc(data) is not None:
        return _conv_accum32(data, weight, tuple(stride), tuple(padding),
                             tuple(lhs_dilation) if lhs_dilation else None,
                             tuple(rhs_dilation) if rhs_dilation else None,
                             dim_spec, int(groups))
    dn = lax.conv_dimension_numbers(data.shape, weight.shape, dim_spec)
    return lax.conv_general_dilated(
        data, weight, window_strides=stride, padding=padding,
        lhs_dilation=lhs_dilation, rhs_dilation=rhs_dilation,
        dimension_numbers=dn, feature_group_count=groups)


# ---------------------------------------------------------------------------
# FullyConnected
# ---------------------------------------------------------------------------


@register("FullyConnected")
def _fully_connected(data, weight, *maybe_bias, num_hidden=None, no_bias=False, flatten=True, **kw):
    """y = x W^T + b  (reference `fully_connected.cc`). Weight layout is
    (num_hidden, in_units) exactly as the reference stores it."""
    if parse_bool(flatten) and data.ndim > 2:
        data = data.reshape(data.shape[0], -1)
    out = lax.dot_general(
        data, weight,
        dimension_numbers=(((data.ndim - 1,), (1,)), ((), ())),
        preferred_element_type=_acc(data),
    )
    out = out.astype(data.dtype)
    if not parse_bool(no_bias) and maybe_bias:
        out = out + maybe_bias[0]
    return out


# ---------------------------------------------------------------------------
# Convolution / Deconvolution
# ---------------------------------------------------------------------------


def _conv_dims(kernel):
    nd = len(kernel)
    if nd == 1:
        return ("NCH", "OIH", "NCH")
    if nd == 2:
        return ("NCHW", "OIHW", "NCHW")
    return ("NCDHW", "OIDHW", "NCDHW")


@register("Convolution")
def _convolution(data, weight, *maybe_bias, kernel=None, stride=None, dilate=None, pad=None,
                 num_filter=None, num_group=1, no_bias=False, layout=None, workspace=1024,
                 cudnn_tune=None, cudnn_off=False, **kw):
    kernel = as_tuple(kernel)
    nd = len(kernel)
    stride = as_tuple(stride, nd) or (1,) * nd
    dilate = as_tuple(dilate, nd) or (1,) * nd
    pad = as_tuple(pad, nd) or (0,) * nd
    out = _conv_any(data, weight, stride, tuple((p, p) for p in pad),
                    None, dilate, _conv_dims(kernel), int(num_group))
    if not parse_bool(no_bias) and maybe_bias:
        b = maybe_bias[0].reshape((1, -1) + (1,) * nd)
        out = out + b
    return out


@register("Deconvolution")
def _deconvolution(data, weight, *maybe_bias, kernel=None, stride=None, dilate=None, pad=None,
                   adj=None, target_shape=None, num_filter=None, num_group=1, no_bias=True,
                   layout=None, workspace=1024, cudnn_tune=None, cudnn_off=False, **kw):
    """Transposed conv (reference `deconvolution.cc`): gradient of Convolution
    wrt data, expressed directly via lhs_dilation (XLA-native)."""
    kernel = as_tuple(kernel)
    nd = len(kernel)
    stride = as_tuple(stride, nd) or (1,) * nd
    dilate = as_tuple(dilate, nd) or (1,) * nd
    pad = as_tuple(pad, nd) or (0,) * nd
    adj = as_tuple(adj, nd) or (0,) * nd
    groups = int(num_group)
    # weight layout (in_channels, out_channels/g, *kernel) → flip spatial, swap io
    w = jnp.flip(weight, axis=tuple(range(2, 2 + nd)))
    if groups == 1:
        w = jnp.swapaxes(w, 0, 1)
    else:
        cin, cog = weight.shape[0], weight.shape[1]
        w = w.reshape((groups, cin // groups, cog) + kernel)
        w = jnp.swapaxes(w, 1, 2).reshape((groups * cog, cin // groups) + kernel)
    pads = [(int(dilate[i]) * (kernel[i] - 1) - pad[i],
             int(dilate[i]) * (kernel[i] - 1) - pad[i] + adj[i]) for i in range(nd)]
    out = _conv_any(data, w, (1,) * nd, tuple(tuple(p) for p in pads),
                    stride, dilate, _conv_dims(kernel), groups)
    if not parse_bool(no_bias) and maybe_bias:
        out = out + maybe_bias[0].reshape((1, -1) + (1,) * nd)
    return out


# ---------------------------------------------------------------------------
# Pooling
# ---------------------------------------------------------------------------


@register("Pooling")
def _pooling(data, kernel=None, pool_type="max", global_pool=False, stride=None, pad=None,
             pooling_convention="valid", cudnn_off=False, p_value=2, count_include_pad=True, **kw):
    nd = data.ndim - 2
    if parse_bool(global_pool):
        axes = tuple(range(2, data.ndim))
        if pool_type == "max":
            return jnp.max(data, axis=axes, keepdims=True)
        if pool_type in ("avg", "sum"):
            r = jnp.sum(data, axis=axes, keepdims=True)
            if pool_type == "avg":
                r = r / math.prod(data.shape[2:])
            return r
        if pool_type == "lp":
            p = float(p_value)
            return jnp.power(jnp.sum(jnp.power(jnp.abs(data), p), axis=axes, keepdims=True), 1.0 / p)
    kernel = as_tuple(kernel, nd)
    stride = as_tuple(stride, nd) or (1,) * nd
    pad = as_tuple(pad, nd) or (0,) * nd
    window = (1, 1) + kernel
    strides = (1, 1) + stride
    if pooling_convention == "full":
        # ceil-mode output: pad right edge enough for a final partial window
        pads = [(0, 0), (0, 0)]
        for i in range(nd):
            in_sz = data.shape[2 + i]
            out_sz = max(0, math.ceil((in_sz + 2 * pad[i] - kernel[i]) / stride[i])) + 1
            need = (out_sz - 1) * stride[i] + kernel[i] - in_sz - pad[i]
            pads.append((pad[i], max(need, pad[i])))
    else:
        pads = [(0, 0), (0, 0)] + [(p, p) for p in pad]
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(data.dtype, jnp.floating) else jnp.iinfo(data.dtype).min
        # numpy scalar init keeps the monoid concrete under an outer trace so
        # jax lowers to reduce_window_max (differentiable), not generic reduce_window
        return lax.reduce_window(data, _np.asarray(init, data.dtype)[()], lax.max, window, strides, pads)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(data, _np.asarray(0, data.dtype)[()], lax.add, window, strides, pads)
        if pool_type == "sum":
            return s
        if parse_bool(count_include_pad):
            return s / math.prod(kernel)
        ones = jnp.ones(data.shape, data.dtype)
        cnt = lax.reduce_window(ones, _np.asarray(0, data.dtype)[()], lax.add, window, strides, pads)
        return s / cnt
    if pool_type == "lp":
        p = float(p_value)
        s = lax.reduce_window(jnp.power(jnp.abs(data), p), _np.asarray(0, data.dtype)[()], lax.add,
                              window, strides, pads)
        return jnp.power(s, 1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


@register("UpSampling")
def _upsampling(*args, scale=1, sample_type="nearest", num_args=1, num_filter=0, multi_input_mode="concat", workspace=512, **kw):
    data = args[0]
    s = int(scale)
    if sample_type == "nearest":
        out = jnp.repeat(jnp.repeat(data, s, axis=2), s, axis=3)
        if len(args) > 1 and multi_input_mode == "concat":
            outs = [out]
            for a in args[1:]:
                f = data.shape[2] * s // a.shape[2]
                outs.append(jnp.repeat(jnp.repeat(a, f, axis=2), f, axis=3))
            out = jnp.concatenate(outs, axis=1)
        return out
    # bilinear: args = (data, weight) — use deconv with bilinear kernel
    weight = args[1]
    kernel = weight.shape[-1]
    pad = (kernel - s) // 2 if (kernel - s) % 2 == 0 else (kernel - s + 1) // 2
    return _deconvolution(data, weight, kernel=(kernel, kernel), stride=(s, s),
                          pad=(pad, pad), num_group=data.shape[1], no_bias=True)


@register("LRN")
def _lrn(data, alpha=1e-4, beta=0.75, knorm=2.0, nsize=5, **kw):
    n = int(nsize)
    sq = jnp.square(data)
    pad = n // 2
    padded = jnp.pad(sq, [(0, 0), (pad, pad), (0, 0), (0, 0)])
    win = sum(padded[:, i:i + data.shape[1]] for i in range(n))
    norm = jnp.power(float(knorm) + float(alpha) / n * win, float(beta))
    return data / norm


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


@register("Activation")
def _activation(data, act_type="relu", **kw):
    fns = {
        "relu": jax.nn.relu,
        "sigmoid": jax.nn.sigmoid,
        "tanh": jnp.tanh,
        "softrelu": jax.nn.softplus,
        "softsign": jax.nn.soft_sign,
    }
    return fns[act_type](data)


@register("LeakyReLU", needs_rng=True, needs_mode=True)
def _leaky_relu(key, data, *maybe_gamma, act_type="leaky", slope=0.25, lower_bound=0.125,
                upper_bound=0.334, _train=False, **kw):
    if act_type == "leaky":
        return jnp.where(data >= 0, data, float(slope) * data)
    if act_type == "prelu":
        gamma = maybe_gamma[0]
        if gamma.ndim == 1 and data.ndim > 1:
            gamma = gamma.reshape((1, -1) + (1,) * (data.ndim - 2))
        return jnp.where(data >= 0, data, gamma * data)
    if act_type == "elu":
        return jnp.where(data >= 0, data, float(slope) * jnp.expm1(data))
    if act_type == "selu":
        alpha, scale = 1.6732632423543772, 1.0507009873554805
        return scale * jnp.where(data >= 0, data, alpha * jnp.expm1(data))
    if act_type == "gelu":
        return jax.nn.gelu(data, approximate=False)
    if act_type == "rrelu":
        lo, hi = float(lower_bound), float(upper_bound)
        if parse_bool(_train):
            slope_r = jax.random.uniform(key, data.shape, minval=lo, maxval=hi).astype(data.dtype)
        else:
            slope_r = (lo + hi) / 2.0
        return jnp.where(data >= 0, data, slope_r * data)
    raise ValueError(act_type)


@register("softmax")
def _softmax(data, axis=-1, temperature=None, dtype=None, use_length=False, length=None, **kw):
    x = data
    if temperature not in (None, "None"):
        x = x / float(temperature)
    out = jax.nn.softmax(x.astype(jnp.float32), axis=int(axis)).astype(data.dtype)
    if dtype not in (None, "None"):
        from ..base import np_dtype

        out = out.astype(np_dtype(dtype))
    return out


@register("log_softmax")
def _log_softmax(data, axis=-1, temperature=None, dtype=None, **kw):
    x = data
    if temperature not in (None, "None"):
        x = x / float(temperature)
    out = jax.nn.log_softmax(x.astype(jnp.float32), axis=int(axis)).astype(data.dtype)
    if dtype not in (None, "None"):
        from ..base import np_dtype

        out = out.astype(np_dtype(dtype))
    return out


@register("softmin")
def _softmin(data, axis=-1, temperature=None, dtype=None, **kw):
    return _softmax(-data, axis=axis, temperature=temperature, dtype=dtype)


@register("SoftmaxActivation")
def _softmax_activation(data, mode="instance", **kw):
    if mode == "channel":
        return jax.nn.softmax(data, axis=1)
    return jax.nn.softmax(data.reshape(data.shape[0], -1), axis=-1).reshape(data.shape)


@register("softmax_cross_entropy")
def _softmax_cross_entropy(data, label, **kw):
    logp = jax.nn.log_softmax(data.astype(jnp.float32), axis=-1)
    lab = label.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, lab[:, None], axis=-1)
    return -jnp.sum(picked).astype(data.dtype)


def _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output, use_ignore,
                         normalization):
    axis = 1 if multi_output else -1
    return jax.nn.softmax(data, axis=axis)


@_partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _softmax_output_core(data, label, grad_scale, ignore_label, multi_output, use_ignore,
                         normalization):
    return _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output,
                                use_ignore, normalization)


def _softmax_output_fwd(data, label, grad_scale, ignore_label, multi_output, use_ignore,
                        normalization):
    p = _softmax_output_impl(data, label, grad_scale, ignore_label, multi_output,
                             use_ignore, normalization)
    return p, (p, label)


def _softmax_output_bwd(grad_scale, ignore_label, multi_output, use_ignore, normalization,
                        res, g):
    """Loss-layer gradient (p - onehot)·grad_scale, independent of the head
    grad — the defining behavior of the reference's softmax_output.cc."""
    p, label = res
    axis = 1 if multi_output else -1
    ncls = p.shape[axis]
    lab = label.astype(jnp.int32)
    onehot = jax.nn.one_hot(lab, ncls, axis=axis, dtype=p.dtype)
    grad = (p - onehot)
    if use_ignore:
        keep = (lab != int(ignore_label)).astype(p.dtype)
        grad = grad * jnp.expand_dims(keep, axis=axis)
    if normalization == "batch":
        grad = grad / p.shape[0]
    elif normalization == "valid" and use_ignore:
        keepn = jnp.maximum(jnp.sum((lab != int(ignore_label)).astype(p.dtype)), 1.0)
        grad = grad / keepn
    elif normalization == "valid":
        grad = grad / p.shape[0]
    grad = grad * grad_scale
    return (grad.astype(p.dtype), jnp.zeros_like(label))


_softmax_output_core.defvjp(_softmax_output_fwd, _softmax_output_bwd)


@register("SoftmaxOutput", aliases=["Softmax"])
def _softmax_output(data, label, grad_scale=1.0, ignore_label=-1, multi_output=False,
                    use_ignore=False, preserve_shape=False, normalization="null",
                    out_grad=False, smooth_alpha=0.0, **kw):
    return _softmax_output_core(data, label, float(grad_scale), int(float(ignore_label)),
                                parse_bool(multi_output), parse_bool(use_ignore),
                                normalization)


# ---------------------------------------------------------------------------
# Normalization
# ---------------------------------------------------------------------------


def _bn_axes(data, axis):
    """The reduced axes, the broadcast shape of a per-channel vector and the
    number of elements a channel."""
    return (tuple(i for i in range(data.ndim) if i != axis),
            tuple(-1 if i == axis else 1 for i in range(data.ndim)),
            data.size // data.shape[axis])


@_partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _batch_norm_train(data, gamma, beta, pivot, axis, eps):
    """BatchNorm's training step in closed form: `(out, mean, var)` with the
    batch's biased statistics in float32. Both sums are ONE pass over the
    activation — each depends on nothing but `data` and the pivot, so XLA
    carries them in the fusion that produces `data` — where `jnp.var` reads
    it a second time, after the mean. `pivot` is a per-channel guess of the
    mean known before the pass (the moving mean): the sums are taken of
    `data - pivot`, so `E[d^2] - E[d]^2` does not cancel where a channel's
    mean is large against its spread. `mean` / `var` feed the moving
    statistics only and carry no gradient (reference `batch_norm.cc`)."""
    return _batch_norm_train_fwd(data, gamma, beta, pivot, axis, eps)[0]


def _batch_norm_train_fwd(data, gamma, beta, pivot, axis, eps):
    red, shape, n = _bn_axes(data, axis)
    xf = data.astype(jnp.float32)
    d = xf - pivot.reshape(shape)
    m1 = jnp.sum(d, axis=red) / n
    m2 = jnp.sum(d * d, axis=red) / n
    mean = pivot + m1
    var = jnp.maximum(m2 - m1 * m1, 0.0)
    inv = lax.rsqrt(var + eps)
    scale = inv * gamma.astype(jnp.float32)
    out = (xf - mean.reshape(shape)) * scale.reshape(shape) \
        + beta.astype(jnp.float32).reshape(shape)
    return (out.astype(data.dtype), mean, var), (data, gamma, beta, mean, inv)


def _batch_norm_train_bwd(axis, eps, res, cts):
    """`dbeta = sum(dy)` and `dgamma = sum(dy * xhat)` are siblings over the
    same two operands — one pass — and `dx` is one elementwise expression of
    them; autodiff of the forward reads the activation at three more
    dependent levels (the transposes of the mean inside the variance, which
    are algebraically zero). `xhat` is recomputed, not stored."""
    data, gamma, beta, mean, inv = res
    red, shape, n = _bn_axes(data, axis)
    dy = cts[0].astype(jnp.float32)
    xhat = (data.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    dbeta = jnp.sum(dy, axis=red)
    dgamma = jnp.sum(dy * xhat, axis=red)
    dx = (gamma.astype(jnp.float32) * inv).reshape(shape) * (
        dy - (dbeta / n).reshape(shape) - xhat * (dgamma / n).reshape(shape))
    return (dx.astype(data.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype), jnp.zeros_like(mean))


_batch_norm_train.defvjp(_batch_norm_train_fwd, _batch_norm_train_bwd)


@register("BatchNorm", aliases=["BatchNorm_v1"], needs_mode=True, num_outputs=3, mutate_aux=(3, 4))
def _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                fix_gamma=True, use_global_stats=False, output_mean_var=False, axis=1,
                cudnn_off=False, _train=False, **kw):
    """Pure-functional BatchNorm: returns (out, new_moving_mean, new_moving_var).
    The frontend writes outputs 1,2 back into the aux NDArrays (mutate_aux),
    matching the reference's in-place moving-stat update (`batch_norm.cc`).
    With telemetry on, a trace of the training step counts
    `batchnorm.train.closed_form` once a node."""
    axis = int(axis) % data.ndim
    eps, momentum = float(eps), float(momentum)
    if parse_bool(fix_gamma):
        gamma = jnp.ones_like(gamma)
    if parse_bool(_train) and not parse_bool(use_global_stats):
        if telemetry._enabled:
            telemetry.counter("batchnorm.train.closed_form").inc()
        out, mean, var = _batch_norm_train(data, gamma, beta,
                                           moving_mean.astype(jnp.float32), axis, eps)
        new_mean = momentum * moving_mean + (1 - momentum) * mean.astype(moving_mean.dtype)
        new_var = momentum * moving_var + (1 - momentum) * var.astype(moving_var.dtype)
        return out, new_mean, new_var
    _, shape, _ = _bn_axes(data, axis)
    mean, var = moving_mean.astype(jnp.float32), moving_var.astype(jnp.float32)
    inv = lax.rsqrt(var + eps)
    out = (data.astype(jnp.float32) - mean.reshape(shape)) * inv.reshape(shape)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype), moving_mean, moving_var


@register("LayerNorm")
def _layer_norm(data, gamma, beta, axis=-1, eps=1e-5, output_mean_var=False, **kw):
    axis = int(axis) % data.ndim
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=axis, keepdims=True)
    var = jnp.var(xf, axis=axis, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + float(eps))
    shape = [1] * data.ndim
    shape[axis] = data.shape[axis]
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("InstanceNorm")
def _instance_norm(data, gamma, beta, eps=1e-3, **kw):
    red = tuple(range(2, data.ndim))
    xf = data.astype(jnp.float32)
    mean = jnp.mean(xf, axis=red, keepdims=True)
    var = jnp.var(xf, axis=red, keepdims=True)
    out = (xf - mean) * lax.rsqrt(var + float(eps))
    shape = (1, -1) + (1,) * (data.ndim - 2)
    out = out * gamma.astype(jnp.float32).reshape(shape) + beta.astype(jnp.float32).reshape(shape)
    return out.astype(data.dtype)


@register("_contrib_SyncBatchNorm", needs_mode=True, num_outputs=3, mutate_aux=(3, 4))
def _sync_batch_norm(data, gamma, beta, moving_mean, moving_var, eps=1e-3, momentum=0.9,
                     fix_gamma=True, use_global_stats=False, output_mean_var=False,
                     ndev=1, key=None, _train=False, **kw):
    """Cross-replica BatchNorm: inside pjit/shard_map the mean/var reductions
    become XLA cross-replica collectives automatically when the batch axis is
    sharded; standalone it equals BatchNorm (reference contrib sync BN)."""
    return _batch_norm(data, gamma, beta, moving_mean, moving_var, eps=eps, momentum=momentum,
                       fix_gamma=fix_gamma, use_global_stats=use_global_stats,
                       output_mean_var=output_mean_var, axis=1, _train=_train)


# ---------------------------------------------------------------------------
# Dropout
# ---------------------------------------------------------------------------


@register("Dropout", needs_rng=True, needs_mode=True)
def _dropout(key, data, p=0.5, mode="training", axes=(), cudnn_off=False, _train=False, **kw):
    p = float(p)
    if (not parse_bool(_train) and mode != "always") or p == 0.0:
        return data
    axes = as_tuple(axes) or ()
    if axes:
        mshape = tuple(1 if i in axes else s for i, s in enumerate(data.shape))
    else:
        mshape = data.shape
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, mshape)
    return jnp.where(mask, data / keep, jnp.zeros((), data.dtype)).astype(data.dtype)


# ---------------------------------------------------------------------------
# Losses as ops
# ---------------------------------------------------------------------------


def _regression_op(fwd_fn, grad_fn):
    """Loss-layer regression outputs: forward transforms data, backward is the
    closed-form residual ÷ batch (reference `src/operator/regression_output-inl.h`:
    igrad = grad_fn(pred, label) * grad_scale / num_batch), ignoring head grads."""

    @_partial(jax.custom_vjp, nondiff_argnums=(2,))
    def core(data, label, grad_scale):
        return fwd_fn(data)

    def fwd(data, label, grad_scale):
        p = fwd_fn(data)
        return p, (p, label)

    def bwd(grad_scale, res, g):
        p, label = res
        grad = grad_fn(p, label.reshape(p.shape)) * grad_scale
        return (grad.astype(p.dtype), jnp.zeros_like(label))

    core.defvjp(fwd, bwd)
    return core


_linreg_core = _regression_op(lambda x: x, lambda p, l: p - l)
_maereg_core = _regression_op(lambda x: x, lambda p, l: jnp.sign(p - l))
_logreg_core = _regression_op(jax.nn.sigmoid, lambda p, l: p - l)


@register("LinearRegressionOutput")
def _linear_regression_output(data, label, grad_scale=1.0, **kw):
    return _linreg_core(data, label, float(grad_scale))


@register("MAERegressionOutput")
def _mae_regression_output(data, label, grad_scale=1.0, **kw):
    return _maereg_core(data, label, float(grad_scale))


@register("LogisticRegressionOutput")
def _logistic_regression_output(data, label, grad_scale=1.0, **kw):
    return _logreg_core(data, label, float(grad_scale))


@register("MakeLoss")
def _make_loss_op(data, grad_scale=1.0, valid_thresh=0.0, normalization="null", **kw):
    return data


# ---------------------------------------------------------------------------
# Embedding-ish / misc nn
# ---------------------------------------------------------------------------


@register("BilinearSampler")
def _bilinear_sampler(data, grid, cudnn_off=False, **kw):
    n, c, h, w = data.shape
    gx = (grid[:, 0] + 1.0) * (w - 1) / 2.0
    gy = (grid[:, 1] + 1.0) * (h - 1) / 2.0
    x0 = jnp.floor(gx); y0 = jnp.floor(gy)
    wx = gx - x0; wy = gy - y0

    def sample(xi, yi):
        xi = jnp.clip(xi, 0, w - 1).astype(jnp.int32)
        yi = jnp.clip(yi, 0, h - 1).astype(jnp.int32)
        idx = yi * w + xi  # (n, ho, wo)
        flat = data.reshape(n, c, h * w)
        return jnp.take_along_axis(flat, idx.reshape(n, 1, -1).repeat(c, 1), axis=2).reshape(
            n, c, *idx.shape[1:]
        )

    v00 = sample(x0, y0); v01 = sample(x0 + 1, y0)
    v10 = sample(x0, y0 + 1); v11 = sample(x0 + 1, y0 + 1)
    wx = wx[:, None]; wy = wy[:, None]
    in_x = ((gx >= 0) & (gx <= w - 1))[:, None]
    in_y = ((gy >= 0) & (gy <= h - 1))[:, None]
    out = (v00 * (1 - wx) * (1 - wy) + v01 * wx * (1 - wy) + v10 * (1 - wx) * wy + v11 * wx * wy)
    return jnp.where(in_x & in_y, out, 0.0).astype(data.dtype)


@register("GridGenerator")
def _grid_generator(data, transform_type="affine", target_shape=(0, 0), **kw):
    th, tw = as_tuple(target_shape)
    ys = jnp.linspace(-1.0, 1.0, th)
    xs = jnp.linspace(-1.0, 1.0, tw)
    gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
    if transform_type == "affine":
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx.reshape(-1), gy.reshape(-1), ones.reshape(-1)], axis=0)
        theta = data.reshape(-1, 2, 3)
        out = jnp.einsum("nij,jk->nik", theta, base)
        return out.reshape(-1, 2, th, tw)
    return data + jnp.stack([gx, gy])[None]


@register("IdentityAttachKLSparseReg")
def _identity_kl(data, sparseness_target=0.1, penalty=0.001, momentum=0.9, **kw):
    return data
