"""Plain reference of the bottleneck ResNet: `jax.numpy`, float32, matmul
precision "highest", no kernels, independent of `mxnet_tpu`. Forward, loss,
and one SGD-with-momentum training step by `jax.value_and_grad`.

Two unit orders, named by the traffic file's `arch` (see
configs/resnet50_v1.json, departures):

* `preact_symbol` — BN-ReLU-conv units, BatchNorm on the input with gamma
  fixed at 1, stride on the 3x3, no conv bias, eps 2e-5. Weights arrive as a
  dict under the Symbol's argument names.
* `v1_gluon` — conv-BN-ReLU units, stride on the first 1x1, bias on the 1x1
  convolutions, eps 1e-5. Weights arrive as the ordered list of
  `(name, array)` in which gluon created them; the reference consumes them in
  order (a bias only where the next name ends in `_bias`).

BatchNorm uses the batch's own statistics (training mode, biased variance), so
the moving averages never enter. Each residual unit is wrapped in
`jax.checkpoint`: the float32 activations of a 256-image batch would not fit
beside the program's own state otherwise. That changes memory, not arithmetic.
"""
import functools

import jax
import jax.numpy as jnp
from jax import lax

EPS = {"preact_symbol": 2e-5, "v1_gluon": 1e-5}


def conv(x, w, stride=1, pad=0, bias=None):
    y = lax.conv_general_dilated(
        x, w, (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return y if bias is None else y + bias[None, :, None, None]


def batch_norm(x, gamma, beta, eps):
    mean = x.mean((0, 2, 3), keepdims=True)
    var = ((x - mean) ** 2).mean((0, 2, 3), keepdims=True)
    y = (x - mean) * lax.rsqrt(var + eps)
    if gamma is not None:
        y = y * gamma[None, :, None, None]
    return y + beta[None, :, None, None]


def max_pool_3x3_s2(x):
    return lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3), (1, 1, 2, 2),
                             [(0, 0), (0, 0), (1, 1), (1, 1)])


def relu(x):
    return jnp.maximum(x, 0)


# ---------------------------------------------------------------------------
# preact_symbol: weights by the Symbol's names
# ---------------------------------------------------------------------------

def _preact_unit(p, name, stride, dim_match, x):
    eps = EPS["preact_symbol"]

    def bn(which, v):
        return batch_norm(v, p[f"{name}_{which}_gamma"],
                          p[f"{name}_{which}_beta"], eps)

    act1 = relu(bn("bn1", x))
    y = conv(act1, p[f"{name}_conv1_weight"])
    y = conv(relu(bn("bn2", y)), p[f"{name}_conv2_weight"], stride, 1)
    y = conv(relu(bn("bn3", y)), p[f"{name}_conv3_weight"])
    shortcut = x if dim_match else conv(act1, p[f"{name}_sc_weight"], stride)
    return y + shortcut


def _forward_preact(config, p, x):
    eps = EPS["preact_symbol"]
    x = batch_norm(x, None, p["bn_data_beta"], eps)         # fix_gamma
    x = conv(x, p["conv0_weight"], 2, 3)
    x = max_pool_3x3_s2(relu(batch_norm(x, p["bn0_gamma"], p["bn0_beta"],
                                        eps)))
    for stage, units in enumerate(config["units"]):
        for unit in range(units):
            stride = 2 if (unit == 0 and stage > 0) else 1
            step = jax.checkpoint(functools.partial(
                _preact_unit, p, f"stage{stage + 1}_unit{unit + 1}", stride,
                unit != 0))
            x = step(x)
    x = relu(batch_norm(x, p["bn1_gamma"], p["bn1_beta"], eps))
    x = x.mean((2, 3))
    return x @ p["fc1_weight"].T + p["fc1_bias"]


# ---------------------------------------------------------------------------
# v1_gluon: weights in creation order
# ---------------------------------------------------------------------------

class _Ordered:
    """Hands out the arrays of a `{name: array}` dict in insertion order."""

    def __init__(self, params):
        self.items = list(params.items())
        self.i = 0

    def take(self, suffix):
        name, arr = self.items[self.i]
        if not name.endswith(suffix):
            raise ValueError(f"expected a *{suffix}, found {name}")
        self.i += 1
        return arr

    def maybe(self, suffix):
        if self.i < len(self.items) and self.items[self.i][0].endswith(suffix):
            return self.take(suffix)
        return None

    def conv(self):
        return self.take("_weight"), self.maybe("_bias")

    def bn(self):
        gamma, beta = self.take("_gamma"), self.take("_beta")
        self.maybe("_running_mean")
        self.maybe("_running_var")
        return gamma, beta


def _forward_v1(config, params, x):
    eps = EPS["v1_gluon"]
    it = _Ordered(params)

    def conv_bn(v, stride=1, pad=0):
        w, b = it.conv()
        gamma, beta = it.bn()
        return batch_norm(conv(v, w, stride, pad, b), gamma, beta, eps)

    x = max_pool_3x3_s2(relu(conv_bn(x, 2, 3)))
    for stage, units in enumerate(config["units"]):
        for unit in range(units):
            stride = 2 if (unit == 0 and stage > 0) else 1
            # the unit's weights, taken now so that the checkpointed body
            # below is a pure function of them
            body = [(it.conv(), it.bn()) for _ in range(3)]
            down = (it.conv(), it.bn()) if unit == 0 else None

            def unit_fn(v, body=body, down=down, stride=stride):
                y = v
                for k, ((w, b), (g, be)) in enumerate(body):
                    y = batch_norm(conv(y, w, stride if k == 0 else 1,
                                        1 if k == 1 else 0, b), g, be, eps)
                    if k < 2:
                        y = relu(y)
                if down is not None:
                    (w, b), (g, be) = down
                    v = batch_norm(conv(v, w, stride, 0, b), g, be, eps)
                return relu(y + v)

            x = jax.checkpoint(unit_fn)(x)
    x = x.mean((2, 3))
    w, b = it.take("_weight"), it.take("_bias")
    if it.i != len(it.items):
        raise ValueError(f"{len(it.items) - it.i} weights left over")
    return x @ w.T + b


# ---------------------------------------------------------------------------

def forward(config, arch, names, arrays, x):
    """Logits [batch, classes] in training mode. `names` and `arrays`: the
    net's weights as float32, in the order the program created them (jit
    would sort a dict's keys, and `v1_gluon` reads by order)."""
    params = dict(zip(names, arrays))
    with jax.default_matmul_precision("highest"):
        if arch == "preact_symbol":
            return _forward_preact(config, params, x)
        if arch == "v1_gluon":
            return _forward_v1(config, params, x)
    raise ValueError(f"unknown ResNet arch {arch!r}")


def loss_and_logits(config, arch, names, arrays, x, labels):
    logits = forward(config, arch, names, arrays, x)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None].astype(jnp.int32), 1)
    return nll.mean(), logits


def decays(arch, name):
    """MXNet's rule: the symbolic path applies weight decay to `*_weight`
    and `*_gamma` only; gluon parameters all carry wd_mult 1."""
    return arch == "v1_gluon" or name.endswith(("_weight", "_gamma"))


def trainable(name):
    return name != "bn_data_gamma" and not name.endswith(
        ("_running_mean", "_running_var", "_moving_mean", "_moving_var"))


def make_train_step(config, arch, names, lr, momentum, wd):
    """`step(arrays, moms, x, labels) -> (arrays, moms, loss, logits)`:
    MXNet's SGD with momentum on the mean cross-entropy,
    `mom = momentum*mom - lr*(grad + wd*w); w += mom`."""
    names = tuple(names)

    def step(arrays, moms, x, labels):
        (loss, logits), grads = jax.value_and_grad(
            lambda a: loss_and_logits(config, arch, names, a, x, labels),
            has_aux=True)(arrays)
        new_a, new_m = [], []
        for name, w, m, g in zip(names, arrays, moms, grads):
            if trainable(name):
                g = g + (wd * w if decays(arch, name) else 0.0)
                m = momentum * m - lr * g
                w = w + m
            new_a.append(w)
            new_m.append(m)
        return new_a, new_m, loss, logits

    return jax.jit(step)
