"""2-bit gradient compression tests.

Pins the arithmetic to the reference's own expected-value simulation
(`tests/nightly/test_kvstore.py:33` compute_expected_2bit_quantization) and
exercises the kvstore integration the reference checks in
`tests/nightly/test_kvstore.py:199` / `dist_sync_kvstore.py:260-330`
(single-worker here; the multi-worker run is `tests/dist/test_dist_kvstore.py`
under `tools/launch.py`).
"""
import numpy as np
import pytest

import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.gradient_compression import (
    GradientCompression, quantize_2bit, dequantize_2bit, quantize_2bit_pallas,
    compressed_size)


def expected_2bit(arr, curr_residual, threshold):
    """Reference simulation: residual folds in; {-t, 0, +t} out."""
    r = np.asarray(arr, np.float32) + curr_residual
    decompr = np.zeros_like(r)
    new_residual = r.copy()
    pos = r >= threshold
    neg = r <= -threshold
    decompr[pos] = threshold
    decompr[neg] = -threshold
    new_residual[pos] -= threshold
    new_residual[neg] += threshold
    return new_residual, decompr


@pytest.mark.parametrize("shape", [(2, 3), (16,), (7, 11), (130,)])
def test_quantize_matches_reference_simulation(shape):
    rng = np.random.RandomState(0)
    threshold = 0.5
    residual_np = np.zeros(shape, np.float32)
    residual = jnp.zeros(shape, jnp.float32)
    for _ in range(4):
        grad = rng.uniform(-1, 1, size=shape).astype(np.float32)
        packed, residual = quantize_2bit(jnp.asarray(grad), residual, threshold)
        assert packed.shape[0] == compressed_size(int(np.prod(shape)))
        decompr = dequantize_2bit(packed, shape, threshold)
        residual_np, expected_decompr = expected_2bit(grad, residual_np, threshold)
        np.testing.assert_allclose(np.asarray(decompr), expected_decompr, atol=1e-7)
        np.testing.assert_allclose(np.asarray(residual), residual_np, atol=1e-6)


def test_residual_semantics():
    """The reference's check_compr_residual ladder (dist_sync_kvstore.py:261)."""
    t = 0.5
    shape = (2, 3)
    res = jnp.zeros(shape, jnp.float32)
    p, res = quantize_2bit(jnp.full(shape, 0.4), res, t)
    assert np.all(np.asarray(dequantize_2bit(p, shape, t)) == 0)
    p, res = quantize_2bit(jnp.full(shape, t - 0.4), res, t)
    assert np.all(np.asarray(dequantize_2bit(p, shape, t)) == t)
    assert np.allclose(np.asarray(res), 0)
    p, res = quantize_2bit(jnp.full(shape, 0.2), res, t)
    assert np.all(np.asarray(dequantize_2bit(p, shape, t)) == 0)
    p, res = quantize_2bit(jnp.full(shape, t - 0.2), res, t)
    assert np.all(np.asarray(dequantize_2bit(p, shape, t)) == t)
    assert np.allclose(np.asarray(res), 0)


def test_negative_and_mixed():
    t = 1.0
    grad = jnp.asarray([-2.5, -1.0, -0.5, 0.0, 0.5, 1.0, 2.5, 0.99])
    p, res = quantize_2bit(grad, jnp.zeros(8), t)
    de = np.asarray(dequantize_2bit(p, (8,), t))
    np.testing.assert_allclose(de, [-1, -1, 0, 0, 0, 1, 1, 0])
    np.testing.assert_allclose(np.asarray(res), [-1.5, 0, -0.5, 0, 0.5, 0, 1.5, 0.99])


def test_pallas_kernel_matches_jnp():
    rng = np.random.RandomState(3)
    for shape in [(64,), (2048,), (100,), (33, 65), (40000,)]:
        grad = rng.uniform(-1, 1, size=shape).astype(np.float32)
        residual = rng.uniform(-0.3, 0.3, size=shape).astype(np.float32)
        p_ref, r_ref = quantize_2bit(jnp.asarray(grad), jnp.asarray(residual), 0.5)
        p_pl, r_pl = quantize_2bit_pallas(jnp.asarray(grad), jnp.asarray(residual), 0.5,
                                          interpret=True)
        np.testing.assert_array_equal(np.asarray(p_ref), np.asarray(p_pl))
        np.testing.assert_array_equal(np.asarray(r_ref), np.asarray(r_pl))


def test_param_validation():
    gc = GradientCompression()
    with pytest.raises(MXNetError):
        gc.set_params({"type": "1bit"})
    with pytest.raises(MXNetError):
        gc.set_params({"type": "2bit", "threshold": 0})
    with pytest.raises(MXNetError):
        gc.set_params({"type": "2bit", "bogus": 1})
    gc.set_params({"type": "2bit", "threshold": 0.25})
    assert gc.active and gc.threshold == 0.25


def test_local_kvstore_compression():
    """Single-worker kvstore semantics with compression + 'test' optimizer
    (mirrors dist_sync_kvstore.py's ladder at nworker=1, rate=2)."""
    rate, t = 2, 0.5
    shape = (2, 3)
    kv = mx.kv.create("device")
    kv.set_optimizer(mx.optimizer.create("test", rescale_grad=rate))
    kv.set_gradient_compression({"type": "2bit", "threshold": t})
    kv.init("a", mx.nd.zeros(shape))
    kv.push("a", mx.nd.ones(shape) * 0.4)
    val = mx.nd.zeros(shape)
    kv.pull("a", out=val)
    assert np.all(val.asnumpy() == 0)
    kv.push("a", mx.nd.ones(shape) * (t - 0.4))
    kv.pull("a", out=val)
    np.testing.assert_allclose(val.asnumpy(), t * rate)
    kv.push("a", mx.nd.zeros(shape))
    kv.pull("a", out=val)
    np.testing.assert_allclose(val.asnumpy(), t * rate)


def test_compressed_size():
    assert compressed_size(16) == 1
    assert compressed_size(17) == 2
    assert compressed_size(1) == 1
    assert compressed_size(32) == 2
