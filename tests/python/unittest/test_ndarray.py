"""NDArray core tests (modeled on reference `tests/python/unittest/test_ndarray.py`)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd


def assert_close(a, b, rtol=1e-5, atol=1e-6):
    np.testing.assert_allclose(
        a.asnumpy() if hasattr(a, "asnumpy") else a,
        b.asnumpy() if hasattr(b, "asnumpy") else b,
        rtol=rtol, atol=atol)


def test_creation():
    a = nd.zeros((3, 4))
    assert a.shape == (3, 4) and a.dtype == np.float32
    assert_close(a, np.zeros((3, 4)))
    b = nd.ones((2, 2), dtype="int32")
    assert b.dtype == np.int32
    c = nd.full((2, 3), 7.5)
    assert_close(c, np.full((2, 3), 7.5))
    d = nd.array([[1, 2], [3, 4]])
    assert d.shape == (2, 2)
    e = nd.arange(10)
    assert_close(e, np.arange(10, dtype=np.float32))


def test_elemwise_arith():
    npa = np.random.rand(3, 4).astype(np.float32)
    npb = np.random.rand(3, 4).astype(np.float32) + 0.1
    a, b = nd.array(npa), nd.array(npb)
    assert_close(a + b, npa + npb)
    assert_close(a - b, npa - npb)
    assert_close(a * b, npa * npb)
    assert_close(a / b, npa / npb)
    assert_close(a ** 2, npa ** 2)
    assert_close(2.0 - a, 2.0 - npa)
    assert_close(1.0 / b, 1.0 / npb)
    assert_close(-a, -npa)
    assert_close(nd.maximum(a, b), np.maximum(npa, npb))
    assert_close(nd.sqrt(b), np.sqrt(npb), rtol=1e-4)
    assert_close(nd.exp(a), np.exp(npa), rtol=1e-4)
    assert_close(nd.log(b), np.log(npb), rtol=1e-4)


def test_broadcast_ops():
    npa = np.random.rand(3, 1, 4).astype(np.float32)
    npb = np.random.rand(1, 5, 4).astype(np.float32)
    a, b = nd.array(npa), nd.array(npb)
    assert_close(nd.broadcast_add(a, b), npa + npb)
    assert_close(nd.broadcast_mul(a, b), npa * npb)
    assert_close(nd.broadcast_to(nd.array([[1], [2]]), shape=(2, 3)),
                 np.broadcast_to(np.array([[1], [2]]), (2, 3)))


def test_reductions():
    npa = np.random.rand(2, 3, 4).astype(np.float32)
    a = nd.array(npa)
    assert_close(a.sum(), npa.sum(), rtol=1e-4)
    assert_close(a.sum(axis=1), npa.sum(axis=1), rtol=1e-4)
    assert_close(nd.sum(a, axis=(0, 2)), npa.sum(axis=(0, 2)), rtol=1e-4)
    assert_close(a.mean(axis=0, keepdims=True), npa.mean(axis=0, keepdims=True), rtol=1e-4)
    assert_close(a.max(axis=2), npa.max(axis=2))
    assert_close(a.min(), npa.min())
    assert_close(nd.sum(a, axis=1, exclude=True), npa.sum(axis=(0, 2)), rtol=1e-4)
    assert int(a.argmax(axis=None).asscalar()) == int(npa.argmax())


def test_shape_ops():
    npa = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
    a = nd.array(npa)
    assert a.reshape(6, 4).shape == (6, 4)
    assert a.reshape((-1, 4)).shape == (6, 4)
    assert a.reshape(0, -1).shape == (2, 12)
    assert a.reshape(-2).shape == (2, 3, 4)
    assert a.reshape(-3, 4).shape == (6, 4)
    assert a.reshape(-4, 1, 2, 0, 0).shape == (1, 2, 3, 4)
    assert a.transpose().shape == (4, 3, 2)
    assert a.transpose((1, 0, 2)).shape == (3, 2, 4)
    assert a.expand_dims(1).shape == (2, 1, 3, 4)
    assert nd.concat(a, a, dim=2).shape == (2, 3, 8)
    assert nd.stack(a, a, axis=0).shape == (2, 2, 3, 4)
    parts = nd.split(a, num_outputs=3, axis=1)
    assert len(parts) == 3 and parts[0].shape == (2, 1, 4)
    assert_close(nd.slice(a, begin=(0, 1), end=(2, 3)), npa[0:2, 1:3])
    assert_close(a.slice_axis(axis=2, begin=1, end=3), npa[:, :, 1:3])
    assert_close(nd.flip(a, axis=1), npa[:, ::-1])
    assert_close(nd.tile(a, reps=(1, 2, 1)), np.tile(npa, (1, 2, 1)))
    assert a.flatten().shape == (2, 12)
    assert nd.squeeze(a.expand_dims(0), axis=0).shape == (2, 3, 4)


def test_dot():
    npa = np.random.rand(4, 5).astype(np.float32)
    npb = np.random.rand(5, 3).astype(np.float32)
    assert_close(nd.dot(nd.array(npa), nd.array(npb)), npa @ npb, rtol=1e-4)
    assert_close(nd.dot(nd.array(npa), nd.array(npb.T), transpose_b=True), npa @ npb, rtol=1e-4)
    assert_close(nd.dot(nd.array(npa.T), nd.array(npb), transpose_a=True), npa @ npb, rtol=1e-4)
    ba = np.random.rand(2, 4, 5).astype(np.float32)
    bb = np.random.rand(2, 5, 3).astype(np.float32)
    assert_close(nd.batch_dot(nd.array(ba), nd.array(bb)), ba @ bb, rtol=1e-4)


def test_indexing():
    npa = np.arange(24, dtype=np.float32).reshape(4, 6)
    a = nd.array(npa)
    assert_close(a[1], npa[1])
    assert_close(a[1:3], npa[1:3])
    assert_close(a[1, 2:4], npa[1, 2:4])
    a[0] = -1.0
    npa[0] = -1.0
    assert_close(a, npa)
    a[1:3, 0] = 5.0
    npa[1:3, 0] = 5.0
    assert_close(a, npa)
    idx = nd.array([0, 2], dtype="int32")
    assert_close(nd.take(a, idx), npa[[0, 2]])
    oh = nd.one_hot(nd.array([1, 3], dtype="int32"), 5)
    assert_close(oh, np.eye(5, dtype=np.float32)[[1, 3]])


def test_ordering():
    npa = np.random.rand(3, 7).astype(np.float32)
    a = nd.array(npa)
    assert_close(a.sort(axis=1), np.sort(npa, axis=1))
    assert_close(nd.topk(a, k=3, ret_typ="value"),
                 -np.sort(-npa, axis=-1)[:, :3])
    assert_close(a.argsort(axis=1), np.argsort(npa, axis=1).astype(np.float32))


def test_astype_cast():
    a = nd.array([1.6, 2.4])
    assert a.astype("int32").dtype == np.int32
    assert nd.cast(a, dtype="float16").dtype == np.float16


def test_inplace_and_out():
    a = nd.ones((2, 2))
    b = nd.zeros((2, 2))
    nd.elemwise_add(a, a, out=b)
    assert_close(b, 2 * np.ones((2, 2)))
    a += 1
    assert_close(a, 2 * np.ones((2, 2)))
    a *= 3
    assert_close(a, 6 * np.ones((2, 2)))


def test_save_load(tmp_path):
    fname = str(tmp_path / "arrs.params")
    a = nd.array(np.random.rand(3, 4).astype(np.float32))
    b = nd.array(np.arange(5, dtype=np.int32))
    nd.save(fname, {"a": a, "b": b})
    loaded = nd.load(fname)
    assert set(loaded.keys()) == {"a", "b"}
    assert_close(loaded["a"], a)
    assert loaded["b"].dtype == np.int32
    nd.save(fname, [a, b])
    arr_list = nd.load(fname)
    assert isinstance(arr_list, list) and len(arr_list) == 2


def test_random():
    mx.random.seed(42)
    a = nd.random.uniform(0, 1, shape=(100,))
    mx.random.seed(42)
    b = nd.random.uniform(0, 1, shape=(100,))
    assert_close(a, b)
    c = nd.random.normal(0, 1, shape=(10000,))
    assert abs(float(c.mean().asscalar())) < 0.05
    d = nd.random.randint(0, 10, shape=(100,))
    assert d.asnumpy().min() >= 0 and d.asnumpy().max() < 10


def test_context():
    a = nd.zeros((2, 2), ctx=mx.cpu(0))
    assert a.context.device_type in ("cpu",)
    b = a.as_in_context(mx.cpu(0))
    assert b.context == mx.cpu(0)


def test_sparse_basics():
    from mxnet_tpu.ndarray import sparse

    dense = np.array([[0, 0], [1, 2], [0, 0], [3, 4]], dtype=np.float32)
    rs = sparse.cast_storage(nd.array(dense), "row_sparse")
    assert rs.stype == "row_sparse"
    assert_close(rs.indices, np.array([1, 3]))
    assert_close(rs, dense)  # dense view matches
    back = rs.tostype("default")
    assert_close(back, dense)
    csr = sparse.cast_storage(nd.array(dense), "csr")
    assert csr.stype == "csr"
    assert_close(csr, dense)


def test_review_regressions():
    """Fixes from the round-1 code review: scalar-lhs comparisons, scalar-scalar
    helpers, topk mask on negative axis, ctx placement, dot transpose."""
    npa = np.array([1.0, 3.0, 5.0], dtype=np.float32)
    a = nd.array(npa)
    assert_close(nd.greater(4.0, a), (4.0 > npa).astype(np.float32))
    assert_close(nd.lesser(4.0, a), (4.0 < npa).astype(np.float32))
    assert_close(nd.greater_equal(3.0, a), (3.0 >= npa).astype(np.float32))
    assert nd.add(1, 2) == 3
    assert nd.maximum(2.0, 3.0) == 3.0
    mask = nd.topk(a.reshape(1, 3), k=2, ret_typ="mask")
    assert mask.shape == (1, 3)
    assert_close(mask, np.array([[0.0, 1.0, 1.0]]))
    z = nd.zeros((2, 2), ctx=mx.cpu(0))
    assert z.context.device_type == "cpu"
    m = np.random.rand(3, 4).astype(np.float32)
    n = np.random.rand(3, 5).astype(np.float32)
    assert_close(nd.dot(nd.array(m), nd.array(n), transpose_a=True), m.T @ n, rtol=1e-4)


def test_loss_layer_gradients():
    """SoftmaxOutput must produce (p - onehot) grads regardless of head grad."""
    from mxnet_tpu import autograd

    logits = nd.array(np.random.randn(4, 3).astype(np.float32))
    label = nd.array(np.array([0, 2, 1, 1], dtype=np.float32))
    logits.attach_grad()
    with autograd.record():
        out = nd.SoftmaxOutput(logits, label)
    out.backward()
    p = np.exp(logits.asnumpy()) / np.exp(logits.asnumpy()).sum(1, keepdims=True)
    onehot = np.eye(3, dtype=np.float32)[label.asnumpy().astype(int)]
    assert_close(logits.grad, p - onehot, rtol=1e-4, atol=1e-5)
    # LinearRegressionOutput: grad = pred - label
    x = nd.array(np.array([[1.0], [2.0]], dtype=np.float32))
    lab = nd.array(np.array([[0.5], [2.5]], dtype=np.float32))
    x.attach_grad()
    with autograd.record():
        o = nd.LinearRegressionOutput(x, lab)
    o.backward()
    assert_close(x.grad, x.asnumpy() - lab.asnumpy())


def test_record_inside_pause():
    from mxnet_tpu import autograd

    x = nd.array([2.0])
    x.attach_grad()
    with autograd.record():
        y = x * 3
        with autograd.pause():
            w = nd.array([1.0])
            w.attach_grad()
            with autograd.record():
                v = w * 7
        z = y * 2
    z.backward()
    assert_close(x.grad, np.array([6.0]))


def test_setitem_keeps_the_array_on_its_device(monkeypatch):
    """`arr[:] = host_value` builds the value on jax's DEFAULT device; the
    array must stay on — and committed to — the device its context names.
    (On a machine with a chip this moved cpu()/tpu(1) arrays to chip 0 under
    an unchanged label, and left chip-0 weights uncommitted, so the second
    fused step recompiled.) The CPU-only placement policy pins nothing, so
    the test switches it to the accelerator-machine policy."""
    import jax

    from mxnet_tpu.ndarray import ndarray as nd_mod

    if len(jax.devices()) < 2:
        pytest.skip("needs 2 virtual devices")
    monkeypatch.setattr(nd_mod, "_default_is_cpu", lambda: False)
    dev1 = jax.devices()[1]
    a = mx.nd.zeros((4, 3), ctx=mx.cpu(1))
    assert a._data.devices() == {dev1}
    a[:] = np.arange(12, dtype=np.float32).reshape(4, 3)
    assert a._data.devices() == {dev1} and a._data.committed
    a[1:3] = 7.0
    assert a._data.devices() == {dev1} and a._data.committed
    np.testing.assert_array_equal(a.asnumpy()[:, 0], [0, 7, 7, 9])
