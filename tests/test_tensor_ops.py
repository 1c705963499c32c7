"""Core tensor/op tests — numpy-golden contract (mirrors reference op_test.py style)."""
import numpy as np
import pytest

import paddle_tpu as paddle


def test_to_tensor_dtypes():
    t = paddle.to_tensor([1, 2, 3])
    assert t.dtype == np.dtype("int64")
    t = paddle.to_tensor([1.0, 2.0])
    assert t.dtype == np.dtype("float32")
    t = paddle.to_tensor(np.zeros((2, 2), np.float64))
    assert t.dtype == np.dtype("float64")
    t = paddle.to_tensor([1, 2], dtype="float32")
    assert t.dtype == np.dtype("float32")


def test_basic_arithmetic():
    x = paddle.to_tensor([1.0, 2.0, 3.0])
    y = paddle.to_tensor([4.0, 5.0, 6.0])
    np.testing.assert_allclose((x + y).numpy(), [5, 7, 9])
    np.testing.assert_allclose((x * y).numpy(), [4, 10, 18])
    np.testing.assert_allclose((y / x).numpy(), [4, 2.5, 2])
    np.testing.assert_allclose((x - y).numpy(), [-3, -3, -3])
    np.testing.assert_allclose((x ** 2).numpy(), [1, 4, 9])
    np.testing.assert_allclose((2.0 - x).numpy(), [1, 0, -1])


def test_int_float_promotion():
    x = paddle.to_tensor([1, 2, 3])
    out = x / 2
    assert "float" in str(out.dtype)
    out2 = x * 2.5
    assert "float" in str(out2.dtype)


def test_shape_is_list():
    x = paddle.zeros([2, 3])
    assert x.shape == [2, 3]
    assert isinstance(x.shape, list)


def test_manipulation():
    x = paddle.arange(24).reshape([2, 3, 4])
    assert x.transpose([2, 0, 1]).shape == [4, 2, 3]
    assert paddle.concat([x, x], axis=1).shape == [2, 6, 4]
    parts = paddle.split(x, 3, axis=1)
    assert len(parts) == 3 and parts[0].shape == [2, 1, 4]
    assert paddle.flatten(x).shape == [24]
    assert paddle.squeeze(paddle.unsqueeze(x, 0), 0).shape == [2, 3, 4]
    assert paddle.stack([x, x]).shape == [2, 2, 3, 4]
    assert paddle.tile(x, [1, 2, 1]).shape == [2, 6, 4]


def test_indexing():
    x = paddle.arange(12).reshape([3, 4])
    np.testing.assert_array_equal(x[1].numpy(), [4, 5, 6, 7])
    np.testing.assert_array_equal(x[:, 1].numpy(), [1, 5, 9])
    np.testing.assert_array_equal(x[1:, ::2].numpy(), [[4, 6], [8, 10]])
    x[0] = 0
    assert int(x.numpy()[0].sum()) == 0
    mask = x > 5
    sel = x[mask]
    np.testing.assert_array_equal(sel.numpy(), [6, 7, 8, 9, 10, 11])


def test_reductions():
    x = paddle.to_tensor(np.arange(12, dtype=np.float32).reshape(3, 4))
    np.testing.assert_allclose(x.sum().numpy(), 66.0)
    np.testing.assert_allclose(x.mean(axis=0).numpy(), [4, 5, 6, 7])
    np.testing.assert_allclose(x.max(axis=1).numpy(), [3, 7, 11])
    assert float(x.std().numpy()) == pytest.approx(np.arange(12).std(ddof=1), rel=1e-5)


def test_matmul():
    a = paddle.to_tensor(np.random.randn(3, 4).astype(np.float32))
    b = paddle.to_tensor(np.random.randn(4, 5).astype(np.float32))
    np.testing.assert_allclose((a @ b).numpy(), a.numpy() @ b.numpy(), rtol=1e-5)
    np.testing.assert_allclose(
        paddle.matmul(a, a, transpose_y=True).numpy(), a.numpy() @ a.numpy().T, rtol=1e-5
    )


def test_search_sort():
    x = paddle.to_tensor([[3.0, 1.0, 2.0], [6.0, 5.0, 4.0]])
    np.testing.assert_array_equal(paddle.argmax(x, axis=1).numpy(), [0, 0])
    vals, idx = paddle.topk(x, 2, axis=1)
    np.testing.assert_allclose(vals.numpy(), [[3, 2], [6, 5]])
    s = paddle.sort(x, axis=1)
    np.testing.assert_allclose(s.numpy(), [[1, 2, 3], [4, 5, 6]])
    w = paddle.where(x > 2.5, x, paddle.zeros_like(x))
    np.testing.assert_allclose(w.numpy(), [[3, 0, 0], [6, 5, 4]])


def test_einsum():
    a = np.random.randn(2, 3).astype(np.float32)
    b = np.random.randn(3, 4).astype(np.float32)
    out = paddle.einsum("ij,jk->ik", paddle.to_tensor(a), paddle.to_tensor(b))
    # atol: unseeded normals now and then land a product near 0, where a
    # relative bound alone fails on the last f32 bit
    np.testing.assert_allclose(out.numpy(), a @ b, rtol=1e-5, atol=1e-6)


def test_creation():
    assert paddle.eye(3).shape == [3, 3]
    assert paddle.full([2, 2], 7).numpy().sum() == 28
    assert paddle.arange(0, 10, 2).shape == [5]
    assert paddle.linspace(0, 1, 5).shape == [5]
    np.testing.assert_allclose(paddle.tril(paddle.ones([3, 3])).numpy().sum(), 6)


def test_inplace_ops():
    x = paddle.to_tensor([1.0, 2.0])
    x.add_(paddle.to_tensor([1.0, 1.0]))
    np.testing.assert_allclose(x.numpy(), [2, 3])
    x.scale_(2.0)
    np.testing.assert_allclose(x.numpy(), [4, 6])


def test_cast():
    x = paddle.to_tensor([1.5, 2.5])
    assert x.astype("int32").dtype == np.dtype("int32")
    assert x.astype(paddle.float64).dtype == np.dtype("float64")


def test_random_reproducible():
    paddle.seed(42)
    a = paddle.rand([4])
    paddle.seed(42)
    b = paddle.rand([4])
    np.testing.assert_allclose(a.numpy(), b.numpy())
