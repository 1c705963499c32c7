"""Custom-vjp training batch norm (_bn_train): gradient parity against the
composed relu(bn(x)+residual) reference + variance numerical stability for
large-mean inputs (guards the exact two-pass form; the one-pass and
shifted variants lose the variance of a large-mean input to cancellation)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
import paddle_tpu.nn.functional as F


def _grads(fn, *tensors):
    loss = fn()
    loss.backward()
    out = [np.asarray(t.grad._value) for t in tensors]
    for t in tensors:
        t.clear_grad()
    return np.asarray(loss._value), out


@pytest.mark.parametrize("with_residual,act", [
    (False, None), (False, "relu"), (True, "relu"), (True, None),
])
def test_bn_train_vjp_matches_composed(with_residual, act):
    paddle.seed(5)
    bn = nn.BatchNorm2D(6)
    bn.train()
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(4, 6, 5, 5).astype("float32"),
                         stop_gradient=False)
    res = paddle.to_tensor(rs.randn(4, 6, 5, 5).astype("float32"),
                           stop_gradient=False) if with_residual else None

    def fused():
        out = bn.forward_fused(x, residual=res, act=act)
        return paddle.sum(out * out)

    tensors = [x] + ([res] if res is not None else []) + [bn.weight, bn.bias]
    loss_f, grads_f = _grads(fused, *tensors)

    bn2 = nn.BatchNorm2D(6)
    bn2.train()

    def composed():
        out = bn2(x)
        if res is not None:
            out = out + res
        if act == "relu":
            out = F.relu(out)
        return paddle.sum(out * out)

    tensors2 = [x] + ([res] if res is not None else []) + [bn2.weight, bn2.bias]
    loss_c, grads_c = _grads(composed, *tensors2)
    np.testing.assert_allclose(loss_f, loss_c, rtol=1e-5)
    for gf, gc in zip(grads_f, grads_c):
        np.testing.assert_allclose(gf, gc, rtol=1e-4, atol=1e-5)
    # running stats evolved identically
    np.testing.assert_allclose(np.asarray(bn._mean._value),
                               np.asarray(bn2._mean._value), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(bn._variance._value),
                               np.asarray(bn2._variance._value), rtol=1e-4)


def test_bn_large_mean_no_cancellation():
    """E[x^2]-E[x]^2 catastrophically cancels for |mean| >> std; the exact
    two-pass variance must not (review regression: output std was 2.56,
    running var clamped to 0)."""
    bn = nn.BatchNorm2D(3)
    bn.train()
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(
        (1000.0 + 0.01 * rs.randn(64, 3, 8, 8)).astype("float32"))
    out = np.asarray(bn(x)._value)
    np.testing.assert_allclose(out.std(), 1.0, rtol=0.05)
    # running var must reflect the true ~1e-4 variance, not clamp to 0
    rv = np.asarray(bn._variance._value)
    assert (rv > 1e-6).all(), rv


def test_bn_act_validation():
    bn = nn.BatchNorm2D(3)
    bn.train()
    x = paddle.to_tensor(np.ones((2, 3, 4, 4), "float32"))
    with pytest.raises(ValueError, match="act"):
        bn.forward_fused(x, act="relu6")


def test_bn_residual_grad_dtype_preserved():
    """An f32 residual on a bf16 input must get an f32 gradient back
    (review regression: cotangent was cast to x.dtype)."""
    bn = nn.BatchNorm2D(3)
    bn.train()
    rs = np.random.RandomState(0)
    x = paddle.to_tensor(rs.randn(2, 3, 4, 4).astype("bfloat16"),
                         stop_gradient=False)
    res = paddle.to_tensor(rs.randn(2, 3, 4, 4).astype("float32"),
                           stop_gradient=False)
    out = bn.forward_fused(x, residual=res, act="relu")
    paddle.sum(paddle.cast(out, "float32")).backward()
    assert str(res.grad.dtype) in ("float32", "paddle.float32")
    assert str(x.grad.dtype) in ("bfloat16", "paddle.bfloat16")
