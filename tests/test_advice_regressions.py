"""Regression tests for round-1 advisor findings (ADVICE.md)."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit.train import TrainStep


def test_bn_running_stats_update_through_trainstep():
    """functional_call restores state; BN running mean/var must still flow out of
    the compiled step (advisor: medium, nn/layer.py functional_call)."""
    paddle.seed(0)
    model = nn.Sequential(
        nn.Conv2D(3, 8, 3, padding=1), nn.BatchNorm2D(8), nn.ReLU(),
        nn.AdaptiveAvgPool2D(1), nn.Flatten(), nn.Linear(8, 4),
    )
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    lf = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda o, y: lf(o, y), opt)
    bn = model[1]
    m0 = np.asarray(bn._mean._value).copy()
    x = paddle.to_tensor(np.random.randn(8, 3, 16, 16).astype("float32") * 3 + 1)
    y = paddle.to_tensor(np.random.randint(0, 4, 8).astype("int64"))
    for _ in range(3):
        step(x, y)
    m1 = np.asarray(bn._mean._value)
    assert not np.allclose(m0, m1)
    v1 = np.asarray(bn._variance._value)
    assert not np.allclose(v1, np.ones_like(v1))


def test_gradscaler_manual_unscale_then_step_no_double_division():
    sc = paddle.amp.GradScaler(init_loss_scaling=128.0)
    lin = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.0, parameters=lin.parameters())
    out = lin(paddle.to_tensor(np.ones((2, 4), "float32")))
    sc.scale(out.sum()).backward()
    sc.unscale_(opt)
    g1 = np.asarray(lin.weight.grad._value).copy()
    sc.step(opt)
    g2 = np.asarray(lin.weight.grad._value)
    np.testing.assert_allclose(g1, g2)
    sc.update()
    # next step unscales again
    opt.clear_grad()
    sc.scale(lin(paddle.to_tensor(np.ones((2, 4), "float32"))).sum()).backward()
    sc.unscale_(opt)
    g3 = np.asarray(lin.weight.grad._value)
    np.testing.assert_allclose(g3, g1)


def test_dropout_downscale_in_infer_eval_scaling():
    x = paddle.to_tensor(np.ones((4, 4), "float32"))
    o = nn.functional.dropout(x, p=0.25, training=False, mode="downscale_in_infer")
    np.testing.assert_allclose(np.asarray(o._value), 0.75)
    # upscale_in_train mode: eval is identity
    o2 = nn.functional.dropout(x, p=0.25, training=False)
    np.testing.assert_allclose(np.asarray(o2._value), 1.0)


def test_flash_attention_no_dead_import():
    q = paddle.to_tensor(np.random.randn(1, 64, 2, 16).astype("float32"))
    out, _ = nn.functional.flash_attention(q, q, q, causal=True)
    assert tuple(out.shape) == (1, 64, 2, 16)


def test_all_reduce_prod_in_trace():
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    import paddle_tpu.distributed as dist

    devs = np.array(jax.devices()[:4])
    mesh = Mesh(devs, ("x",))
    g = dist.collective.Group(ranks=list(range(4)), axis_name="x")

    def f(v):
        t = paddle.Tensor(v.reshape(()))
        dist.all_reduce(t, op=dist.ReduceOp.PROD, group=g)
        return t._value.reshape(1)

    vals = jnp.asarray([1.0, 2.0, -3.0, 4.0])
    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x")))(vals)
    np.testing.assert_allclose(np.asarray(out), -24.0)


# ------------------------------------------------------------- round-2 advice
def test_partial_to_replicate_psum():
    """Partial→Replicate reshard must emit the pending reduction (round-2
    advisor + VERDICT weak #4: the api.py stub)."""
    import paddle_tpu.distributed as dist

    mesh = dist.ProcessMesh(np.arange(4), ["dp"])
    x = paddle.to_tensor(np.full((8, 4), 2.0, np.float32))
    t = dist.shard_tensor(x, mesh, [dist.Partial()])
    out = dist.reshard(t, mesh, [dist.Replicate()])
    # each of the 4 devices holds a partial contribution of 2.0 -> sum = 8.0
    np.testing.assert_allclose(np.asarray(out._value), 8.0)


def test_flashmask_fully_masked_rows_zero():
    """Rows with no allowed position output exactly 0 with zero grads (round-2
    advisor medium: kernel emitted uniform mean of V instead)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    b, s, h, d = 1, 128, 1, 16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, s, h, d), jnp.float32)
    # causal + start=0: every key column masked from row 0 on -> all rows fully
    # masked (row i's only causal-allowed cols are <= i, all masked)
    sri = jnp.zeros((b, 1, s, 1), jnp.int32)
    out = fa.flashmask_attention(q, k, v, sri, causal=True)
    np.testing.assert_allclose(np.asarray(out), 0.0)

    def loss(q_, k_, v_):
        return jnp.sum(fa.flashmask_attention(q_, k_, v_, sri, causal=True) ** 2)

    dq, dk, dv = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(np.asarray(dq), 0.0)
    np.testing.assert_allclose(np.asarray(dk), 0.0)
    np.testing.assert_allclose(np.asarray(dv), 0.0)


def test_batch_isend_irecv_bidirectional():
    """Distinct send/recv pairs must each get their own payload (round-2
    advisor medium: every recv got sends[0]'s ppermute result)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P
    from jax import shard_map

    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.collective import P2POp, batch_isend_irecv, isend, irecv

    W = 4
    devs = np.array(jax.devices()[:W])
    mesh = Mesh(devs, ("x",))
    g = dist.collective.Group(ranks=list(range(W)), axis_name="x")

    def f(v):
        me = jax.lax.axis_index("x")
        fwd_out = paddle.Tensor(jnp.zeros(()))
        bwd_out = paddle.Tensor(jnp.zeros(()))
        send_fwd = paddle.Tensor(v.reshape(()) + 100.0)   # to rank+1
        send_bwd = paddle.Tensor(v.reshape(()) + 200.0)   # to rank-1
        # group-rank peers; use rank 0's static view (uniform offsets)
        ops = [
            P2POp(isend, send_fwd, 1 % W, g),
            P2POp(irecv, fwd_out, (W - 1) % W, g),
            P2POp(isend, send_bwd, (W - 1) % W, g),
            P2POp(irecv, bwd_out, 1 % W, g),
        ]
        batch_isend_irecv(ops)
        return jnp.stack([fwd_out._value, bwd_out._value]).reshape(1, 2)

    vals = jnp.arange(W, dtype=jnp.float32)
    out = np.asarray(
        jax.jit(shard_map(f, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                          check_vma=False))(vals))
    # rank r receives fwd payload from r-1 (= r-1+100) and bwd from r+1 (= r+1+200)
    for r in range(W):
        assert out[r, 0] == (r - 1) % W + 100.0, out
        assert out[r, 1] == (r + 1) % W + 200.0, out


def test_gradscaler_found_inf_not_overwritten():
    """Two optimizers sharing a scaler: a clean second unscale_ must not erase
    the first's inf (round-2 advisor low)."""
    from paddle_tpu.amp import GradScaler

    p1 = paddle.to_tensor(np.ones(2, np.float32))
    p1.stop_gradient = False
    p2 = paddle.to_tensor(np.ones(2, np.float32))
    p2.stop_gradient = False
    o1 = paddle.optimizer.SGD(learning_rate=0.1, parameters=[p1])
    o2 = paddle.optimizer.SGD(learning_rate=0.1, parameters=[p2])
    scaler = GradScaler(init_loss_scaling=2.0)
    p1._grad = paddle.to_tensor(np.array([np.inf, 1.0], np.float32))._value
    p2._grad = paddle.to_tensor(np.ones(2, np.float32))._value
    scaler.unscale_(o1)
    assert scaler._found_inf
    scaler.unscale_(o2)
    assert scaler._found_inf  # must survive the clean second unscale_


def test_trainstep_aot_prime_shape_fallback():
    """After aot_prime, a different batch shape falls back to the jitted path
    instead of raising (round-2 advisor low)."""
    from paddle_tpu.jit.train import TrainStep

    paddle.seed(0)
    model = nn.Linear(4, 2)
    opt = paddle.optimizer.SGD(learning_rate=0.1, parameters=model.parameters())
    lf = nn.CrossEntropyLoss()
    step = TrainStep(model, lambda o, y: lf(o, y), opt)
    x8 = paddle.to_tensor(np.random.randn(8, 4).astype("float32"))
    y8 = paddle.to_tensor(np.random.randint(0, 2, 8).astype("int64"))
    step.aot_prime(x8, y8)
    step(x8, y8)
    x4 = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    y4 = paddle.to_tensor(np.random.randint(0, 2, 4).astype("int64"))
    loss = step(x4, y4)  # must not raise
    assert np.isfinite(float(loss._value))
