"""AMP O1/O2 auto_cast wiring + collective API tests (VERDICT r1 item 6)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu import nn

W = 8  # virtual devices


# --------------------------------------------------------------------- AMP O1
def test_auto_cast_o1_whitelists_matmul():
    x = paddle.to_tensor(np.random.randn(4, 8).astype("float32"))
    w = paddle.to_tensor(np.random.randn(8, 8).astype("float32"))
    with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
        out = paddle.matmul(x, w)
        assert out.dtype == jnp.bfloat16  # white-list op ran in bf16
        s = paddle.nn.functional.softmax(out)
        assert s.dtype == jnp.float32  # black-list op promoted to fp32
    out2 = paddle.matmul(x, w)
    assert out2.dtype == jnp.float32  # outside the context: untouched


def test_auto_cast_o1_custom_lists():
    x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    with paddle.amp.auto_cast(level="O1", dtype="bfloat16",
                              custom_white_list={"tanh"}):
        assert paddle.tanh(x).dtype == jnp.bfloat16
    with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
        assert paddle.tanh(x).dtype == jnp.float32  # not listed: input dtype


def test_auto_cast_o1_grads_keep_param_dtype():
    lin = nn.Linear(8, 4)
    x = paddle.to_tensor(np.random.randn(2, 8).astype("float32"))
    with paddle.amp.auto_cast(level="O1", dtype="bfloat16"):
        out = lin(x)
    out.sum().backward()
    assert lin.weight.grad is not None
    assert lin.weight.grad.dtype == jnp.float32  # cast VJP restored fp32


def test_auto_cast_o2_casts_everything_but_blacklist():
    x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    with paddle.amp.auto_cast(level="O2", dtype="bfloat16"):
        assert paddle.tanh(x).dtype == jnp.bfloat16  # unlisted op: low precision
        assert paddle.nn.functional.softmax(x).dtype == jnp.float32


def test_auto_cast_disabled_is_identity():
    x = paddle.to_tensor(np.random.randn(4, 4).astype("float32"))
    with paddle.amp.auto_cast(enable=False):
        assert paddle.matmul(x, x).dtype == jnp.float32


# ------------------------------------------------------------------ collectives
def _group():
    return dist.new_group(list(range(W)))


def _mesh_of(g):
    return g.jax_mesh


def test_new_group_has_real_axis_and_mesh():
    g = _group()
    assert g.axis_name is not None
    assert g.jax_mesh is not None
    assert g.jax_mesh.shape[g.axis_name] == W


def test_all_reduce_in_shard_map():
    g = _group()
    x = jnp.arange(W, dtype=jnp.float32)

    def f(v):
        t = paddle.Tensor(v.reshape(()))
        dist.all_reduce(t, group=g)
        return t._value.reshape(1)

    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(W, x.sum()))


def test_all_reduce_eager_sharded_array():
    g = _group()
    sh = NamedSharding(g.jax_mesh, P(g.axis_name))
    x = jax.device_put(jnp.arange(W, dtype=jnp.float32), sh)
    t = paddle.Tensor(x)
    dist.all_reduce(t, group=g)
    np.testing.assert_allclose(np.asarray(t._value), 28.0)


def test_broadcast_in_shard_map():
    g = _group()
    x = jnp.arange(W, dtype=jnp.float32)

    def f(v):
        t = paddle.Tensor(v.reshape(()))
        dist.broadcast(t, src=3, group=g)
        return t._value.reshape(1)

    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(W, 3.0))


def test_scatter_in_shard_map():
    g = _group()
    # src rank 2 holds the authoritative list; each rank ends with list[rank]
    def f(v):
        me = jax.lax.axis_index(g.axis_name)
        lst = [paddle.Tensor((v.reshape(()) * 0 + 10.0 * i + me * 0)) for i in range(W)]
        out = paddle.Tensor(v.reshape(()))
        dist.scatter(out, lst, src=2, group=g)
        return out._value.reshape(1)

    x = jnp.arange(W, dtype=jnp.float32)
    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), 10.0 * np.arange(W))


def test_gather_and_all_gather_in_shard_map():
    g = _group()
    x = jnp.arange(W, dtype=jnp.float32)

    def f(v):
        lst = []
        dist.all_gather(lst, paddle.Tensor(v.reshape(())), group=g)
        return jnp.stack([t._value for t in lst])

    out = g.shard_map(f, P(g.axis_name), P(None))(x)
    np.testing.assert_allclose(np.asarray(out), np.arange(W))


def test_reduce_scatter_in_shard_map():
    g = _group()
    x = jnp.ones((W, W), jnp.float32)

    def f(v):
        out = paddle.Tensor(v.reshape(W))
        dist.reduce_scatter(out, paddle.Tensor(v.reshape(W)), group=g)
        return out._value.reshape(1)

    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), np.full(W, float(W)))


def test_shift_ppermute():
    g = _group()
    x = jnp.arange(W, dtype=jnp.float32)

    def f(v):
        t = dist.collective.shift(paddle.Tensor(v.reshape(())), offset=1, group=g)
        return t._value.reshape(1)

    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), np.roll(np.arange(W), 1))


def test_alltoall_in_shard_map():
    g = _group()
    x = jnp.arange(W * W, dtype=jnp.float32).reshape(W, W)

    def f(v):
        ins = [paddle.Tensor(v[0, i].reshape(1)) for i in range(W)]
        outs = []
        dist.alltoall(outs, ins, group=g)
        return jnp.concatenate([t._value for t in outs]).reshape(1, W)

    out = g.shard_map(f, P(g.axis_name), P(g.axis_name))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x).T)
