"""Ring attention / Ulysses / sequence-parallel layers over the 8-device CPU mesh.

Parity contract: sequence-sharded attention over sep=4/8 must match single-device
attention (VERDICT round-2 item 6)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

import paddle_tpu as paddle
import paddle_tpu.distributed as dist
from paddle_tpu.distributed.context_parallel import (
    ring_attention, split_sequence, ulysses_attention,
)

B, S, H, D = 2, 64, 4, 8


def _qkv(seed=0):
    rng = np.random.RandomState(seed)
    return [jnp.asarray(rng.randn(B, S, H, D), jnp.float32) for _ in range(3)]


def _reference(q, k, v, causal):
    qh = jnp.swapaxes(q, 1, 2)
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    sc = jnp.einsum("bhsd,bhtd->bhst", qh, kh) / np.sqrt(D)
    if causal:
        mask = jnp.tril(jnp.ones((S, S), bool))
        sc = jnp.where(mask, sc, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(sc, axis=-1)
    return jnp.swapaxes(jnp.einsum("bhst,bhtd->bhsd", p, vh), 1, 2)


def _sep_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("sep",))


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("n", [4, 8])
def test_ring_attention_parity(causal, n):
    q, k, v = _qkv()
    mesh = _sep_mesh(n)

    def f(q_, k_, v_):
        return ring_attention(q_, k_, v_, axis_name="sep", causal=causal)

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(None, "sep"), out_specs=P(None, "sep"),
        check_vma=False))(q, k, v)
    ref = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_grad_parity(causal):
    q, k, v = _qkv(1)
    mesh = _sep_mesh(4)

    def loss_ring(q_, k_, v_):
        f = shard_map(
            lambda a, b, c: ring_attention(a, b, c, axis_name="sep", causal=causal),
            mesh=mesh, in_specs=P(None, "sep"), out_specs=P(None, "sep"),
            check_vma=False)
        return jnp.sum(f(q_, k_, v_) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(_reference(q_, k_, v_, causal) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_parity(causal):
    q, k, v = _qkv(2)
    mesh = _sep_mesh(4)  # H=4 divisible by 4

    def f(q_, k_, v_):
        return ulysses_attention(q_, k_, v_, axis_name="sep", causal=causal)

    out = jax.jit(shard_map(
        f, mesh=mesh, in_specs=P(None, "sep"), out_specs=P(None, "sep"),
        check_vma=False))(q, k, v)
    ref = _reference(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_ring_attention_gqa():
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(B, S, H, D), jnp.float32)
    k = jnp.asarray(rng.randn(B, S, H // 2, D), jnp.float32)
    v = jnp.asarray(rng.randn(B, S, H // 2, D), jnp.float32)
    mesh = _sep_mesh(4)
    out = jax.jit(shard_map(
        lambda a, b, c: ring_attention(a, b, c, axis_name="sep", causal=True),
        mesh=mesh, in_specs=P(None, "sep"), out_specs=P(None, "sep"),
        check_vma=False))(q, k, v)
    kr = jnp.repeat(k, 2, axis=2)
    vr = jnp.repeat(v, 2, axis=2)
    ref = _reference(q, kr, vr, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


def test_split_sequence():
    x = jnp.arange(32, dtype=jnp.float32).reshape(1, 32)
    mesh = _sep_mesh(4)
    out = jax.jit(shard_map(
        lambda v: split_sequence(v, "sep", seq_dim=1),
        mesh=mesh, in_specs=P(), out_specs=P(None, "sep"), check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))


# ------------------------------------------------------------ megatron SP layers
def test_sequence_parallel_linear_gspmd_parity():
    """Column+Row SP pair under jit over an mp mesh == plain two-layer MLP."""
    from paddle_tpu.distributed.fleet import (
        ColumnSequenceParallelLinear, RowSequenceParallelLinear,
    )

    mesh = dist.auto_mesh(4, dim_names=["mp"])
    dist.set_mesh(mesh)
    try:
        paddle.seed(0)
        col = ColumnSequenceParallelLinear(16, 32, has_bias=True)
        row = RowSequenceParallelLinear(32, 16, has_bias=True)
        x = paddle.to_tensor(np.random.RandomState(0).randn(2, 8, 16).astype("float32"))

        def run(xv):
            out = row(col(paddle.Tensor(xv)))
            return out._value

        out_jit = jax.jit(run)(x._value)
        # reference: dense matmuls with the same (full logical) weights
        ref = (x._value @ col.weight._value + col.bias._value) @ row.weight._value \
            + row.bias._value
        np.testing.assert_allclose(np.asarray(out_jit), np.asarray(ref), atol=1e-5)
    finally:
        dist.set_mesh(None)


def test_sp_scatter_gather_explicit():
    """Explicit shard_map regime: scatter slices, all_gather restores."""
    from paddle_tpu.distributed.fleet.sequence_parallel_utils import (
        all_gather, scatter,
    )

    mesh = Mesh(np.array(jax.devices()[:4]), ("mp",))
    x = jnp.arange(2 * 16 * 3, dtype=jnp.float32).reshape(2, 16, 3)

    def f(v):
        shard = scatter(v, seq_dim=1)
        assert shard.shape == (2, 4, 3)
        return all_gather(shard, seq_dim=1)

    out = jax.jit(shard_map(f, mesh=mesh, in_specs=P(), out_specs=P(),
                            check_vma=False))(x)
    np.testing.assert_allclose(np.asarray(out), np.asarray(x))
