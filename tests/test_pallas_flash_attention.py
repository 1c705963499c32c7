"""Parity tests: Pallas flash attention (interpret mode on CPU) vs the naive
XLA softmax(QK^T)V path. Mirrors the reference OpTest contract (numpy/naive
golden + gradient check) for the attention kernel family."""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.flash_attention import _sdpa_core
from paddle_tpu.ops.pallas import flash_attention as pfa

B, S, H, D = 2, 256, 3, 32


def _rand(shape, seed):
    return jnp.asarray(np.random.RandomState(seed).randn(*shape).astype("float32"))


def _naive(q, k, v, causal, mask=None):
    scale = 1.0 / math.sqrt(q.shape[-1])
    return _sdpa_core(q, k, v, mask, scale, causal, 0.0, False)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_parity(causal):
    q, k, v = _rand((B, S, H, D), 0), _rand((B, S, H, D), 1), _rand((B, S, H, D), 2)
    out = pfa.flash_attention(q, k, v, causal=causal, block_q=128)
    ref = _naive(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_parity(causal):
    q, k, v = _rand((B, S, H, D), 3), _rand((B, S, H, D), 4), _rand((B, S, H, D), 5)
    w = _rand((B, S, H, D), 6)

    def f_pallas(q, k, v):
        return jnp.sum(pfa.flash_attention(q, k, v, causal=causal, block_q=128) * w)

    def f_naive(q, k, v):
        return jnp.sum(_naive(q, k, v, causal) * w)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_flash_gqa_forward():
    kvh = 1
    q = _rand((B, S, H, D), 7)
    k, v = _rand((B, S, kvh, D), 8), _rand((B, S, kvh, D), 9)
    out = pfa.flash_attention(q, k, v, causal=True, block_q=128)
    kk = jnp.repeat(k, H, axis=2)
    vv = jnp.repeat(v, H, axis=2)
    ref = _naive(q, kk, vv, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def _sri_causal_doc_mask(doc_lens, total):
    """Causal document mask encoded as LT-start rows (n=1): attention cannot
    cross document boundaries (the canonical flashmask example)."""
    starts = np.zeros(total, np.int32)
    pos = 0
    for L in doc_lens:
        starts[pos:pos + L] = pos + L  # rows >= end-of-doc are masked for these cols
        pos += L
    return starts.reshape(1, 1, total, 1)


def _naive_flashmask(q, k, v, sri, causal):
    from paddle_tpu.nn import functional as F
    from paddle_tpu.tensor import Tensor

    out = F.flashmask_attention(
        Tensor(q), Tensor(k), Tensor(v),
        startend_row_indices=Tensor(sri), causal=causal,
    )
    return out._value


@pytest.mark.parametrize("n_cols", [1, 2])
def test_flashmask_causal_parity(n_cols):
    q, k, v = _rand((1, S, 2, D), 10), _rand((1, S, 2, D), 11), _rand((1, S, 2, D), 12)
    if n_cols == 1:
        sri = jnp.asarray(_sri_causal_doc_mask([100, 60, 96], S))
    else:
        rs = np.random.RandomState(13)
        start = rs.randint(0, S // 2, (1, 1, S, 1)).astype(np.int32)
        end = start + rs.randint(1, S // 2, (1, 1, S, 1)).astype(np.int32)
        sri = jnp.asarray(np.concatenate([start, end], axis=-1))
    out = pfa.flashmask_attention(q, k, v, sri, causal=True, block_q=128)
    ref = _naive_flashmask(q, k, v, sri, True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flashmask_noncausal_parity():
    rs = np.random.RandomState(14)
    q, k, v = _rand((1, S, 2, D), 15), _rand((1, S, 2, D), 16), _rand((1, S, 2, D), 17)
    lts = rs.randint(S // 2, S, (1, 1, S, 1)).astype(np.int32)
    lte = np.minimum(lts + rs.randint(1, 50, lts.shape), S).astype(np.int32)
    uts = np.zeros_like(lts)
    ute = rs.randint(0, S // 4, lts.shape).astype(np.int32)
    sri = jnp.asarray(np.concatenate([lts, lte, uts, ute], axis=-1))
    out = pfa.flashmask_attention(q, k, v, sri, causal=False, block_q=128)
    ref = _naive_flashmask(q, k, v, sri, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)


def test_flashmask_grad_parity():
    q, k, v = _rand((1, S, 2, D), 18), _rand((1, S, 2, D), 19), _rand((1, S, 2, D), 20)
    sri = jnp.asarray(_sri_causal_doc_mask([128, 128], S))
    w = _rand((1, S, 2, D), 21)

    def f_pallas(q, k, v):
        return jnp.sum(pfa.flashmask_attention(q, k, v, sri, causal=True, block_q=128) * w)

    def f_naive(q, k, v):
        return jnp.sum(_naive_flashmask(q, k, v, sri, True) * w)

    gp = jax.grad(f_pallas, argnums=(0, 1, 2))(q, k, v)
    gn = jax.grad(f_naive, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gn):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-5, rtol=5e-5)


def test_flash_under_a_mesh_runs_per_shard():
    """Under a dp x mp mesh the kernels run per (batch, head) shard, and
    inside an enclosing shard_map as they are: same values and gradients as
    with no mesh, GQA and the flashmask index included. The check is the one
    `chip_smoke.py --four-chips` runs on real chips."""
    import chip_smoke

    out = chip_smoke.mesh_kernel_parity(2, 128, 4, 2, 16, "float32", 2e-5,
                                        expect_mosaic=False)
    assert out["max_err"] <= 2e-5


def test_supports_gate():
    assert pfa.supports((2, 256, 4, 64), (2, 256, 4, 64))
    assert not pfa.supports((2, 250, 4, 64), (2, 250, 4, 64))  # seq not divisible
    assert not pfa.supports((2, 256, 4, 64), (2, 128, 4, 64))  # cross-attention


@pytest.mark.slow   # ~16s: slow-marked in PR 15 (tier-1 budget rule) — the
# smaller-S flash_grad_parity legs keep the backward-parity canary tier-1
def test_chunked_backward_matches_reference_s8192():
    """S>4096 routes the backward through the chunk-accumulating kernels
    (VMEM-safe at any S); gradients must match the dense reference."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as FA

    bh, S, d = 1, 8192, 8
    rs = np.random.RandomState(0)
    q = jnp.asarray(rs.randn(bh, S, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(bh, S, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(bh, S, d).astype(np.float32) * 0.3)
    scale = 1.0 / np.sqrt(d)

    def flash_loss(q, k, v):
        out = FA._flash(q, k, v, True, float(scale), FA._auto_block_q(S))
        return jnp.sum(out * jnp.cos(out))

    def ref_loss(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask, s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bqk,bkd->bqd", p, v)
        return jnp.sum(out * jnp.cos(out))

    g_flash = jax.grad(flash_loss, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-4, err_msg=name)
