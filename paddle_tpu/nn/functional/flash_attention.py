"""Attention functionals.

Reference parity: python/paddle/nn/functional/flash_attention.py:358 (flash_attention),
:1299 (flashmask_attention), scaled_dot_product_attention, sdp_kernel selector (:144).
TPU-native: the default path is a fused XLA softmax(QK^T)V (jnp ops fused by XLA); a
Pallas flash kernel (paddle_tpu/ops/pallas/flash_attention.py) is used on TPU for long
sequences where HBM-resident scores would dominate.
"""
from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp

from ...ops import apply_op
from ...tensor import Tensor

__all__ = [
    "flash_attention", "flash_attn_unpadded", "flashmask_attention",
    "scaled_dot_product_attention", "sdp_kernel",
]

_sdp_config = {"enable_flash": True, "enable_math": True, "enable_mem_efficient": True}

# Which implementation served the LAST attention call in this process —
# "pallas" (Mosaic kernel) or "xla" (fused softmax(QK^T)V). Fallbacks used to
# be silent (round-2 finding); tests and users can now assert the path.
_last_backend = {"name": None}


def get_last_attention_backend():
    return _last_backend["name"]


def _mark(name):
    _last_backend["name"] = name


@contextlib.contextmanager
def sdp_kernel(enable_flash=True, enable_math=True, enable_mem_efficient=True):
    prev = dict(_sdp_config)
    _sdp_config.update(
        enable_flash=enable_flash, enable_math=enable_math,
        enable_mem_efficient=enable_mem_efficient,
    )
    try:
        yield
    finally:
        _sdp_config.update(prev)


def _same_cu(cu_q, cu_k):
    """True iff the q and k segment boundaries are PROVABLY identical — the
    pallas varlen route masks by k-documents only, which is wrong for
    cross-attention with different boundaries (fall back to XLA there)."""
    if cu_q is cu_k:
        return True
    a = cu_q._value if isinstance(cu_q, Tensor) else cu_q
    b = cu_k._value if isinstance(cu_k, Tensor) else cu_k
    if isinstance(a, jax.core.Tracer) or isinstance(b, jax.core.Tracer):
        return False
    import numpy as _np

    a, b = _np.asarray(a), _np.asarray(b)
    return a.shape == b.shape and bool((a == b).all())


def _use_pallas(q_shape, k_shape) -> bool:
    if not _sdp_config["enable_flash"] or jax.default_backend() != "tpu":
        return False
    from ...ops.pallas import flash_attention as pfa

    # pallas pays off once the [B,H,S,S] score tensor would round-trip HBM
    return q_shape[1] >= 1024 and pfa.supports(tuple(q_shape), tuple(k_shape))


def _sdpa_core(q, k, v, mask, scale, is_causal, dropout_p, training):
    """q/k/v: [B, S, H, D] (paddle flash_attention layout)."""
    qh = jnp.swapaxes(q, 1, 2)  # [B,H,S,D]
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    # grouped-query: broadcast kv heads
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    scores = jnp.einsum("bhsd,bhtd->bhst", qh, kh) * scale
    if is_causal:
        s, t = scores.shape[-2], scores.shape[-1]
        causal = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        scores = jnp.where(causal, scores, jnp.finfo(scores.dtype).min)
    if mask is not None:
        if mask.dtype == jnp.bool_:
            scores = jnp.where(mask, scores, jnp.finfo(scores.dtype).min)
        else:
            scores = scores + mask
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_p and training:
        from ...framework import random as _rng

        keep = jax.random.bernoulli(_rng.next_key(), 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0).astype(probs.dtype)
    out = jnp.einsum("bhst,bhtd->bhsd", probs, vh)
    return jnp.swapaxes(out, 1, 2)  # back to [B,S,H,D]


def flash_attention(query, key, value, dropout=0.0, causal=False, return_softmax=False,
                    fixed_seed_offset=None, rng_name="", training=True, name=None):
    """Reference: flash_attention.py:358. Layout [batch, seq, heads, head_dim]."""
    head_dim = query.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)

    if _use_pallas(tuple(query.shape), tuple(key.shape)) and not dropout:
        from ...ops.pallas.flash_attention import flash_attention as _pallas_fa

        _mark("pallas")
        out = apply_op(
            lambda q, k, v: _pallas_fa(q, k, v, causal=causal, scale=scale),
            "flash_attention_pallas", query, key, value,
        )
        return out, None

    _mark("xla")
    out = apply_op(
        lambda q, k, v: _sdpa_core(q, k, v, None, scale, causal, dropout, training),
        "flash_attention", query, key, value,
    )
    return out, None


def flash_attn_unpadded(query, key, value, cu_seqlens_q, cu_seqlens_k, max_seqlen_q,
                        max_seqlen_k, scale, dropout=0.0, causal=False,
                        return_softmax=False, fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Varlen attention (reference :756): tokens packed as [total, heads, dim]
    with cu_seqlens boundaries.

    TPU paths (check get_last_attention_backend()):
    - pallas: the packed sequence is ONE flashmask batch — per-column document
      bounds from cu_seqlens become startend_row_indices, so the kernel skips
      cross-document blocks and never materializes [total, total] scores.
      Requires total % 128 == 0 (kernel block) — the wrapper pads with a fully
      masked tail (masked rows produce exact zeros) and slices it off.
    - xla fallback: segment-mask over the full score matrix (fine for short
      totals; memory-bound for long ones).
    """
    q_len = int(query.shape[0])
    block = 128
    pad = (-q_len) % block
    total = q_len + pad
    same_qk = (query.shape[0] == key.shape[0]) and _same_cu(cu_seqlens_q,
                                                            cu_seqlens_k)
    if (same_qk and not dropout
            and _use_pallas((1, total, query.shape[1], query.shape[2]),
                            (1, total, key.shape[1], key.shape[2]))):
        from ...ops.pallas.flash_attention import (
            flashmask_attention as _pallas_fm,
        )

        def fp(q, k, v, cu_k):
            cu = cu_k.astype(jnp.int32)
            seg = jnp.cumsum(
                jnp.zeros(q_len, jnp.int32).at[cu[1:-1]].add(1))
            doc_end = jnp.take(cu, seg + 1)        # [q_len] per-column doc end
            doc_start = jnp.take(cu, seg)
            if pad:
                cfg = [(0, pad)] + [(0, 0)] * (q.ndim - 1)
                q = jnp.pad(q, cfg)
                k = jnp.pad(k, cfg)
                v = jnp.pad(v, cfg)
                doc_end = jnp.pad(doc_end, (0, pad))     # end=0: all rows masked
                doc_start = jnp.pad(doc_start, (0, pad))
            qb = q[None]  # [1, total, H, D]
            kb = k[None]
            vb = v[None]
            if causal:
                # LT mask per column: rows >= doc_end are other documents
                sri = doc_end[None, None, :, None]
            else:
                # mask rows outside [doc_start, doc_end): lower [end, total),
                # upper [0, start)
                sri = jnp.stack(
                    [doc_end, jnp.full_like(doc_end, total),
                     jnp.zeros_like(doc_end), doc_start], -1)[None, None]
            out = _pallas_fm(qb, kb, vb, sri.astype(jnp.int32),
                             causal=causal, scale=scale)  # [1, total, H, D]
            return out[0, :q_len]

        _mark("pallas")
        out = apply_op(fp, "flash_attn_unpadded_pallas", query, key, value,
                       cu_seqlens_k)
        return out, None

    _mark("xla")

    def f(q, k, v, cu_q, cu_k):
        total_q = q.shape[0]
        seg_q = jnp.cumsum(
            jnp.zeros(total_q, jnp.int32).at[cu_q[1:-1].astype(jnp.int32)].add(1)
        )
        total_k = k.shape[0]
        seg_k = jnp.cumsum(
            jnp.zeros(total_k, jnp.int32).at[cu_k[1:-1].astype(jnp.int32)].add(1)
        )
        scores = jnp.einsum("qhd,khd->hqk", q, k) * scale
        seg_mask = seg_q[:, None] == seg_k[None, :]
        if causal:
            pos_q = jnp.arange(total_q) - jnp.take(cu_q, seg_q)
            pos_k = jnp.arange(total_k) - jnp.take(cu_k, seg_k)
            seg_mask = seg_mask & (pos_q[:, None] >= pos_k[None, :])
        scores = jnp.where(seg_mask[None], scores, jnp.finfo(scores.dtype).min)
        probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
        return jnp.einsum("hqk,khd->qhd", probs, v)

    out = apply_op(f, "flash_attn_unpadded", query, key, value, cu_seqlens_q, cu_seqlens_k)
    return out, None


def flashmask_attention(query, key, value, startend_row_indices=None, dropout=0.0,
                        causal=False, window_size=None, return_softmax_lse=False,
                        return_seed_offset=False, fixed_seed_offset=None, rng_name="",
                        training=True, name=None):
    """Reference: flash_attention.py:1299. startend_row_indices [B, H|1, S, {1,2,4}]
    encodes per-column sparse masks (causal doc masks etc.) — here materialized as a
    boolean mask; a Pallas blockwise-skip kernel is the optimization path."""
    head_dim = query.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)

    if (startend_row_indices is not None and not dropout
            and _use_pallas(tuple(query.shape), tuple(key.shape))):
        from ...ops.pallas.flash_attention import flashmask_attention as _pallas_fm

        _mark("pallas")
        out = apply_op(
            lambda q, k, v, sri: _pallas_fm(q, k, v, sri, causal=causal, scale=scale),
            "flashmask_attention_pallas", query, key, value, startend_row_indices,
        )
        if return_softmax_lse or return_seed_offset:
            extras = [None] * (int(return_softmax_lse) + int(return_seed_offset))
            return (out, *extras)
        return out

    _mark("xla")

    def f(q, k, v, sri):
        B, S = q.shape[0], q.shape[1]
        T = k.shape[1]
        rows = jnp.arange(S)[:, None]  # query row index
        if sri is None:
            mask = None
        else:
            sri_i = sri.astype(jnp.int32)  # [B, H', T, n]
            n = sri_i.shape[-1]
            cols = jnp.arange(T)[None, None, None, :]
            if causal:
                if n == 1:
                    # LT start: mask rows >= start (below start) for each column
                    start = jnp.moveaxis(sri_i, -1, 0)[0]  # [B,H',T]
                    masked = rows[None, None, :, :] * 0  # broadcast helper
                    m = rows[None, None] >= start[:, :, None, :]
                else:
                    start = sri_i[..., 0]
                    end = sri_i[..., 1]
                    m = (rows[None, None] >= start[:, :, None, :]) & (
                        rows[None, None] < end[:, :, None, :]
                    )
                causal_m = rows >= jnp.arange(T)[None, :]
                mask = (~m) & causal_m[None, None]
            else:
                # [LTS, LTE, UTS, UTE]
                lts = sri_i[..., 0]
                lte = sri_i[..., 1] if n > 1 else jnp.full_like(lts, S)
                uts = sri_i[..., 2] if n > 2 else jnp.zeros_like(lts)
                ute = sri_i[..., 3] if n > 3 else jnp.zeros_like(lts)
                lower = (rows[None, None] >= lts[:, :, None, :]) & (
                    rows[None, None] < lte[:, :, None, :]
                )
                upper = (rows[None, None] >= uts[:, :, None, :]) & (
                    rows[None, None] < ute[:, :, None, :]
                )
                mask = ~(lower | upper)
        return _sdpa_core(q, k, v, mask, scale, causal and sri is None, dropout, training)

    out = apply_op(f, "flashmask_attention", query, key, value, startend_row_indices)
    if return_softmax_lse or return_seed_offset:
        extras = [None] * (int(return_softmax_lse) + int(return_seed_offset))
        return (out, *extras)
    return out


def scaled_dot_product_attention(query, key, value, attn_mask=None, dropout_p=0.0,
                                 is_causal=False, training=True, name=None):
    """Reference: paddle.nn.functional.scaled_dot_product_attention — [B,S,H,D] layout."""
    head_dim = query.shape[-1]
    scale = 1.0 / math.sqrt(head_dim)
    return apply_op(
        lambda q, k, v, m: _sdpa_core(q, k, v, m, scale, is_causal, dropout_p, training),
        "scaled_dot_product_attention", query, key, value, attn_mask,
    )
