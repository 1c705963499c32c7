"""Flash attention (+ FlashMask) as Pallas TPU kernels.

Reference parity surface: python/paddle/nn/functional/flash_attention.py:358
(flash_attention), :1299 (flashmask_attention startend_row_indices encoding).
The reference binds an external CUDA flashattn library; here the kernel is
TPU-native Pallas (MXU matmuls, VMEM-resident K/V, f32 accumulation).

Design: grid over (batch*heads, q_blocks). Each grid step loads one q block
[BQ, D] plus the whole K/V [S, D] into VMEM and computes its exact softmax rows
— no online max/sum rescaling needed, while still never materialising the
[B, H, S, S] score tensor in HBM (that HBM round-trip is what makes the naive
path memory-bound at long S). K/V VMEM residency bounds S at ~8K for D=128
bf16; beyond that the sequence axis is sharded by ring attention
(paddle_tpu/distributed/context_parallel.py), which calls back into this kernel
per shard.

Backward is the standard two-kernel flash split: dq over q blocks, dk/dv over
k blocks, with delta = rowsum(dO * O) precomputed.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

_NEG = float(jnp.finfo(jnp.float32).min)


def _interpret() -> bool:
    """Interpret mode is the CPU path (CI) and nothing else: on the TPU the
    kernels compile through Mosaic, and any other backend is an error rather
    than a quiet interpreter run under the kernel's name."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    if backend == "cpu":
        return True
    raise RuntimeError(
        f"the Pallas kernels run compiled on 'tpu' and interpreted on 'cpu'; "
        f"jax.default_backend() is {backend!r}")


def _no_x64():
    """paddle_tpu enables jax_enable_x64 globally (paddle int64 dtype parity);
    under x64 pallas' internal index arithmetic emits i64 ops Mosaic cannot
    legalize. Kernel dtypes here are all explicit, so tracing the pallas_call
    with x64 off is semantics-preserving."""
    return jax.enable_x64(False)


# --------------------------------------------------------------------------- masks
def _allowed_mask(rows, cols, sri, causal: bool, seq: int):
    """(BQ, S) boolean mask of allowed positions; matches the semantics of the
    naive flashmask path (nn/functional/flash_attention.py flashmask_attention).

    rows/cols: int32 [BQ, S] query-row / key-col indices.
    sri: None or [S, n] int32 startend_row_indices for this (batch, head).
    """
    if causal:
        allowed = rows >= cols
    else:
        allowed = jnp.ones(rows.shape, jnp.bool_)
    if sri is None:
        return allowed
    n = sri.shape[-1]
    if causal:
        start = sri[:, 0][None, :]  # per-column mask start row
        if n == 1:
            masked = rows >= start
        else:
            end = sri[:, 1][None, :]
            masked = (rows >= start) & (rows < end)
        return allowed & ~masked
    lts = sri[:, 0][None, :]
    lte = sri[:, 1][None, :] if n > 1 else jnp.full_like(lts, seq)
    uts = sri[:, 2][None, :] if n > 2 else jnp.zeros_like(lts)
    ute = sri[:, 3][None, :] if n > 3 else jnp.zeros_like(lts)
    lower = (rows >= lts) & (rows < lte)
    upper = (rows >= uts) & (rows < ute)
    return allowed & ~(lower | upper)


def _row_col(qi, block_q: int, seq: int):
    rows = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, seq), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (block_q, seq), 1)
    return rows, cols


# --------------------------------------------------------------------------- fwd
def _fwd_kernel(*refs, scale, causal, block_q, seq, has_sri):
    scale = jnp.float32(scale)  # x64 mode: bare python floats promote f32->f64
    if has_sri:
        q_ref, k_ref, v_ref, sri_ref, o_ref, lse_ref = refs
        sri = sri_ref[0]
    else:
        q_ref, k_ref, v_ref, o_ref, lse_ref = refs
        sri = None
    qi = pl.program_id(1)
    # matmul INPUTS stay in the storage dtype (bf16 under AMP): the MXU runs
    # bf16×bf16→f32 at full rate, f32×f32 at half. Softmax statistics are f32
    # via preferred_element_type — the standard flash-attention precision split.
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows, cols = _row_col(qi, block_q, seq)
    allowed = _allowed_mask(rows, cols, sri, causal, seq)
    s = jnp.where(allowed, s, jnp.float32(_NEG))
    m = jnp.max(s, axis=1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=1, keepdims=True)
    o = jax.lax.dot_general(e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # Rows with no allowed position (possible under flashmask encodings) must
    # output exactly zero, not the uniform mean of V; lse=0 for such rows makes
    # backward's p = exp(_NEG - 0) = 0 so no gradient leaks through them.
    # NOT jnp.any: Mosaic lowers bool reduce_or via a float conversion in the
    # DEFAULT float dtype — f64 under jax_enable_x64 (which paddle_tpu sets
    # globally), and f64 vector reductions don't exist on TPU. An explicit f32
    # max-reduce lowers cleanly regardless of the x64 setting.
    any_allowed = jnp.max(allowed.astype(jnp.float32), axis=1,
                          keepdims=True) > jnp.float32(0.0)
    o = jnp.where(any_allowed, o / l, jnp.float32(0.0))
    o_ref[0] = o.astype(o_ref.dtype)
    lse_ref[0] = jnp.where(any_allowed, m + jnp.log(l), jnp.float32(0.0))


def _mha_fwd(q, k, v, sri, causal, scale, block_q):
    """q/k/v: [BH, S, D]; sri: [BH, S, n] int32 or None. Returns (out, lse)."""
    bh, seq, d = q.shape
    nq = seq // block_q
    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=block_q, seq=seq,
        has_sri=sri is not None,
    )
    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),
    ]
    args = [q, k, v]
    if sri is not None:
        in_specs.append(pl.BlockSpec((1, seq, sri.shape[-1]), lambda b, i: (b, 0, 0)))
        args.append(sri)
    with _no_x64():
        out, lse = pl.pallas_call(
            kernel,
            grid=(bh, nq),
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                # lse as a [BH, S, 1] column: block (1, BQ, 1) is legal TPU tiling
                # (lane dim equals the array's) and every kernel op stays 2D
                pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, seq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, seq, 1), jnp.float32),
            ],
            interpret=_interpret(),
            name="_fwd_kernel",
        )(*args)
    return out, lse.reshape(bh, seq)


# --------------------------------------------------------------------------- bwd
def _dq_kernel(*refs, scale, causal, block_q, seq, has_sri):
    scale = jnp.float32(scale)
    if has_sri:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, sri_ref, dq_ref = refs
        sri = sri_ref[0]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref = refs
        sri = None
    qi = pl.program_id(1)
    # bf16 matmul inputs, f32 accumulation/statistics (see _fwd_kernel note)
    q = q_ref[0]
    k = k_ref[0]
    v = v_ref[0]
    do = do_ref[0]
    lse = lse_ref[0]    # (BQ, 1)
    delta = dl_ref[0]   # (BQ, 1)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    rows, cols = _row_col(qi, block_q, seq)
    allowed = _allowed_mask(rows, cols, sri, causal, seq)
    s = jnp.where(allowed, s, jnp.float32(_NEG))
    p = jnp.exp(s - lse)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta) * scale
    dq = jax.lax.dot_general(ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _dkv_kernel(*refs, scale, causal, block_k, seq, has_sri):
    scale = jnp.float32(scale)
    if has_sri:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, sri_ref, dk_ref, dv_ref = refs
        sri_blk = sri_ref[0]
    else:
        q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref = refs
        sri_blk = None
    ki = pl.program_id(1)
    # bf16 matmul inputs, f32 accumulation/statistics (see _fwd_kernel note)
    q = q_ref[0]                          # (S, D)
    k = k_ref[0]                          # (BK, D)
    v = v_ref[0]
    do = do_ref[0]                        # (S, D)
    lse = lse_ref[0]                      # (S, 1)
    delta = dl_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (S, BK)
    rows = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    allowed = _allowed_mask(rows, cols, sri_blk, causal, seq)
    s = jnp.where(allowed, s, jnp.float32(_NEG))
    p = jnp.exp(s - lse)
    dv = jax.lax.dot_general(p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (BK, D)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (S, BK)
    ds = p * (dp - delta) * scale
    dk = jax.lax.dot_general(ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)  # (BK, D)
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


def _dq_kernel_chunked(*refs, scale, causal, block_q, block_kc, seq):
    """dq for one q block, accumulated over k/v CHUNKS via the innermost grid
    dim — every tile is [block_q, block_kc], so VMEM stack use is independent
    of S (the full-sequence variant holds [block, S] f32 tiles and blows the
    16 MiB scoped limit at S=8192)."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref = refs
    qi = pl.program_id(1)
    kc = pl.program_id(2)

    @pl.when(kc == 0)
    def _init():
        dq_ref[0] = jnp.zeros_like(dq_ref[0])

    row0 = qi * block_q
    col0 = kc * block_kc
    # causal: a chunk strictly above the diagonal contributes nothing
    live = jnp.logical_or(not causal, col0 <= row0 + block_q - 1)

    @pl.when(live)
    def _body():
        scale32 = jnp.float32(scale)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = dl_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale32
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allowed = _allowed_mask(rows, cols, None, causal, seq)
        s = jnp.where(allowed, s, jnp.float32(_NEG))
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale32
        dq_ref[0] += jax.lax.dot_general(
            ds.astype(q.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _dkv_kernel_chunked(*refs, scale, causal, block_k, block_qc, seq):
    """dk/dv for one k block, accumulated over q/do CHUNKS (see
    _dq_kernel_chunked)."""
    q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref = refs
    ki = pl.program_id(1)
    qc = pl.program_id(2)

    @pl.when(qc == 0)
    def _init():
        dk_ref[0] = jnp.zeros_like(dk_ref[0])
        dv_ref[0] = jnp.zeros_like(dv_ref[0])

    row0 = qc * block_qc
    col0 = ki * block_k
    live = jnp.logical_or(not causal, col0 <= row0 + block_qc - 1)

    @pl.when(live)
    def _body():
        scale32 = jnp.float32(scale)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]
        delta = dl_ref[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale32
        rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        allowed = _allowed_mask(rows, cols, None, causal, seq)
        s = jnp.where(allowed, s, jnp.float32(_NEG))
        p = jnp.exp(s - lse)                                     # (QC, BK)
        dv_ref[0] += jax.lax.dot_general(
            p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta) * scale32
        dk_ref[0] += jax.lax.dot_general(
            ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def _mha_bwd_chunked(q, k, v, out, lse, g, causal, scale):
    """Backward via chunk-accumulating kernels: VMEM-safe at any S (tiles are
    [512, 512] f32 regardless of sequence). Accumulation is f32 (the outputs
    are f32 and cast once at the end — bf16 += over S/512 chunks would lose
    precision)."""
    bh, seq, d = q.shape
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse = lse.reshape(bh, seq, 1)
    delta = delta.reshape(bh, seq, 1)
    blk = 512
    n = seq // blk
    with _no_x64():
        dq = pl.pallas_call(
            functools.partial(_dq_kernel_chunked, scale=scale, causal=causal,
                              block_q=blk, block_kc=blk, seq=seq),
            grid=(bh, n, n),
            in_specs=[
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),   # q
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0)),   # k
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0)),   # v
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),   # do
                pl.BlockSpec((1, blk, 1), lambda b, i, j: (b, i, 0)),   # lse
                pl.BlockSpec((1, blk, 1), lambda b, i, j: (b, i, 0)),   # delta
            ],
            out_specs=pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, jnp.float32),
            interpret=_interpret(),
            name="_dq_kernel_chunked",
        )(q, k, v, g, lse, delta)
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel_chunked, scale=scale, causal=causal,
                              block_k=blk, block_qc=blk, seq=seq),
            grid=(bh, n, n),
            in_specs=[
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0)),   # q
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),   # k
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),   # v
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, j, 0)),   # do
                pl.BlockSpec((1, blk, 1), lambda b, i, j: (b, j, 0)),   # lse
                pl.BlockSpec((1, blk, 1), lambda b, i, j: (b, j, 0)),   # delta
            ],
            out_specs=[
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),
                pl.BlockSpec((1, blk, d), lambda b, i, j: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, jnp.float32),
                jax.ShapeDtypeStruct(v.shape, jnp.float32),
            ],
            interpret=_interpret(),
            name="_dkv_kernel_chunked",
        )(q, k, v, g, lse, delta)
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _mha_bwd(q, k, v, sri, out, lse, g, causal, scale, block_q):
    bh, seq, d = q.shape
    if sri is None and seq > 4096 and seq % 512 == 0:
        # the full-sequence kernels hold [block, S] f32 score tiles — at
        # S=8192 that exceeds the 16 MiB VMEM scoped limit (measured on
        # v5e); the chunked variant's footprint is S-independent
        return _mha_bwd_chunked(q, k, v, out, lse, g, causal, scale)
    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse = lse.reshape(bh, seq, 1)
    delta = delta.reshape(bh, seq, 1)
    nq = seq // block_q
    has_sri = sri is not None

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # q
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),       # k
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),       # v
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # do
        pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),   # lse
        pl.BlockSpec((1, block_q, 1), lambda b, i: (b, i, 0)),   # delta
    ]
    dq_args = [q, k, v, g, lse, delta]
    if has_sri:
        dq_in_specs.append(pl.BlockSpec((1, seq, sri.shape[-1]), lambda b, i: (b, 0, 0)))
        dq_args.append(sri)
    with _no_x64():
        dq = pl.pallas_call(
            functools.partial(_dq_kernel, scale=scale, causal=causal, block_q=block_q,
                              seq=seq, has_sri=has_sri),
            grid=(bh, nq),
            in_specs=dq_in_specs,
            out_specs=pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
            interpret=_interpret(),
            name="_dq_kernel",
        )(*dq_args)

    dkv_in_specs = [
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),       # q
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # k block
        pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),   # v block
        pl.BlockSpec((1, seq, d), lambda b, i: (b, 0, 0)),       # do
        pl.BlockSpec((1, seq, 1), lambda b, i: (b, 0, 0)),       # lse
        pl.BlockSpec((1, seq, 1), lambda b, i: (b, 0, 0)),       # delta
    ]
    dkv_args = [q, k, v, g, lse, delta]
    if has_sri:
        # sri is indexed by key column: this kernel only sees its k block's columns
        dkv_in_specs.append(
            pl.BlockSpec((1, block_q, sri.shape[-1]), lambda b, i: (b, i, 0))
        )
        dkv_args.append(sri)
    with _no_x64():
        dk, dv = pl.pallas_call(
            functools.partial(_dkv_kernel, scale=scale, causal=causal, block_k=block_q,
                              seq=seq, has_sri=has_sri),
            grid=(bh, nq),
            in_specs=dkv_in_specs,
            out_specs=[
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
                pl.BlockSpec((1, block_q, d), lambda b, i: (b, i, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct(k.shape, k.dtype),
                jax.ShapeDtypeStruct(v.shape, v.dtype),
            ],
            interpret=_interpret(),
            name="_dkv_kernel",
        )(*dkv_args)
    return dq, dk, dv


# ------------------------------------------------------------------- custom vjp
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, scale, block_q):
    out, _ = _mha_fwd(q, k, v, None, causal, scale, block_q)
    return out


def _flash_fwd(q, k, v, causal, scale, block_q):
    out, lse = _mha_fwd(q, k, v, None, causal, scale, block_q)
    return out, (q, k, v, out, lse)


def _flash_bwd(causal, scale, block_q, res, g):
    q, k, v, out, lse = res
    return _mha_bwd(q, k, v, None, out, lse, g, causal, scale, block_q)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _flash_masked(q, k, v, sri, causal, scale, block_q):
    out, _ = _mha_fwd(q, k, v, sri, causal, scale, block_q)
    return out


def _flash_masked_fwd(q, k, v, sri, causal, scale, block_q):
    out, lse = _mha_fwd(q, k, v, sri, causal, scale, block_q)
    return out, (q, k, v, sri, out, lse)


def _flash_masked_bwd(causal, scale, block_q, res, g):
    q, k, v, sri, out, lse = res
    dq, dk, dv = _mha_bwd(q, k, v, sri, out, lse, g, causal, scale, block_q)
    dsri = np.zeros(sri.shape, dtype=jax.dtypes.float0)
    return dq, dk, dv, dsri


_flash_masked.defvjp(_flash_masked_fwd, _flash_masked_bwd)


# ------------------------------------------------------------------ public API
def _to_bhsd(x):
    """[B, S, H, D] -> [B*H, S, D] (paddle flash layout -> kernel layout)."""
    b, s, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, s, d)


def _from_bhsd(x, b, h):
    bh, s, d = x.shape
    return jnp.swapaxes(x.reshape(b, h, s, d), 1, 2)


def _repeat_kv(kv, n_rep):
    if n_rep == 1:
        return kv
    return jnp.repeat(kv, n_rep, axis=2)


def _auto_block_q(seq: int) -> int:
    """Largest q-block that divides the sequence and keeps the [BQ, S] f32
    score tile within a conservative VMEM budget. Bigger blocks amortize the
    K/V VMEM loads over more MXU work — measured on v5e (GPT-350M, S=1024):
    128→42.9% MFU, 256→46.9%, 512→49.2%, 1024→50.7%."""
    # 4 MiB f32 score-tile budget: the BACKWARD dkv kernel holds two [S, BK]
    # f32 tiles (p and dp) plus full-sequence q/do, so the fwd-only 8 MiB
    # budget VMEM-OOMs at S=8192 (measured: 36 KB over the 16 MiB stack)
    budget = 4 * 2**20
    for bq in (1024, 512, 256, 128):
        if seq % bq == 0 and bq * seq * 4 <= budget:
            return bq
    return 128


def supports(q_shape, k_shape, block_q=128) -> bool:
    """Static check: can the kernel run these shapes (self-attention, divisible seq)."""
    b, s, h, d = q_shape
    return (
        s == k_shape[1] and s % block_q == 0 and s >= block_q
        and d <= 256 and q_shape[0] == k_shape[0]
    )


def flash_attention(q, k, v, causal=False, scale=None, block_q=None):
    """Pallas flash attention over paddle layout [B, S, H, D]; GQA via kv-head
    broadcast. Differentiable (custom VJP flash backward)."""
    s, d = q.shape[1], q.shape[3]
    if block_q is None:
        block_q = _auto_block_q(s)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def local(q, k, v):
        b, h = q.shape[0], q.shape[2]
        n_rep = h // k.shape[2]
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        out = _flash(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), bool(causal),
                     float(scale), int(block_q))
        return _from_bhsd(out, b, h)

    from ...distributed.mesh import per_shard

    return per_shard(local, (q, k, v), ("b.h.",) * 3, "b.h.")


def flashmask_attention(q, k, v, startend_row_indices, causal=True, scale=None,
                        block_q=None):
    """FlashMask (reference flash_attention.py:1299): startend_row_indices
    [B, H'|1, S, n] sparse-mask encoding evaluated inside the kernel — no
    [B, H, S, S] mask materialisation."""
    s, d = q.shape[1], q.shape[3]
    if block_q is None:
        block_q = _auto_block_q(s)
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def local(q, k, v, sri):
        b, h = q.shape[0], q.shape[2]
        n_rep = h // k.shape[2]
        k, v = _repeat_kv(k, n_rep), _repeat_kv(v, n_rep)
        if sri.shape[1] == 1 and h > 1:
            sri = jnp.broadcast_to(sri, (b, h, sri.shape[2], sri.shape[3]))
        sri = sri.reshape(b * h, sri.shape[2], sri.shape[3])
        out = _flash_masked(_to_bhsd(q), _to_bhsd(k), _to_bhsd(v), sri,
                            bool(causal), float(scale), int(block_q))
        return _from_bhsd(out, b, h)

    from ...distributed.mesh import per_shard

    return per_shard(
        local, (q, k, v, startend_row_indices.astype(jnp.int32)),
        ("b.h.", "b.h.", "b.h.", "bh.."), "b.h.")
