"""Split-KV decode attention (flash-decode style) + paged variant, as Pallas
TPU kernels.

Reference parity surface: the LLM-serving kernels the reference binds from
CUDA — masked_multihead_attention_kernel.cu:1201 (single-token attention over
a dense cache) and block_multi_head_attention_kernel.cu (paged / block-table
cache). Here both are TPU-native Pallas.

Design (Flash-Decoding, Dao et al. 2023): decode attention at small batch is
memory-bandwidth-bound — one query row per (batch, head) must stream the whole
KV prefix. A single-block kernel would serialize that stream; instead the KV
prefix is PARTITIONED across grid blocks:

  stage 1 (Pallas): grid (B*Hkv, T/block_k). Each step loads one contiguous
    [block_k, D] KV block into VMEM once and computes the block-local softmax
    statistics for its head's query group — running max m, normalizer l, and
    the unnormalized partial output o = e @ V (the classic (m, l, o) flash
    triple), written per block.
  stage 2 (XLA): the per-block partials are combined with the standard
    rescaling reduction: m* = max_j m_j, out = sum_j o_j e^{m_j - m*} /
    sum_j l_j e^{m_j - m*}. The partials are [BH, nb, rows, D] — a few
    hundred KB — so this reduction is noise; XLA fuses it into one kernel.

Layout contract: caches are HEAD-LEADING — [B, Hkv, T, D] dense, [Hkv, P,
BS, D] paged — so every kernel block is a plain (1, rows, D) / (1, 1, BS, D)
tile over the two minor dims and the head axis is resolved by the grid /
index_map, never sliced in-kernel (in-kernel head slicing would relayout the
whole block per head under Mosaic; this is the same 3-D-block idiom as
flash_attention.py and the shape the DMA engine streams contiguously). The
models pay only a [B, S, Hkv, D] -> [B, Hkv, S, D] transpose of the NEW rows
per step — S is 1 at decode.

GQA is native: q rows are grouped per kv head ([B*Hkv, S*G, D], G =
num_q_heads / num_kv_heads), so K/V are never materialized at the
`rep`-expanded shape the old jnp.repeat path paid G× cache traffic for.

Masked length: `lengths` (per-request int32 [B]) bounds the live prefix —
padded cache slots are masked in-kernel (col <= length + row//G), never
gathered. Blocks entirely past the live region skip compute via pl.when.

The paged variant reads KV through per-request block tables
(PrefetchScalarGridSpec: the table is scalar-prefetched so the BlockSpec
index_map itself selects the page, PagedAttention-style) — the serving
layer's block-paged KV cache (paddle_tpu/inference/kv_cache.py) feeds it.

Everything runs compiled on TPU and in interpreter mode elsewhere (CPU CI).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import _interpret, _no_x64

_NEG = float(jnp.finfo(jnp.float32).min)


# ----------------------------------------------------------------- reference
def decode_attention_xla(q, k_cache, v_cache, lengths, scale=None):
    """Grouped-GQA cache attention in plain XLA — the correctness reference
    and the `decode_kernel="xla"` serving path.

    q: [B, S, Hq, D] at absolute positions length..length+S-1.
    k_cache/v_cache: [B, Hkv, T, D] (head-leading); entries [0, length+S) are
    live (the S new rows were just written at [length, length+S)).
    lengths: int32 scalar or [B] — per-request live-prefix length.

    The q-head axis is grouped over kv heads via einsum ("bsngd,bntd->bngst"),
    so K/V are consumed at their stored [B, Hkv, T, D] shape — no jnp.repeat
    materialization of the G-expanded heads.
    """
    B, S, Hq, D = q.shape
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    lengths = _norm_lengths(lengths, B)
    qg = q.reshape(B, S, Hkv, G, D)
    scores = jnp.einsum("bsngd,bntd->bngst", qg, k_cache,
                        preferred_element_type=jnp.float32) * jnp.float32(scale)
    pos_q = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    pos_k = jnp.arange(T, dtype=jnp.int32)
    allowed = pos_k[None, None, :] <= pos_q[:, :, None]          # [B,S,T]
    scores = jnp.where(allowed[:, None, None], scores, jnp.float32(_NEG))
    probs = jax.nn.softmax(scores, axis=-1).astype(v_cache.dtype)
    out = jnp.einsum("bngst,bntd->bsngd", probs, v_cache)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def _norm_lengths(lengths, B):
    lengths = jnp.asarray(lengths, jnp.int32).reshape(-1)
    return jnp.broadcast_to(lengths, (B,))


# -------------------------------------------------------------- kernel body
def _partials(length, col0, q, k, v, *, scale, g):
    """Block-local (o, m, l) partials for one (batch*head, kv-block) step.
    q: [SG, D] (S query steps × G grouped q heads, row-major (s, g));
    k/v: [BK, D]."""
    sg, bk = q.shape[0], k.shape[0]
    scale32 = jnp.float32(scale)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (sg, bk), 1)
    rloc = jax.lax.broadcasted_iota(jnp.int32, (sg, bk), 0)
    # row r is query step s = r//G at absolute position length + s — causal
    # over the live prefix + the new rows
    qrow = jax.lax.div(rloc, jnp.int32(g)) if g > 1 else rloc
    allowed = cols <= length + qrow
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale32
    s = jnp.where(allowed, s, jnp.float32(_NEG))
    m = jnp.max(s, axis=1, keepdims=True)
    # e must be exactly 0 on masked cols even when the WHOLE block is masked
    # for a row (m == _NEG would make exp(s - m) = 1 there)
    e = jnp.where(allowed, jnp.exp(s - m), jnp.float32(0.0))
    l = jnp.sum(e, axis=1, keepdims=True)
    o = jax.lax.dot_general(e.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    return o, m, l


def _dead_partials(sg, d):
    # partials that contribute nothing under the stage-2 rescale
    return (jnp.zeros((sg, d), jnp.float32),
            jnp.full((sg, 1), _NEG, jnp.float32),
            jnp.zeros((sg, 1), jnp.float32))


def _store_partials(lead, refs, partials):
    """Write (o, m, l) at the block's leading unit indices. Indexed stores
    only: a `ref.at[...]` view of an output block whose lane extent (D=64, or
    the 1-wide m/l columns) is narrower than the 128-lane tile is a
    tpu.memref_slice Mosaic refuses ("Slice shape ... must be aligned to
    tiling (128)", libtpu 0.0.34)."""
    for ref, val in zip(refs, partials):
        ref[lead] = val


def _splitkv_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *,
                    scale, block_k, hkv, g, s_new):
    j = pl.program_id(1)
    bh = pl.program_id(0)
    length = len_ref[jax.lax.div(bh, jnp.int32(hkv)), 0]
    col0 = j * block_k
    live = col0 < length + s_new
    outs = (o_ref, m_ref, l_ref)

    @pl.when(live)
    def _body():
        _store_partials((0, 0), outs, _partials(
            length, col0, q_ref[0], k_ref[0], v_ref[0], scale=scale, g=g))

    @pl.when(jnp.logical_not(live))
    def _dead():
        _store_partials((0, 0), outs, _dead_partials(*q_ref.shape[1:]))


def _combine_partials(o_p, m_p, l_p, B, Hkv, S, G, D, dtype):
    """Stage 2: rescale-and-sum the per-block (m, l, o) partials (XLA — the
    reduction is over [nb] of tiny tiles; one fused kernel)."""
    m_star = jnp.max(m_p, axis=1, keepdims=True)
    w = jnp.exp(m_p - m_star)
    l_star = jnp.sum(l_p * w, axis=1)               # [BH, SG, 1]
    o = jnp.sum(o_p * w, axis=1)                    # [BH, SG, D]
    out = jnp.where(l_star > 0, o / jnp.where(l_star > 0, l_star, 1.0), 0.0)
    out = out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, Hkv * G, D).astype(dtype)


def _q_rows(q, Hkv, G):
    """[B, S, Hq, D] -> [B*Hkv, S*G, D], rows grouped per kv head
    (h = n*G + g)."""
    B, S, Hq, D = q.shape
    return (q.reshape(B, S, Hkv, G, D).transpose(0, 2, 1, 3, 4)
            .reshape(B * Hkv, S * G, D))


def auto_block_k(T: int) -> int | None:
    """Largest KV block that divides the cache length. Bigger blocks amortize
    grid/DMA overhead; smaller ones give stage 1 more parallelism — 256 is the
    sweet spot for bandwidth-bound decode on v5e-class chips (512 KB of KV in
    flight per step at D=64 bf16 under double buffering)."""
    for bk in (256, 512, 128, 64):
        if T % bk == 0 and T >= bk:
            return bk
    return T if T <= 1024 else None


def supports(q_shape, cache_shape, block_k=None) -> bool:
    """Static check: can the split-KV kernel run these shapes.
    cache_shape is head-leading [B, Hkv, T, D]."""
    B, S, Hq, D = q_shape
    Hkv, T = cache_shape[1], cache_shape[2]
    bk = block_k or auto_block_k(T)
    return (bk is not None and T % bk == 0 and D <= 256
            and Hq % Hkv == 0 and cache_shape[0] == B)


def decode_attention(q, k_cache, v_cache, lengths, scale=None, block_k=None,
                     kernel="pallas"):
    """Decode attention over a dense per-request KV cache.

    q [B, S, Hq, D]; caches [B, Hkv, T, D] (head-leading); lengths int32
    scalar or [B]. kernel: "pallas" (split-KV flash-decode) | "xla" (grouped
    einsum reference). Pallas falls back to XLA when shapes are unsupported.
    """
    B, S, Hq, D = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if kernel != "pallas" or not supports(q.shape, k_cache.shape, block_k):
        return decode_attention_xla(q, k_cache, v_cache, lengths, scale)
    Hkv, T = k_cache.shape[1], k_cache.shape[2]
    G = Hq // Hkv
    bk = block_k or auto_block_k(T)
    nb = T // bk
    sg = S * G
    BH = B * Hkv
    lengths = _norm_lengths(lengths, B).reshape(B, 1)
    qr = _q_rows(q.astype(k_cache.dtype), Hkv, G)
    kf = k_cache.reshape(BH, T, D)
    vf = v_cache.reshape(BH, T, D)
    kernel_fn = functools.partial(_splitkv_kernel, scale=float(scale),
                                  block_k=bk, hkv=Hkv, g=G, s_new=S)
    with _no_x64():
        o_p, m_p, l_p = pl.pallas_call(
            kernel_fn,
            grid=(BH, nb),
            in_specs=[
                pl.BlockSpec(memory_space=pltpu.SMEM),       # lengths [B, 1]
                pl.BlockSpec((1, sg, D), lambda b, j: (b, 0, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
                pl.BlockSpec((1, bk, D), lambda b, j: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((1, 1, sg, D), lambda b, j: (b, j, 0, 0)),
                pl.BlockSpec((1, 1, sg, 1), lambda b, j: (b, j, 0, 0)),
                pl.BlockSpec((1, 1, sg, 1), lambda b, j: (b, j, 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((BH, nb, sg, D), jnp.float32),
                jax.ShapeDtypeStruct((BH, nb, sg, 1), jnp.float32),
                jax.ShapeDtypeStruct((BH, nb, sg, 1), jnp.float32),
            ],
            interpret=_interpret(),
            name="_splitkv_kernel",
        )(lengths, qr, kf, vf)
    return _combine_partials(o_p, m_p, l_p, B, Hkv, S, G, D, q.dtype)


# ------------------------------------------------------------------- paged
def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                  *, scale, block_size, g, s_new):
    """Same math as _splitkv_kernel over a 3-D (batch, head, kv-block) grid;
    the KV block arrived via the block-table-driven index_map (page
    tbl[b, j]), col0 = j * block_size."""
    b = pl.program_id(0)
    j = pl.program_id(2)
    length = len_ref[b]
    col0 = j * block_size
    live = col0 < length + s_new
    outs = (o_ref, m_ref, l_ref)

    @pl.when(live)
    def _body():
        _store_partials((0, 0, 0), outs, _partials(
            length, col0, q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            scale=scale, g=g))

    @pl.when(jnp.logical_not(live))
    def _dead():
        _store_partials((0, 0, 0), outs, _dead_partials(*q_ref.shape[2:]))


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           scale=None, kernel="pallas"):
    """Decode attention reading KV through per-request block tables.

    q: [B, S, Hq, D]; k_pages/v_pages: [Hkv, P, BS, D] (the shared
    head-leading page pool); block_tables: [B, NB] int32 page ids (entries
    past a request's extent must still be VALID page ids, e.g. 0 — they are
    fetched but fully masked); lengths: [B] int32 live prefix per request.

    Pallas path: PrefetchScalarGridSpec prefetches the table so the k/v
    BlockSpec index_map picks page tbl[b, j] directly — the PagedAttention
    access pattern, no gather materialization.

    Under a mesh with a tensor axis (the serving mesh's tp, ISSUE-12), the
    whole call runs per head shard (`distributed.mesh.per_shard`): each chip
    runs the split-KV kernel on its LOCAL heads against its LOCAL pool shard
    (attention is head-local, so no collective is needed here — the only
    cross-chip exchange per launch is the sampled-logit gather after the
    vocab-sharded lm_head). The slot dimension stays replicated: the serving
    mesh's dp is the replica axis, not a batch axis.
    """
    B, D = q.shape[0], q.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    from ...distributed.mesh import per_shard

    return per_shard(
        functools.partial(_paged_decode_attention_impl, scale=float(scale),
                          kernel=kernel),
        (q, k_pages, v_pages, jnp.asarray(block_tables, jnp.int32),
         _norm_lengths(lengths, B)),
        ("..h.", "h", "h", "", ""), "..h.")


def _paged_decode_attention_impl(q, k_pages, v_pages, block_tables, lengths,
                                 scale=None, kernel="pallas"):
    B, S, Hq, D = q.shape
    Hkv, P_, BS = k_pages.shape[0], k_pages.shape[1], k_pages.shape[2]
    NB = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    lengths = _norm_lengths(lengths, B)
    block_tables = jnp.asarray(block_tables, jnp.int32)
    if kernel != "pallas" or D > 256 or Hq % Hkv != 0:
        # gather-based reference: pages -> contiguous head-leading dense cache
        k_dense = (k_pages[:, block_tables]        # [Hkv, B, NB, BS, D]
                   .reshape(Hkv, B, NB * BS, D).swapaxes(0, 1))
        v_dense = (v_pages[:, block_tables]
                   .reshape(Hkv, B, NB * BS, D).swapaxes(0, 1))
        return decode_attention_xla(q, k_dense, v_dense, lengths, scale)
    sg = S * G
    BH = B * Hkv
    # [B, Hkv, sg, D]: the 3-D (batch, head, block) grid indexes heads
    # directly — no index_map arithmetic (python // or % on a traced grid
    # index promotes through an i64 helper under the global x64 flag)
    qr = _q_rows(q.astype(k_pages.dtype), Hkv, G).reshape(B, Hkv, sg, D)
    kernel_fn = functools.partial(_paged_kernel, scale=float(scale),
                                  block_size=BS, g=G, s_new=S)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,            # block_tables, lengths
        grid=(B, Hkv, NB),
        in_specs=[
            pl.BlockSpec((1, 1, sg, D), lambda b, h, j, tbl, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, BS, D),
                         lambda b, h, j, tbl, ln: (h, tbl[b, j], 0, 0)),
            pl.BlockSpec((1, 1, BS, D),
                         lambda b, h, j, tbl, ln: (h, tbl[b, j], 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, sg, D),
                         lambda b, h, j, tbl, ln: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, sg, 1),
                         lambda b, h, j, tbl, ln: (b, h, j, 0, 0)),
            pl.BlockSpec((1, 1, 1, sg, 1),
                         lambda b, h, j, tbl, ln: (b, h, j, 0, 0)),
        ],
    )
    with _no_x64():
        o_p, m_p, l_p = pl.pallas_call(
            kernel_fn,
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((B, Hkv, NB, sg, D), jnp.float32),
                jax.ShapeDtypeStruct((B, Hkv, NB, sg, 1), jnp.float32),
                jax.ShapeDtypeStruct((B, Hkv, NB, sg, 1), jnp.float32),
            ],
            interpret=_interpret(),
            name="_paged_kernel",
        )(block_tables, lengths, qr, k_pages, v_pages)
    o_p = o_p.reshape(BH, NB, sg, D)
    m_p = m_p.reshape(BH, NB, sg, 1)
    l_p = l_p.reshape(BH, NB, sg, 1)
    return _combine_partials(o_p, m_p, l_p, B, Hkv, S, G, D, q.dtype)


def paged_cache_update(k_pages, v_pages, k_new, v_new, block_tables,
                       positions):
    """Scatter S new KV rows per request into the page pool at their (page,
    slot) targets. k_pages/v_pages: [Hkv, P, BS, D]; k_new/v_new:
    [B, S, Hkv, D]; `positions` is [B, S] int32 absolute cache positions; rows
    at position >= NB*BS (see `write_positions`) get a poisoned page id so
    XLA's out-of-bounds scatter DROPS them — that is how mixed-length prompts
    padded to a common S skip their padding rows without a mask gather."""
    BS = k_pages.shape[2]
    NB = block_tables.shape[1]
    pos = jnp.asarray(positions, jnp.int32)
    page = jnp.take_along_axis(block_tables.astype(jnp.int32),
                               jnp.clip(pos // BS, 0, NB - 1), axis=1)
    page = jnp.where(pos < NB * BS, page, jnp.int32(k_pages.shape[1]))
    slot = pos % BS
    # [B, S, Hkv, D] -> [Hkv, B, S, D] so the (page, slot) index arrays land
    # on the pool's middle axes under one leading full slice
    k_vals = k_new.astype(k_pages.dtype).transpose(2, 0, 1, 3)
    v_vals = v_new.astype(v_pages.dtype).transpose(2, 0, 1, 3)
    k_pages = k_pages.at[:, page, slot].set(k_vals, mode="drop")
    v_pages = v_pages.at[:, page, slot].set(v_vals, mode="drop")
    # keep the pool head-sharded over tp through the scatter so the step
    # programs' committed outputs preserve the serving-mesh layout (no-op
    # without a tp mesh — `constrain` drops absent/non-dividing axes)
    from ...distributed.mesh import constrain

    k_pages = constrain(k_pages, ["tp", None, None, None])
    v_pages = constrain(v_pages, ["tp", None, None, None])
    return k_pages, v_pages


def write_positions(lengths, S, valid=None, capacity=None):
    """[B, S] absolute write positions starting at each request's length;
    rows where `valid` is False are pushed to `capacity` (= NB*BS) so
    paged_cache_update drops them."""
    B = jnp.asarray(lengths).reshape(-1).shape[0]
    pos = _norm_lengths(lengths, B)[:, None] + jnp.arange(S, dtype=jnp.int32)
    if valid is None:
        return pos
    return jnp.where(valid, pos, jnp.int32(capacity))
