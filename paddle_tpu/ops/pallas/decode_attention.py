"""Decode attention over a KV cache as Pallas TPU kernels: a split-KV
(flash-decode) kernel over a dense cache, and a paged kernel that walks each
request's live pages through its block table.

Reference parity surface: the LLM-serving kernels the reference binds from
CUDA — masked_multihead_attention_kernel.cu:1201 (single-token attention over
a dense cache) and block_multi_head_attention_kernel.cu (paged / block-table
cache). Here both are TPU-native Pallas.

Dense (Flash-Decoding, Dao et al. 2023): decode attention at small batch is
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
    sum_j l_j e^{m_j - m*}.

Paged (the serving path; PagedAttention, Kwon et al. 2023): ONE stage whose
work follows the live context, not the table's width. The page pools stay in
HBM; grid (B, Hkv / heads_per_step). A step reads its slot's length and trip
count from scalar-prefetched arrays and runs a loop with that DYNAMIC trip
count: each trip DMAs `pages_per_step` pages into a double-buffered VMEM
scratch (the next trip's copies are in flight while this one is multiplied)
and folds them into running (m, l, acc) accumulators in VMEM — the same
block-local `_partials` and the same rescale as the dense stage 2, online —
then writes the normalised output once. Pages past a slot's length cost
nothing: no grid step, no DMA, no HBM store; a slot with no valid new row (an
idle slot of the decode tick, a slot that takes nothing of a prefill chunk)
runs zero trips. `heads_per_step` and `pages_per_step` come from the static
shapes and a VMEM budget (`paged_tiling`); the trip count comes from
`paged_walk_blocks`, which the scheduler's `walked_rows` counter uses too.

Layout contract: the dense cache is HEAD-LEADING, [B, Hkv, T, D], so the head
axis is a leading index of every block. The paged pool is ROW-MAJOR,
[P, BS, Hkv*D]: a row holds every kv head's D numbers side by side (head h
in lanes h*D .. (h+1)*D). That ONE layout is what the row writer scatters
into (`paged_cache_update`, the new [B, S, Hkv, D] rows as they come), what
the step programs carry from layer to layer and token to token, and what
the kernel reads (`pl.ANY`: a page is one contiguous [BS, Hkv*D] DMA), so
no program holds an XLA op of the pool's size: XLA's scatter, the loop's
carry and Mosaic all agree on row-major. A head's K and V are a static lane
slice of the VMEM buffer; for D < 128 the 128 // D heads of one 128-lane
group are multiplied at once under a block-diagonal q (`_partials`).

GQA is native: q rows are grouped per kv head ([B*Hkv, S*G, D], G =
num_q_heads / num_kv_heads), so K/V are never materialized at the
`rep`-expanded shape the old jnp.repeat path paid G× cache traffic for.

Masked length: `lengths` (per-request int32 [B]) bounds the live prefix —
padded cache slots are masked in-kernel (col <= length + row//G), never
gathered. The dense kernel's blocks entirely past the live region skip
compute via pl.when; the paged kernel never visits them.

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
def _partials(length, col0, q, k, v, *, scale, g, heads=1):
    """Block-local (o, m, l) partials for one (batch*head, kv-block) step.
    q: [SG, D] (S query steps × G grouped q heads, row-major (s, g));
    k/v: [BK, D].

    heads = R > 1 is the paged kernel's form for D < 128, where R kv heads
    share a 128-lane group of the pool's rows: k/v are [BK, R*D], head p in
    lane group p, and q is [R*SG, R*D], block-diagonal: row p*SG + i carries
    query row i of head p in lane group p and zeros elsewhere, so its scores
    are that head's alone and lane group p of its o is that head's output
    (the other lanes of the row are another head's V under this head's
    weights: never read). Every row sees every K row."""
    sg, bk = q.shape[0], k.shape[0]
    scale32 = jnp.float32(scale)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, (sg, bk), 1)
    rloc = jax.lax.broadcasted_iota(jnp.int32, (sg, bk), 0)
    if heads > 1:
        rloc = jax.lax.rem(rloc, jnp.int32(sg // heads))
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
_WALK_ROWS = 256          # K,V rows one loop trip of the paged kernel folds
_VMEM_BUDGET = 10 << 20   # bytes of VMEM a grid step's buffers may take
_VMEM_LIMIT = 32 << 20    # the scoped limit asked of Mosaic for the call


def _heads_per_group(D):
    """Heads that sit side by side in one lane group of a pool row: 128 // D
    for D < 128 (two heads of 64 share a 128-lane tile), else one. Mosaic
    slices refs only in whole 128-lane tiles, so a group is what the kernel
    multiplies at once."""
    return 128 // D if D < 128 else 1


def paged_kernel_takes(Hq, Hkv, D):
    """Static check: can the Mosaic paged kernel read a pool of `Hkv` heads
    of `D` (the LOCAL heads under tp). A row must be whole 128-lane tiles
    and a head must not straddle one; anything else takes the XLA gather."""
    return (Hq % Hkv == 0 and D <= 256 and (Hkv * D) % 128 == 0
            and (128 % D == 0 or D % 128 == 0))


def paged_pages_per_step(NB, BS):
    """Pages one loop trip of the paged kernel folds: about `_WALK_ROWS`
    rows, never more than the table holds."""
    return max(1, min(NB, _WALK_ROWS // BS))


def paged_tiling(Hkv, NB, BS, D, SG, itemsize):
    """(heads_per_step, pages_per_step) of the paged kernel, from static
    shapes alone. A loop trip folds `paged_pages_per_step` pages; a grid
    step serves the largest divisor of the row's lane groups (see
    `_heads_per_group`) whose buffers fit `_VMEM_BUDGET`: the
    double-buffered K and V blocks, the pipelined q and output blocks and
    the f32 (acc, m, l) accumulators, at VMEM's (16, 128) padding."""
    pps = paged_pages_per_step(NB, BS)
    R = _heads_per_group(D)
    lanes = -(-R * D // 128) * 128
    rows = -(-R * SG // 16) * 16
    per_group = (2 * 2 * pps * BS * lanes * itemsize     # K, V: 2 slots
                 + 2 * rows * lanes * (itemsize + 4)     # q, out (pipelined)
                 + rows * (lanes + 2 * 128) * 4)         # acc, m, l
    fit = max(1, _VMEM_BUDGET // per_group)
    groups = Hkv // R
    gps = max(n for n in range(1, groups + 1)
              if groups % n == 0 and n <= fit)
    return gps * R, pps


def paged_walk_blocks(lengths, new_rows, block_rows):
    """Blocks of `block_rows` K,V rows the paged kernel walks for each slot:
    cdiv(length + new_rows, block_rows), and 0 for a slot with no valid new
    row (its output is ignored, so it walks nothing). numpy or jax arrays.
    The kernel's trip count (block_rows = pages_per_step x page rows), its
    live-page bound (block_rows = page rows) and the tick ledger's
    `walked_rows` (inference/scheduler.py:_kv_rows) are all this."""
    rows = lengths + new_rows
    return (new_rows > 0) * ((rows + (block_rows - 1)) // block_rows)


def _paged_kernel(tbl_ref, len_ref, trips_ref, pages_ref, q_ref, k_hbm, v_hbm,
                  o_ref, k_buf, v_buf, sem, m_ref, l_ref, acc_ref, *, scale,
                  block_size, g, gps, pps, heads, d):
    """One grid step = slot b x `gps` lane groups of `heads` kv heads each.
    Walks the slot's LIVE pages only: `trips_ref[b]` loop trips, each
    folding `pps` pages (DMA'd HBM -> VMEM as [BS, lanes] blocks — one
    contiguous piece a page where the step serves every head — the next
    trip's in flight meanwhile) into the running (m, l, acc) — the online
    form of the split-KV rescale — and writes the normalised output once. A
    page index past the slot's last live page is clamped to it (its columns
    are masked), so nothing a slot does not own is ever read. A head's K
    and V are a static lane slice of the buffers."""
    b = pl.program_id(0)
    length = len_ref[b]
    trips = trips_ref[b]
    last = pages_ref[b] - 1
    gw = heads * d                      # lanes of one group
    lw = gps * gw                       # lanes this step serves: all, or
    step_lanes = (slice(None) if lw == k_hbm.shape[-1] else pl.ds(  # a slice
        pl.multiple_of(pl.program_id(1) * lw, 128), lw))

    def pages_of(i, slot, act):
        """`act` (start or wait) on the K and V copy of each page of trip i
        into buffer `slot`; a rolled loop, the kernel's code stays small."""
        def page_copies(j, carry):
            page = tbl_ref[b, jnp.minimum(i * pps + j, last)]
            rows = pl.ds(pl.multiple_of(j * block_size, block_size),
                         block_size)
            for hbm, buf, kv in ((k_hbm, k_buf, 0), (v_hbm, v_buf, 1)):
                act(pltpu.make_async_copy(
                    hbm.at[page, :, step_lanes], buf.at[slot, rows, :],
                    sem.at[kv, slot]))
            return carry

        jax.lax.fori_loop(0, pps, page_copies, 0)

    def start(copy):
        copy.start()

    def wait(copy):
        copy.wait()

    @pl.when(trips > 0)
    def _first():
        pages_of(0, 0, start)

    m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def trip(i, carry):
        slot = jax.lax.rem(i, 2)

        @pl.when(i + 1 < trips)
        def _next():
            pages_of(i + 1, 1 - slot, start)

        pages_of(i, slot, wait)
        col0 = i * (pps * block_size)

        # a static loop: unrolled, the groups' products overlap (measured on
        # the v5e at 20 heads: a decode call 0.30 ms against 0.37 rolled)
        for j in range(gps):
            lanes = slice(j * gw, (j + 1) * gw)
            o, m, l = _partials(length, col0, q_ref[0, j],
                                k_buf[slot, :, lanes], v_buf[slot, :, lanes],
                                scale=scale, g=g, heads=heads)
            m_old = m_ref[j]
            m_new = jnp.maximum(m_old, m)
            a, c = jnp.exp(m_old - m_new), jnp.exp(m - m_new)
            l_ref[j] = a * l_ref[j] + c * l
            acc_ref[j] = a * acc_ref[j] + c * o
            m_ref[j] = m_new
        return carry

    jax.lax.fori_loop(0, trips, trip, 0)
    # a row that saw no live column (an idle slot) comes out 0; head p of a
    # group is its own rows and its own lanes of the accumulator
    l_all = l_ref[...]
    out = jnp.where(l_all > 0,
                    acc_ref[...] / jnp.where(l_all > 0, l_all, 1.0), 0.0)
    sg = out.shape[1] // heads
    for p in range(heads):
        o_ref[0, :, p] = out[:, p * sg:(p + 1) * sg,
                             p * d:(p + 1) * d].astype(o_ref.dtype)


def paged_decode_attention(q, k_pages, v_pages, block_tables, lengths,
                           scale=None, kernel="pallas", new_rows=None):
    """Decode attention reading KV through per-request block tables.

    q: [B, S, Hq, D]; k_pages/v_pages: [P, BS, Hkv*D] (the shared page pool:
    a row holds every kv head's D numbers side by side, head h in lanes
    h*D .. (h+1)*D); block_tables: [B, NB] int32 page ids (entries
    past a request's extent must still be VALID page ids, e.g. 0 — the
    Pallas kernel never fetches them, the XLA gather does and masks them);
    lengths: [B] int32 live prefix per request. new_rows: [B] int32, how
    many of the S new rows of each slot are valid (default S, all of them):
    row s of slot b attends over columns <= lengths[b] + s, so the kernel
    walks pages 0 .. cdiv(lengths[b] + new_rows[b], BS) - 1 and nothing
    else; a slot with new_rows 0 (idle) walks nothing and its output rows
    are zeros. The XLA path takes no notice of it: rows at or past new_rows
    are the caller's to ignore under either kernel.

    Pallas path: the pools stay in HBM, in the layout the writer and the
    step programs' carry hold them in; the kernel DMAs the live pages of
    each slot through its scalar-prefetched table row — the PagedAttention
    access pattern, no gather materialization and no copy of the pool.

    Under a mesh with a tensor axis (the serving mesh's tp, ISSUE-12), the
    whole call runs per head shard (`distributed.mesh.per_shard`): each chip
    runs the kernel on its LOCAL heads against its LOCAL pool shard, the
    lanes of its heads (attention is head-local, so no collective is needed
    here — the only cross-chip exchange per launch is the sampled-logit
    gather after the vocab-sharded lm_head). The slot dimension stays
    replicated: the serving mesh's dp is the replica axis, not a batch axis.
    """
    B, S, D = q.shape[0], q.shape[1], q.shape[3]
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if new_rows is None:
        new_rows = jnp.full((B,), S, jnp.int32)
    from ...distributed.mesh import per_shard

    return per_shard(
        functools.partial(_paged_decode_attention_impl, scale=float(scale),
                          kernel=kernel),
        (q, k_pages, v_pages, jnp.asarray(block_tables, jnp.int32),
         _norm_lengths(lengths, B), _norm_lengths(new_rows, B)),
        ("..h.", "..H", "..H", "", "", ""), "..h.", head_dim=D)


def _paged_decode_attention_impl(q, k_pages, v_pages, block_tables, lengths,
                                 new_rows, scale=None, kernel="pallas"):
    B, S, Hq, D = q.shape
    BS, Hkv = k_pages.shape[1], k_pages.shape[2] // D
    NB = block_tables.shape[1]
    G = Hq // Hkv
    if scale is None:
        scale = 1.0 / math.sqrt(D)
    if kernel != "pallas" or not paged_kernel_takes(Hq, Hkv, D):
        # gather-based reference: pages -> contiguous head-leading dense cache
        def dense(pages):
            return (pages[block_tables]              # [B, NB, BS, Hkv*D]
                    .reshape(B, NB * BS, Hkv, D).swapaxes(1, 2))

        return decode_attention_xla(q, dense(k_pages), dense(v_pages),
                                    lengths, scale)
    hps, pps = paged_tiling(Hkv, NB, BS, D, S * G, k_pages.dtype.itemsize)
    return _paged_pallas(
        q, k_pages, v_pages, block_tables, lengths,
        paged_walk_blocks(lengths, new_rows, pps * BS).astype(jnp.int32),
        jnp.minimum(paged_walk_blocks(lengths, new_rows, BS),
                    NB).astype(jnp.int32),
        scale=float(scale), hps=hps, pps=pps, interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("scale", "hps", "pps", "interpret"))
def _paged_pallas(q, k_pages, v_pages, block_tables, lengths, trips, pages, *,
                  scale, hps, pps, interpret):
    """The paged kernel's call, given each slot's trip count and live pages.
    A jit of its own: every layer of a step program calls it with the same
    shapes, so the kernel is traced and lowered to Mosaic ONCE a program
    (36 layers x 0.4 s each otherwise, which `setup_s` would pay at every
    start, compile cache or not); XLA inlines the calls, one Mosaic
    instruction a layer. The pools go in as they are: no reshape, no copy."""
    B, S, Hq, D = q.shape
    BS, Hkv = k_pages.shape[1], k_pages.shape[2] // D
    G = Hq // Hkv
    sg = S * G
    R = _heads_per_group(D)
    J, gps = Hkv // R, hps // R
    qr = _q_rows(q.astype(k_pages.dtype), Hkv, G).reshape(B, J, R, sg, D)
    if R > 1:
        # block-diagonal q: row p*sg + i of group j = query row i of head
        # j*R + p in that head's lanes, zeros in its neighbours'
        qr = jnp.einsum("pr,bjpid->bjpird", jnp.eye(R, dtype=qr.dtype), qr)
    qr = qr.reshape(B, J, R * sg, R * D)
    kernel_fn = functools.partial(_paged_kernel, scale=scale, block_size=BS,
                                  g=G, gps=gps, pps=pps, heads=R, d=D)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,      # block_tables, lengths, trips, pages
        grid=(B, J // gps),
        in_specs=[
            pl.BlockSpec((1, gps, R * sg, R * D),
                         lambda b, h, *_: (b, h, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((1, gps, R, sg, D),
                               lambda b, h, *_: (b, h, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, pps * BS, gps * R * D), k_pages.dtype),
            pltpu.VMEM((2, pps * BS, gps * R * D), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.VMEM((gps, R * sg, 1), jnp.float32),
            pltpu.VMEM((gps, R * sg, 1), jnp.float32),
            pltpu.VMEM((gps, R * sg, R * D), jnp.float32),
        ],
    )
    with _no_x64():
        out = pl.pallas_call(
            kernel_fn,
            grid_spec=grid_spec,
            # f64 (the global x64 flag) never crosses the kernel boundary
            out_shape=jax.ShapeDtypeStruct(
                (B, J, R, sg, D),
                q.dtype if q.dtype.itemsize <= 4 else jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=_VMEM_LIMIT),
            interpret=interpret,
            name="_paged_kernel",
        )(block_tables, lengths, trips, pages, qr, k_pages, v_pages)
    out = out.reshape(B, Hkv, S, G, D).transpose(0, 2, 1, 3, 4)
    return out.reshape(B, S, Hq, D).astype(q.dtype)


def paged_cache_update(k_pages, v_pages, k_new, v_new, block_tables,
                       positions):
    """Scatter S new KV rows per request into the page pool at their (page,
    slot) targets. k_pages/v_pages: [P, BS, Hkv*D]; k_new/v_new:
    [B, S, Hkv, D], whose rows are the pool's rows as they come (no
    transpose); `positions` is [B, S] int32 absolute cache positions; rows
    at position >= NB*BS (see `write_positions`) get a poisoned page id so
    XLA's out-of-bounds scatter DROPS them — that is how mixed-length prompts
    padded to a common S skip their padding rows without a mask gather."""
    P, BS, W = k_pages.shape
    B, S, Hkv = k_new.shape[:3]
    NB = block_tables.shape[1]
    pos = jnp.asarray(positions, jnp.int32)
    page = jnp.take_along_axis(block_tables.astype(jnp.int32),
                               jnp.clip(pos // BS, 0, NB - 1), axis=1)
    page = jnp.where(pos < NB * BS, page, jnp.int32(P))
    slot = pos % BS
    k_pages = k_pages.at[page, slot].set(
        k_new.astype(k_pages.dtype).reshape(B, S, W), mode="drop")
    v_pages = v_pages.at[page, slot].set(
        v_new.astype(v_pages.dtype).reshape(B, S, W), mode="drop")
    # keep the pool head-sharded over tp through the scatter so the step
    # programs' committed outputs preserve the serving-mesh layout (no-op
    # without a tp mesh, or where tp does not divide the heads)
    from ...distributed.mesh import constrain, mesh_axis_size

    if Hkv % mesh_axis_size("tp") == 0:
        k_pages = constrain(k_pages, [None, None, "tp"])
        v_pages = constrain(v_pages, [None, None, "tp"])
    return k_pages, v_pages


def valid_new_rows(valid, S):
    """[B] int32 for `paged_decode_attention(new_rows=)` from the step
    programs' [B, S] (or [B, 1]) mask of valid new rows: one past the last
    valid row, 0 for a slot with none; None (no mask) stays None."""
    if valid is None:
        return None
    valid = jnp.broadcast_to(valid, (valid.shape[0], S))
    return jnp.max(jnp.where(valid, jnp.arange(1, S + 1, dtype=jnp.int32), 0),
                   axis=1)


def write_positions(lengths, S, valid=None, capacity=None):
    """[B, S] absolute write positions starting at each request's length;
    rows where `valid` is False are pushed to `capacity` (= NB*BS) so
    paged_cache_update drops them."""
    B = jnp.asarray(lengths).reshape(-1).shape[0]
    pos = _norm_lengths(lengths, B)[:, None] + jnp.arange(S, dtype=jnp.int32)
    if valid is None:
        return pos
    return jnp.where(valid, pos, jnp.int32(capacity))
