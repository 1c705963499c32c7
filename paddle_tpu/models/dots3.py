"""dots3-note: a decoder with latent attention of two kinds and an expert
layer of which a chip holds a share. Text path only (no vision tower, no
audio encoder, no multi-token-prediction module); inference only: the
forward runs on raw values, off the tape.

Block (pre-norm, RMSNorm): x + Attn(N1(x)), then y + FFN(N2(y)).

Latent attention (DeepSeek-V2's MLA): a token leaves ONE row for all heads,
the normed compressed key/value `c_kv` beside the rotary key `k_rope`. With
`apply_mla_qkv_lora_rescale` the normed low-rank paths `c_q`, `c_kv` are
scaled by sqrt(hidden / rank). The heads' outputs are gated head-wise,
o_i * sigmoid(u W_g)_i, before the output projection.

  full layers     rows of kv_lora_rank + rope numbers, kept for the whole
                  sequence in pages; a learned indexer (DeepSeek sparse
                  attention: I(t, s) = sum_j w_tj relu(qI_tj . kI_s) over
                  `index_n_heads` heads of `index_head_dim`) keeps the
                  `index_topk` best keys s <= t a query; its key is a second
                  row of the same pages.
  window layers   their own ranks and head sizes, no indexer; token t
                  attends t - window < s <= t, so a slot keeps a ring of the
                  last rows and no pages (`LayerCache`, under `nn/functional`).

Two forms of one attention. Many queries (a prefill chunk, a whole
sequence): the selection is a MASK inside blocked attention over the slot's
whole context, keys and values expanded from the latent rows a block at a
time. One query (a decode step): the `index_topk` chosen rows are GATHERED
and attended in the latent space (the up-projections absorbed into the query
and the output), so a step reads 2,048 rows a slot and not the context.

Precision: weights, the layers' operands and the pools in the model's dtype
(bfloat16 when served); the residual stream and the norms in float32; and
the INDEXER in float32 from the normed stream on (its projections, its own
low-rank query, its scores at `highest`; only its keys in the pool are the
pool's dtype): a key's rank at the k-th decides whether it is attended at
all, and with bfloat16 scores a twentieth of the chosen keys change.

Feed-forward: the first `first_k_dense_replace` layers a dense SwiGLU, the
others `HeldExperts` (this chip's `n_routed_experts` of the published count,
from `ep_rank * n_routed_experts` on, routed over all) plus a shared expert.
The embedding and the head hold `vocab_size` rows: a slice of the published
vocabulary is a smaller vocabulary.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.held_experts import (
    HeldExperts,
    launch_counts,
)
from ..nn import initializer as I
from ..nn.functional.cached_attention import AttnCache, CacheSpec, LayerCache
from ..nn.layer import Layer
from ..nn.layer_common import LayerList
from ..tensor import Tensor
from .generation import GenerationMixin

__all__ = ["Dots3Config", "Dots3ForCausalLM", "Dots3Model", "dots3_tiny"]

KEY_BLOCK = 2048        # keys a block of the many-query form
INDEX_HEAD_GROUP = 16   # indexer heads scored at once
CHUNK_LANES = 2         # slots a chunk launch carries through the layers at once
NEG = -1e30             # a masked score: finite, so a row of none is no NaN
HIGHEST = jax.lax.Precision.HIGHEST


class Dots3Config:
    """The language model's keys of the published `config.json`, under their
    own names, plus the share: `n_routed_experts` experts HELD here of
    `published_n_routed_experts`, from `ep_rank * n_routed_experts` on;
    `vocab_size` rows of the vocabulary held."""

    def __init__(self, vocab_size=152064, hidden_size=5120,
                 num_hidden_layers=46, layer_types=None,
                 first_k_dense_replace=1, intermediate_size=13824,
                 moe_intermediate_size=1536, n_routed_experts=256,
                 published_n_routed_experts=None, ep_rank=0,
                 n_shared_experts=1, num_experts_per_tok=8,
                 norm_topk_prob=True, routed_scaling_factor=1.0,
                 rms_norm_eps=1e-5, apply_mla_qkv_lora_rescale=True,
                 num_attention_heads=128, q_lora_rank=1024, kv_lora_rank=512,
                 qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
                 rope_theta=80000000, index_n_heads=64, index_head_dim=128,
                 index_topk=2048, index_norm_eps=1e-6,
                 swa_num_attention_heads=64, swa_q_lora_rank=1024,
                 swa_kv_lora_rank=1024, swa_qk_nope_head_dim=192,
                 swa_qk_rope_head_dim=64, swa_v_head_dim=128,
                 swa_rope_theta=50000, sliding_window_size=513,
                 dtype="bfloat16", **_unused):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_hidden_layers = num_hidden_layers
        self.layer_types = list(layer_types or (
            ["full_attention"] * num_hidden_layers))[:num_hidden_layers]
        self.first_k_dense_replace = first_k_dense_replace
        self.intermediate_size = intermediate_size
        self.moe_intermediate_size = moe_intermediate_size
        self.n_routed_experts = n_routed_experts
        self.published_n_routed_experts = (published_n_routed_experts
                                           or n_routed_experts)
        self.ep_rank = ep_rank
        self.n_shared_experts = n_shared_experts
        self.num_experts_per_tok = num_experts_per_tok
        self.norm_topk_prob = norm_topk_prob
        self.routed_scaling_factor = routed_scaling_factor
        self.rms_norm_eps = rms_norm_eps
        self.apply_mla_qkv_lora_rescale = apply_mla_qkv_lora_rescale
        self.index_n_heads = index_n_heads
        self.index_head_dim = index_head_dim
        self.index_topk = index_topk
        self.index_norm_eps = index_norm_eps
        self.sliding_window_size = sliding_window_size
        self.full = dict(heads=num_attention_heads, q_rank=q_lora_rank,
                         kv_rank=kv_lora_rank, nope=qk_nope_head_dim,
                         rope=qk_rope_head_dim, v=v_head_dim,
                         theta=rope_theta)
        self.window = dict(heads=swa_num_attention_heads,
                           q_rank=swa_q_lora_rank, kv_rank=swa_kv_lora_rank,
                           nope=swa_qk_nope_head_dim,
                           rope=swa_qk_rope_head_dim, v=swa_v_head_dim,
                           theta=swa_rope_theta)
        self.dtype = dtype

    def is_full(self, index):
        return self.layer_types[index] == "full_attention"


def _rms_norm(x, w, eps):
    """In float32, whatever comes in; the result in the gain's precision
    (the residual stream is float32, the layers' operands the weights')."""
    x32 = x.astype(jnp.float32)
    x32 = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                              + eps)
    return (x32 * w.astype(jnp.float32)).astype(w.dtype)


def _layer_norm(x, w, b, eps):
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mean), -1, keepdims=True)
    out = (x32 - mean) * jax.lax.rsqrt(var + eps)
    return (out * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(
        x.dtype)


def _rope(t, pos, theta):
    """t: [B, C, ..., D] at positions pos [B, C]: adjacent dims (2i, 2i + 1)
    are one pair, turned by pos * theta ** (-2i / D)."""
    dim = t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = pos.astype(jnp.float32)[..., None] * inv            # [B, C, D/2]
    angle = angle.reshape(pos.shape + (1,) * (t.ndim - 3) + (dim // 2,))
    t32 = t.astype(jnp.float32)
    even, odd = t32[..., 0::2], t32[..., 1::2]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.stack([even * cos - odd * sin, even * sin + odd * cos],
                     -1).reshape(t.shape).astype(t.dtype)


def _swiglu(x, gate_up, down):
    gate, up = jnp.split(jnp.dot(x, gate_up), 2, axis=-1)
    return jnp.dot(jax.nn.silu(gate) * up, down)


def kth_largest_mask(score, k):
    """score: [C, K] float32 -> bool [C, K]: each row's k largest (all of
    them where K <= k), found without a sort: the k-th largest value's bits,
    one at a time from the top (32 counting passes), on the order-keeping
    image of a float in the unsigned integers."""
    if score.shape[-1] <= k:
        return jnp.ones(score.shape, bool)
    bits = jax.lax.bitcast_convert_type(score, jnp.uint32)
    keys = jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))

    def one_bit(i, kth):
        trial = kth | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= trial[:, None], -1) >= k
        return jnp.where(enough, trial, kth)
    kth = jax.lax.fori_loop(0, 32, one_bit,
                            jnp.zeros(score.shape[:1], jnp.uint32))
    return keys >= kth[:, None]


def index_scores(q_index, w_index, k_index, scale):
    """One slot's indexer scores. q_index: [C, J, D]; w_index: [C, J];
    k_index: [K, D] -> [C, K] float32, the heads scored a group at a time."""
    heads = q_index.shape[1]
    group = math.gcd(heads, INDEX_HEAD_GROUP)
    q = q_index.reshape(q_index.shape[0], heads // group, group, -1)
    w = w_index.reshape(w_index.shape[0], heads // group, group)

    def one_group(total, qw):
        qg, wg = qw                                  # [C, group, D], [C, group]
        # float32 from the products on: a key's rank near the k-th decides
        # whether it is attended at all
        dots = jnp.einsum("cjd,kd->cjk", qg, k_index.astype(jnp.float32),
                          precision=HIGHEST)
        return total + jnp.einsum("cjk,cj->ck", jax.nn.relu(dots), wg,
                                  precision=HIGHEST), None
    total, _ = jax.lax.scan(
        one_group, jnp.zeros((q_index.shape[0], k_index.shape[0]),
                             jnp.float32),
        (jnp.swapaxes(q, 0, 1), jnp.swapaxes(w, 0, 1)))
    return total * scale


def attend_expanded(q_nope, q_rope, rows, allowed, kv_b, d, scale):
    """The many-query form, one slot: q_nope [C, H, nope], q_rope [C, H,
    rope], the context's latent rows [K, kv_rank + rope], allowed [C, K]
    bool -> [C, H, v]. Keys and values are expanded from the rows a block at
    a time; the softmax runs over the blocks online, in float32."""
    queries, heads = q_nope.shape[:2]
    keys = rows.shape[0]
    block = min(KEY_BLOCK, keys)
    pad = -keys % block
    rows = jnp.pad(rows, [(0, pad), (0, 0)])
    allowed = jnp.pad(allowed, [(0, 0), (0, pad)])
    rows = rows.reshape(-1, block, rows.shape[-1])
    allowed = jnp.swapaxes(allowed.reshape(queries, -1, block), 0, 1)

    def one_block(carry, xs):
        out, top, total = carry
        r, ok = xs                                      # [block, row], [C, block]
        kvb = jnp.dot(r[:, :d["kv_rank"]], kv_b).reshape(
            block, heads, d["nope"] + d["v"])
        s = jnp.einsum("chn,khn->hck", q_nope, kvb[..., :d["nope"]],
                       preferred_element_type=jnp.float32)
        s = s + jnp.einsum("chr,kr->hck", q_rope, r[:, d["kv_rank"]:],
                           preferred_element_type=jnp.float32)
        s = jnp.where(ok[None], s * scale, NEG)
        new_top = jnp.maximum(top, jnp.max(s, -1))
        p = jnp.where(ok[None], jnp.exp(s - new_top[..., None]), 0.0)
        fade = jnp.exp(top - new_top)
        out = out * fade[..., None] + jnp.einsum(
            "hck,khv->hcv", p.astype(rows.dtype), kvb[..., d["nope"]:],
            preferred_element_type=jnp.float32)
        return (out, new_top, total * fade + jnp.sum(p, -1)), None
    (out, _, total), _ = jax.lax.scan(
        one_block,
        (jnp.zeros((heads, queries, d["v"]), jnp.float32),
         jnp.full((heads, queries), NEG, jnp.float32),
         jnp.zeros((heads, queries), jnp.float32)), (rows, allowed))
    out = out / jnp.maximum(total, 1e-30)[..., None]
    return jnp.swapaxes(out, 0, 1).astype(q_nope.dtype)


def attend_latent(q_nope, q_rope, rows, allowed, kv_b, d, scale):
    """The one-query form, all slots: q_nope [B, H, nope], q_rope [B, H,
    rope], rows [B, n, kv_rank + rope] (gathered, or a ring), allowed [B, n]
    -> [B, H, v], in the latent space: the key's up-projection absorbed
    into the query, the value's applied to the output."""
    heads = q_nope.shape[1]
    up = kv_b.reshape(d["kv_rank"], heads, d["nope"] + d["v"])
    latent, k_rope = rows[..., :d["kv_rank"]], rows[..., d["kv_rank"]:]
    q_lat = jnp.einsum("bhn,chn->bhc", q_nope, up[..., :d["nope"]])
    s = jnp.einsum("bhc,bkc->bhk", q_lat, latent,
                   preferred_element_type=jnp.float32)
    s = s + jnp.einsum("bhr,bkr->bhk", q_rope, k_rope,
                       preferred_element_type=jnp.float32)
    s = jnp.where(allowed[:, None], s * scale, NEG)
    p = jnp.where(allowed[:, None],
                  jnp.exp(s - jnp.max(s, -1, keepdims=True)), 0.0)
    p = p / jnp.maximum(jnp.sum(p, -1, keepdims=True), 1e-30)
    o_lat = jnp.einsum("bhk,bkc->bhc", p.astype(rows.dtype), latent)
    return jnp.einsum("bhc,chv->bhv", o_lat, up[..., d["nope"]:])


class Dots3Attention(Layer):
    """Latent attention of one kind (`full`: with the indexer; else the
    window). `forward(u, pos, valid, cache)` -> (out, cache): u [B, C, h]
    normed, pos [B, C] int32, valid [B, C] bool (rows that are no token
    write nothing), cache None (the keys are this call's own rows) or an
    `AttnCache`: the layer's pair of pool arrays with the block tables."""

    def __init__(self, config: Dots3Config, full: bool):
        super().__init__()
        c, self.full = config, full
        self.config = c
        self.d = d = c.full if full else c.window
        h, heads, dt = c.hidden_size, d["heads"], c.dtype
        init = I.Normal(0.0, 0.02)

        def make(*shape, gain=False):
            return self.create_parameter(
                list(shape), dtype=dt,
                default_initializer=I.Constant(1.0) if gain else init)
        self.q_a = make(h, d["q_rank"])
        self.q_a_norm = make(d["q_rank"], gain=True)
        self.q_b = make(d["q_rank"], heads * (d["nope"] + d["rope"]))
        self.kv_a = make(h, d["kv_rank"] + d["rope"])
        self.kv_a_norm = make(d["kv_rank"], gain=True)
        self.kv_b = make(d["kv_rank"], heads * (d["nope"] + d["v"]))
        self.o = make(heads * d["v"], h)
        self.gate = make(h, heads)
        if full:
            self.idx_q = make(d["q_rank"], c.index_n_heads * c.index_head_dim)
            self.idx_k = make(h, c.index_head_dim)
            self.idx_k_norm_w = make(c.index_head_dim, gain=True)
            self.idx_k_norm_b = self.create_parameter(
                [c.index_head_dim], dtype=dt,
                default_initializer=I.Constant(0.0))
            self.idx_w = make(h, c.index_n_heads)

    def cache_kind(self):
        d, c = self.d, self.config
        if self.full:
            return LayerCache("latent", row=d["kv_rank"] + d["rope"],
                              index_row=c.index_head_dim)
        return LayerCache("latent", row=d["kv_rank"] + d["rope"],
                          window=c.sliding_window_size)

    # ------------------------------------------------------------ pieces
    def _project(self, u, pos):
        """(q_nope [B, C, H, nope], q_rope, the token's row [B, C, kv_rank +
        rope])."""
        c, d = self.config, self.d
        batch, chunk, h = u.shape
        c_q = _rms_norm(jnp.dot(u, self.q_a._value), self.q_a_norm._value,
                        c.rms_norm_eps)
        kv = jnp.dot(u, self.kv_a._value)
        c_kv = _rms_norm(kv[..., :d["kv_rank"]], self.kv_a_norm._value,
                         c.rms_norm_eps)
        if c.apply_mla_qkv_lora_rescale:
            c_q = c_q * math.sqrt(h / d["q_rank"])
            c_kv = c_kv * math.sqrt(h / d["kv_rank"])
        q = jnp.dot(c_q, self.q_b._value).reshape(
            batch, chunk, d["heads"], d["nope"] + d["rope"])
        k_rope = _rope(kv[..., d["kv_rank"]:], pos, d["theta"])
        return (q[..., :d["nope"]], _rope(q[..., d["nope"]:], pos, d["theta"]),
                jnp.concatenate([c_kv, k_rope], -1))

    def _index(self, u32, pos):
        """(qI [B, C, J, D], w [B, C, J], kI [B, C, D]), roped, in FLOAT32
        from the float32 normed input on (its own low-rank query path too):
        a key's rank near the k-th decides whether it is attended at all,
        and bfloat16 scores move a twentieth of the chosen keys."""
        c, d, rope = self.config, self.d, self.d["rope"]
        batch, chunk, h = u32.shape

        def mm(x, w):
            return jnp.dot(x, w._value.astype(jnp.float32), precision=HIGHEST)
        c_q = _rms_norm(mm(u32, self.q_a),
                        self.q_a_norm._value.astype(jnp.float32),
                        c.rms_norm_eps)
        if c.apply_mla_qkv_lora_rescale:
            c_q = c_q * math.sqrt(h / d["q_rank"])
        q = mm(c_q, self.idx_q).reshape(batch, chunk, c.index_n_heads,
                                        c.index_head_dim)
        k = _layer_norm(mm(u32, self.idx_k),
                        self.idx_k_norm_w._value.astype(jnp.float32),
                        self.idx_k_norm_b._value.astype(jnp.float32),
                        c.index_norm_eps)
        theta = d["theta"]
        q = jnp.concatenate([_rope(q[..., :rope], pos, theta),
                             q[..., rope:]], -1)
        k = jnp.concatenate([_rope(k[..., :rope], pos, theta),
                             k[..., rope:]], -1)
        return q, mm(u32, self.idx_w), k

    def _index_scale(self):
        c = self.config
        return 1.0 / math.sqrt(c.index_head_dim * c.index_n_heads)

    def selected(self, q_index, w_index, k_index, pos, key_pos):
        """One slot, many queries: bool [C, K], the keys each query keeps:
        causal, and on a full layer the indexer's `index_topk` best."""
        causal = key_pos[None, :] <= pos[:, None]
        if not self.full:
            return causal & (key_pos[None, :]
                             > pos[:, None] - self.config.sliding_window_size)
        with jax.named_scope("attn.indexer"):
            score = index_scores(q_index, w_index, k_index,
                                 self._index_scale())
            score = jnp.where(causal, score, -jnp.inf)
            return causal & kth_largest_mask(score, self.config.index_topk)

    # ------------------------------------------------------------- forward
    def forward(self, u32, pos, valid, cache=None, slots=None):
        d = self.d
        u = u32.astype(self.q_a._value.dtype)
        batch, chunk, _ = u.shape
        scale = 1.0 / math.sqrt(d["nope"] + d["rope"])
        scope = "attn.latent" if self.full else "attn.window"
        with jax.named_scope(scope):
            q_nope, q_rope, row = self._project(u, pos)
        index = self._index(u32, pos) if self.full else None
        if cache is None:
            out = self._many(q_nope, q_rope, index, pos, scale,
                             lambda b: (row[b], None if index is None
                                        else index[2][b], pos[b]))
            new_cache = None
        elif self.full:
            out, new_cache = self._paged(q_nope, q_rope, row, index, pos,
                                         valid, scale, cache)
        else:
            out, new_cache = self._ring(q_nope, q_rope, row, pos, valid,
                                        scale, cache, slots)
        with jax.named_scope("attn.gate"):
            gate = jax.nn.sigmoid(jnp.dot(u, self.gate._value))
            out = out * gate[..., None].astype(out.dtype)
        out = jnp.dot(out.reshape(batch, chunk, d["heads"] * d["v"]),
                      self.o._value)
        return out, new_cache

    def _many(self, q_nope, q_rope, index, pos, scale, context):
        """The many-query form a slot at a time. `context(b)` gives slot b's
        (latent rows [K, row], indexer keys [K, D] or None, key positions
        [K]; a position below 0 marks a row that holds nothing)."""
        scope = "attn.latent" if self.full else "attn.window"

        def one_slot(b):
            rows, k_index, key_pos = context(b)
            q_index, w_index = ((index[0][b], index[1][b])
                                if self.full else (None, None))
            allowed = self.selected(q_index, w_index, k_index, pos[b],
                                    key_pos) & (key_pos >= 0)[None, :]
            with jax.named_scope(scope):
                return attend_expanded(q_nope[b], q_rope[b], rows, allowed,
                                       self.kv_b._value, self.d, scale)
        return jax.lax.map(one_slot, jnp.arange(pos.shape[0]))

    def _paged(self, q_nope, q_rope, row, index, pos, valid, scale, cache):
        """A full layer over its pages: the new rows are written in place,
        then read back with the context through the block tables."""
        rows_pool, index_pool = cache.first, cache.second
        tables = cache.tables
        pages, block, width = rows_pool.shape
        batch, chunk = pos.shape
        span = tables.shape[1] * block
        page = jnp.take_along_axis(tables, jnp.clip(pos // block, 0,
                                                    tables.shape[1] - 1), 1)
        at = jnp.where(valid & (pos < span), page * block + pos % block,
                       pages * block).reshape(-1)      # past the pool: dropped
        rows_pool = rows_pool.reshape(pages * block, width).at[at].set(
            row.reshape(-1, width).astype(rows_pool.dtype),
            mode="drop").reshape(pages, block, width)
        index_pool = index_pool.reshape(pages * block, -1).at[at].set(
            index[2].reshape(at.shape[0], -1).astype(index_pool.dtype),
            mode="drop").reshape(index_pool.shape)
        key_pos = jnp.arange(span, dtype=jnp.int32)
        topk = min(self.config.index_topk, span)
        if chunk > 1:
            out = self._many(
                q_nope, q_rope, index, pos, scale,
                lambda b: (rows_pool[tables[b]].reshape(span, width),
                           index_pool[tables[b]].reshape(span, -1), key_pos))
            return out, (rows_pool, index_pool)
        # one query a slot: score the slot's keys, gather the chosen rows
        with jax.named_scope("attn.indexer"):
            k_index = index_pool[tables].reshape(batch, span, -1)
            dots = jnp.einsum("bjd,bkd->bjk", index[0][:, 0],
                              k_index.astype(jnp.float32), precision=HIGHEST)
            score = jnp.einsum("bjk,bj->bk", jax.nn.relu(dots),
                               index[1][:, 0], precision=HIGHEST)
            score = score * self._index_scale()
            score = jnp.where(key_pos[None, :] <= pos, score, -jnp.inf)
            best, chosen = jax.lax.top_k(score, topk)
        with jax.named_scope("attn.latent"):
            page = jnp.take_along_axis(tables, chosen // block, 1)
            rows = rows_pool.reshape(pages * block, width)[
                page * block + chosen % block]            # [B, topk, row]
            out = attend_latent(q_nope[:, 0], q_rope[:, 0], rows,
                                best > -jnp.inf, self.kv_b._value, self.d,
                                scale)
        return out[:, None], (rows_pool, index_pool)

    def _ring(self, q_nope, q_rope, row, pos, valid, scale, cache, slots):
        """A window layer over its ring: position t lives in row t mod the
        ring of its slot (`slots` [B]: the slot of each row of the batch;
        None: row b is slot b); the ring holds the window and one launch's
        rows, so what a query of this launch needs is never overwritten by
        it."""
        ring = cache.first
        count, length, width = ring.shape
        batch, chunk = pos.shape
        if chunk + self.config.sliding_window_size - 1 > length:
            raise ValueError(f"a launch of {chunk} rows does not fit a ring "
                             f"of {length} with the window")
        if slots is None:
            slots = jnp.arange(batch, dtype=jnp.int32)
        at = jnp.where(valid, slots[:, None] * length + pos % length,
                       count * length).reshape(-1)
        ring = ring.reshape(count * length, width).at[at].set(
            row.reshape(-1, width).astype(ring.dtype),
            mode="drop").reshape(count, length, width)
        # the newest position of each slot, and with it what each row holds
        last = jnp.max(jnp.where(valid, pos, -1), axis=1)          # [B]
        held = jnp.arange(length, dtype=jnp.int32)[None, :]
        key_pos = last[:, None] - (last[:, None] - held) % length   # [B, R]
        key_pos = jnp.where(last[:, None] >= 0, key_pos, -1)
        if chunk > 1:
            out = self._many(q_nope, q_rope, None, pos, scale,
                             lambda b: (ring[slots[b]], None, key_pos[b]))
            return out, (ring, None)
        with jax.named_scope("attn.window"):
            allowed = ((key_pos >= 0) & (key_pos <= pos)
                       & (key_pos > pos - self.config.sliding_window_size))
            out = attend_latent(q_nope[:, 0], q_rope[:, 0], ring[slots],
                                allowed, self.kv_b._value, self.d, scale)
        return out[:, None], (ring, None)


class Dots3Block(Layer):
    def __init__(self, config: Dots3Config, index: int):
        super().__init__()
        c = config
        self.config = c
        self.dense = index < c.first_k_dense_replace
        h, dt = c.hidden_size, c.dtype
        init = I.Normal(0.0, 0.02)
        self.norm1 = self.create_parameter(
            [h], dtype=dt, default_initializer=I.Constant(1.0))
        self.attn = Dots3Attention(c, c.is_full(index))
        self.norm2 = self.create_parameter(
            [h], dtype=dt, default_initializer=I.Constant(1.0))
        if self.dense:
            self.mlp_gate_up = self.create_parameter(
                [h, 2 * c.intermediate_size], dtype=dt,
                default_initializer=init)
            self.mlp_down = self.create_parameter(
                [c.intermediate_size, h], dtype=dt, default_initializer=init)
        else:
            self.experts = HeldExperts(
                h, c.moe_intermediate_size, held=c.n_routed_experts,
                published=c.published_n_routed_experts,
                first=c.ep_rank * c.n_routed_experts,
                top_k=c.num_experts_per_tok, norm_topk=c.norm_topk_prob,
                scale=c.routed_scaling_factor, dtype=dt)
            shared = c.moe_intermediate_size * c.n_shared_experts
            self.shared_gate_up = self.create_parameter(
                [h, 2 * shared], dtype=dt, default_initializer=init)
            self.shared_down = self.create_parameter(
                [shared, h], dtype=dt, default_initializer=init)

    def feed_forward(self, y, valid=None):
        """y: [N, h] normed -> (what the held experts add, what every chip
        computes alike, the expert layer's counts or None)."""
        if self.dense:
            return None, _swiglu(y, self.mlp_gate_up._value,
                                 self.mlp_down._value), None
        routed, counts = self.experts(y, valid=valid)
        with jax.named_scope("moe.shared"):
            alike = _swiglu(y, self.shared_gate_up._value,
                            self.shared_down._value)
        return routed, alike, counts

    def forward(self, x, pos, valid, cache=None, slots=None):
        eps = self.config.rms_norm_eps
        out, cache = self.attn(
            _rms_norm(x, self.norm1._value.astype(jnp.float32), eps), pos,
            valid, cache, slots)
        # the residual stream is float32: a layer adds a tenth of what the
        # stream holds, and bfloat16 would round an eighth of that away
        x = x + out.astype(jnp.float32)
        batch, chunk, h = x.shape
        y = _rms_norm(x, self.norm2._value, eps).reshape(batch * chunk, h)
        routed, alike, counts = self.feed_forward(y, valid.reshape(-1))
        alike = alike.astype(jnp.float32)
        if routed is not None:
            alike = alike + routed.astype(jnp.float32)
        return x + alike.reshape(batch, chunk, h), cache, counts


class Dots3Model(Layer):
    """The decode layer: `forward(ids, caches=, cache_offset=, paged_tables=,
    cache_valid=, logits_at=)` -> logits, or (logits, new caches, counts)
    with caches. `logits_at` [B]: the head runs over that one position of
    each row (a prefill chunk samples only its last)."""

    def __init__(self, config: Dots3Config):
        super().__init__()
        c = config
        self.config = c
        init = I.Normal(0.0, 0.02)
        self.embed = self.create_parameter(
            [c.vocab_size, c.hidden_size], dtype=c.dtype,
            default_initializer=init)
        self.layers = LayerList([Dots3Block(c, i)
                                 for i in range(c.num_hidden_layers)])
        self.norm_f = self.create_parameter(
            [c.hidden_size], dtype=c.dtype,
            default_initializer=I.Constant(1.0))
        self.lm_head = self.create_parameter(
            [c.hidden_size, c.vocab_size], dtype=c.dtype,
            default_initializer=init)

    def forward(self, input_ids, caches=None, cache_offset=None,
                decode_kernel=None, paged_tables=None, cache_valid=None,
                logits_at=None):
        def raw(t):
            return t._value if isinstance(t, Tensor) else t
        ids = jnp.asarray(raw(input_ids))
        batch, chunk = ids.shape
        if caches is not None and paged_tables is None:
            raise NotImplementedError(
                "latent rows live in pages and rings: serve this model "
                "through the paged step programs, not dense caches")
        start = (jnp.zeros((batch,), jnp.int32) if cache_offset is None
                 else jnp.broadcast_to(jnp.asarray(raw(cache_offset),
                                                   jnp.int32), (batch,)))
        pos = start[:, None] + jnp.arange(chunk, dtype=jnp.int32)[None, :]
        valid = (jnp.ones((batch, chunk), bool) if cache_valid is None
                 else jnp.broadcast_to(raw(cache_valid), (batch, chunk)))
        if logits_at is not None:
            logits_at = jnp.asarray(raw(logits_at), jnp.int32)
        if caches is None:
            return Tensor(self._layers(ids, pos, valid, None, None, None,
                                       logits_at)[0])
        tables = jnp.asarray(raw(paged_tables), jnp.int32)
        pools = [(raw(first), raw(second)) for first, second in caches]
        walk = chunk > 1 and batch > CHUNK_LANES
        logits, pools, stats = (self._in_lanes if walk else self._layers)(
            ids, pos, valid, tables, pools, None, logits_at)
        return Tensor(logits), pools, stats

    def _layers(self, ids, pos, valid, tables, pools, slots, logits_at):
        """Every layer and the head over one batch of rows, on raw values:
        (logits, the pools after the rows were written, the expert layers'
        counts stacked a layer). `slots` [B]: the slot of each row, where a
        row is not its own."""
        x = self.embed._value[ids].astype(jnp.float32)
        new_pools, counts = [], []
        for i, blk in enumerate(self.layers):
            cache = (None if pools is None
                     else AttnCache(*pools[i], tables=tables))
            x, cache, got = blk(x, pos, valid, cache, slots)
            new_pools.append(cache)
            if got is not None:
                counts.append(got)
        with jax.named_scope("head"):
            if logits_at is not None:
                x = jnp.take_along_axis(x, logits_at[:, None, None], axis=1)
            x = _rms_norm(x, self.norm_f._value, self.config.rms_norm_eps)
            logits = jnp.dot(x, self.lm_head._value)
        stats = ({f"moe_{k}": jnp.stack([c[k] for c in counts])
                  for k in counts[0]} if counts else {})
        return logits, new_pools, stats

    def _in_lanes(self, ids, pos, valid, tables, pools, _, logits_at):
        """A chunk launch over many slots: the slots that hold a chunk (a
        valid position) are walked `CHUNK_LANES` at a time through all the
        layers, and the others cost nothing: a launch costs by the groups
        it takes. (The scheduler's default budget of two chunks a tick,
        which it never cuts a chunk to fit, is one group but where two
        prompts' tails leave room for a third slot.)"""
        c = self.config
        batch, chunk = ids.shape
        lanes = CHUNK_LANES
        holds = jnp.any(valid, axis=1)
        order = jnp.argsort(~holds, stable=True).astype(jnp.int32)
        order = jnp.concatenate([order, jnp.full((-batch % lanes,), batch,
                                                 jnp.int32)])
        groups = -(-jnp.sum(holds).astype(jnp.int32) // lanes)
        experts = sum(1 for blk in self.layers if not blk.dense)
        counts = {} if not experts else {
            "moe_expert_tokens": jnp.zeros((experts, c.n_routed_experts),
                                           jnp.int32),
            "moe_elsewhere": jnp.zeros((experts,), jnp.int32),
            "moe_rows_issued": jnp.zeros((experts,), jnp.int32)}
        logits = jnp.zeros((batch, chunk if logits_at is None else 1,
                            c.vocab_size), self.lm_head._value.dtype)

        def one_group(carry):
            g, pools, logits, counts = carry
            slots = jax.lax.dynamic_slice(order, (g * lanes,), (lanes,))
            real = slots < batch          # the last group may not be full
            at = jnp.minimum(slots, batch - 1)
            got, pools, more = self._layers(
                ids[at], pos[at], valid[at] & real[:, None], tables[at],
                pools, at, None if logits_at is None else logits_at[at])
            logits = logits.at[jnp.where(real, slots, batch)].set(
                got, mode="drop")
            return (g + 1, pools, logits,
                    {k: counts[k] + more[k] for k in counts})
        _, pools, logits, counts = jax.lax.while_loop(
            lambda carry: carry[0] < groups, one_group,
            (jnp.int32(0), pools, logits, counts))
        return logits, pools, counts


class Dots3ForCausalLM(Layer, GenerationMixin):
    """Served by `ContinuousGenerateBatchingPredictor` through
    `prefill_chunk` and `decode_step` as the other causal models are."""
    _decode_logits_at = True    # the head runs where a token is sampled

    def __init__(self, config: Dots3Config):
        super().__init__()
        self.model = Dots3Model(config)
        self.config = config

    def forward(self, input_ids):
        return self.model(input_ids)

    # ------------------------------------------- GenerationMixin hooks
    def _decode_layer(self):
        return self.model

    def _decode_cache_spec(self):
        return CacheSpec(tuple(blk.attn.cache_kind()
                               for blk in self.model.layers))

    def _decode_validate(self, prompt_len, max_new_tokens):
        pass    # rotary positions; the pool bounds the length

    def _launch_counts(self, program, stats, positions, kv_cache,
                       table_width, steps=1, holding=0):
        """The expert layers' counts of the launch (`stats`, off the device)
        under the tick ledger's names, and attention's rows by arithmetic
        (`attn_rows_needed`, `attn_rows_read`, `indexer_rows_scored`) beside
        the positions the launch issued. `positions`: the position of every
        real query of the launch; `holding`: the slots that hold a chunk.
        All three count (query, cache row) pairs. Needed: over the layers,
        min(context, index_topk) or min(context, window) rows a real query.
        Read: the rows each query of the batch the program carries is
        attended over (a decode step every slot; a chunk launch the slots
        that hold a chunk, in whole groups of `CHUNK_LANES`, every position
        of the chunk): the many-query form the whole table span (its
        selection is a mask) or the ring, the one-query form the gathered
        `index_topk` rows or the ring. Scored: the indexer's pairs, which
        scores the whole span."""
        import numpy as np

        c = self.config
        counts = {}
        if "moe_expert_tokens" in stats:
            counts = launch_counts(stats["moe_expert_tokens"],
                                   stats["moe_elsewhere"],
                                   stats["moe_rows_issued"])
        context = np.asarray(positions, np.int64) + 1
        span = table_width * kv_cache.block_size
        if program == "decode_step":
            rows, width, launches = kv_cache.slots, 1, steps
        else:
            rows, width, launches = kv_cache.slots, kv_cache.launch_rows, 1
            if width > 1 and rows > CHUNK_LANES:
                rows = -(-holding // CHUNK_LANES) * CHUNK_LANES
        needed = read = scored = 0
        for blk, pool in zip(self.model.layers, kv_cache.k_pages):
            if blk.attn.full:
                needed += int(np.minimum(context, c.index_topk).sum())
                read += launches * rows * width * (
                    min(c.index_topk, span) if width == 1 else span)
                scored += launches * rows * width * span
            else:
                needed += int(np.minimum(context,
                                         c.sliding_window_size).sum())
                read += launches * rows * width * pool.shape[1]
        counts.update(attn_rows_needed=needed, attn_rows_read=read,
                      indexer_rows_scored=scored,
                      issued_positions=launches * rows * width)
        return counts


def dots3_tiny(**over):
    """Every mechanism at a size for CPU tests: a dense full layer, an
    expert full layer and an expert window layer, 8 experts of which a
    share can be held. Eight indexer heads: with two, a query's scores tie
    at 0 (both heads' products under the relu), and a tie at the k-th score
    is where the two forms part: the mask keeps every tied key, the gather
    exactly k."""
    cfg = dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=3,
        layer_types=["full_attention", "full_attention",
                     "sliding_attention"],
        first_k_dense_replace=1, intermediate_size=96,
        moe_intermediate_size=32, n_routed_experts=8, n_shared_experts=1,
        num_experts_per_tok=2, num_attention_heads=4, q_lora_rank=24,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, index_n_heads=8, index_head_dim=8, index_topk=6,
        swa_num_attention_heads=2, swa_q_lora_rank=24, swa_kv_lora_rank=24,
        swa_qk_nope_head_dim=12, swa_qk_rope_head_dim=4, swa_v_head_dim=8,
        sliding_window_size=5, dtype="float32")
    cfg.update(over)
    return Dots3Config(**cfg)
