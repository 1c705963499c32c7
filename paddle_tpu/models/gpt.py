"""GPT-style decoder — the flagship LLM reference model.

Reference: the PaddleNLP GPT/ERNIE model family is OUT of the reference repo
(SURVEY.md §7.0) — this is the in-repo reference training script target for the
BASELINE configs 3-5. Built TPU-first:
- TP via fleet mpu layers (VocabParallelEmbedding / Column/RowParallelLinear) whose
  weights carry 'mp' shardings — GSPMD inserts ICI collectives.
- Sequence axis: activations carry a ('dp','sep') batch/seq sharding constraint.
- Attention is paddle-layout [B, S, H, D] flash_attention (Pallas on long seqs).
- RoPE + RMSNorm (pre-norm) or learned positions + LayerNorm (GPT-2 style).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.mesh import get_mesh
from ..nn import functional as F
from ..nn.functional.cached_attention import (
    AttnCache,
    CacheSpec,
    cache_positions,
    cached_attention,
)
from ..nn.layer import Layer
from ..nn.layer_common import Dropout, Embedding, LayerList, Linear
from ..nn.layer_conv_norm import LayerNorm, RMSNorm
from ..ops import apply_op
from ..tensor import Tensor
from .generation import GenerationMixin


class GPTConfig:
    def __init__(self, vocab_size=50304, hidden_size=768, num_layers=12, num_heads=12,
                 num_kv_heads=None, intermediate_size=None, max_position=2048,
                 dropout=0.0, use_rope=True, use_rms_norm=True, use_swiglu=True,
                 tie_embeddings=True, dtype="float32", recompute=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.intermediate_size = intermediate_size or 4 * hidden_size
        self.max_position = max_position
        self.dropout = dropout
        self.use_rope = use_rope
        self.use_rms_norm = use_rms_norm
        self.use_swiglu = use_swiglu
        self.tie_embeddings = tie_embeddings
        self.dtype = dtype
        # None | "block" (save only block inputs) | "dots" (selective: save
        # matmul outputs, recompute elementwise — LLM remat recipe that
        # replaces XLA's unpredictable panic-remat under memory pressure)
        if recompute not in (None, "block", "dots"):
            raise ValueError(
                f"recompute must be None, 'block' or 'dots', got {recompute!r}")
        self.recompute = recompute


def _shard_seq(x):
    """Constrain activations to a ('dp','sep') batch/seq layout when a mesh exists —
    the sequence-parallel (SEP axis) recipe. Targets the stage sub-mesh inside
    pipeline programs via the compute-mesh override."""
    from paddle_tpu.distributed.mesh import constrain

    entries = [None] * x.ndim
    entries[0] = "dp"
    if x.ndim >= 2:
        entries[1] = "sep"
    x._value = constrain(x._value, entries)
    return x


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.num_kv_heads = c.num_kv_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.use_rope = c.use_rope
        q_size = c.hidden_size
        kv_size = self.num_kv_heads * self.head_dim
        self.qkv_proj = ColumnParallelLinear(c.hidden_size, q_size + 2 * kv_size,
                                             has_bias=not c.use_rms_norm,
                                             gather_output=False)
        self.out_proj = RowParallelLinear(c.hidden_size, c.hidden_size,
                                          has_bias=not c.use_rms_norm,
                                          input_is_parallel=True)
        self.dropout = c.dropout

    def forward(self, x, position_ids=None, cache=None, decode_kernel=None):
        B, S = x.shape[0], x.shape[1]
        qkv = self.qkv_proj(x)
        q_size = self.num_heads * self.head_dim
        kv_size = self.num_kv_heads * self.head_dim

        def split_qkv(v):
            q = v[..., :q_size].reshape(B, S, self.num_heads, self.head_dim)
            k = v[..., q_size:q_size + kv_size].reshape(B, S, self.num_kv_heads,
                                                        self.head_dim)
            vv = v[..., q_size + kv_size:].reshape(B, S, self.num_kv_heads,
                                                   self.head_dim)
            return q, k, vv

        q, k, v = apply_op(split_qkv, "split_qkv", qkv)
        if self.use_rope:
            from ..incubate.nn.functional import fused_rotary_position_embedding

            if cache is not None and position_ids is None:
                position_ids = cache_positions(cache, S)    # absolute
            q, k, _ = fused_rotary_position_embedding(q, k, position_ids=position_ids)
        if cache is not None:
            # autoregressive decode: the new rows into the cache, attention
            # over the live prefix (nn/functional/cached_attention)
            out, new_kv = cached_attention(
                q, k, v, cache, scale=1.0 / math.sqrt(self.head_dim),
                decode_kernel=decode_kernel)
            return self.out_proj(out.reshape([B, S, q_size])), new_kv
        out, _ = F.flash_attention(q, k, v, dropout=self.dropout, causal=True,
                                   training=self.training)
        out = out.reshape([B, S, q_size])
        return self.out_proj(out)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.use_swiglu = c.use_swiglu
        inner = c.intermediate_size
        if c.use_swiglu:
            self.gate_up = ColumnParallelLinear(c.hidden_size, 2 * inner,
                                                has_bias=False, gather_output=False)
        else:
            self.fc1 = ColumnParallelLinear(c.hidden_size, inner, has_bias=True,
                                            gather_output=False)
        self.down = RowParallelLinear(inner, c.hidden_size,
                                      has_bias=not c.use_swiglu,
                                      input_is_parallel=True)

    def forward(self, x):
        if self.use_swiglu:
            from ..incubate.nn.functional import swiglu

            return self.down(swiglu(self.gate_up(x)))
        return self.down(F.gelu(self.fc1(x)))


class GPTBlock(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        Norm = RMSNorm if c.use_rms_norm else LayerNorm
        self.ln1 = Norm(c.hidden_size)
        self.attn = GPTAttention(c)
        self.ln2 = Norm(c.hidden_size)
        self.mlp = GPTMLP(c)
        self.dropout = Dropout(c.dropout)

    def forward(self, x, position_ids=None, cache=None, decode_kernel=None):
        if cache is not None:
            attn_out, new_kv = self.attn(self.ln1(x), position_ids,
                                         cache=cache,
                                         decode_kernel=decode_kernel)
            x = x + attn_out
            x = x + self.mlp(self.ln2(x))
            return x, new_kv
        x = _shard_seq(x)
        x = x + self.dropout(self.attn(self.ln1(x), position_ids))
        x = x + self.dropout(self.mlp(self.ln2(x)))
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        if not c.use_rope:
            self.embed_positions = Embedding(c.max_position, c.hidden_size)
        self.blocks = LayerList([GPTBlock(c) for _ in range(c.num_layers)])
        Norm = RMSNorm if c.use_rms_norm else LayerNorm
        self.ln_f = Norm(c.hidden_size)
        if not c.tie_embeddings:
            self.lm_head = ColumnParallelLinear(c.hidden_size, c.vocab_size,
                                                has_bias=False)

    def forward(self, input_ids, position_ids=None, caches=None, cache_offset=None,
                decode_kernel=None, paged_tables=None, cache_valid=None):
        x = self.embed_tokens(input_ids)
        if not self.config.use_rope:
            from ..ops.creation import arange

            if position_ids is None:
                if paged_tables is not None:
                    # per-request offsets; padding rows clip into the table
                    # (their logits/cache writes are dropped downstream)
                    off = (cache_offset._value
                           if isinstance(cache_offset, Tensor) else cache_offset)
                    position_ids = jnp.clip(
                        jnp.asarray(off, jnp.int32)[:, None]
                        + jnp.arange(input_ids.shape[1], dtype=jnp.int32),
                        0, self.config.max_position - 1)
                else:
                    start = cache_offset if cache_offset is not None else 0
                    position_ids = arange(input_ids.shape[1]) + start
            x = x + self.embed_positions(position_ids)
        if caches is not None:
            new_caches = []
            for blk, (kc, vc) in zip(self.blocks, caches):
                cache = AttnCache(kc, vc, cache_offset, paged_tables,
                                  cache_valid)
                x, new_kv = blk(x, position_ids, cache=cache,
                                decode_kernel=decode_kernel)
                new_caches.append(new_kv)
        else:
            x = _shard_seq(x)
            remat = self.config.recompute if self.training else None
            if remat:
                from ..distributed.fleet.recompute import recompute as _rc

                policy = (jax.checkpoint_policies.checkpoint_dots
                          if remat == "dots" else None)
                for blk in self.blocks:
                    x = _rc(blk, x, position_ids, policy=policy)
            else:
                for blk in self.blocks:
                    x = blk(x, position_ids)
        x = self.ln_f(x)
        if self.config.tie_embeddings:
            logits = apply_op(lambda h, w: h @ w.T, "lm_head_tied", x,
                              self.embed_tokens.weight)
        else:
            logits = self.lm_head(x)
        if caches is not None:
            return logits, new_caches, {}   # this model counts nothing
        return logits


class GPTForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.gpt = GPTModel(config)
        self.config = config

    def forward(self, input_ids, labels=None, position_ids=None):
        logits = self.gpt(input_ids, position_ids)
        if labels is not None:
            # vocab-sharded CE: reductions over the (possibly mp-sharded) vocab
            # axis only — never gathers a replicated [B*S, V] (mp_layers.py:744)
            from ..distributed.fleet.meta_parallel import ParallelCrossEntropy

            per_token = ParallelCrossEntropy()(
                logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))
            loss = per_token.mean()
            return logits, loss
        return logits

    # ------------------------------------------- GenerationMixin hooks
    def _decode_layer(self):
        return self.gpt

    def _decode_cache_spec(self):
        c = self.config
        return CacheSpec.uniform(c.num_layers, c.num_kv_heads,
                                 c.hidden_size // c.num_heads)

    def _decode_validate(self, prompt_len, max_new_tokens):
        c = self.config
        if not c.use_rope and prompt_len + max_new_tokens > c.max_position:
            # learned positions: JAX's OOB-gather clamping would silently
            # reuse the last position embedding past the table
            raise ValueError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds max_position ({c.max_position})")


def gpt3_1p3b():
    """GPT-3 1.3B (BASELINE config 4)."""
    return GPTConfig(vocab_size=50304, hidden_size=2048, num_layers=24, num_heads=16,
                     use_rope=False, use_rms_norm=False, use_swiglu=False)


def gpt_350m(max_position=1024):
    """GPT-350M (GPT-medium class, rope / RMSNorm / SwiGLU): the width
    chip_smoke.py brings up."""
    return GPTConfig(vocab_size=50304, hidden_size=1024, num_layers=24,
                     num_heads=16, max_position=max_position, use_rope=True,
                     use_rms_norm=True, use_swiglu=True)


def gpt_tiny():
    return GPTConfig(vocab_size=1024, hidden_size=64, num_layers=2, num_heads=4,
                     max_position=128)


# ---------------------------------------------------------------- pipeline form
class GPTEmbeddingPipe(Layer):
    """Token (+ learned position) embedding as a pipeline stage-0 layer."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        if not c.use_rope:
            self.embed_positions = Embedding(c.max_position, c.hidden_size)

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        if not self.config.use_rope:
            from ..ops.creation import arange

            x = x + self.embed_positions(arange(input_ids.shape[1]))
        return _shard_seq(x)


class GPTNormPipe(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        Norm = RMSNorm if config.use_rms_norm else LayerNorm
        self.ln_f = Norm(config.hidden_size)

    def forward(self, x):
        return self.ln_f(x)


class GPTHeadPipe(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.lm_head = ColumnParallelLinear(
            config.hidden_size, config.vocab_size, has_bias=False)

    def forward(self, x):
        return self.lm_head(x)


def _tied_lm_head(embed_layer: GPTEmbeddingPipe, x):
    return apply_op(lambda h, w: h @ w.T, "lm_head_tied", x,
                    embed_layer.embed_tokens.weight)


def gpt_causal_lm_loss(logits, labels):
    logits = logits if isinstance(logits, Tensor) else Tensor(logits)
    return F.cross_entropy(
        logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))


def gpt_pipeline(config: GPTConfig, num_stages: int, loss_fn=None, **pp_kwargs):
    """GPTForCausalLM as a PipelineLayer (BASELINE config 4: GPT-3 DP+MP+PP).
    Tied embeddings become a SharedLayerDesc spanning the first and last stage
    (reference pp_layers.py:77); each GPTBlock is one LayerDesc so SegmentLayers
    can balance stages."""
    from ..distributed.fleet.meta_parallel import (
        LayerDesc, PipelineLayer, SharedLayerDesc,
    )

    c = config
    blocks = [LayerDesc(GPTBlock, c) for _ in range(c.num_layers)]
    if c.tie_embeddings:
        descs = (
            [SharedLayerDesc("gpt_embed", GPTEmbeddingPipe, None,
                             "embed_tokens.weight", c)]
            + blocks
            + [LayerDesc(GPTNormPipe, c),
               SharedLayerDesc("gpt_embed", GPTEmbeddingPipe, _tied_lm_head,
                               "embed_tokens.weight", c)]
        )
    else:
        descs = ([LayerDesc(GPTEmbeddingPipe, c)] + blocks
                 + [LayerDesc(GPTNormPipe, c), LayerDesc(GPTHeadPipe, c)])
    return PipelineLayer(descs, num_stages=num_stages,
                         loss_fn=loss_fn or gpt_causal_lm_loss, **pp_kwargs)
