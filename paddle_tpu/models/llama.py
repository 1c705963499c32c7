"""LLaMA-family decoder (BASELINE config 5: llama2-7b sharding-stage-3).

The reference repo ships no LLaMA model (PaddleNLP does, out of tree) — this is
the in-repo reference training script target, built TPU-first like models/gpt.py:

- Separate q/k/v/o and gate/up/down projections carrying the LLaMA checkpoint
  naming (q_proj, k_proj, v_proj, o_proj, gate_proj, up_proj, down_proj,
  input_layernorm, post_attention_layernorm) so reference-side LLaMA state
  dicts map by name.
- GQA: num_kv_heads < num_heads; the flash-attention path handles the
  head-group broadcast natively (ops/pallas/flash_attention.py).
- TP via the fleet mpu layers (Column/RowParallelLinear, VocabParallelEmbedding)
  — weights carry 'mp' shardings, GSPMD inserts the ICI collectives.
- ZeRO stage-3 comes from the optimizer wrapper (dist.shard_optimizer with
  ShardingStage3), not from the model: params are dim-0 sharded over dp and
  gathered on use by GSPMD, the reference's group_sharded_stage3.py:904
  gather-on-use semantics expressed as layouts.
"""
from __future__ import annotations

import math

import jax

from ..distributed.fleet.meta_parallel import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..nn import functional as F
from ..nn.functional.cached_attention import (
    AttnCache,
    CacheSpec,
    cache_positions,
    cached_attention,
)
from ..nn.layer import Layer
from ..nn.layer_common import LayerList
from ..nn.layer_conv_norm import RMSNorm
from .generation import GenerationMixin
from .gpt import _shard_seq


class LlamaConfig:
    def __init__(self, vocab_size=32000, hidden_size=4096, num_layers=32,
                 num_heads=32, num_kv_heads=None, intermediate_size=11008,
                 max_position=4096, rms_eps=1e-5, rope_theta=10000.0,
                 recompute=None):
        self.vocab_size = vocab_size
        self.hidden_size = hidden_size
        self.num_layers = num_layers
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.intermediate_size = intermediate_size
        self.max_position = max_position
        self.rms_eps = rms_eps
        self.rope_theta = rope_theta
        if recompute not in (None, "block", "dots"):
            raise ValueError(f"recompute must be None|'block'|'dots', got {recompute!r}")
        self.recompute = recompute


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.num_kv_heads = c.num_kv_heads
        self.head_dim = c.hidden_size // c.num_heads
        self.rope_theta = c.rope_theta
        kv_size = self.num_kv_heads * self.head_dim
        self.q_proj = ColumnParallelLinear(c.hidden_size, c.hidden_size,
                                           has_bias=False, gather_output=False)
        self.k_proj = ColumnParallelLinear(c.hidden_size, kv_size,
                                           has_bias=False, gather_output=False)
        self.v_proj = ColumnParallelLinear(c.hidden_size, kv_size,
                                           has_bias=False, gather_output=False)
        self.o_proj = RowParallelLinear(c.hidden_size, c.hidden_size,
                                        has_bias=False, input_is_parallel=True)

    def forward(self, x, position_ids=None, cache=None, decode_kernel=None):
        B, S = x.shape[0], x.shape[1]
        q = self.q_proj(x).reshape([B, S, self.num_heads, self.head_dim])
        k = self.k_proj(x).reshape([B, S, self.num_kv_heads, self.head_dim])
        v = self.v_proj(x).reshape([B, S, self.num_kv_heads, self.head_dim])
        from ..incubate.nn.functional import fused_rotary_position_embedding

        if cache is not None and position_ids is None:
            position_ids = cache_positions(cache, S)    # absolute
        q, k, _ = fused_rotary_position_embedding(
            q, k, position_ids=position_ids, rotary_emb_base=self.rope_theta)
        if cache is not None:
            # decode: the new rows into the cache (dense or paged), GQA
            # attention over the live prefix WITHOUT expanding K/V to q
            # heads (nn/functional/cached_attention)
            out, new_kv = cached_attention(
                q, k, v, cache, scale=1.0 / math.sqrt(self.head_dim),
                decode_kernel=decode_kernel)
            return self.o_proj(
                out.reshape([B, S, self.num_heads * self.head_dim])), new_kv
        out, _ = F.flash_attention(q, k, v, causal=True, training=self.training)
        return self.o_proj(out.reshape([B, S, self.num_heads * self.head_dim]))


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.gate_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                              has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                            has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(c.intermediate_size, c.hidden_size,
                                           has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(F.silu(self.gate_proj(x)) * self.up_proj(x))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.input_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_eps)
        self.self_attn = LlamaAttention(c)
        self.post_attention_layernorm = RMSNorm(c.hidden_size, epsilon=c.rms_eps)
        self.mlp = LlamaMLP(c)

    def forward(self, x, position_ids=None, cache=None, decode_kernel=None):
        if cache is not None:
            attn_out, new_kv = self.self_attn(
                self.input_layernorm(x), position_ids, cache=cache,
                decode_kernel=decode_kernel)
            x = x + attn_out
            x = x + self.mlp(self.post_attention_layernorm(x))
            return x, new_kv
        x = _shard_seq(x)
        x = x + self.self_attn(self.input_layernorm(x), position_ids)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.config = c
        self.embed_tokens = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(c) for _ in range(c.num_layers)])
        self.norm = RMSNorm(c.hidden_size, epsilon=c.rms_eps)

    def forward(self, input_ids, position_ids=None, caches=None,
                cache_offset=None, decode_kernel=None, paged_tables=None,
                cache_valid=None):
        x = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for blk, (kc, vc) in zip(self.layers, caches):
                cache = AttnCache(kc, vc, cache_offset, paged_tables,
                                  cache_valid)
                x, new_kv = blk(x, position_ids, cache=cache,
                                decode_kernel=decode_kernel)
                new_caches.append(new_kv)
            return self.norm(x), new_caches
        x = _shard_seq(x)
        remat = self.config.recompute if self.training else None
        if remat:
            from ..distributed.fleet.recompute import recompute as _rc

            policy = (jax.checkpoint_policies.checkpoint_dots
                      if remat == "dots" else None)
            for blk in self.layers:
                x = _rc(blk, x, position_ids, policy=policy)
        else:
            for blk in self.layers:
                x = blk(x, position_ids)
        return self.norm(x)


class LlamaForCausalLM(Layer, GenerationMixin):
    """Untied lm_head (LLaMA-2 convention). GQA makes this the model where
    decode caching pays most: kv_heads < heads shrinks cache bytes streamed
    per token by num_heads/num_kv_heads."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        self.lm_head = ColumnParallelLinear(config.hidden_size, config.vocab_size,
                                            has_bias=False)

    def forward(self, input_ids, labels=None, position_ids=None, caches=None,
                cache_offset=None, decode_kernel=None, paged_tables=None,
                cache_valid=None):
        if caches is not None:
            h, new_caches = self.llama(input_ids, position_ids, caches=caches,
                                       cache_offset=cache_offset,
                                       decode_kernel=decode_kernel,
                                       paged_tables=paged_tables,
                                       cache_valid=cache_valid)
            return self.lm_head(h), new_caches, {}  # this model counts nothing
        h = self.llama(input_ids, position_ids)
        logits = self.lm_head(h)
        if labels is not None:
            from ..distributed.fleet.meta_parallel import ParallelCrossEntropy

            per_token = ParallelCrossEntropy()(
                logits.reshape([-1, logits.shape[-1]]), labels.reshape([-1]))
            return logits, per_token.mean()
        return logits

    # ------------------------------------------- GenerationMixin hooks
    def _decode_layer(self):
        return self

    def _decode_cache_spec(self):
        c = self.config
        return CacheSpec.uniform(c.num_layers, c.num_kv_heads,
                                 c.hidden_size // c.num_heads)

    def _decode_validate(self, prompt_len, max_new_tokens):
        pass  # rope positions extrapolate; no learned-position table to overrun


def llama2_7b():
    """LLaMA-2-7B (BASELINE config 5)."""
    return LlamaConfig()


def llama_tiny():
    """CPU-testable shape with real GQA (4 q-heads over 2 kv-heads)."""
    return LlamaConfig(vocab_size=512, hidden_size=64, num_layers=2,
                       num_heads=4, num_kv_heads=2, intermediate_size=128,
                       max_position=128)
