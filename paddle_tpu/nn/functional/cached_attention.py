"""The decode cache's format, and attention over K,V rows kept in it.

The format sits here, below the models that declare it and the serving
layer that builds pools from it: a model's `_decode_cache_spec()` returns a
`CacheSpec` (one `LayerCache` a layer), `inference.kv_cache.PagedKVCache`
makes the arrays, and a layer's attention is handed its share of them in a
call as an `AttnCache`.

`cached_attention` is the ONE attention-with-cache for rows of kind "kv":
positions from the length (`cache_positions`), the new rows written (a
dense slice, or a scatter through the block tables) and the attend call
over the live prefix (`ops/pallas/decode_attention`: the grouped-GQA einsum
or the Pallas kernels, by `decode_kernel`). Dense or paged is told by what
it is handed: an `AttnCache` with `tables` is paged. A model applies its
own rotary embedding first (they differ in base and in having one) and
calls it. Latent rows are their model's mechanism (`models/dots3.py`).
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from ...ops import apply_op
from ...tensor import Tensor

__all__ = ["LayerCache", "CacheSpec", "AttnCache", "cache_positions",
           "cached_attention"]


@dataclasses.dataclass(frozen=True)
class LayerCache:
    """What one layer keeps of a token, and for how long.

    kind "kv": a K and a V row of [heads, head_dim] each, in pages a request
    reaches through its block table (GPT, LLaMA). kind "latent": ONE row of
    `row` numbers shared by all heads (latent attention: the compressed
    key/value beside the rotary key) and, where `index_row` > 0, the
    indexer's key of that many numbers in a second array of the same pages.
    `window` None keeps every row, in pages; a number keeps a slot's last
    `window` rows and what one launch writes, in a ring of its own per slot
    that needs no table: position t lives in ring row t mod the ring."""
    kind: str = "kv"
    heads: int = 0
    head_dim: int = 0
    row: int = 0
    index_row: int = 0
    window: int | None = None

    def row_numbers(self) -> int:
        """Numbers a token leaves in this layer."""
        if self.kind == "kv":
            return 2 * self.heads * self.head_dim
        return self.row + self.index_row


@dataclasses.dataclass(frozen=True)
class CacheSpec:
    """A model's decode cache: one `LayerCache` a layer. The ONE object the
    pool is built from (`PagedKVCache.for_model`) and the residency plan
    counts (`analysis/hbm.py`). A model whose layers are all of kind "kv"
    and alike unpacks as a triple: `layers, kv_heads, head_dim = spec`."""
    layers: tuple

    @classmethod
    def uniform(cls, num_layers, num_kv_heads, head_dim):
        one = LayerCache("kv", int(num_kv_heads), int(head_dim))
        return cls((one,) * int(num_layers))

    def is_uniform_kv(self) -> bool:
        first = self.layers[0]
        return first.kind == "kv" and first.window is None and all(
            c == first for c in self.layers)

    def kv_triple(self):
        if not self.is_uniform_kv():
            raise TypeError("this cache is not (layers, kv_heads, head_dim): "
                            f"{sorted({c.kind for c in self.layers})} rows")
        first = self.layers[0]
        return len(self.layers), first.heads, first.head_dim

    def __iter__(self):
        return iter(self.kv_triple())

    def signature_head(self):
        """The model's part of a pool signature: (layers, kv_heads,
        head_dim) where that says it all, else (spec, 0, 0)."""
        return self.kv_triple() if self.is_uniform_kv() else (self, 0, 0)

    def ring_rows(self, cache, block_size, launch_rows) -> int:
        """Rows of a window layer's ring: the window, one launch's rows and
        a page to spare, in whole pages."""
        pages = -(-(cache.window + int(launch_rows)) // block_size) + 1
        return pages * int(block_size)

    def block_bytes(self, block_size, itemsize) -> int:
        """Bytes one page costs over the layers that keep every row."""
        return int(block_size) * int(itemsize) * sum(
            c.row_numbers() for c in self.layers if c.window is None)

    def window_bytes(self, block_size, itemsize, slots, launch_rows) -> int:
        """Bytes of the window layers' rings, all slots."""
        return int(slots) * int(itemsize) * sum(
            c.row_numbers() * self.ring_rows(c, block_size, launch_rows)
            for c in self.layers if c.window is not None)


class AttnCache(NamedTuple):
    """What a layer's attention is handed of its cache in one call: the
    layer's pair of arrays (K and V pages `[P, BS, Hkv*D]` or dense caches
    `[B, Hkv, T, D]`; a latent layer's rows beside its indexer's keys, or
    its ring and None), the rows present before the call (`length`: `[B]`
    where paged, one number where dense), and, where the rows live in
    pages, the block `tables` `[B, NB]` and which of the call's rows are a
    token (`valid` `[B, S]` bool, None: all). `tables` None says dense."""
    first: Any
    second: Any
    length: Any = None
    tables: Any = None
    valid: Any = None


def cache_positions(cache: AttnCache, S):
    """Absolute positions of a call's `S` new rows, from the cache's length:
    `[B, S]` where paged (a length a request), `[S]` where dense."""
    if cache.tables is not None:
        ln = (cache.length._value if isinstance(cache.length, Tensor)
              else cache.length)
        return (jnp.asarray(ln, jnp.int32)[:, None]
                + jnp.arange(S, dtype=jnp.int32)[None, :])
    from ...ops.creation import arange

    return arange(S) + cache.length


def cached_attention(q, k, v, cache: AttnCache, *, scale, decode_kernel=None):
    """q `[B, S, H, D]`, new rows k, v `[B, S, Hkv, D]` (already rotated):
    write the rows into the cache at the length on, attend q over the live
    prefix without expanding K,V to q's heads. Returns (out `[B, S, H, D]`,
    (k_cache, v_cache) after the write). `decode_kernel` None: "pallas"
    where paged, "xla" where dense."""
    from ...ops.pallas import decode_attention as da

    S = q.shape[1]
    paged = cache.tables is not None
    kernel = decode_kernel or ("pallas" if paged else "xla")
    if paged:
        def attend_paged(qv, kv, vv, kp, vp, tbl, ln, vld):
            ln = jnp.asarray(ln, jnp.int32)
            capacity = tbl.shape[1] * kp.shape[1]
            pos = da.write_positions(ln, S, valid=vld, capacity=capacity)
            kp, vp = da.paged_cache_update(kp, vp, kv, vv, tbl, pos)
            out = da.paged_decode_attention(
                qv, kp, vp, tbl, ln, scale=scale, kernel=kernel,
                new_rows=da.valid_new_rows(vld, S))
            return out, kp, vp

        out, k_cache, v_cache = apply_op(
            attend_paged, "paged_decode_attention", q, k, v, cache.first,
            cache.second, cache.tables, cache.length, cache.valid, nout=3)
        return out, (k_cache, v_cache)

    def attend(qv, kv, vv, kc, vc, ln):
        ln = ln.astype(jnp.int32) if hasattr(ln, "astype") else jnp.int32(ln)
        zero = jnp.int32(0)
        # caches are head-leading [B, Hkv, T, D] (the decode kernel's
        # DMA-contiguous layout); only the NEW rows transpose, S=1 at decode
        kc = jax.lax.dynamic_update_slice(
            kc, jnp.swapaxes(kv, 1, 2).astype(kc.dtype),
            (zero, zero, ln, zero))
        vc = jax.lax.dynamic_update_slice(
            vc, jnp.swapaxes(vv, 1, 2).astype(vc.dtype),
            (zero, zero, ln, zero))
        out = da.decode_attention(qv, kc, vc, ln, scale=scale, kernel=kernel)
        return out, kc, vc

    out, k_cache, v_cache = apply_op(
        attend, "decode_attention", q, k, v, cache.first, cache.second,
        cache.length, nout=3)
    return out, (k_cache, v_cache)
