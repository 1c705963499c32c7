"""Context parallelism: ring attention + Ulysses over the 'sep' mesh axis.

Reference capability row (SURVEY.md §2.5 CP): the reference repo has no ring
attention / Ulysses implementation — long context there = the SEP topology axis
(fleet/base/topology.py:199) + SegmentParallel wrapper
(fleet/meta_parallel/segment_parallel.py:26) + sequence-parallel utils
(fleet/utils/sequence_parallel_utils.py:85-137). On TPU these become native
algorithms over ICI:

- **Ring attention** (`ring_attention`): K/V shards rotate around the sep ring
  via `lax.ppermute` while each device holds its Q shard; softmax is combined
  online (running max / sum), so the full [S, S] score matrix never exists and
  per-device sequence length is S/sep — this also lifts the Pallas kernel's
  K/V-in-VMEM cap (ops/pallas/flash_attention.py) past S≈8K.
- **Ulysses** (`ulysses_attention`): all_to_all swaps the sequence shard for a
  head shard ([B, S/n, H, D] → [B, S, H/n, D]), attention runs over the full
  sequence with 1/n of the heads (the Pallas flash kernel applies), and a
  second all_to_all restores the sequence layout.

Both are pure traceable collectives: `jax.grad` differentiates through them
(ppermute/all_to_all have transpose rules), so there is no hand-written
backward ring.

All functions take paddle flash-attention layout [B, S_local, H, D] and must be
called inside a trace where `axis_name` is a manual (shard_map) mesh axis.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "ring_attention",
    "ulysses_attention",
    "split_sequence",
    "RingFlashAttention",
    "SegmentParallel",
]


def _axis_size(axis_name) -> int:
    # psum of a python int over a named axis constant-folds to the static size
    return jax.lax.psum(1, axis_name)


def _bhsd(x):
    return jnp.swapaxes(x, 1, 2)  # [B,S,H,D] <-> [B,H,S,D]


def _broadcast_kv(qh, kh, vh):
    if kh.shape[1] != qh.shape[1]:
        rep = qh.shape[1] // kh.shape[1]
        kh = jnp.repeat(kh, rep, axis=1)
        vh = jnp.repeat(vh, rep, axis=1)
    return kh, vh


def ring_attention(q, k, v, axis_name="sep", causal=False, scale=None):
    """Blockwise ring attention over a sequence-sharded axis.

    q/k/v: [B, S_local, H, D] — the local sequence shard of each device, laid
    out so that device i on `axis_name` holds global positions
    [i*S_local, (i+1)*S_local). Returns the local output shard, same shape.

    Each of the `n` ring steps computes scores of the resident Q block against
    the currently-held K/V block (origin tracked per step for global causal
    masking), accumulating with the online-softmax recurrence; K/V then rotate
    one hop along the ring (device i receives from i+1, so step t holds origin
    (i+t) mod n).
    """
    n = _axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    b, s_loc, h, d = q.shape
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    scale = jnp.float32(scale)

    qh = _bhsd(q).astype(jnp.float32)
    kh, vh = _broadcast_kv(qh, _bhsd(k).astype(jnp.float32),
                           _bhsd(v).astype(jnp.float32))

    neg = jnp.float32(jnp.finfo(jnp.float32).min)
    o = jnp.zeros_like(qh)
    m = jnp.full((b, qh.shape[1], s_loc, 1), neg, jnp.float32)
    l = jnp.zeros((b, qh.shape[1], s_loc, 1), jnp.float32)

    rows = me * s_loc + jnp.arange(s_loc)  # global query positions
    # receive from the next rank: src i sends to dst i-1
    perm = [(i, (i - 1) % n) for i in range(n)]

    k_cur, v_cur = kh, vh
    for step in range(n):
        origin = (me + step) % n
        sc = jnp.einsum("bhsd,bhtd->bhst", qh, k_cur,
                        preferred_element_type=jnp.float32) * scale
        if causal:
            cols = origin * s_loc + jnp.arange(s_loc)  # global key positions
            allowed = rows[:, None] >= cols[None, :]
            sc = jnp.where(allowed[None, None], sc, neg)
        m_new = jnp.maximum(m, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_new)
        corr = jnp.exp(m - m_new)
        l = l * corr + jnp.sum(p, axis=-1, keepdims=True)
        o = o * corr + jnp.einsum("bhst,bhtd->bhsd", p, v_cur,
                                  preferred_element_type=jnp.float32)
        m = m_new
        if step + 1 < n:
            k_cur = jax.lax.ppermute(k_cur, axis_name, perm)
            v_cur = jax.lax.ppermute(v_cur, axis_name, perm)

    # under global causal masking every row attends at least to itself, so
    # l > 0; guard anyway for the non-causal fully-masked-degenerate case
    out = o / jnp.maximum(l, jnp.float32(1e-38))
    return _bhsd(out).astype(q.dtype)


def ulysses_attention(q, k, v, axis_name="sep", causal=False, scale=None,
                      attention_fn=None):
    """DeepSpeed-Ulysses style context parallelism: a2a head-split.

    q/k/v: [B, S_local, H, D] sequence shards; H must be divisible by the axis
    size. After the first all_to_all each device holds [B, S, H/n, D] — the
    full sequence for a head subset — so any single-device attention (incl. the
    Pallas flash kernel) applies; a second all_to_all restores [B, S_local, H, D].
    """
    n = _axis_size(axis_name)
    b, s_loc, h, d = q.shape
    if h % n != 0:
        raise ValueError(f"Ulysses needs heads ({h}) divisible by axis size ({n})")
    if scale is None:
        scale = 1.0 / math.sqrt(d)

    def seq_to_head(x):
        # [B, S/n, H, D] -> [B, S, H/n, D]
        return jax.lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    qf, kf, vf = seq_to_head(q), seq_to_head(k), seq_to_head(v)
    if attention_fn is None:
        attention_fn = _local_attention
    out = attention_fn(qf, kf, vf, causal=causal, scale=scale)
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return jax.lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)


def _local_attention(q, k, v, causal, scale):
    """Single-device attention on [B, S, H, D]; Pallas flash kernel when the
    shapes support it on TPU, fused-XLA softmax otherwise."""
    from ..ops.pallas import flash_attention as pfa

    if (jax.default_backend() == "tpu"
            and pfa.supports(tuple(q.shape), tuple(k.shape))):
        return pfa.flash_attention(q, k, v, causal=causal, scale=scale)
    qh = _bhsd(q).astype(jnp.float32)
    kh, vh = _broadcast_kv(qh, _bhsd(k).astype(jnp.float32),
                           _bhsd(v).astype(jnp.float32))
    sc = jnp.einsum("bhsd,bhtd->bhst", qh, kh,
                    preferred_element_type=jnp.float32) * jnp.float32(scale)
    if causal:
        s, t = sc.shape[-2], sc.shape[-1]
        mask = jnp.tril(jnp.ones((s, t), bool), k=t - s)
        sc = jnp.where(mask, sc, jnp.finfo(jnp.float32).min)
    p = jax.nn.softmax(sc, axis=-1)
    out = jnp.einsum("bhst,bhtd->bhsd", p, vh,
                     preferred_element_type=jnp.float32)
    return _bhsd(out).astype(q.dtype)


def split_sequence(x, axis_name="sep", seq_dim=1):
    """Take this device's sequence shard of a replicated array (the entry point
    for feeding a sequence-parallel region inside shard_map)."""
    n = _axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    s = x.shape[seq_dim]
    if s % n != 0:
        raise ValueError(f"sequence length {s} not divisible by sep={n}")
    chunk = s // n
    return jax.lax.dynamic_slice_in_dim(x, me * chunk, chunk, axis=seq_dim)


class RingFlashAttention:
    """Callable facade matching the reference's attention-module plug points:
    constructed with (axis_name, causal), called with paddle-layout tensors."""

    def __init__(self, axis_name="sep", causal=True, scale=None):
        self.axis_name = axis_name
        self.causal = causal
        self.scale = scale

    def __call__(self, q, k, v):
        from ..tensor import Tensor

        vals = [t._value if isinstance(t, Tensor) else t for t in (q, k, v)]
        out = ring_attention(*vals, axis_name=self.axis_name, causal=self.causal,
                             scale=self.scale)
        return Tensor(out) if isinstance(q, Tensor) else out


class SegmentParallel:
    """Reference fleet/meta_parallel/segment_parallel.py:26 — model wrapper for
    the sep axis. TPU-native: the wrapper only records the axis; sequence
    sharding itself is carried by GSPMD constraints (models annotate activations
    with Shard on the seq dim) and attention goes through ring/Ulysses above.
    Gradient sync over fused dp-sep groups is GSPMD's job once activations are
    sep-sharded, so no Reducer is needed."""

    def __init__(self, layers, hcg=None, strategy=None, axis_name="sep"):
        self._layers = layers
        self._hcg = hcg
        self.axis_name = axis_name

    def __getattr__(self, name):
        return getattr(self._layers, name)

    def __call__(self, *args, **kwargs):
        return self._layers(*args, **kwargs)

    def forward(self, *args, **kwargs):
        return self._layers(*args, **kwargs)
