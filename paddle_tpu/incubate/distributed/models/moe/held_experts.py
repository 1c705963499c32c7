"""HeldExperts: one chip's share of an expert layer.

Where `MoELayer` holds every expert and gives each a padded capacity (tokens
over it are dropped), this layer is TOLD which experts it holds — `held` of
them from number `first` on, of `published` that the router scores — routes
over all of them, and computes what its own experts add for the tokens routed
to them; what the absent experts would add is left out. It is what expert
parallelism asks of a chip; on one chip it runs without its exchange, and no
code stands in for the absent chips.

Grouped products, no capacity and no dropped token: the (token, expert)
assignments held here are sorted by expert and walked in tiles of `tile`
rows, each tile one expert's, with a trip count that follows the load — an
expert nobody chose costs nothing, and a product over all experts for every
token (`held` times the routed work) is never formed. A tile's rows are
gathered, run through the expert's SwiGLU, and laid down in sorted order;
one gather then brings each token its experts' rows. Inference only: the
walk is a `while` loop and runs on raw values, off the tape.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from .....nn import initializer as I
from .....nn.layer import Layer
from .....tensor import Tensor

__all__ = ["HeldExperts", "route_sigmoid", "held_experts_ffn", "tile_for",
           "launch_counts"]


def route_sigmoid(y, router, bias, top_k, *, norm_topk=True, scale=1.0):
    """Sigmoid scores over every published expert; the `top_k` of largest
    score + bias are chosen (the bias chooses and does not weigh), each
    weighed by its score over the sum of the chosen scores.
    y: [N, h] -> (chosen [N, k] int32 published numbers, weights [N, k] f32)."""
    z = jax.nn.sigmoid(jnp.dot(y, router,
                               preferred_element_type=jnp.float32))
    _, chosen = jax.lax.top_k(z + bias.astype(jnp.float32), top_k)
    w = jnp.take_along_axis(z, chosen, -1)
    if norm_topk:
        w = w / jnp.sum(w, -1, keepdims=True)
    return chosen.astype(jnp.int32), w * scale


def _swiglu(x, gate_up, down):
    gate, up = jnp.split(jnp.dot(x, gate_up), 2, axis=-1)
    return jnp.dot(jax.nn.silu(gate) * up, down)


def tile_for(rows, top_k, published):
    """Rows a tile: twice a held expert's expected share of `rows` tokens,
    as a power of two from 16 to 256. A tile reads its expert's weights once
    whatever it carries, so a tile an expert's whole load fits is the
    cheapest; a tile much larger than the load only pads."""
    share = 2 * rows * top_k / published
    return min(256, max(16, 1 << math.ceil(math.log2(max(share, 1)))))


def held_experts_ffn(y, chosen, weights, gate_up, down, *, first, valid=None,
                     tile=256):
    """What the experts held here add. y: [N, h]; chosen, weights: [N, k];
    gate_up: [held, h, 2 * inner]; down: [held, inner, h]; `first`: the
    published number of the first expert held; `valid` [N] bool: rows that
    are no token (padding) are routed nowhere. Returns (out [N, h] in y's
    dtype, counts): `expert_tokens` [held] assignments each held expert got,
    `elsewhere` assignments of real tokens to absent experts, `rows_issued`
    the rows of the tiles walked."""
    n, h = y.shape
    k = chosen.shape[1]
    held = gate_up.shape[0]
    tile = min(int(tile), n * k)
    local = chosen - first
    here = (local >= 0) & (local < held)
    real = here if valid is None else here & valid[:, None]
    expert = jnp.where(real, local, held).reshape(-1)       # held: not here
    order = jnp.argsort(expert, stable=True).astype(jnp.int32)
    token = order // k                                       # sorted -> token
    counts = jnp.bincount(expert, length=held + 1)[:held].astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    tiles = -(-counts // tile)
    tile_ends = jnp.cumsum(tiles)
    # where assignment (token, j) lies in the tiled sorted order: its
    # expert's first tile, then its rank among that expert's assignments
    rank = jnp.argsort(order).astype(jnp.int32)     # a permutation's inverse
    safe = jnp.minimum(expert, held - 1)
    place = (tile_ends[safe] - tiles[safe]) * tile + rank - starts[safe]
    rows = n * k + held * tile          # every assignment here, tiles padded
    arange = jnp.arange(tile, dtype=jnp.int32)

    def one_tile(carry):
        i, laid = carry
        e = jnp.searchsorted(tile_ends, i, side="right").astype(jnp.int32)
        at = starts[e] + (i - (tile_ends[e] - tiles[e])) * tile + arange
        at = jnp.minimum(at, n * k - 1)
        out = _swiglu(y[token[at]], gate_up[e], down[e])
        return i + 1, jax.lax.dynamic_update_slice(
            laid, out.astype(laid.dtype), (i * tile, jnp.int32(0)))

    _, laid = jax.lax.while_loop(
        lambda c: c[0] < tile_ends[-1], one_tile,
        (jnp.int32(0), jnp.zeros((rows, h), y.dtype)))
    got = laid[jnp.where(real.reshape(-1), place, 0)].reshape(n, k, h)
    w = jnp.where(real, weights, 0.0).astype(jnp.float32)
    # a row never laid down is never weighed: `where`, not a product by 0
    out = jnp.sum(jnp.where(real[..., None], got.astype(jnp.float32), 0.0)
                  * w[..., None], axis=1)
    elsewhere = jnp.sum(~here if valid is None else (~here) & valid[:, None])
    return out.astype(y.dtype), {"expert_tokens": counts,
                                 "elsewhere": elsewhere.astype(jnp.int32),
                                 "rows_issued": (tile_ends[-1] * tile).astype(
                                     jnp.int32)}


def launch_counts(expert_tokens, elsewhere, rows_issued):
    """One launch's counts of `held_experts_ffn`, read back and stacked a
    layer (`expert_tokens` [layers, held]), under the tick ledger's names:
    the rows of the tiles walked, the assignments the held experts got (in
    all, and each expert's over the layers), and the assignments of real
    tokens to experts held elsewhere."""
    per = np.asarray(expert_tokens).sum(axis=0)
    return {"moe_expert_tokens": [int(n) for n in per],
            "moe_rows_useful": int(per.sum()),
            "moe_rows_issued": int(np.sum(rows_issued)),
            "moe_assignments_elsewhere": int(np.sum(elsewhere))}


class HeldExperts(Layer):
    """`held` SwiGLU experts of width `inner`, numbers `first` ..
    `first + held - 1` of `published`, behind a sigmoid router of the
    published width that picks `top_k` a token (`route_sigmoid`).

    forward(y [N, h], valid=None) -> (out [N, h], counts): raw values in and
    out (`held_experts_ffn`, in tiles of `tile_for` the rows of the call).
    Parameters: `router` [h, published],
    `router_bias` [published], `experts_gate_up` [held, h, 2 * inner],
    `experts_down` [held, inner, h]."""

    def __init__(self, d_model, inner, *, held, published, first=0, top_k=8,
                 norm_topk=True, scale=1.0, dtype=None):
        super().__init__()
        if not 0 <= first <= published - held:
            raise ValueError(f"experts {first}..{first + held - 1} are not "
                             f"among {published}")
        self.held, self.published, self.first = held, published, first
        self.top_k, self.norm_topk, self.scale = top_k, norm_topk, scale
        init = I.Normal(0.0, 0.02)
        self.router = self.create_parameter(
            [d_model, published], dtype=dtype, default_initializer=init)
        self.router_bias = self.create_parameter(
            [published], dtype=dtype, default_initializer=I.Constant(0.0))
        self.experts_gate_up = self.create_parameter(
            [held, d_model, 2 * inner], dtype=dtype, default_initializer=init)
        self.experts_down = self.create_parameter(
            [held, inner, d_model], dtype=dtype, default_initializer=init)

    def forward(self, y, valid=None):
        y = y._value if isinstance(y, Tensor) else y
        with jax.named_scope("moe.route"):
            chosen, weights = route_sigmoid(
                y, self.router._value, self.router_bias._value, self.top_k,
                norm_topk=self.norm_topk, scale=self.scale)
        with jax.named_scope("moe.experts"):
            return held_experts_ffn(
                y, chosen, weights, self.experts_gate_up._value,
                self.experts_down._value, first=self.first, valid=valid,
                tile=tile_for(y.shape[0], self.top_k, self.published))
