"""A second family, as a test fixture: the block `GPTConfig(use_rope=True,
use_rms_norm=True, use_swiglu=True, tie_embeddings=False)` builds, which the
`gpt2` family does not describe. Rotary positions (halves rotated, base
`rope_theta`), RMSNorm, a fused q | k | v projection and a fused gate | up
projection without biases, SwiGLU, an output head of its own.

Its leaves are named a layer each (`L3.qkv`), not stacked: a part is a draw
of its own and nothing larger is ever made. It imports nothing of the
program and nothing of the harness."""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LAYER_KINDS = ("norm1", "qkv", "out", "norm2", "gate_up", "down")
GAINS = ("norm1", "norm2", "norm_f")


def layer_kinds(cfg):
    return ["block"] * cfg["num_hidden_layers"]


def leaves(cfg):
    h, inner = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    block = {"norm1": (h,), "qkv": (h, 3 * h), "out": (h, h), "norm2": (h,),
             "gate_up": (h, 2 * inner), "down": (inner, h)}
    shapes = {"embed": (cfg["vocab_size"], h), "norm_f": (h,),
              "lm_head": (h, cfg["vocab_size"])}
    shapes.update({f"L{i}.{k}": s for i in range(cfg["num_hidden_layers"])
                   for k, s in block.items()})
    return {name: (shape, 1.0 if name.split(".")[-1] in GAINS else 0.0, std)
            for name, shape in shapes.items()}


def embed_leaves(cfg):
    return {"embed": "embed"}


def layer_leaves(cfg, index):
    return {k: f"L{index}.{k}" for k in LAYER_KINDS}


def head_leaves(cfg):
    return {"norm_f": "norm_f", "lm_head": "lm_head"}


def parts(name, value):
    return (jnp.split(value, 3, axis=-1) if name.endswith(".qkv")
            else [value])


def leaf_names(cfg):
    return [(name, p, None) for name in sorted(leaves(cfg))
            for p in range(3 if name.endswith(".qkv") else 1)]


def leaf_norms(cfg, tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(parts(name, tree[name])[p])))
                      for name, p, _ in leaf_names(cfg)])


def _rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * w


def _rope(t, theta):
    """t: [B, S, heads, D]; position s rotates the two halves of D."""
    seq, dim = t.shape[1], t.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * inv
    angle = jnp.concatenate([angle, angle], -1)[None, :, None, :]
    t1, t2 = jnp.split(t, 2, axis=-1)
    return t * jnp.cos(angle) + jnp.concatenate([-t2, t1], -1) * jnp.sin(angle)


def embed(cfg, p, ids):
    return p["embed"][ids]


def layer(cfg, kind, p, x, mm):
    batch, seq, h = x.shape
    heads = cfg["num_attention_heads"]
    dim = h // heads
    eps = cfg["rms_norm_eps"]
    qkv = mm(_rms_norm(x, p["norm1"], eps), p["qkv"])
    q, k, v = (t.reshape(batch, seq, heads, dim)
               for t in jnp.split(qkv, 3, axis=-1))
    q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HIGHEST)
    scores = jnp.where(jnp.tril(jnp.ones((seq, seq), bool)),
                       scores / math.sqrt(dim), -jnp.inf)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v,
                     precision=HIGHEST).reshape(batch, seq, h)
    x = x + mm(ctx, p["out"])
    gate, up = jnp.split(mm(_rms_norm(x, p["norm2"], eps), p["gate_up"]), 2,
                         axis=-1)
    return x + mm(jax.nn.silu(gate) * up, p["down"])


def stack(cfg, tree, x, mm, remat=False):
    """Every layer in turn over a whole tree."""
    def body(kind, p, x):
        return layer(cfg, kind, p, x, mm)
    if remat:
        body = jax.checkpoint(body, static_argnums=(0,))
    for index, kind in enumerate(layer_kinds(cfg)):
        x = body(kind, {k: tree[name] for k, name in
                        layer_leaves(cfg, index).items()}, x)
    return x


def head(cfg, p, x, mm):
    return mm(_rms_norm(x, p["norm_f"], cfg["rms_norm_eps"]), p["lm_head"])


def forward_bytes(cfg, seq):
    return 4 * seq * (2 * cfg["num_attention_heads"] * seq
                      + 3 * cfg["intermediate_size"] + 2 * cfg["vocab_size"]
                      + 8 * cfg["hidden_size"])
