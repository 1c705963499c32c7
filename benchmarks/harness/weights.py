"""Weights from `--seed`, made on the device in one jitted call.

The tree is the reference's own: block parameters stacked over layers, so
that the reference scans them and one draw makes a kind for all layers. The
seed enters as data, not as a constant, so every seed runs the same
compiled program. `round_to` rounds the float32 draws to a narrower type and
back: a served checkpoint IS its bfloat16 values, and the reference holds the
same values in float32."""
import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_KINDS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
               "ln2_w", "ln2_b", "fc1_w", "fc1_b", "down_w", "down_b")
TOP_KINDS = ("wte", "wpe", "ln_f_w", "ln_f_b")


def shapes(cfg):
    h, layers = cfg["n_embd"], cfg["n_layer"]
    inner = cfg["n_inner"] or 4 * h
    block = {"ln1_w": (h,), "ln1_b": (h,), "qkv_w": (h, 3 * h),
             "qkv_b": (3 * h,), "out_w": (h, h), "out_b": (h,),
             "ln2_w": (h,), "ln2_b": (h,), "fc1_w": (h, inner),
             "fc1_b": (inner,), "down_w": (inner, h), "down_b": (h,)}
    out = {"wte": (cfg["vocab_size"], h), "wpe": (cfg["n_positions"], h),
           "ln_f_w": (h,), "ln_f_b": (h,)}
    out.update({k: (layers,) + s for k, s in block.items()})
    return out


# the fused projection is three matrices side by side, q | k | v: each is a
# leaf of its own in the comparison (the key's bias has no gradient under
# softmax, the other two thirds of that vector have)
FUSED = {"qkv_w": 3, "qkv_b": 3}


def parts(kind, value):
    """A leaf as the comparison sees it: itself, or its fused parts (split
    along the last axis)."""
    n = FUSED.get(kind, 1)
    return [value] if n == 1 else jnp.split(value, n, axis=-1)


def leaf_names(cfg):
    """One entry per compared leaf: (kind, part, layer), in the order both
    sides list their norms."""
    return ([(k, 0, None) for k in TOP_KINDS]
            + [(k, p, i) for k in BLOCK_KINDS for p in range(FUSED.get(k, 1))
               for i in range(cfg["n_layer"])])


def seed_words(seed):
    """Any whole number up to a little over 2**31, as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnames=("shape_items", "std", "round_to",
                                             "out_dtype"))
def _draw(words, shape_items, std, round_to, out_dtype):
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    tree = {}
    for i, (kind, shape) in enumerate(shape_items):
        noise = std * jax.random.normal(jax.random.fold_in(key, i), shape,
                                        jnp.float32)
        # LayerNorm gains sit around 1, everything else around 0
        value = 1.0 + noise if kind in ("ln1_w", "ln2_w", "ln_f_w") else noise
        if round_to is not None:
            value = value.astype(round_to).astype(jnp.float32)
        tree[kind] = value.astype(out_dtype)
    return tree


def make_weights(cfg, seed, *, round_to=None, out_dtype="float32"):
    """The whole tree, on the device. Same seed, same values, whatever
    `out_dtype`: `round_to="bfloat16", out_dtype="float32"` is the float32
    image of the checkpoint that `out_dtype="bfloat16"` serves."""
    return _draw(jnp.asarray(seed_words(seed)),
                 tuple(sorted(shapes(cfg).items())),
                 float(cfg["initializer_range"]), round_to, out_dtype)
