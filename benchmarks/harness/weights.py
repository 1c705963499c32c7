"""Weights from `--seed`, made on the device, whole or in parts.

What the leaves are is the family's (`families/<model_type>/model.py`:
`leaves(cfg)` gives each leaf's shape and the mean and spread it is drawn
with); how they are drawn is the same for every family and lives here. Leaf
number i of the SORTED WHOLE list is `mean + std * normal(fold_in(key, i))`,
so a part drawn alone holds bit for bit what the whole tree holds there. The
seed enters as data, not as a constant, so every seed runs the same compiled
program. `round_to` rounds the float32 draws to a narrower type and back: a
served checkpoint IS its bfloat16 values, and the reference holds the same
values in float32."""
import functools

import jax
import jax.numpy as jnp
import numpy as np


def seed_words(seed):
    """Any whole number up to a little over 2**31, as two uint32 words."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed {seed} is not a whole number below 2**64")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


@functools.partial(jax.jit, static_argnames=("items", "round_to", "out_dtype"))
def _draw(words, items, round_to, out_dtype):
    """`items`: (index, shape, mean, std, layers) a leaf, where `layers` is
    None for the leaf as it is or the layers wanted of a leaf stacked over
    its first axis: the leaf is drawn once and sliced. A flat list."""
    key = jax.random.wrap_key_data(words, impl="threefry2x32")
    out = []
    for index, shape, mean, std, layers in items:
        noise = std * jax.random.normal(jax.random.fold_in(key, index), shape,
                                        jnp.float32)
        value = mean + noise if mean else noise
        if round_to is not None:
            value = value.astype(round_to).astype(jnp.float32)
        value = value.astype(out_dtype)
        out.extend([value] if layers is None else [value[i] for i in layers])
    return out


def make_weights(family, cfg, seed, *, only=None, round_to=None,
                 out_dtype="float32"):
    """The whole tree {name: value}, on the device, or with `only` the leaves
    it names: an entry is a leaf's name, or (name, layer) for one layer of a
    leaf that is stacked over layers. The result is keyed by the entries.
    Same seed, same values, whatever `out_dtype` and whatever else is drawn
    beside: `round_to="bfloat16", out_dtype="float32"` is the float32 image
    of the checkpoint that `out_dtype="bfloat16"` serves."""
    leaves = family.model.leaves(cfg)
    index = {name: i for i, name in enumerate(sorted(leaves))}
    if only is None:
        wanted = {name: None for name in index}
    else:
        wanted = {}
        for entry in only:
            if isinstance(entry, str):
                wanted[entry] = None
            else:
                wanted.setdefault(entry[0], []).append(int(entry[1]))
    wanted = sorted(wanted.items())
    items = tuple(
        (index[name], tuple(leaves[name][0]), float(leaves[name][1]),
         float(leaves[name][2]), None if layers is None else tuple(layers))
        for name, layers in wanted)
    keys = [key for name, layers in wanted for key in
            ([name] if layers is None else [(name, i) for i in layers])]
    return dict(zip(keys, _draw(jnp.asarray(seed_words(seed)), items,
                                round_to, out_dtype)))


def pick(tree, entries):
    """{parameter: value} for a map {parameter: entry} (as a family's
    `embed_leaves`, `layer_leaves`, `head_leaves` give it), out of a whole
    tree or out of what `make_weights(only=entries.values())` returned."""
    def one(entry):
        if isinstance(entry, str) or entry in tree:
            return tree[entry]
        return tree[entry[0]][entry[1]]
    return {param: one(entry) for param, entry in entries.items()}


def image_bytes(family, cfg, dtype_bytes=4):
    """Bytes of the whole tree."""
    return dtype_bytes * sum(int(np.prod(shape)) for shape, _, _ in
                             family.model.leaves(cfg).values())
