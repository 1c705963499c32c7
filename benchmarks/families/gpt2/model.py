"""The `gpt2` family's plain half: its leaves, how each is drawn, which are
compared, and the float32 forward in three pieces (embed, one layer, head).

Straightforward jax.numpy, float32, no kernel, no cache, no batching tricks.
It follows the published description (Radford et al. 2019; the `gpt2` model
of the source's config.json): learned positions, pre-LayerNorm blocks with
biases, fused qkv projection split as q | k | v, causal softmax attention, a
4h MLP with the activation the configuration names, a final LayerNorm, the
output head tied to the token embedding.

It imports nothing of the program and nothing of the harness. Every linear
product (qkv, out, fc1, down, the head) goes through the `mm(x, w)` it is
handed, so the harness's fp8 control is the harness's own; the attention
products are its own, at `highest`. Block leaves are stacked over layers:
one draw makes a kind for all layers, and `stack` scans them."""
import math

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

BLOCK_KINDS = ("ln1_w", "ln1_b", "qkv_w", "qkv_b", "out_w", "out_b",
               "ln2_w", "ln2_b", "fc1_w", "fc1_b", "down_w", "down_b")
TOP_KINDS = ("wte", "wpe", "ln_f_w", "ln_f_b")
GAINS = ("ln1_w", "ln2_w", "ln_f_w")    # LayerNorm gains sit around 1


def layer_kinds(cfg):
    """One entry a layer: which of the family's layer programs it runs. All
    of GPT-2's are alike."""
    return ["block"] * cfg["n_layer"]


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


def leaves(cfg):
    """{leaf: (shape, mean, spread)}: everything normal(0,
    initializer_range) but the LayerNorm gains, which sit around 1."""
    std = cfg["initializer_range"]
    return {k: (s, 1.0 if k in GAINS else 0.0, std)
            for k, s in shapes(cfg).items()}


# which leaves each piece of the forward takes: {parameter: entry}, an entry
# being what `make_weights(only=...)` draws alone
def embed_leaves(cfg):
    return {"wte": "wte", "wpe": "wpe"}


def layer_leaves(cfg, index):
    return {k: (k, index) for k in BLOCK_KINDS}


def head_leaves(cfg):
    return {"ln_f_w": "ln_f_w", "ln_f_b": "ln_f_b", "wte": "wte"}


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


def leaf_norms(cfg, tree):
    """L2 norm of every leaf of a whole tree, in `leaf_names` order."""
    def norm(x, axes):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axes))
    top = [norm(tree[k], None).reshape(1) for k in TOP_KINDS]
    blocks = [norm(part, tuple(range(1, part.ndim)))
              for k in BLOCK_KINDS for part in parts(k, tree[k])]
    return jnp.concatenate(top + blocks)


def _layer_norm(x, w, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * w + b


def _gelu(x, form):
    if form == "gelu_new":      # the tanh form of the GPT-2 source
        return 0.5 * x * (1.0 + jnp.tanh(
            math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))
    if form == "gelu":
        return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))
    raise ValueError(f"unknown activation_function {form!r}")


def embed(cfg, p, ids):
    """ids: [B, S] int -> [B, S, h] float32."""
    return p["wte"][ids] + p["wpe"][:ids.shape[1]]


def layer(cfg, kind, p, x, mm):
    """x: [B, S, h] float32; p: one layer's leaves; `kind` is the layer's
    entry of `layer_kinds`, of which there is one here."""
    batch, seq, h = x.shape
    heads = cfg["n_head"]
    dim = h // heads
    eps = cfg["layer_norm_epsilon"]
    a = _layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = mm(a, p["qkv_w"]) + p["qkv_b"]
    q, k, v = (t.reshape(batch, seq, heads, dim).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(batch, seq, h)
    x = x + mm(ctx, p["out_w"]) + p["out_b"]
    m = _layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    m = _gelu(mm(m, p["fc1_w"]) + p["fc1_b"], cfg["activation_function"])
    return x + mm(m, p["down_w"]) + p["down_b"]


def stack(cfg, tree, x, mm, remat=False):
    """Every layer in turn over a whole tree: a scan of `layer` over the
    stacked leaves."""
    def body(x, p):
        return layer(cfg, "block", p, x, mm)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x,
                        {k: tree[k] for k in BLOCK_KINDS})
    return x


def head(cfg, p, x, mm):
    """x: [..., h] -> logits [..., vocab] float32."""
    x = _layer_norm(x, p["ln_f_w"], p["ln_f_b"], cfg["layer_norm_epsilon"])
    return mm(x, p["wte"].T)


def forward_bytes(cfg, seq):
    """Float32 bytes alive at once in one row's forward of `seq` positions,
    roughly: scores and probabilities of a layer, the MLP's inner
    activations, the logits, a few copies of the residual stream. The serve
    check adds it to the image's bytes to see whether both fit the chip."""
    h = cfg["n_embd"]
    inner = cfg["n_inner"] or 4 * h
    return 4 * seq * (2 * cfg["n_head"] * seq + 2 * inner
                      + 2 * cfg["vocab_size"] + 8 * h)
