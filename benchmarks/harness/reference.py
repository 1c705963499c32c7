"""The plain reference: GPT-2 in straightforward jax.numpy, float32, matmuls
at `highest` precision, no kernel, no cache, no batching tricks. It follows
the published description (Radford et al. 2019; the `gpt2` model of the
source's config.json): learned positions, pre-LayerNorm blocks with biases,
fused qkv projection split as q | k | v, causal softmax attention, a 4h MLP
with the activation the configuration names, a final LayerNorm, the output
head tied to the token embedding; loss is the mean cross entropy over all
positions; AdamW (Loshchilov & Hutter 2019) after clipping by the global norm.

It imports nothing of the program and takes nothing the program made. Weights
are `weights.make_weights(cfg, seed)`; block leaves are stacked over layers
and scanned. `quant=True` is the CONTROL: every linear product (qkv, out, fc1,
down, the head) with both operands rounded to fp8 (e4m3: 3 bits of mantissa,
each tensor scaled so that its largest value sits at the format's 448) — the
nearest precision below bfloat16 that a later PR might be tempted by. (int8
per tensor keeps about 2 bits fewer than bfloat16 on bell-shaped values and
read only 1.1-2.3 times the program's gaps on the chip; e4m3 keeps 5 fewer.)"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import weights as W

HIGHEST = jax.lax.Precision.HIGHEST


def round_e4m3(x):
    """x rounded to the nearest value of float8 e4m3 (1 sign, 4 exponent,
    3 mantissa bits; largest 448, normals from 2**-6, subnormals on the
    2**-9 grid), by arithmetic so that it is the same on every backend."""
    exponent = jnp.floor(jnp.log2(jnp.maximum(jnp.abs(x), 2.0 ** -6)))
    step = jnp.exp2(exponent - 3)
    return jnp.clip(jnp.round(x / step) * step, -448.0, 448.0)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    q = round_e4m3(x / scale) * scale
    return x + jax.lax.stop_gradient(q - x)      # straight-through


def _mm(x, w, quant):
    if quant:
        x, w = _fp8(x), _fp8(w)
    return jnp.matmul(x, w, precision=HIGHEST)


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


def _block(cfg, quant, x, p):
    """x: [B, S, h] float32; p: one layer's leaves."""
    batch, seq, h = x.shape
    heads = cfg["n_head"]
    dim = h // heads
    eps = cfg["layer_norm_epsilon"]
    a = _layer_norm(x, p["ln1_w"], p["ln1_b"], eps)
    qkv = _mm(a, p["qkv_w"], quant) + p["qkv_b"]
    q, k, v = (t.reshape(batch, seq, heads, dim).transpose(0, 2, 1, 3)
               for t in jnp.split(qkv, 3, axis=-1))
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision=HIGHEST)
    scores = scores / math.sqrt(dim)
    causal = jnp.tril(jnp.ones((seq, seq), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhqk,bhkd->bhqd", probs, v, precision=HIGHEST)
    ctx = ctx.transpose(0, 2, 1, 3).reshape(batch, seq, h)
    x = x + _mm(ctx, p["out_w"], quant) + p["out_b"]
    m = _layer_norm(x, p["ln2_w"], p["ln2_b"], eps)
    m = _gelu(_mm(m, p["fc1_w"], quant) + p["fc1_b"],
              cfg["activation_function"])
    return x + _mm(m, p["down_w"], quant) + p["down_b"]


def logits_fn(cfg, params, ids, quant=False, remat=False):
    """ids: [B, S] int -> logits [B, S, vocab] float32."""
    seq = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:seq]
    body = functools.partial(_block, cfg, quant)
    if remat:
        body = jax.checkpoint(body)
    x, _ = jax.lax.scan(lambda c, p: (body(c, p), None), x,
                        {k: params[k] for k in W.BLOCK_KINDS})
    x = _layer_norm(x, params["ln_f_w"], params["ln_f_b"],
                    cfg["layer_norm_epsilon"])
    return _mm(x, params["wte"].T, quant)


def loss_sum(cfg, params, ids, labels, quant=False):
    """Summed token cross entropy of rows [B, S] (the caller divides)."""
    logits = logits_fn(cfg, params, ids, quant, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - picked)


def leaf_norms(cfg, tree):
    """L2 norm of every leaf, in `weights.leaf_names` order."""
    def norm(x, axes):
        return jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axes))
    top = [norm(tree[k], None).reshape(1) for k in W.TOP_KINDS]
    blocks = [norm(part, tuple(range(1, part.ndim)))
              for k in W.BLOCK_KINDS for part in W.parts(k, tree[k])]
    return jnp.concatenate(top + blocks)


class TrainReference:
    """Follows the first steps of a training job, row by row so that float32
    attention scores of one row are all that is alive at a time."""

    def __init__(self, cfg, opt, seed, *, quant=False, drop_half=False):
        self.cfg, self.opt = cfg, opt
        self.quant, self.drop_half = quant, drop_half
        self.params = W.make_weights(cfg, seed)
        self.start = W.make_weights(cfg, seed)   # params are donated
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self.m, self.v = zeros(self.params), zeros(self.params)
        self.t = 0
        self._row = jax.jit(jax.value_and_grad(
            functools.partial(loss_sum, cfg, quant=quant)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._norms = jax.jit(functools.partial(leaf_norms, cfg))
        self._update = jax.jit(self._adamw, donate_argnums=(0, 1, 2))

    def _adamw(self, params, m, v, grads, t, scale):
        o = self.opt
        b1, b2 = o["beta1"], o["beta2"]
        sq = sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads))
        gnorm = jnp.sqrt(sq) * scale
        clip = jnp.minimum(o["clip_norm"] / jnp.maximum(gnorm, 1e-12), 1.0)
        grads = jax.tree.map(lambda g: g * (scale * clip), grads)
        m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t

        def step(p, a, b):
            return (p * (1 - o["learning_rate"] * o["weight_decay"])
                    - o["learning_rate"] * (a / c1)
                    / (jnp.sqrt(b / c2) + o["epsilon"]))
        return jax.tree.map(step, params, m, v), m, v, grads

    def step(self, ids, labels):
        """One step on a batch [B, S]; returns (loss, norms of the clipped
        gradient per leaf)."""
        ids, labels = np.asarray(ids), np.asarray(labels)
        rows = range(ids.shape[0] // 2 if self.drop_half else ids.shape[0])
        total, grads = 0.0, None
        for r in rows:
            loss, g = self._row(self.params, ids[r:r + 1], labels[r:r + 1])
            total = total + loss
            grads = g if grads is None else self._add(grads, g)
        tokens = len(rows) * ids.shape[1]
        self.t += 1
        self.params, self.m, self.v, clipped = self._update(
            self.params, self.m, self.v, grads,
            jnp.float32(self.t), jnp.float32(1.0 / tokens))
        return float(total) / tokens, np.asarray(self._norms(clipped))

    def delta_norms(self):
        """Norm of each leaf's change since the start."""
        diff = jax.jit(lambda a, b: jax.tree.map(jnp.subtract, a, b))(
            self.params, self.start)
        return np.asarray(self._norms(diff))


@functools.partial(jax.jit, static_argnames=("cfg_items", "control"))
def _serve_gaps(params, ids, cfg_items, control):
    cfg = dict(cfg_items)
    ref = logits_fn(cfg, params, ids)[0]                  # [S, vocab]
    best = jnp.max(ref, -1)
    nxt = jnp.concatenate([ids[0, 1:], ids[0, :1]])
    served = jnp.take_along_axis(ref, nxt[:, None], -1)[:, 0]
    out = {"served_gap": best - served}
    if control:
        low = logits_fn(cfg, params, ids, quant=True)[0]
        first = jnp.argmax(low, -1)
        out["control_gap"] = best - jnp.take_along_axis(
            ref, first[:, None], -1)[:, 0]
    return out


def serve_gaps(cfg, params, ids, *, control=False):
    """ids: one request's prompt and served tokens, padded to a fixed length,
    [1, S]. Position i predicts ids[i + 1]: `served_gap[i]` is how far the
    reference's logit of that next token lies below the reference's best;
    `control_gap[i]` the same for the token that the fp8 control puts
    first. The caller keeps the positions that predict a served token."""
    items = tuple(sorted((k, v) for k, v in cfg.items()
                         if isinstance(v, (int, float, str))
                         and not isinstance(v, bool)))
    return {k: np.asarray(v) for k, v in
            _serve_gaps(params, jnp.asarray(ids), items, control).items()}
