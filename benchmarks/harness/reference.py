"""The plain reference, as far as it is the same for every family: float32,
matmuls at `highest` precision, no kernel, no cache, no batching tricks. The
forward itself is the family's (`families/<model_type>/model.py`: embed, one
layer, head); here are the product it is handed, the loss (the mean cross
entropy over all positions), AdamW (Loshchilov & Hutter 2019) after clipping
by the global norm, and the served tokens' logit gaps.

It imports nothing of the program and takes nothing the program made. Weights
are `weights.make_weights(family, cfg, seed)`. `quant=True` is the CONTROL:
every linear product the family sends through `mm` with both operands rounded
to fp8 (e4m3: 3 bits of mantissa, each tensor scaled so that its largest
value sits at the format's 448) — the nearest precision below bfloat16 that
a later PR might be tempted by. (int8 per tensor keeps about 2 bits fewer
than bfloat16 on bell-shaped values and read only 1.1-2.3 times the
program's gaps on the chip; e4m3 keeps 5 fewer.)"""
import functools

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


def product(quant):
    """The `mm(x, w)` a family's forward is handed: plain, or the control's."""
    return functools.partial(_mm, quant=quant)


def logits_fn(family, cfg, tree, ids, quant=False, remat=False):
    """ids: [B, S] int -> logits [B, S, vocab] float32, over a whole tree:
    the family's embed, its layers in turn, its head."""
    model, mm = family.model, product(quant)
    x = model.embed(cfg, W.pick(tree, model.embed_leaves(cfg)), ids)
    x = model.stack(cfg, tree, x, mm, remat)
    return model.head(cfg, W.pick(tree, model.head_leaves(cfg)), x, mm)


def loss_sum(family, cfg, tree, ids, labels, quant=False):
    """Summed token cross entropy of rows [B, S] (the caller divides)."""
    logits = logits_fn(family, cfg, tree, ids, quant, remat=True)
    logz = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]
    return jnp.sum(logz - picked)


class TrainReference:
    """Follows the first steps of a training job, row by row so that float32
    attention scores of one row are all that is alive at a time."""

    def __init__(self, family, cfg, opt, seed, *, quant=False,
                 drop_half=False):
        self.cfg, self.opt = cfg, opt
        self.quant, self.drop_half = quant, drop_half
        self.params = W.make_weights(family, cfg, seed)
        self.start = W.make_weights(family, cfg, seed)   # params are donated
        zeros = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))
        self.m, self.v = zeros(self.params), zeros(self.params)
        self.t = 0
        self._row = jax.jit(jax.value_and_grad(
            functools.partial(loss_sum, family, cfg, quant=quant)))
        self._add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b))
        self._norms = jax.jit(functools.partial(family.model.leaf_norms, cfg))
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


def gap_rows(ref, nxt, low=None):
    """ref: [N, vocab] the reference's logits at N positions; nxt: [N] the
    token served next at each. `served_gap[i]` is how far the reference's
    logit of that token lies below the reference's best; with `low`, the
    fp8 control's logits at the same positions, `control_gap[i]` is the same
    for the token that the control puts first."""
    best = jnp.max(ref, -1)

    def below(tokens):
        return best - jnp.take_along_axis(ref, tokens[:, None], -1)[:, 0]
    out = {"served_gap": below(nxt)}
    if low is not None:
        out["control_gap"] = below(jnp.argmax(low, -1))
    return out


class ServeCheck:
    """The reference over finished requests, by one of two routes made of
    the same three pieces of the family. Where the float32 image of the
    checkpoint and a row's activations fit the chip, the whole image is made
    once and each row runs through it in one program. Where they do not (a
    10 GB bfloat16 checkpoint has a 20 GB image), the image is never made:
    one layer's float32 leaves are drawn, every row's activations carried
    through that layer, the leaves freed, and so on; the head runs over the
    positions that are compared. Which route is reckoned from the family's
    shapes and the chip's memory, not from a switch."""
    ROOM = 0.75         # of the chip's memory, for the image and a forward
    PARKED = 0.125      # of it, for the rows' activations between layers

    def __init__(self, family, cfg, seed, width, *, most_new=None,
                 hbm_bytes=None, control=False):
        self.family, self.cfg, self.seed = family, cfg, seed
        self.width, self.hbm_bytes = width, hbm_bytes
        # the reference alone, or the fp8 control (quant=True) beside it
        self.sides = (False, True) if control else (False,)
        # head rows a call: the compared ones, as many as an answer has at
        # most; the control scales a tensor by its largest value, so it
        # sees a whole row there as it does in the whole image's program
        self.block = width if control else min(width, most_new or width)

    def whole_image_fits(self):
        """Off the chip (no `hbm_bytes`) there is nothing to fit."""
        if self.hbm_bytes is None:
            return True
        need = (W.image_bytes(self.family, self.cfg) + len(self.sides)
                * self.family.model.forward_bytes(self.cfg, self.width))
        return need <= self.ROOM * self.hbm_bytes

    def gaps(self, rows):
        """rows: [(ids [1, width] int, keep)], a request's prompt and served
        tokens padded to the width, and the slice of positions that predict
        a served token (position i predicts ids[i + 1]). Returns, a row,
        {"served_gap": [kept], "control_gap": [kept]} as numpy."""
        route = self._whole if self.whole_image_fits() else self._streamed
        return route([(jnp.asarray(ids), keep) for ids, keep in rows])

    def _whole(self, rows):
        tree = W.make_weights(self.family, self.cfg, self.seed,
                              round_to="bfloat16")

        @jax.jit
        def run(tree, ids):
            ref, low = [logits_fn(self.family, self.cfg, tree, ids, quant)[0]
                        if quant in self.sides else None
                        for quant in (False, True)]
            return gap_rows(ref, jnp.concatenate([ids[0, 1:], ids[0, :1]]),
                            low)
        return [{k: np.asarray(v)[keep] for k, v in run(tree, ids).items()}
                for ids, keep in rows]

    def _leaves(self, entries):
        drawn = W.make_weights(self.family, self.cfg, self.seed,
                               only=list(entries.values()),
                               round_to="bfloat16")
        return W.pick(drawn, entries)

    def _streamed(self, rows):
        model, cfg = self.family.model, self.cfg
        p = self._leaves(model.embed_leaves(cfg))
        embed = jax.jit(functools.partial(model.embed, cfg))
        start = [embed(p, ids) for ids, _ in rows]
        # between layers the rows wait on the device, or on the host where
        # they would crowd the layer out
        crowded = (self.hbm_bytes is not None and len(self.sides) * sum(
            x.nbytes for x in start) > self.PARKED * self.hbm_bytes)
        park = np.asarray if crowded else (lambda x: x)
        acts = {quant: [park(x) for x in start] for quant in self.sides}
        del p, start
        # one program a kind of layer and route, whatever the layer

        @functools.partial(jax.jit, static_argnums=(2, 3))
        def run(p, x, kind, quant):
            return model.layer(cfg, kind, p, x, product(quant))
        for index, kind in enumerate(model.layer_kinds(cfg)):
            p = self._leaves(model.layer_leaves(cfg, index))
            for quant in self.sides:
                acts[quant] = [park(run(p, x, kind, quant))
                               for x in acts[quant]]
            del p
        p = self._leaves(model.head_leaves(cfg))

        @jax.jit
        def gaps(p, ids, x, x_low, at):
            def rows(a):
                """`self.block` positions from `at` on; the caller drops
                what lies outside the kept ones."""
                a = jnp.pad(a, [(0, self.block)] + [(0, 0)] * (a.ndim - 1))
                return jax.lax.dynamic_slice_in_dim(a, at, self.block)
            low = (None if x_low is None
                   else model.head(cfg, p, rows(x_low[0]), product(True)))
            return gap_rows(model.head(cfg, p, rows(x[0]), product(False)),
                            rows(ids[0, 1:]), low)
        out = []
        for r, (ids, keep) in enumerate(rows):
            got = []
            first = 0 if True in acts else keep.start
            for at in range(first, keep.stop, self.block):
                g = gaps(p, ids, acts[False][r], acts[True][r]
                         if True in acts else None, at)
                got.append({k: np.asarray(v)[max(keep.start - at, 0):
                                             keep.stop - at]
                            for k, v in g.items()})
            out.append({k: np.concatenate([g[k] for g in got])
                        for k in got[0]})
        return out
