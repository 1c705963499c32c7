"""Export-parity fill-ins: the remaining `paddle.*` surface.

Reference: python/paddle/__init__.py (435 exports) / python/paddle/tensor/*.
Three groups:
1. small ops the round-1..3 sets skipped (stacking/splitting variants,
   scatter-into views, special functions, dlpack, constants);
2. in-place variants (`op_`): paddle mutates the tensor and keeps autograd —
   here the base op runs and the result is grafted back into the same Tensor
   (value + tape linkage), which is semantically identical under the tape;
3. environment shims (printoptions, LazyGuard, signal handler) that are
   no-ops or thin state in the trace-and-compile world (documented each).
"""
from __future__ import annotations

import math as _math

import jax
import jax.numpy as jnp
import numpy as np

from ..tensor import Tensor, to_tensor
from . import apply_op

__all__ = [
    # stacking / splitting
    "add_n", "block_diag", "column_stack", "row_stack", "hstack", "vstack",
    "dstack", "hsplit", "vsplit", "dsplit", "tensor_split", "cartesian_prod",
    "combinations", "unflatten", "as_strided", "matrix_transpose", "reverse",
    # scatter-into-view family
    "diagonal_scatter", "select_scatter", "slice_scatter", "index_fill",
    "index_fill_",
    # special functions / math
    "gammaln", "gammainc", "gammaincc", "multigammaln", "polygamma", "i0e",
    "i1", "i1e", "sinc", "polar", "frexp", "signbit", "isin", "isneginf",
    "isposinf", "histogram_bin_edges", "renorm", "reduce_as",
    "negative", "positive", "less", "floor_mod", "pdist", "cdist",
    # dlpack + misc env
    "from_dlpack", "to_dlpack", "set_printoptions", "disable_signal_handler",
    "check_shape", "LazyGuard", "create_parameter", "rank", "shape",
    "get_cuda_rng_state", "set_cuda_rng_state",
    # constants / dtypes
    "pi", "e", "inf", "nan", "newaxis", "float8_e4m3fn", "float8_e5m2",
]


def _val(x):
    return x._value if isinstance(x, Tensor) else jnp.asarray(x)


# ------------------------------------------------------------- stacks/splits
def add_n(inputs, name=None):
    """Reference: tensor/math.py add_n — elementwise sum of a tensor list."""
    items = inputs if isinstance(inputs, (list, tuple)) else [inputs]

    def f(*vals):
        out = vals[0]
        for v in vals[1:]:
            out = out + v
        return out

    return apply_op(f, "add_n", *items)


def block_diag(inputs, name=None):
    def f(*vals):
        vals = [v.reshape(1, -1) if v.ndim <= 1 else v for v in vals]
        rows = sum(v.shape[0] for v in vals)
        cols = sum(v.shape[1] for v in vals)
        out = jnp.zeros((rows, cols), vals[0].dtype)
        r = c = 0
        for v in vals:
            out = jax.lax.dynamic_update_slice(out, v.astype(out.dtype), (r, c))
            r += v.shape[0]
            c += v.shape[1]
        return out

    return apply_op(f, "block_diag", *inputs)


def column_stack(x, name=None):
    def f(*vals):
        vals = [v[:, None] if v.ndim == 1 else v for v in vals]
        return jnp.concatenate(vals, axis=1)

    return apply_op(f, "column_stack", *x)


def row_stack(x, name=None):
    return apply_op(lambda *v: jnp.vstack(v), "row_stack", *x)


def hstack(x, name=None):
    return apply_op(lambda *v: jnp.hstack(v), "hstack", *x)


def vstack(x, name=None):
    return apply_op(lambda *v: jnp.vstack(v), "vstack", *x)


def dstack(x, name=None):
    return apply_op(lambda *v: jnp.dstack(v), "dstack", *x)


def _split_like(fn_name, jfn):
    def f(x, num_or_indices, name=None):
        n = (tuple(num_or_indices) if isinstance(num_or_indices, (list, tuple))
             else num_or_indices)
        out = apply_op(lambda v: list(jfn(v, n)), fn_name, x)
        return out if isinstance(out, list) else [out]

    f.__name__ = fn_name
    return f


hsplit = _split_like("hsplit", jnp.hsplit)
vsplit = _split_like("vsplit", jnp.vsplit)
dsplit = _split_like("dsplit", jnp.dsplit)


def tensor_split(x, num_or_indices, axis=0, name=None):
    n = (tuple(num_or_indices) if isinstance(num_or_indices, (list, tuple))
         else num_or_indices)
    return apply_op(lambda v: list(jnp.array_split(v, n, axis=axis)),
                    "tensor_split", x)


def cartesian_prod(x, name=None):
    def f(*vals):
        grids = jnp.meshgrid(*vals, indexing="ij")
        return jnp.stack([g.reshape(-1) for g in grids], axis=-1)

    return apply_op(f, "cartesian_prod", *x)


def combinations(x, r=2, with_replacement=False, name=None):
    import itertools

    n = int(_val(x).shape[0])
    gen = (itertools.combinations_with_replacement(range(n), r)
           if with_replacement else itertools.combinations(range(n), r))
    idx = np.array(list(gen), dtype=np.int64).reshape(-1, r)
    return apply_op(lambda v: v[jnp.asarray(idx)], "combinations", x)


def unflatten(x, axis, shape, name=None):
    def f(v):
        ax = axis % v.ndim
        new = list(v.shape[:ax]) + list(shape) + list(v.shape[ax + 1:])
        return v.reshape(new)

    return apply_op(f, "unflatten", x)


def as_strided(x, shape, stride, offset=0, name=None):
    """View by explicit strides (reference: tensor/manipulation as_strided
    over the stride kernels). Gather-based on TPU (no raw pointers)."""
    def f(v):
        flat = v.reshape(-1)
        idx = jnp.full((), offset, jnp.int64)
        grids = jnp.meshgrid(*[jnp.arange(s) for s in shape], indexing="ij")
        lin = sum(g.astype(jnp.int64) * st for g, st in zip(grids, stride))
        return flat[idx + lin]

    return apply_op(f, "as_strided", x)


def matrix_transpose(x, name=None):
    return apply_op(lambda v: jnp.swapaxes(v, -1, -2), "matrix_transpose", x)


def reverse(x, axis, name=None):
    ax = tuple(axis) if isinstance(axis, (list, tuple)) else (axis,)
    return apply_op(lambda v: jnp.flip(v, ax), "reverse", x)


# --------------------------------------------------------- scatter-into-view
def diagonal_scatter(x, y, offset=0, axis1=0, axis2=1, name=None):
    def f(v, src):
        ii, jj = jnp.diag_indices(min(v.shape[axis1], v.shape[axis2]))
        if offset >= 0:
            ii, jj = ii[: v.shape[axis2] - offset], jj[: v.shape[axis2] - offset] + offset
        else:
            ii, jj = ii[: v.shape[axis1] + offset] - offset, jj[: v.shape[axis1] + offset]
        moved = jnp.moveaxis(v, (axis1, axis2), (0, 1))
        moved = moved.at[ii, jj].set(src.astype(v.dtype))
        return jnp.moveaxis(moved, (0, 1), (axis1, axis2))

    return apply_op(f, "diagonal_scatter", x, y)


def select_scatter(x, values, axis, index, name=None):
    def f(v, src):
        idx = [slice(None)] * v.ndim
        idx[axis] = index
        return v.at[tuple(idx)].set(src.astype(v.dtype))

    return apply_op(f, "select_scatter", x, values)


def slice_scatter(x, value, axes, starts, ends, strides, name=None):
    def f(v, src):
        idx = [slice(None)] * v.ndim
        for ax, st, en, sd in zip(axes, starts, ends, strides):
            idx[ax] = slice(st, en, sd)
        return v.at[tuple(idx)].set(src.astype(v.dtype))

    return apply_op(f, "slice_scatter", x, value)


def index_fill(x, index, axis, fill_value, name=None):
    def f(v, idx):
        moved = jnp.moveaxis(v, axis, 0)
        moved = moved.at[idx].set(jnp.asarray(fill_value, v.dtype))
        return jnp.moveaxis(moved, 0, axis)

    return apply_op(f, "index_fill", x, index)


def index_fill_(x, index, axis, fill_value, name=None):
    out = index_fill(x, index, axis, fill_value)
    return _graft(x, out)


# ------------------------------------------------------------------ special
def gammaln(x, name=None):
    from jax.scipy.special import gammaln as f

    return apply_op(lambda v: f(v.astype(jnp.float32) if not
                                jnp.issubdtype(v.dtype, jnp.floating) else v),
                    "gammaln", x)


def gammainc(x, y, name=None):
    from jax.scipy.special import gammainc as f

    return apply_op(f, "gammainc", x, y)


def gammaincc(x, y, name=None):
    from jax.scipy.special import gammaincc as f

    return apply_op(f, "gammaincc", x, y)


def multigammaln(x, p, name=None):
    from jax.scipy.special import gammaln as g

    def f(v):
        i = jnp.arange(1, p + 1, dtype=jnp.float32)
        return (p * (p - 1) / 4.0 * _math.log(_math.pi)
                + g(v[..., None] + (1.0 - i) / 2.0).sum(-1))

    return apply_op(f, "multigammaln", x)


def polygamma(x, n, name=None):
    from jax.scipy.special import polygamma as f

    return apply_op(lambda v: f(n, v), "polygamma", x)


def i0e(x, name=None):
    from jax.scipy.special import i0e as f

    return apply_op(f, "i0e", x)


def i1(x, name=None):
    from jax.scipy.special import i1 as f

    return apply_op(f, "i1", x)


def i1e(x, name=None):
    from jax.scipy.special import i1e as f

    return apply_op(f, "i1e", x)


def sinc(x, name=None):
    return apply_op(jnp.sinc, "sinc", x)


def polar(abs, angle, name=None):
    def f(r, t):
        return (r * jnp.cos(t) + 1j * r * jnp.sin(t)).astype(jnp.complex64)

    return apply_op(f, "polar", abs, angle)


def frexp(x, name=None):
    def f(v):
        m, e = jnp.frexp(v)
        return m, e.astype(jnp.int32)

    return apply_op(f, "frexp", x, nout=2)


def signbit(x, name=None):
    return apply_op(jnp.signbit, "signbit", x)


def isin(x, test_x, assume_unique=False, invert=False, name=None):
    return apply_op(lambda v, t: jnp.isin(v, t, invert=invert), "isin",
                    x, test_x)


def isneginf(x, name=None):
    return apply_op(jnp.isneginf, "isneginf", x)


def isposinf(x, name=None):
    return apply_op(jnp.isposinf, "isposinf", x)


def histogram_bin_edges(input, bins=100, min=0, max=0, name=None):
    def f(v):
        lo, hi = (jnp.min(v), jnp.max(v)) if min == 0 and max == 0 else (min, max)
        return jnp.linspace(lo, hi, bins + 1).astype(jnp.float32)

    return apply_op(f, "histogram_bin_edges", input)


def renorm(x, p, axis, max_norm, name=None):
    def f(v):
        moved = jnp.moveaxis(v, axis, 0).reshape(v.shape[axis], -1)
        norms = jnp.sum(jnp.abs(moved) ** p, axis=1) ** (1.0 / p)
        scale = jnp.where(norms > max_norm, max_norm / (norms + 1e-7), 1.0)
        out = moved * scale[:, None]
        return jnp.moveaxis(out.reshape(jnp.moveaxis(v, axis, 0).shape), 0, axis)

    return apply_op(f, "renorm", x)


def reduce_as(x, target, name=None):
    """Sum-reduce `x` to `target`'s shape (reference: reduce_as op)."""
    tgt = tuple(_val(target).shape)

    def f(v):
        out = v
        while out.ndim > len(tgt):
            out = out.sum(0)
        for i, (a, b) in enumerate(zip(out.shape, tgt)):
            if a != b:
                out = out.sum(i, keepdims=True)
        return out

    return apply_op(f, "reduce_as", x)


def less(x, y, name=None):
    """Alias of less_than (reference exports both)."""
    from .logic import less_than

    return less_than(x, y)


def floor_mod(x, y, name=None):
    """Alias of mod (reference exports both)."""
    from .math import mod

    return mod(x, y)


def negative(x, name=None):
    return apply_op(jnp.negative, "negative", x)


def positive(x, name=None):
    return apply_op(lambda v: +v, "positive", x)


def pdist(x, p=2.0, name=None):
    from ..nn.functional.common import pdist as f

    return f(x, p)


def cdist(x, y, p=2.0, compute_mode="use_mm_for_euclid_dist_if_necessary",
          name=None):
    from ..nn.functional.common import cdist as f

    return f(x, y, p, compute_mode)


# ----------------------------------------------------------------- env shims
def from_dlpack(dlpack):
    return Tensor(jnp.from_dlpack(dlpack))


def to_dlpack(x):
    """The array is its own DLPack exporter (`__dlpack__`/`__dlpack_device__`),
    which is what `from_dlpack` consumers take; bare capsules are refused."""
    return _val(x)


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """Maps to numpy printoptions (Tensor repr prints via numpy)."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    np.set_printoptions(**kw)


def disable_signal_handler():
    """Reference: disables paddle's C++ signal handlers; here the only
    installed handler is faulthandler's SIGUSR1 dump — unregister it."""
    import faulthandler
    import signal as _signal

    try:
        faulthandler.unregister(_signal.SIGUSR1)
    except Exception:
        pass


def check_shape(x):  # static-graph debug helper; shape is always concrete here
    return list(_val(x).shape)


from ..framework.lazy_init import LazyGuard  # noqa: E402,F401


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from ..nn.layer import Layer

    helper = Layer()
    return helper.create_parameter(list(shape), attr=attr, dtype=dtype,
                                   is_bias=is_bias,
                                   default_initializer=default_initializer)


def rank(input):
    return to_tensor(np.asarray(_val(input).ndim, np.int32))


def shape(input):
    return to_tensor(np.asarray(_val(input).shape, np.int64))


def get_cuda_rng_state():
    from ..framework.random import get_rng_state

    return get_rng_state()


def set_cuda_rng_state(state):
    from ..framework.random import set_rng_state

    return set_rng_state(state)


# ------------------------------------------------------------------ constants
pi = _math.pi
e = _math.e
inf = float("inf")
nan = float("nan")
newaxis = None
float8_e4m3fn = jnp.float8_e4m3fn
float8_e5m2 = jnp.float8_e5m2


# ------------------------------------------------------------- inplace family
def _graft(x: Tensor, out: Tensor) -> Tensor:
    """Install `out`'s value + tape linkage into `x` (paddle inplace
    semantics under the tape: the mutated tensor continues the graph)."""
    x._value = out._value
    x._grad_node = out._grad_node
    x._grad_index = out._grad_index
    x.stop_gradient = out.stop_gradient
    return x


def make_inplace(base_fn, name):
    def inplace(x, *args, **kwargs):
        from ..autograd import tape

        if (not x.stop_gradient and x._grad_node is None
                and tape.is_grad_enabled()):
            # same contract as the reference/torch: the pre-op value of a
            # grad-requiring leaf would be lost for its own backward
            raise RuntimeError(
                f"{name}: a leaf Tensor that requires grad is being used in "
                "an in-place operation")
        # the tape records input OBJECTS: pass a detached alias carrying the
        # ORIGINAL graph linkage so grafting the result onto `x` does not
        # splice the recorded input out of the chain
        alias = Tensor(x._value, stop_gradient=x.stop_gradient)
        alias._grad_node = x._grad_node
        alias._grad_index = x._grad_index
        return _graft(x, base_fn(alias, *args, **kwargs))

    inplace.__name__ = name
    inplace.__doc__ = (f"In-place variant of `{name[:-1]}` (reference "
                       f"tensor API): mutates and returns the input tensor.")
    return inplace
