"""Traceable control flow: while_loop / cond / case / switch_case / Assert / Print.

Reference: python/paddle/static/nn/control_flow.py (while_loop:755, case:1062,
switch_case:1185, cond:1637, Assert:59, Print:2215). The reference builds
sub-block ops (While/ConditionalBlock/select_input) into a static Program; the
TPU-native design has no Program — instead each construct has dual behavior:

- **Eager** (all predicates concrete): plain Python control flow. The chosen
  branch / loop body runs through the normal op layer, so tape autograd works
  through it unchanged (this matches the reference's dygraph branch, which also
  just evaluates the predicate and calls one fn).
- **Traced** (a predicate is a jax tracer, i.e. inside ``paddle.jit.to_static``
  or any jit): lowers to ``lax.while_loop`` / ``lax.cond`` / ``lax.switch`` so
  data-dependent control flow compiles into the XLA program instead of raising
  (closes the round-3 dy2static gap). Branches/bodies execute on Tensors that
  wrap tracers; tape recording is disabled inside (reverse-mode AD through a
  traced while_loop is not supported — same restriction as lax).
"""
from __future__ import annotations

import builtins

import jax
import jax.numpy as jnp
from jax import lax

from ...autograd import tape
from ...tensor import Tensor

__all__ = ["Assert", "Print", "case", "cond", "switch_case", "while_loop"]


def _unwrap(x):
    return x._value if isinstance(x, Tensor) else x


def _is_tensor_leaf(x):
    return isinstance(x, Tensor)


def _flatten(nest):
    """Flatten a nest of Tensors (list/tuple/dict allowed) to jax arrays."""
    leaves, treedef = jax.tree_util.tree_flatten(
        nest, is_leaf=_is_tensor_leaf
    )
    arrays = [jnp.asarray(_unwrap(leaf)) for leaf in leaves]
    return arrays, treedef


def _rebuild(arrays, treedef):
    tensors = [Tensor(a, stop_gradient=True) for a in arrays]
    return jax.tree_util.tree_unflatten(treedef, tensors)


def _scalar_bool(x):
    """Predicate Tensor/array -> scalar jax bool (shape [] or [1] accepted)."""
    v = jnp.asarray(_unwrap(x))
    if v.ndim > 0:
        v = v.reshape(())
    return v.astype(jnp.bool_)


def _is_traced(*preds) -> bool:
    return builtins.any(
        isinstance(jnp.asarray(_unwrap(p)), jax.core.Tracer) for p in preds
    )


def _check_dtypes(got, want, got_name, want_name):
    for g, w in zip(got, want):
        if g.dtype != w.dtype:
            raise ValueError(
                f"{got_name} output dtype {g.dtype} does not match "
                f"{want_name} dtype {w.dtype}; branches/bodies must return "
                "identical dtypes (cast explicitly)")


def _none_fn():
    return None


def _probe(fn):
    """Trace `fn` abstractly (no ops emitted) to learn its output structure."""
    box = []

    def probe():
        arrays, td = _flatten(fn())
        box.append(td)
        return tuple(arrays)

    specs = jax.eval_shape(probe)
    return box[0], list(specs)


def while_loop(cond, body, loop_vars, is_test=False, name=None):
    """Repeat `body` while `cond(*loop_vars)` holds.

    Reference: control_flow.py:755. `loop_vars` is a non-empty list/tuple of
    Tensors (nests allowed); `body` must return the same structure with the
    same shapes/dtypes. Returns the final loop vars (list, matching reference).
    """
    if not isinstance(loop_vars, (list, tuple)) or len(loop_vars) == 0:
        raise TypeError("loop_vars must be a non-empty list or tuple")
    loop_vars = list(loop_vars)

    first_pred = cond(*loop_vars)
    if not _is_traced(first_pred, *jax.tree_util.tree_leaves(
            loop_vars, is_leaf=_is_tensor_leaf)):
        # eager: plain Python loop, tape autograd flows through body ops
        pred = first_pred
        while builtins.bool(_unwrap(pred)):
            out = body(*loop_vars)
            if not isinstance(out, (list, tuple)):
                out = [out]
            loop_vars = list(out)
            pred = cond(*loop_vars)
        return loop_vars

    init_arrays, treedef = _flatten(loop_vars)

    def cond_fn(arrays):
        with tape.no_grad():
            vars_ = _rebuild(arrays, treedef)
            return _scalar_bool(cond(*vars_))

    def body_fn(arrays):
        with tape.no_grad():
            vars_ = _rebuild(arrays, treedef)
            out = body(*vars_)
            if not isinstance(out, (list, tuple)):
                out = [out]
            out_arrays, out_treedef = _flatten(list(out))
            if out_treedef != treedef:
                raise ValueError(
                    "body output structure does not match loop_vars: "
                    f"{out_treedef} vs {treedef}")
            _check_dtypes(out_arrays, init_arrays, "while_loop body", "loop_vars")
            return out_arrays

    final = lax.while_loop(cond_fn, body_fn, init_arrays)
    return list(_rebuild(final, treedef))


def _run_branch(fn):
    out = fn() if fn is not None else None
    return out


def cond(pred, true_fn=None, false_fn=None, name=None, return_names=None):
    """Run `true_fn()` if pred else `false_fn()`. Reference: control_flow.py:1637."""
    if not _is_traced(pred):
        if builtins.bool(_unwrap(pred)):
            return _run_branch(true_fn)
        return _run_branch(false_fn)

    # traced: each branch's ops are emitted ONLY inside its lax.cond branch
    # (so the unselected branch never executes at runtime); the output
    # structure/dtypes are probed up front with eval_shape, which traces
    # abstractly without adding ops to the outer program.
    with tape.no_grad():
        treedef, protos = _probe(true_fn if true_fn is not None else _none_fn)

        def t_fn(_):
            return _flatten(true_fn() if true_fn is not None else None)[0]

        def f_fn(_):
            out_arrays, out_treedef = _flatten(
                false_fn() if false_fn is not None else None)
            if out_treedef != treedef:
                raise ValueError(
                    "true_fn and false_fn must return the same structure: "
                    f"{treedef} vs {out_treedef}")
            _check_dtypes(out_arrays, protos, "false_fn", "true_fn")
            return out_arrays

        result = lax.cond(_scalar_bool(pred), t_fn, f_fn, None)
    return _rebuild(result, treedef)


def case(pred_fn_pairs, default=None, name=None):
    """First pair whose pred is True runs. Reference: control_flow.py:1062."""
    if not isinstance(pred_fn_pairs, (list, tuple)) or not pred_fn_pairs:
        raise TypeError("pred_fn_pairs must be a non-empty list or tuple")
    for pair in pred_fn_pairs:
        if not isinstance(pair, tuple) or len(pair) != 2 or not callable(pair[1]):
            raise TypeError("each element must be a (pred, callable) tuple")
    preds = [p for p, _ in pred_fn_pairs]
    fns = [f for _, f in pred_fn_pairs]
    if default is None:
        default = fns[-1]  # reference semantics: last fn doubles as default

    if not _is_traced(*preds):
        for p, f in zip(preds, fns):
            if builtins.bool(_unwrap(p)):
                return f()
        return default()

    # traced: index of first true pred, else len(preds) -> default branch
    stacked = jnp.stack([_scalar_bool(p) for p in preds])
    any_true = jnp.any(stacked)
    first = jnp.argmax(stacked)  # first True (argmax of bools)
    index = jnp.where(any_true, first, len(preds))
    return _switch_traced(index, fns + [default])


def switch_case(branch_index, branch_fns, default=None, name=None):
    """Select a branch by integer index. Reference: control_flow.py:1185.

    `branch_fns` is a dict {int: fn}, a list of (int, fn), or a list of fns
    (implicitly enumerated).
    """
    if isinstance(branch_fns, dict):
        items = sorted(branch_fns.items())
    elif isinstance(branch_fns, (list, tuple)):
        if branch_fns and callable(branch_fns[0]):
            items = list(enumerate(branch_fns))
        else:
            items = sorted(((int(k), f) for k, f in branch_fns),
                           key=lambda kv: kv[0])
    else:
        raise TypeError("branch_fns must be a dict, list or tuple")
    if not items:
        raise TypeError("branch_fns must not be empty")
    keys = [k for k, _ in items]
    if len(set(keys)) != len(keys):
        raise ValueError(f"duplicate branch keys: {keys}")
    fns = [f for _, f in items]
    if default is None:
        default = fns[-1]

    if not _is_traced(branch_index):
        idx = builtins.int(_unwrap(branch_index))
        for k, f in items:
            if k == idx:
                return f()
        return default()

    idx = jnp.asarray(_unwrap(branch_index)).reshape(()).astype(jnp.int32)
    pos = jnp.full((), len(fns), jnp.int32)  # default slot
    for i, k in enumerate(keys):
        pos = jnp.where(idx == k, jnp.int32(i), pos)
    return _switch_traced(pos, fns + [default])


def _switch_traced(index, fns):
    """lax.switch over no-arg branch closures returning matching nests."""
    with tape.no_grad():
        treedef, protos = _probe(fns[0])

        def make(fn):
            def branch(_):
                out_arrays, out_treedef = _flatten(fn())
                if out_treedef != treedef:
                    raise ValueError(
                        "all branches must return the same structure: "
                        f"{treedef} vs {out_treedef}")
                _check_dtypes(out_arrays, protos, "branch", "branch 0")
                return out_arrays
            return branch

        index = jnp.clip(jnp.asarray(index).astype(jnp.int32), 0, len(fns) - 1)
        result = lax.switch(index, [make(f) for f in fns], None)
    return _rebuild(result, treedef)


def Assert(cond, data=None, summarize=20, name=None):
    """Assert a condition holds. Reference: control_flow.py:59.

    Eager: raises ValueError with the first `summarize` elements of each tensor
    in `data`. Traced: emits a debug print (a host callback) only when
    violated.
    """
    if not _is_traced(cond):
        if not builtins.bool(jnp.asarray(_unwrap(cond)).all()):
            parts = []
            for d in (data or []):
                v = jnp.asarray(_unwrap(d)).reshape(-1)[:summarize]
                parts.append(str(v))
            raise ValueError(
                f"Assert failed{': ' + ', '.join(parts) if parts else ''}")
        return None
    ok = _scalar_bool(cond)
    msg = "Assert violated" + ("" if not name else f" ({name})")
    lax.cond(ok, lambda: None, lambda: jax.debug.print(msg))
    return None


def Print(input, first_n=-1, message=None, summarize=20,
          print_tensor_name=True, print_tensor_type=True,
          print_tensor_shape=True, print_tensor_layout=True,
          print_tensor_lod=True, print_phase="both"):
    """Print a tensor's value (works inside traced programs via jax.debug.print).

    Reference: control_flow.py:2215. Returns the input unchanged.
    """
    prefix = (message + " ") if message else ""
    v = _unwrap(input)
    if isinstance(jnp.asarray(v), jax.core.Tracer):
        jax.debug.print(prefix + "{x}", x=v)
    else:
        arr = jnp.asarray(v).reshape(-1)[:summarize]
        print(f"{prefix}{arr}")
    return input
