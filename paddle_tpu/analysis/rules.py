"""The graph-lint rule catalog (see docs/ANALYSIS.md for the full taxonomy).

Six rules, each targeting one way a traced-and-compiled program silently
burns money on a TPU:

* ``donation-miss``       — a large aliasable input (params, optimizer
  state, KV pools) is consumed but not donated: XLA holds input AND output
  copies, doubling that buffer's HBM. Cross-checked against the compiled
  executable's ``memory_stats`` alias bytes when one is attached.
* ``dtype-upcast``        — an f32/f64 ``convert_element_type`` chain feeds
  an MXU op (dot/conv) whose operand was bf16/f16: the matmul runs at half
  (or an eighth, f64) MXU throughput for no numerics the caller asked for.
  Any float64 anywhere is flagged too (accidental weak-type promotion).
* ``host-sync``           — ``pure_callback``/``io_callback``/
  ``debug_callback`` inside a hot program (TrainStep, decode): each one
  forces a device→host round trip per step.
* ``constant-bloat``      — big arrays baked into the program as constants:
  they live in HBM per-executable, re-stage on every compile, and hash into
  the trace fingerprint (slow retraces).
* ``recompile-hazard``    — argument/closure patterns that make XLA rebuild
  the program per step: weak-typed Python scalars (alternating with NumPy
  scalars refingerprints — the same aval-fingerprint machinery as the
  StepMonitor recompilation sentinel), identity-hashed or unhashable
  static arguments.
* ``collective-axis``     — psum/ppermute/all_gather axis names validated
  against the enclosing shard_map/pmap scope and the declared deployment
  mesh axes.

Rules are pure functions ``rule(Program) -> [Finding]`` registered in
``RULES``; the runner in ``core.py`` caps, attributes and allowlists them.
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
from jax.extend import core as jex_core

from .core import (
    Thresholds,
    _as_open,
    _sub_jaxprs,
    aval_bytes,
    fmt_bytes,
    iter_consts,
    iter_eqns,
    source_of,
)
from .findings import HIGH, WARN, Finding

__all__ = ["RULES", "lint_lowered"]

NARROW = {jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16)}
WIDE = {jnp.dtype(jnp.float32), jnp.dtype(jnp.float64)}
MXU_PRIMS = {"dot_general", "conv_general_dilated"}
# shape/layout ops that carry an upcast value unchanged into a matmul
LAYOUT_PRIMS = {"transpose", "reshape", "broadcast_in_dim", "squeeze",
                "slice", "dynamic_slice", "rev", "copy", "gather",
                "concatenate"}
CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback",
                  "debug_print"}
COLLECTIVE_PRIMS = {"psum", "psum2", "pbroadcast", "pmax", "pmin",
                    "ppermute", "all_gather", "all_to_all", "psum_scatter",
                    "pgather", "axis_index"}


def _is_float(dtype) -> bool:
    try:
        return jnp.issubdtype(dtype, jnp.floating)
    except Exception:
        return False


def _np_dtype(dtype):
    """numpy dtype or None for extended dtypes (PRNG keys) that
    ``jnp.dtype`` refuses."""
    try:
        return jnp.dtype(dtype)
    except Exception:
        return None


# ------------------------------------------------------------ donation-miss
def rule_donation_miss(prog):
    """Large array inputs consumed but not donated while a same-shaped
    output exists (the state-in/state-out pattern XLA could alias)."""
    if all(i.donated is None for i in prog.inputs):
        return []  # not a jitted program and no donate_argnums declared
    th = prog.thresholds.donation_min_bytes
    out_shapes = {}
    for v in prog.jaxpr.outvars:
        aval = getattr(v, "aval", None)
        dt = _np_dtype(getattr(aval, "dtype", None))
        if aval is not None and dt is not None \
                and getattr(aval, "shape", None) is not None:
            key = (tuple(aval.shape), dt.name)
            out_shapes[key] = out_shapes.get(key, 0) + 1
    findings = []
    for info in prog.inputs:
        if info.donated or info.donated is None:
            continue
        nbytes = info.nbytes
        dt = _np_dtype(getattr(info.aval, "dtype", None))
        if nbytes < th or dt is None:
            continue
        key = (tuple(info.aval.shape), dt.name)
        if out_shapes.get(key, 0) <= 0:
            continue
        out_shapes[key] -= 1  # each output aliases at most one input
        findings.append(Finding(
            "donation-miss", HIGH,
            f"input {info.path} ({fmt_bytes(nbytes)}, {dt.name}"
            f"{list(info.aval.shape)}) is consumed and a same-shaped output "
            f"exists, but the buffer is not donated — XLA holds two copies",
            where=info.path,
            remediation="add the argument to donate_argnums (jax.jit) so "
                        "XLA aliases it in place; saves "
                        f"{fmt_bytes(nbytes)} of HBM"))
    # cross-check declared donation against what the executable actually
    # aliased (observability.xla memory_stats)
    if prog.compiled is not None and any(i.donated for i in prog.inputs):
        from ..observability.xla import memory_stats

        mem = memory_stats(prog.compiled)
        donated_bytes = sum(i.nbytes for i in prog.inputs if i.donated)
        if mem and donated_bytes >= th and mem.get("alias_bytes", 0) == 0:
            findings.append(Finding(
                "donation-miss", WARN,
                f"{fmt_bytes(donated_bytes)} declared donated but the "
                "compiled executable aliases 0 bytes "
                "(memory_stats.alias_bytes) — this backend ignores "
                "donation, the memory plan still holds both copies",
                remediation="expected on CPU; on TPU investigate why XLA "
                            "refused the aliasing (dtype/layout mismatch "
                            "between the input and its would-be output)"))
    return findings


# ------------------------------------------------------------- dtype-upcast
def _strong_f64(aval) -> bool:
    """A float64 aval that is genuinely f64 compute: weak-typed scalars
    (Python floats under global x64) demote on promotion and are the
    recompile-hazard rule's business, not this one's."""
    if aval is None or getattr(aval, "dtype", None) is None:
        return False
    dt = _np_dtype(aval.dtype)
    if dt is None or dt != jnp.dtype(jnp.float64):
        return False
    return not (getattr(aval, "weak_type", False)
                and getattr(aval, "shape", ()) == ())


def _taint_walk(jaxpr, tainted, findings, stack, seen_f64):
    """Track values that are pure upcasts of narrow tensors; flag MXU ops
    consuming them. `tainted` maps Var -> source dtype name."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        # strong float64 anywhere is its own hazard (weak-type promotion)
        for v in eqn.outvars:
            if not seen_f64 and _strong_f64(getattr(v, "aval", None)):
                seen_f64.append(source_of(eqn) or name)
        if name in MXU_PRIMS:
            hit = [tainted[v] for v in eqn.invars
                   if not isinstance(v, jex_core.Literal) and v in tainted]
            if hit:
                out_dt = _np_dtype(eqn.outvars[0].aval.dtype)
                findings.append(Finding(
                    "dtype-upcast", HIGH,
                    f"{name} consumes operand(s) upcast from {hit[0]} — the "
                    f"matmul runs in "
                    f"{out_dt.name if out_dt is not None else '?'} at half "
                    "MXU throughput" + (f" (inside {'/'.join(stack)})"
                                        if stack else ""),
                    where=source_of(eqn),
                    remediation="keep the operands in their narrow dtype "
                                "(drop the .astype) or, if f32 accumulation "
                                "is the goal, use preferred_element_type "
                                "instead of upcasting the inputs"))
        if name == "convert_element_type":
            src = eqn.invars[0]
            src_aval = getattr(src, "aval", None)
            dst = _np_dtype(eqn.params.get("new_dtype",
                                           eqn.outvars[0].aval.dtype))
            if (dst is not None and src_aval is not None
                    and _is_float(src_aval.dtype)):
                if (jnp.dtype(src_aval.dtype) in NARROW and dst in WIDE):
                    tainted[eqn.outvars[0]] = jnp.dtype(src_aval.dtype).name
                elif (not isinstance(src, jex_core.Literal)
                      and src in tainted and dst in NARROW):
                    pass  # downcast back: taint does not propagate
                elif (not isinstance(src, jex_core.Literal)
                      and src in tainted):
                    tainted[eqn.outvars[0]] = tainted[src]
        elif name in LAYOUT_PRIMS:
            src = eqn.invars[0]
            if not isinstance(src, jex_core.Literal) and src in tainted:
                tainted[eqn.outvars[0]] = tainted[src]
        # recurse with taint mapped across the sub-jaxpr boundary
        subs = _sub_jaxprs(eqn.params)
        for tag, sub in subs:
            open_sub = _as_open(sub)
            inner = {}
            n_in, n_sub = len(eqn.invars), len(open_sub.invars)
            if n_sub == n_in:
                pairs = zip(eqn.invars, open_sub.invars)
            elif n_sub == n_in - 1:  # cond/switch: index operand first
                pairs = zip(eqn.invars[1:], open_sub.invars)
            else:
                pairs = ()
            for outer_v, inner_v in pairs:
                if (not isinstance(outer_v, jex_core.Literal)
                        and outer_v in tainted):
                    inner[inner_v] = tainted[outer_v]
            _taint_walk(open_sub, inner, findings, stack + (name,), seen_f64)


def rule_dtype_upcast(prog):
    """f32/f64 upcast chains feeding MXU ops inside bf16/f16 regions, and
    any float64 leakage (weak-type promotion)."""
    findings: list = []
    seen_f64: list = []
    _taint_walk(prog.jaxpr, {}, findings, (), seen_f64)
    for v in list(prog.jaxpr.invars) + list(prog.jaxpr.constvars):
        if not seen_f64 and _strong_f64(getattr(v, "aval", None)):
            seen_f64.append("program input/constant")
    if seen_f64:
        findings.append(Finding(
            "dtype-upcast", HIGH,
            f"float64 appears in the program (first at {seen_f64[0]}) — "
            "on TPU f64 matmuls run ~8x slower than bf16 and usually mean "
            "an accidental weak-type promotion (Python float * array)",
            where=seen_f64[0],
            remediation="cast to float32/bfloat16 explicitly, or keep "
                        "jax_enable_x64 off"))
    return findings


# --------------------------------------------------------------- host-sync
def rule_host_sync(prog):
    """Host callbacks inside compiled programs: each is a device->host
    round trip per execution (per STEP in a train/decode program, per scan
    iteration when inside the loop body)."""
    findings = []
    for eqn, stack, _scope in iter_eqns(prog.closed_jaxpr):
        name = eqn.primitive.name
        if name not in CALLBACK_PRIMS and "callback" not in name:
            continue
        in_loop = any(s.startswith(("scan", "while")) for s in stack)
        sev = HIGH if (prog.hot or in_loop) else WARN
        cb = eqn.params.get("callback", None)
        cb_name = getattr(cb, "__name__", None) or getattr(
            getattr(cb, "callback_func", None), "__name__", "") or ""
        where_note = (" inside the compiled loop body" if in_loop
                      else " in a hot-path program" if prog.hot else "")
        findings.append(Finding(
            "host-sync", sev,
            f"{name}{f' ({cb_name})' if cb_name else ''}{where_note}"
            f"{' [' + '/'.join(stack) + ']' if stack else ''} forces a "
            "device→host sync every execution",
            where=source_of(eqn),
            remediation="remove the callback from the step program (fetch "
                        "results outside, or gate debug prints behind an "
                        "eager-only flag); io_callback/debug_callback also "
                        "block XLA's async dispatch"))
    return findings


# ------------------------------------------------------------ constant-bloat
def rule_constant_bloat(prog):
    """Arrays baked into the graph as constants above the byte thresholds:
    HBM cost per executable + trace-time hashing + re-staging per compile."""
    th = prog.thresholds
    findings = []
    for var, _val, stack in iter_consts(prog.closed_jaxpr):
        # sized off the constvar's aval: the value may be a jax literal
        # wrapper (TypedNdArray) that carries no nbytes
        nbytes = aval_bytes(var.aval)
        if nbytes < th.const_warn_bytes:
            continue
        sev = HIGH if nbytes >= th.const_high_bytes else WARN
        shape = tuple(var.aval.shape)
        dtype = var.aval.dtype
        findings.append(Finding(
            "constant-bloat", sev,
            f"constant {dtype}{list(shape)} ({fmt_bytes(nbytes)}) is baked "
            f"into the program"
            f"{' [' + '/'.join(stack) + ']' if stack else ''} — it occupies "
            "HBM per executable, hashes into every trace, and re-stages on "
            "each compile",
            where="/".join(stack) or "top-level consts",
            remediation="pass the array as an argument (jit will stage it "
                        "once as an input buffer) instead of closing over "
                        "it"))
    return findings


# ---------------------------------------------------------- recompile-hazard
def _default_hash_identity(v) -> bool:
    t = type(v)
    return (getattr(t, "__hash__", None) is object.__hash__
            and getattr(t, "__eq__", None) is object.__eq__)


def rule_recompile_hazard(prog):
    """Argument/closure patterns that re-fingerprint the program per call —
    the same aval-fingerprint machinery the StepMonitor recompilation
    sentinel counts at runtime, caught at trace time instead."""
    findings = []
    inputs = prog.inputs
    for i, v in enumerate(prog.jaxpr.invars):
        aval = getattr(v, "aval", None)
        if aval is None:
            continue
        if getattr(aval, "weak_type", False) and aval.shape == ():
            label = inputs[i].path if i < len(inputs) else f"arg[{i}]"
            findings.append(Finding(
                "recompile-hazard", WARN,
                f"scalar argument {label} is weak-typed (traced from a "
                "Python scalar): alternating Python and NumPy/jnp scalars "
                "across calls changes the aval fingerprint and silently "
                "recompiles",
                where=label,
                remediation="pass a committed-dtype scalar "
                            "(jnp.asarray(x, jnp.float32)) consistently, or "
                            "hoist it to a closure constant if it never "
                            "changes"))
    for var, val, stack in iter_consts(prog.closed_jaxpr):
        aval = getattr(var, "aval", None)
        if (aval is not None and getattr(aval, "weak_type", False)
                and aval.shape == ()):
            findings.append(Finding(
                "recompile-hazard", WARN,
                "a Python scalar is closed over and baked as a weak-typed "
                f"constant (value {np.asarray(val).item()!r}"
                f"{' [' + '/'.join(stack) + ']' if stack else ''}): a "
                "closure rebuilt per step retraces, and a value change "
                "after the first trace is silently ignored",
                where="/".join(stack) or "top-level consts",
                remediation="pass the scalar as an argument, or inline it "
                            "as a literal if truly constant"))
    findings.extend(static_arg_findings(prog.static_args))
    return findings


def static_arg_findings(static_args):
    """The static-argument half of recompile-hazard, callable on its own:
    ``analyze`` falls back to it when an unhashable static argument makes
    the program refuse to trace at all."""
    findings = []
    for label, v in static_args.items():
        try:
            hash(v)
        except TypeError:
            findings.append(Finding(
                "recompile-hazard", HIGH,
                f"static argument {label} ({type(v).__name__}) is "
                "unhashable — jit rejects it, and hashable wrappers built "
                "per call recompile every step",
                where=label,
                remediation="use a hashable static value (tuple instead of "
                            "list, frozen dataclass instead of dict)"))
            continue
        if _default_hash_identity(v):
            findings.append(Finding(
                "recompile-hazard", HIGH,
                f"static argument {label} ({type(v).__name__}) hashes by "
                "object identity — a fresh instance per call fingerprints "
                "differently and compiles a NEW program every step",
                where=label,
                remediation="define __hash__/__eq__ over the fields that "
                            "matter, or pass a stable singleton"))
    return findings


# ----------------------------------------------------------- collective-axis
def _axis_names(params):
    axes = params.get("axes", params.get("axis_name", ()))
    if isinstance(axes, (str, int)):
        axes = (axes,)
    return tuple(a for a in axes if isinstance(a, str))


def rule_collective_axis(prog):
    """Collective axis names must be bound by an enclosing shard_map/pmap
    and — when the caller declares the deployment mesh — exist on it."""
    declared = prog.mesh_axes
    findings = []
    for eqn, stack, scope in iter_eqns(prog.closed_jaxpr):
        name = eqn.primitive.name
        if name == "shard_map" and declared is not None:
            mesh = eqn.params.get("mesh")
            for ax in getattr(mesh, "axis_names", ()) or ():
                if isinstance(ax, str) and ax not in declared:
                    findings.append(Finding(
                        "collective-axis", HIGH,
                        f"shard_map binds mesh axis '{ax}' but the declared "
                        f"deployment mesh has axes {declared} — this "
                        "program cannot run on that mesh",
                        where=source_of(eqn),
                        remediation="rename the program's mesh axes to the "
                                    "deployment mesh's, or extend the mesh"))
        if name not in COLLECTIVE_PRIMS:
            continue
        for ax in _axis_names(eqn.params):
            if ax not in scope:
                findings.append(Finding(
                    "collective-axis", HIGH,
                    f"{name} uses axis '{ax}' which no enclosing "
                    f"shard_map/pmap binds (scope: {scope or '()'})",
                    where=source_of(eqn),
                    remediation="run the collective inside a shard_map "
                                "whose mesh defines the axis"))
            elif declared is not None and ax not in declared:
                findings.append(Finding(
                    "collective-axis", HIGH,
                    f"{name} reduces over axis '{ax}' but the declared "
                    f"deployment mesh has axes {declared}",
                    where=source_of(eqn),
                    remediation="align the collective's axis_name with the "
                                "deployment mesh axes"))
    return findings


RULES = {
    "donation-miss": rule_donation_miss,
    "dtype-upcast": rule_dtype_upcast,
    "host-sync": rule_host_sync,
    "constant-bloat": rule_constant_bloat,
    "recompile-hazard": rule_recompile_hazard,
    "collective-axis": rule_collective_axis,
}


# ------------------------------------------------------- lowered-text rules
_MLIR_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "i64": 8,
                     "ui64": 8, "i32": 4, "ui32": 4, "i16": 2, "ui16": 2,
                     "i8": 1, "ui8": 1, "i1": 1, "i4": 1, "ui4": 1}


def _mlir_dtype(dtype) -> str:
    name = jnp.dtype(dtype).name
    return {"float64": "f64", "float32": "f32", "bfloat16": "bf16",
            "float16": "f16", "int64": "i64", "int32": "i32",
            "int16": "i16", "int8": "i8", "uint8": "ui8",
            "bool": "i1"}.get(name, name)


def _tensor_type(shape, dtype) -> str:
    dims = "x".join(str(d) for d in shape)
    return f"tensor<{dims + 'x' if dims else ''}{_mlir_dtype(dtype)}>"


def _tensor_bytes(type_str) -> int:
    m = re.match(r"tensor<([0-9x]*)x?([a-z]+[0-9]+|i1)>", type_str)
    if not m:
        return 0
    dims, dt = m.groups()
    size = 1
    for d in filter(None, dims.split("x")):
        size *= int(d)
    return size * _MLIR_DTYPE_BYTES.get(dt, 4)


def lint_lowered(lowered, *, name, hot, thresholds: Thresholds):
    """The StableHLO-text subset of the rules for ``analyze_lowered``:
    donation (args_info + main signature), host-sync (callback custom
    calls), constant bloat (constant op tensor types)."""
    findings = []
    try:
        text = lowered.as_text()
    except Exception:
        text = ""
    # --- donation-miss from args_info + result types
    try:
        infos = jax.tree_util.tree_leaves(
            lowered.args_info, is_leaf=lambda l: hasattr(l, "donated"))
    except Exception:
        infos = []
    results = []
    m = re.search(r"func\.func public @main\((.*?)\)\s*->\s*\((.*?)\)\s*{",
                  text, re.S)
    if m:
        results = re.findall(r"tensor<[^>]+>", m.group(2))
    result_counts: dict = {}
    for r in results:
        result_counts[r] = result_counts.get(r, 0) + 1
    for i, info in enumerate(infos):
        if info.donated:
            continue
        tt = _tensor_type(info.shape, info.dtype)
        nbytes = _tensor_bytes(tt)
        if nbytes < thresholds.donation_min_bytes:
            continue
        if result_counts.get(tt, 0) <= 0:
            continue
        result_counts[tt] -= 1
        findings.append(Finding(
            "donation-miss", HIGH,
            f"lowered arg #{i} ({tt}, {fmt_bytes(nbytes)}) is not donated "
            "but a same-typed result exists — XLA holds two copies",
            where=f"args_info[{i}]", subject=name,
            remediation="add the argument to donate_argnums"))
    # --- host-sync from callback custom calls
    for ln in text.splitlines():
        if "custom_call" in ln and "callback" in ln:
            findings.append(Finding(
                "host-sync", HIGH if hot else WARN,
                "callback custom_call in the lowered module — a "
                "device→host sync every execution",
                where=ln.strip()[:160], subject=name,
                remediation="remove host callbacks from the compiled "
                            "program"))
    # --- constant-bloat from constant op types
    for m2 in re.finditer(
            r"stablehlo\.constant[^\n]*?:\s*(tensor<[^>]+>)", text):
        nbytes = _tensor_bytes(m2.group(1))
        if nbytes < thresholds.const_warn_bytes:
            continue
        sev = HIGH if nbytes >= thresholds.const_high_bytes else WARN
        findings.append(Finding(
            "constant-bloat", sev,
            f"constant {m2.group(1)} ({fmt_bytes(nbytes)}) baked into the "
            "lowered module",
            where="stablehlo.constant", subject=name,
            remediation="pass the array as an argument instead of closing "
                        "over it"))
    return findings
