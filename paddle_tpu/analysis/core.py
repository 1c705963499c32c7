"""Graph lint core: trace a program, hand its jaxpr to the rules, report.

The framework's thesis makes this possible: every training step and decode
loop is ONE traced program (ClosedJaxpr -> StableHLO), so hazards that only
surface as a melted dashboard at runtime — a forgotten donation doubling
HBM, an f32 matmul inside a bf16 block, a host callback inside the decode
scan — are statically visible before anything executes. This module owns the
program model and the walk; the rules live in ``rules.py``; severities,
findings and the allowlist in ``findings.py``.

Entry points (all return a ``Report``):

* ``analyze(fn, *args, **kwargs)`` — trace ``fn`` abstractly
  (``jax.make_jaxpr``; no device execution) and lint the jaxpr. Donation
  flags are read off the pjit equation when ``fn`` is jitted.
* ``analyze_jaxpr(closed_jaxpr, ...)`` — lint an already-traced program.
* ``analyze_lowered(lowered, ...)`` — lint a ``jax.stages.Lowered``: donation
  from ``args_info`` + the StableHLO text rules (reduced rule set; the
  jaxpr-level rules need ``analyze``/``analyze_jaxpr``).
* ``analyze_train_step(step, *args, **kwargs)`` — lint a
  ``jit/train.py:TrainStep`` exactly as its next ``__call__`` would trace,
  without mutating optimizer bookkeeping.

Nothing here executes the analyzed program and nothing raises out of the
rule loop: a rule that crashes on an exotic jaxpr becomes a ``high``
finding (rule-error) — never an exception in the caller's training loop,
and never a "clean" report from a lint whose rules did not run.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.extend import core as jex_core

from .findings import BUILTIN_ALLOWLIST, HIGH, INFO, WARN, Finding

__all__ = ["Thresholds", "InputInfo", "Program", "Report", "analyze",
           "analyze_jaxpr", "analyze_lowered", "analyze_train_step",
           "iter_eqns", "iter_consts", "source_of"]


class Thresholds:
    """Byte/count knobs the rules read. Defaults target real models; tests
    and the CLI can tighten them to exercise rules on smoke programs."""

    def __init__(self, donation_min_bytes=1 << 20, const_high_bytes=1 << 20,
                 const_warn_bytes=128 << 10, max_findings_per_rule=16):
        self.donation_min_bytes = int(donation_min_bytes)
        self.const_high_bytes = int(const_high_bytes)
        self.const_warn_bytes = int(const_warn_bytes)
        self.max_findings_per_rule = int(max_findings_per_rule)


class InputInfo:
    """One flattened program input: tree path, aval, donation flag
    (None = unknown: the program was not jitted and no donate_argnums were
    declared, so donation cannot be judged)."""

    __slots__ = ("path", "aval", "donated")

    def __init__(self, path, aval, donated):
        self.path = path
        self.aval = aval
        self.donated = donated

    @property
    def nbytes(self) -> int:
        return aval_bytes(self.aval)


def aval_bytes(aval) -> int:
    try:
        size = int(math.prod(aval.shape)) if aval.shape else 1
        return size * jnp.dtype(aval.dtype).itemsize
    except Exception:
        return 0


def fmt_bytes(n: int) -> str:
    for unit in ("B", "KiB", "MiB", "GiB"):
        if n < 1024 or unit == "GiB":
            return f"{n:.1f} {unit}" if unit != "B" else f"{n} B"
        n /= 1024.0
    return f"{n} B"


class Program:
    """Everything a rule may inspect about one traced program."""

    def __init__(self, name, closed_jaxpr, inputs, *, mesh_axes=None,
                 hot=True, static_args=None, compiled=None,
                 thresholds=None):
        self.name = name
        self.closed_jaxpr = closed_jaxpr
        self.inputs = inputs                    # list[InputInfo]
        self.mesh_axes = (tuple(mesh_axes) if mesh_axes is not None else None)
        self.hot = bool(hot)
        self.static_args = static_args or {}    # label -> value
        self.compiled = compiled                # optional jax executable
        self.thresholds = thresholds or Thresholds()

    @property
    def jaxpr(self):
        return self.closed_jaxpr.jaxpr


class Report:
    """The outcome of linting one program: kept findings, suppressed
    (finding, allowlist-entry) pairs, and the rules that ran."""

    def __init__(self, name, findings, suppressed, rules_run):
        self.name = name
        self.findings = list(findings)
        self.suppressed = list(suppressed)
        self.rules_run = tuple(rules_run)

    def high(self):
        return [f for f in self.findings if f.severity == HIGH]

    def by_rule(self) -> dict:
        out: dict = {}
        for f in self.findings:
            out[f.rule] = out.get(f.rule, 0) + 1
        return out

    def by_severity(self) -> dict:
        out: dict = {}
        for f in self.findings:
            out[f.severity] = out.get(f.severity, 0) + 1
        return out

    def to_dict(self) -> dict:
        return {
            "program": self.name,
            "findings": [f.to_dict() for f in self.findings],
            "suppressed": [
                {"finding": f.to_dict(), "reason": e.reason}
                for f, e in self.suppressed
            ],
            "by_rule": self.by_rule(),
            "high_total": len(self.high()),
        }

    def render(self) -> str:
        lines = [f"== {self.name}: {len(self.findings)} finding(s), "
                 f"{len(self.suppressed)} allowlisted =="]
        order = {HIGH: 0, WARN: 1, INFO: 2}
        for f in sorted(self.findings, key=lambda f: order[f.severity]):
            lines.append("  " + f.render().replace("\n", "\n  "))
        for f, e in self.suppressed:
            lines.append(f"  [allowlisted] {f.rule}: {f.message}")
            lines.append(f"      reason: {e.reason}")
        if not self.findings and not self.suppressed:
            lines.append("  clean")
        return "\n".join(lines)


# ------------------------------------------------------------------ walking
def _sub_jaxprs(params):
    """(tag, ClosedJaxpr|Jaxpr) pairs hiding in an equation's params —
    pjit/scan ('jaxpr'), while ('cond_jaxpr'/'body_jaxpr'), cond
    ('branches'), shard_map (open 'jaxpr'), custom_* calls, remat, etc.
    Generic over param names so new primitives keep walking."""
    found = []
    for k, v in params.items():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for i, item in enumerate(vs):
            if isinstance(item, (jex_core.Jaxpr, jex_core.ClosedJaxpr)):
                tag = k if len(vs) == 1 else f"{k}[{i}]"
                found.append((tag, item))
    return found


def _as_open(j):
    return j.jaxpr if isinstance(j, jex_core.ClosedJaxpr) else j


def _eqn_scope(eqn, scope):
    """Axis names brought into scope by this equation (shard_map mesh,
    pmap axis_name)."""
    name = eqn.primitive.name
    extra = ()
    if name == "shard_map":
        mesh = eqn.params.get("mesh")
        axes = getattr(mesh, "axis_names", None)
        if axes:
            extra = tuple(a for a in axes if isinstance(a, str))
    elif name == "xla_pmap":
        ax = eqn.params.get("axis_name")
        if isinstance(ax, str):
            extra = (ax,)
    return scope + extra if extra else scope


def iter_eqns(closed_jaxpr):
    """Yield (eqn, stack, axis_scope) over the whole program, recursing into
    every sub-jaxpr. ``stack`` is a tuple like ('jit:step_fn', 'scan');
    ``axis_scope`` the mesh/pmap axis names bound at that point."""

    def walk(jaxpr, stack, scope):
        for eqn in jaxpr.eqns:
            yield eqn, stack, scope
            subs = _sub_jaxprs(eqn.params)
            if not subs:
                continue
            name = eqn.primitive.name
            label = name
            if name in ("jit", "closed_call", "core_call", "custom_vjp_call",
                        "custom_jvp_call", "remat", "checkpoint"):
                label = f"{name}:{eqn.params.get('name', '')}".rstrip(":")
            inner_scope = _eqn_scope(eqn, scope)
            for tag, sub in subs:
                sub_label = label if len(subs) == 1 else f"{label}/{tag}"
                yield from walk(_as_open(sub), stack + (sub_label,),
                                inner_scope)

    yield from walk(closed_jaxpr.jaxpr, (), ())


def iter_consts(closed_jaxpr):
    """Yield (constvar, value, stack) for every captured constant, including
    those hoisted into nested ClosedJaxprs (jit closures land there)."""

    def walk(closed, stack):
        if isinstance(closed, jex_core.ClosedJaxpr):
            jaxpr = closed.jaxpr
            for var, val in zip(jaxpr.constvars, closed.consts):
                yield var, val, stack
        else:
            jaxpr = closed
        for eqn in jaxpr.eqns:
            for tag, sub in _sub_jaxprs(eqn.params):
                yield from walk(sub, stack + (f"{eqn.primitive.name}",))

    yield from walk(closed_jaxpr, ())


def source_of(eqn) -> str:
    """User-frame provenance of an equation, 'file:line (fn)' or ''."""
    from jax._src import source_info_util

    frame = source_info_util.user_frame(eqn.source_info.traceback)
    if frame is None:
        return ""
    fn = frame.function_name
    return f"{frame.file_name}:{frame.start_line}" + (f" ({fn})" if fn else "")


# ------------------------------------------------------------ rule running
def _run_rules(prog, rules, allowlist):
    from .rules import RULES

    selected = RULES if rules is None else {
        r: RULES[r] for r in rules
    }
    findings = []
    for rule_id, rule_fn in selected.items():
        try:
            got = list(rule_fn(prog))
        except Exception as e:  # a broken rule must not break the caller
            got = [Finding("rule-error", HIGH,
                           f"rule {rule_id} crashed: {e!r}",
                           subject=prog.name)]
        cap = prog.thresholds.max_findings_per_rule
        if len(got) > cap:
            got = got[:cap] + [Finding(
                rule_id, got[cap].severity,
                f"... {len(got) - cap} more {rule_id} finding(s) truncated",
                subject=prog.name)]
        for f in got:
            f.subject = f.subject or prog.name
        findings.extend(got)
    if allowlist is None:
        allowlist = BUILTIN_ALLOWLIST
    try:
        backend = jax.default_backend()
    except Exception:
        backend = ""
    kept, suppressed = allowlist.apply(findings, backend)
    return Report(prog.name, kept, suppressed, tuple(selected))


# ------------------------------------------------------------- entry points
def _flat_inputs(args, kwargs, invars, donated_flags, arg_labels=None):
    """Pair flattened (args, kwargs) tree paths with the jaxpr's input avals
    (same flatten order) and per-invar donation flags."""
    leaves, _ = jax.tree_util.tree_flatten_with_path((args, kwargs))
    infos = []
    for i, v in enumerate(invars):
        label = f"arg[{i}]"
        if i < len(leaves):
            path, _val = leaves[i]
            # paths look like [0][1]['w']; strip the (args, kwargs) pair
            # index and optionally swap the positional index for a name
            inner = path[1:]
            label = jax.tree_util.keystr(inner) or jax.tree_util.keystr(path)
            if (arg_labels is not None and inner
                    and getattr(path[0], "idx", None) == 0):
                idx = getattr(inner[0], "idx", None)
                if idx is not None and idx < len(arg_labels):
                    label = (arg_labels[idx]
                             + jax.tree_util.keystr(inner[1:]))
        donated = donated_flags[i] if donated_flags is not None else None
        infos.append(InputInfo(label, v.aval, donated))
    return infos


def _traceable_leaf(val) -> bool:
    return (hasattr(val, "shape") or hasattr(val, "_value")
            or isinstance(val, (int, float, complex, bool)))


def _is_static_arg(val) -> bool:
    """A top-level argument is static (jit would require static_argnums)
    when any of its leaves cannot be traced as an array."""
    leaves = jax.tree_util.tree_leaves(val)
    if not leaves:
        return False  # empty containers trace fine
    return not all(_traceable_leaf(v) for v in leaves)


def _split_static(args, kwargs):
    """Partition into (dynamic args/kwargs, static {label: value}) and a
    caller that re-merges statics at their original positions — make_jaxpr
    abstractifies every argument it is handed, so static values must be
    closed over instead."""
    static = {}
    dyn_args, static_pos = [], {}
    for i, a in enumerate(args):
        if _is_static_arg(a):
            static_pos[i] = a
            static[f"[{i}]"] = a
        else:
            dyn_args.append(a)
    dyn_kwargs, static_kw = {}, {}
    for k, v in kwargs.items():
        if _is_static_arg(v):
            static_kw[k] = v
            static[f"['{k}']"] = v
        else:
            dyn_kwargs[k] = v

    def merge(dyn):
        full, it = [], iter(dyn)
        for i in range(len(args)):
            full.append(static_pos[i] if i in static_pos else next(it))
        return tuple(full)

    return tuple(dyn_args), dyn_kwargs, static, static_kw, merge


def analyze(fn, *args, _name=None, _mesh_axes=None, _hot=True,
            _donate_argnums=None, _thresholds=None, _allowlist=None,
            _rules=None, _arg_labels=None, _compiled=None, **kwargs):
    """Trace ``fn(*args, **kwargs)`` abstractly and lint the program.

    Keyword knobs are underscore-prefixed so they can never collide with the
    analyzed function's own kwargs. ``_donate_argnums`` declares donation for
    non-jitted callables (jitted ones carry it in their pjit equation);
    ``_mesh_axes`` declares the deployment mesh axis names the
    collective-axis rule validates against; ``_hot=False`` relaxes the
    host-sync rule to warnings (the program is not a per-step hot path).
    """
    dyn_args, dyn_kwargs, static_args, static_kw, merge = _split_static(
        args, kwargs)
    # Tensors are registered pytrees: make_jaxpr flattens them itself, and
    # functions written over Tensors (TrainStep's step_fn) need them intact
    raw_args, raw_kwargs = dyn_args, dyn_kwargs
    if static_args:
        def traced_fn(*dyn, **kw):
            return fn(*merge(dyn), **dict(kw, **static_kw))
    else:
        traced_fn = fn
    name = _name or getattr(fn, "__name__", None) or repr(fn)
    try:
        closed = jax.make_jaxpr(traced_fn)(*raw_args, **raw_kwargs)
    except Exception as e:
        # an unhashable static argument (itself a finding) aborts tracing;
        # report what can be judged without a jaxpr instead of raising
        from .rules import static_arg_findings

        findings = static_arg_findings(static_args)
        findings.append(Finding(
            "rule-error", HIGH,
            f"program failed to trace, jaxpr rules skipped: {e!r}"[:300],
            subject=name))
        for f in findings:
            f.subject = f.subject or name
        return Report(name, findings, [], ("recompile-hazard",))

    donated = None
    n_in = len(closed.jaxpr.invars)
    eqns = closed.jaxpr.eqns
    if (len(eqns) == 1 and eqns[0].primitive.name == "jit"
            and "donated_invars" in eqns[0].params):
        # map per-eqn-operand flags back onto the outer invars (operand
        # order can differ from invar order when args are unused)
        flag_of = {v: d for v, d in zip(eqns[0].invars,
                                        eqns[0].params["donated_invars"])
                   if not isinstance(v, jex_core.Literal)}
        donated = tuple(flag_of.get(v, False) for v in closed.jaxpr.invars)
    elif _donate_argnums is not None:
        dn = set(_donate_argnums)
        flags = []
        for i, a in enumerate(dyn_args):
            flags.extend([i in dn] * len(jax.tree_util.tree_leaves(a)))
        flags.extend([False] * len(jax.tree_util.tree_leaves(dyn_kwargs)))
        donated = tuple(flags) if len(flags) == n_in else None

    inputs = _flat_inputs(dyn_args, dyn_kwargs, closed.jaxpr.invars, donated,
                          arg_labels=_arg_labels)
    prog = Program(name, closed, inputs, mesh_axes=_mesh_axes, hot=_hot,
                   static_args=static_args, compiled=_compiled,
                   thresholds=_thresholds)
    return _run_rules(prog, _rules, _allowlist)


def analyze_jaxpr(closed_jaxpr, *, donated=None, arg_names=None, name="jaxpr",
                  mesh_axes=None, hot=True, thresholds=None, allowlist=None,
                  rules=None, compiled=None):
    """Lint an already-traced ``ClosedJaxpr``. ``donated`` is an optional
    per-invar tuple of flags; ``arg_names`` optional per-invar labels."""
    invars = closed_jaxpr.jaxpr.invars
    inputs = []
    for i, v in enumerate(invars):
        label = (arg_names[i] if arg_names is not None and i < len(arg_names)
                 else f"arg[{i}]")
        flag = donated[i] if donated is not None and i < len(donated) else None
        inputs.append(InputInfo(label, v.aval, flag))
    prog = Program(name, closed_jaxpr, inputs, mesh_axes=mesh_axes, hot=hot,
                   thresholds=thresholds, compiled=compiled)
    return _run_rules(prog, rules, allowlist)


def analyze_lowered(lowered, *, name=None, hot=True, thresholds=None,
                    allowlist=None):
    """Lint a ``jax.stages.Lowered``: donation judged from ``args_info`` +
    the StableHLO main signature, host-sync and constant bloat from the
    module text. Reduced rule set (the jaxpr rules need ``analyze``)."""
    from .rules import lint_lowered

    th = thresholds or Thresholds()
    name = name or "lowered"
    findings = lint_lowered(lowered, name=name, hot=hot, thresholds=th)
    if allowlist is None:
        allowlist = BUILTIN_ALLOWLIST
    try:
        backend = jax.default_backend()
    except Exception:
        backend = ""
    kept, suppressed = allowlist.apply(findings, backend)
    return Report(name, kept, suppressed,
                  ("donation-miss", "host-sync", "constant-bloat"))


def analyze_train_step(step, *args, name=None, thresholds=None,
                       allowlist=None, rules=None, mesh_axes=None, **kwargs):
    """Lint a ``jit/train.py:TrainStep`` over the exact traced-input tuple
    its next ``__call__`` would consume (peeked — no optimizer bookkeeping
    is mutated, nothing executes). The compiled AOT executable, when primed,
    rides along so donation findings can cross-check
    ``observability.xla.memory_stats`` alias bytes."""
    _, traced = step._prep_inputs(advance=False)
    if name is None:
        name = f"train_step:{type(step.model).__name__}"
    return analyze(
        step._jitted, *traced, args, kwargs,
        _name=name, _mesh_axes=mesh_axes, _hot=True,
        _thresholds=thresholds, _allowlist=allowlist, _rules=rules,
        _compiled=getattr(step, "_compiled", None),
        _arg_labels=("state", "acc_state", "step_i", "lr", "rng_key",
                     "batch", "batch_kwargs"))
