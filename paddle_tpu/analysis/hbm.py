"""HBM residency lint: static peak-memory analysis + the deployment budget.

The paper's TPU-native design lives or dies on HBM residency (ROADMAP item
1: "tp sized by KV residency first") — yet until this pass nothing in the
repo could statically answer "will this ServingConfig fit on a chip?". Two
halves, same shape as every prior lint (hazard checkable before deploy):

1. **Liveness / peak-memory estimator** (`estimate_peak`) — the spirit of
   XLA's buffer-assignment liveness analysis run at the jaxpr level: walk
   the equations in schedule order tracking the live buffer set. Invars are
   held to their last use when donated (released to their output aliases)
   and to program end otherwise (the caller still owns them); consts and
   outvars are resident to the end; scan/while/cond bodies are analyzed
   recursively — scan/while carries are pinned live across their body so
   the old+new carry coexist (double buffering), cond takes the max over
   branches. The result is a per-program ``peak_bytes`` watermark, the
   top-K live buffers AT the peak with per-buffer provenance (the jaxpr
   equation's user frame), and a ``memory_stats``-shaped dict for the
   observability fallback (``estimated=True``).

   Known approximations (documented in docs/ANALYSIS.md): the walk uses
   the jaxpr's textual schedule (XLA may reorder), it never fuses (XLA's
   elementwise fusion elides temps the walk counts — an OVER-estimate),
   and nested-call donation frees inside the callee but not the caller's
   operand slot (a second over-estimate). Both biases are conservative:
   the static number errs toward "needs more HBM", which is the safe
   direction for a budget gate, and `estimate-drift` keeps it honest
   against the real ``CompiledMemoryStats`` wherever a backend has them.

2. **`DeploymentPlan`** — the per-chip residency contract for one
   ``ServingConfig`` (reusing the ISSUE-13 config → program-inventory
   derivation): params/tp (optimizer-free serving state), the
   ``PagedKVCache`` pool per chip, a prefix-cache parked tier carved out
   of the pool, the max static temp peak across every manifest
   program, and (ISSUE-15) the resident multi-LoRA adapter banks — all
   evaluated against a declared chip HBM budget with headroom.

Rules (shared Finding/Allowlist machinery):

* ``hbm-over-budget`` (HIGH) — planned residency exceeds
  budget × (1 − headroom): the replica OOMs or swaps before it serves.
* ``estimate-drift``   (HIGH) — static peak vs the compiled program's
  ``memory_stats().peak_bytes`` diverge beyond tolerance where real stats
  exist. The estimator is self-validating: drift means the plan's temp
  numbers are fiction, not that the chip is fine.
* ``oversized-temp``   (WARN; HIGH in strict/fixture mode) — one live
  buffer at a program's peak exceeds 25% of the budget: a remat/chunking
  opportunity, and the classic giant-broadcast footgun.
* ``pool-misfit``      (WARN; HIGH in strict/fixture mode) — the pool
  cannot cover ``max_slots × blocks_for(max_seq_len)`` (requests queue on
  blocks at exactly full concurrency), or >30% of the pool is unreachable
  by any admissible request (HBM bought, never used).

Gating: ``python -m paddle_tpu.analysis --self-check`` runs the
``hbm_residency`` zoo entry (smoke GPT step programs + the smoke pool
against a smoke budget, drift-checked against real stats where the backend
provides them); ``--hbm [NAME|FILE.json]`` prints the residency table (the
deploy-review artifact) or runs seeded fixtures strict; ``plan_kv_pool``
is the runtime half — the continuous scheduler's ``hbm_budget=`` knob
sizes its pool from the plan and publishes
``paddle_hbm_planned_bytes{component=params|kv_pool|prefix_tier|temps|``
``adapter_bank}`` next to ``paddle_hbm_budget_bytes`` so a scrape shows
plan vs actual.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os

from jax.extend import core as jex_core

from .core import Report, aval_bytes, fmt_bytes, source_of, _sub_jaxprs
from .findings import HIGH, WARN, Allowlist, Finding

__all__ = [
    "HBM_RULES", "BUILTIN_HBM_ALLOWLIST", "PeakEstimate", "ProgramEstimate",
    "DeploymentPlan", "estimate_peak", "estimate_memory_stats",
    "analyze_hbm_plan", "plan_kv_pool", "params_bytes_of",
    "blocks_for", "per_block_bytes", "smoke_plan", "smoke_budget_bytes",
    "hbm_fixture_reports", "analyze_hbm_residency",
]

HBM_RULES = {
    "hbm-over-budget":
        "the planned per-chip residency (params/tp + KV pool + prefix tier "
        "+ max program temp peak) exceeds budget x (1 - headroom) — the "
        "replica OOMs or thrashes before it serves",
    "estimate-drift":
        "the static peak estimate and the compiled program's real "
        "memory_stats().peak_bytes diverge beyond tolerance — the plan's "
        "numbers are fiction until the estimator (or the trace) is fixed",
    "oversized-temp":
        "one live buffer at a program's static peak exceeds 25% of the "
        "budget — a remat/chunking opportunity (HIGH in strict mode)",
    "pool-misfit":
        "the KV pool cannot cover max_slots x blocks_for(max_seq_len), or "
        ">30% of its blocks are unreachable by any admissible request",
}

DEFAULT_HEADROOM = 0.08           # fragmentation + allocator slack
OVERSIZED_TEMP_FRACTION = 0.25
POOL_WASTE_FRACTION = 0.30
# estimate-drift gate: the walk never fuses and XLA reorders, so agreement
# is order-of-magnitude, not byte-exact. Static must land within
# [real/(1+tol), real*(1+tol)] (tol=1.0: within 2x either way) above a
# 1 MiB absolute floor — forgetting the KV pool arguments (the dominant
# serving bytes) or double-counting a scan still blows this wide open.
DRIFT_REL_TOL = 1.0
DRIFT_ABS_FLOOR = 1 << 20

# The hbm allowlist ships EMPTY on purpose: the zoo residency entry is
# expected to be clean with no explained exceptions (unlike the donation/
# paged-key lists). It exists so fixture/CLI plumbing and the stale-entry
# audit treat all four lints uniformly.
BUILTIN_HBM_ALLOWLIST = Allowlist([])


# ===================================================================== walk
def _is_var(v):
    import jax

    return isinstance(v, jex_core.Var) and not isinstance(v, jax.core.DropVar)


class _Buf:
    """One live buffer during the walk: bytes + provenance for the top-K
    breakdown. ``kind``: argument | const | temp | output | internal."""

    __slots__ = ("label", "bytes", "where", "kind")

    def __init__(self, label, nbytes, where, kind):
        self.label = label
        self.bytes = int(nbytes)
        self.where = where
        self.kind = kind

    def to_dict(self):
        return {"label": self.label, "bytes": self.bytes,
                "where": self.where, "kind": self.kind}


class PeakEstimate:
    """The estimator's verdict on one program. ``at_peak`` is the live set
    snapshot (top-K by bytes) at the watermark; ``peak_bytes_undonated``
    re-runs the walk with donation ignored — the number to compare against
    a backend that does not implement donation (CPU keeps both copies, so
    its real stats match the undonated walk, not the donated one)."""

    __slots__ = ("name", "peak_bytes", "peak_bytes_undonated",
                 "argument_bytes", "output_bytes", "alias_bytes",
                 "temp_bytes", "at_peak", "eqn_count")

    def __init__(self, name, peak_bytes, peak_bytes_undonated,
                 argument_bytes, output_bytes, alias_bytes, temp_bytes,
                 at_peak, eqn_count):
        self.name = name
        self.peak_bytes = int(peak_bytes)
        self.peak_bytes_undonated = int(peak_bytes_undonated)
        self.argument_bytes = int(argument_bytes)
        self.output_bytes = int(output_bytes)
        self.alias_bytes = int(alias_bytes)
        self.temp_bytes = int(temp_bytes)
        self.at_peak = tuple(at_peak)
        self.eqn_count = int(eqn_count)

    @property
    def largest_temp(self):
        """(label, bytes, where) of the biggest non-argument buffer live at
        the peak, or None — the oversized-temp rule's subject."""
        temps = [b for b in self.at_peak if b.kind in ("temp", "internal")]
        if not temps:
            return None
        top = max(temps, key=lambda b: b.bytes)
        return (top.label, top.bytes, top.where)

    def to_memory_stats(self) -> dict:
        """The observability/xla.py ``memory_stats`` shape, estimated."""
        return {
            "argument_bytes": self.argument_bytes,
            "output_bytes": self.output_bytes,
            "temp_bytes": self.temp_bytes,
            "generated_code_bytes": 0,
            "alias_bytes": self.alias_bytes,
            "peak_bytes": self.peak_bytes,
            "estimated": True,
        }

    def to_dict(self) -> dict:
        out = self.to_memory_stats()
        out.update({
            "name": self.name,
            "peak_bytes_undonated": self.peak_bytes_undonated,
            "eqn_count": self.eqn_count,
            "at_peak": [b.to_dict() for b in self.at_peak],
        })
        return out


def _unwrap_single_pjit(closed_jaxpr, donated):
    """make_jaxpr over a jitted fn yields one pjit eqn wrapping the real
    program; analyze the inner jaxpr so donation has its aliasing effect
    (an outer walk would hold every operand across the one eqn and
    donation could never release anything). Mirrors core.analyze's
    donation extraction off the pjit params."""

    jaxpr = closed_jaxpr.jaxpr
    eqns = jaxpr.eqns
    if (donated is None and len(eqns) == 1
            and eqns[0].primitive.name == "jit"
            and set(map(id, eqns[0].invars)) == set(map(id, jaxpr.invars))):
        inner = eqns[0].params.get("jaxpr")
        flags = eqns[0].params.get("donated_invars")
        if isinstance(inner, jex_core.ClosedJaxpr) and flags is not None:
            return inner, tuple(flags)
    return closed_jaxpr, donated


def _estimate_open(jaxpr, const_bytes, donated, pinned, arg_names, top_k,
                   depth=0):
    """Schedule-order liveness walk over one (open) jaxpr.

    Returns (peak_bytes, snapshot, entry_bytes): ``entry_bytes`` is the
    resident set at entry (invars + consts) — recursion subtracts it so an
    equation's "internal extra" never double-counts operands already live
    in the caller's scope."""
    eqns = jaxpr.eqns
    last = {}
    for i, eqn in enumerate(eqns):
        for v in eqn.invars:
            if _is_var(v):
                last[v] = i
    outset = {v for v in jaxpr.outvars if _is_var(v)}
    donated = tuple(donated or ())
    donated_set = {v for i, v in enumerate(jaxpr.invars)
                   if i < len(donated) and donated[i] and _is_var(v)}
    consts = set(jaxpr.constvars)

    live: dict = {}
    running = 0

    def _add(v, label, where, kind):
        nonlocal running
        if v in live:
            return
        b = aval_bytes(v.aval)
        if b <= 0:
            return
        live[v] = _Buf(label, b, where, kind)
        running += b

    for i, v in enumerate(jaxpr.invars):
        label = (arg_names[i] if arg_names and i < len(arg_names)
                 else f"arg[{i}]")
        _add(v, label, "", "argument")
    for i, v in enumerate(jaxpr.constvars):
        b = const_bytes[i] if i < len(const_bytes) else aval_bytes(v.aval)
        if v not in live and b > 0:
            live[v] = _Buf(f"const[{i}]", b, "", "const")
            running += b
    entry_bytes = running

    peak = running
    snapshot = list(live.values())
    invar_set = set(jaxpr.invars)

    for i, eqn in enumerate(eqns):
        out_bufs = []
        where = source_of(eqn)
        for o in eqn.outvars:
            if not _is_var(o) or o in live:
                continue
            b = aval_bytes(o.aval)
            if b > 0:
                kind = "output" if o in outset else "temp"
                out_bufs.append((o, _Buf(eqn.primitive.name, b, where,
                                         kind)))
        extra = _inner_extra(eqn, depth)
        working = running + sum(b.bytes for _, b in out_bufs) + extra
        if working > peak:
            peak = working
            snapshot = list(live.values()) + [b for _, b in out_bufs]
            if extra > 0:
                snapshot.append(_Buf(f"{eqn.primitive.name}:internal",
                                     extra, where, "internal"))
        for o, buf in out_bufs:
            live[o] = buf
            running += buf.bytes
        for v in {v for v in eqn.invars if _is_var(v)}:
            if last.get(v) != i or v not in live:
                continue
            if v in outset or v in pinned or v in consts:
                continue
            if v in invar_set and v not in donated_set:
                continue                # caller still owns the buffer
            running -= live.pop(v).bytes
    return peak, snapshot, entry_bytes


def _inner_extra(eqn, depth):
    """Bytes an equation holds BEYOND its operands and results: the inner
    temp watermark of its sub-jaxprs. Alternatives (cond branches, while
    cond/body) never run concurrently, so the max is taken; scan/while
    carries are pinned inside their body — the body's new-carry outputs
    then coexist with the pinned old carry, which is exactly the
    double-buffering XLA's loop lowering pays."""

    if depth > 24:
        return 0
    subs = _sub_jaxprs(eqn.params)
    if not subs:
        return 0
    name = eqn.primitive.name
    extras = [0]
    for _tag, sub in subs:
        if isinstance(sub, jex_core.ClosedJaxpr):
            open_j = sub.jaxpr
            const_bytes = [getattr(c, "nbytes", aval_bytes(v.aval))
                           for v, c in zip(open_j.constvars, sub.consts)]
        else:
            open_j = sub
            const_bytes = []
        donated = ()
        if name == "jit":
            flags = eqn.params.get("donated_invars")
            if flags is not None:
                donated = tuple(flags)
        pinned = frozenset()
        if name == "scan":
            nc = int(eqn.params.get("num_consts", 0))
            ncar = int(eqn.params.get("num_carry", 0))
            pinned = frozenset(v for v in open_j.invars[nc:nc + ncar]
                               if _is_var(v))
        elif name == "while":
            pinned = frozenset(v for v in open_j.invars if _is_var(v))
        sub_peak, _snap, sub_entry = _estimate_open(
            open_j, const_bytes, donated, pinned, None, 0, depth + 1)
        extras.append(max(0, sub_peak - sub_entry) + sum(const_bytes))
    return max(extras)


def estimate_peak(closed_jaxpr, *, donated=None, arg_names=None,
                  name="program", top_k=8) -> PeakEstimate:
    """Statically estimate the HBM watermark of one traced program.

    ``donated``: per-invar flags; when omitted and the program is a single
    jitted call, the flags are read off its pjit equation (same extraction
    as core.analyze). ``top_k`` bounds the at-peak breakdown."""

    inner, donated = _unwrap_single_pjit(closed_jaxpr, donated)
    if isinstance(inner, jex_core.ClosedJaxpr):
        open_j = inner.jaxpr
        const_bytes = [getattr(c, "nbytes", aval_bytes(v.aval))
                       for v, c in zip(open_j.constvars, inner.consts)]
    else:
        open_j = inner
        const_bytes = []
    donated = tuple(donated or ())
    peak, snapshot, _entry = _estimate_open(
        open_j, const_bytes, donated, frozenset(), arg_names, top_k)
    if any(donated):
        undonated, _, _ = _estimate_open(
            open_j, const_bytes, (), frozenset(), arg_names, top_k)
    else:
        undonated = peak
    argument = sum(aval_bytes(v.aval) for v in open_j.invars)
    seen = set()
    output = 0
    for v in open_j.outvars:
        if _is_var(v) and v not in seen:
            seen.add(v)
            output += aval_bytes(v.aval)
    alias = sum(aval_bytes(v.aval) for i, v in enumerate(open_j.invars)
                if i < len(donated) and donated[i])
    at_peak = sorted(snapshot, key=lambda b: -b.bytes)[:top_k]
    temp = sum(b.bytes for b in snapshot
               if b.kind in ("temp", "internal"))
    return PeakEstimate(name, peak, undonated, argument, output, alias,
                        temp, at_peak, len(open_j.eqns))


def estimate_memory_stats(closed_jaxpr=None, *, compiled=None, donated=None,
                          name="program") -> dict:
    """``memory_stats``-shaped dict from the static estimator, for backends
    with no ``CompiledMemoryStats`` (observability/xla.py falls back here).

    Full tier with a jaxpr; degraded tier from a compiled executable's
    aval/donation metadata alone (``args_info``) — argument + output bytes
    with temps unknown, still non-zero where the real stats read zero.
    ``{}`` when neither source yields anything."""
    if closed_jaxpr is not None:
        return estimate_peak(closed_jaxpr, donated=donated,
                             name=name).to_memory_stats()
    if compiled is None:
        return {}
    argument = output = alias = 0
    try:
        infos = compiled.args_info
        flat = []
        for entry in (infos if isinstance(infos, tuple) else (infos,)):
            if isinstance(entry, dict):
                flat.extend(entry.values())
            elif isinstance(entry, (list, tuple)):
                flat.extend(entry)
            else:
                flat.append(entry)
        for info in flat:
            aval = getattr(info, "_aval", None) or getattr(info, "aval",
                                                           None)
            b = aval_bytes(aval) if aval is not None else 0
            argument += b
            if getattr(info, "donated", False):
                alias += b
    except Exception:
        argument = alias = 0
    try:
        out_avals = getattr(compiled, "out_avals", None)
        if not out_avals:       # jax 0.4.x: avals live on the executable
            out_avals = getattr(getattr(compiled, "_executable", None),
                                "out_avals", None)
        if out_avals:
            output = sum(aval_bytes(a) for a in out_avals)
    except Exception:
        output = 0
    if argument <= 0 and output <= 0:
        return {}
    return {
        "argument_bytes": argument,
        "output_bytes": output,
        "temp_bytes": 0,
        "generated_code_bytes": 0,
        "alias_bytes": alias,
        "peak_bytes": max(0, argument + output - alias),
        "estimated": True,
    }


# ================================================================= the plan
def blocks_for(seq_len, block_size) -> int:
    """PagedKVCache.blocks_for, pool-free (plan-time arithmetic)."""
    return max(1, math.ceil(int(seq_len) / int(block_size)))


def per_block_bytes(kv_signature, tp=1) -> int:
    """Per-chip bytes one pool block costs across k+v and all layers:
    2 * layers * (kv_heads/tp) * block_size * head_dim * itemsize —
    must agree with PagedKVCache.per_chip_pool_bytes()/num_blocks (the
    plan/pool parity test pins this)."""
    import jax.numpy as jnp

    from ..inference.kv_cache import CacheSpec

    layers, kv_heads, head_dim, block_size, _nb, dtype = kv_signature[:6]
    if isinstance(layers, CacheSpec):   # rows that are not K,V of like layers
        return layers.block_bytes(block_size, jnp.dtype(dtype).itemsize)
    tp = max(1, int(tp))
    heads = int(kv_heads) / tp if int(kv_heads) % tp == 0 else int(kv_heads)
    return int(2 * int(layers) * heads * int(block_size) * int(head_dim)
               * jnp.dtype(dtype).itemsize)


@dataclasses.dataclass(frozen=True)
class ProgramEstimate:
    """One manifest program's contribution to the plan: the static peak /
    temp watermark (estimator), the largest single live buffer at the peak
    (oversized-temp's subject), and the real compiled peak where the
    backend provided one (estimate-drift's other hand)."""
    name: str
    peak_bytes: int
    temp_bytes: int
    largest_label: str = ""
    largest_bytes: int = 0
    largest_where: str = ""
    measured_peak_bytes: object = None      # int | None

    @classmethod
    def from_estimate(cls, est: PeakEstimate,
                      measured=None) -> "ProgramEstimate":
        top = est.largest_temp or ("", 0, "")
        return cls(name=est.name, peak_bytes=est.peak_bytes,
                   temp_bytes=est.temp_bytes, largest_label=top[0],
                   largest_bytes=top[1], largest_where=top[2],
                   measured_peak_bytes=measured)

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_json(cls, obj) -> "ProgramEstimate":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(obj) - known)
        if unknown:
            raise ValueError(f"unknown ProgramEstimate fields {unknown}; "
                             f"known: {sorted(known)}")
        return cls(**obj)


@dataclasses.dataclass(frozen=True)
class DeploymentPlan:
    """Per-chip HBM residency for one ServingConfig against a budget.

    Components are DISJOINT so they sum to ``planned_total_bytes``:
    ``prefix_blocks`` is carved OUT of the pool (parked prefix blocks are
    pool blocks — reserving them in the plan keeps the kv_pool number
    honest about blocks actually available to live requests)."""
    config: object                       # compilesurface.ServingConfig
    budget_bytes: int
    headroom: float = DEFAULT_HEADROOM
    params_bytes: int = 0                # FULL params; the plan divides by tp
    tp: int = 1
    prefix_blocks: int = 0
    programs: tuple = ()                 # ProgramEstimate per manifest entry
    temps_bytes: int = 0                 # declared floor when no programs
    adapter_bank_bytes: int = 0          # ISSUE-15: resident LoRA banks
    window_pool_bytes: int = 0           # rings of layers that keep a window
    # ISSUE-20: the interconnect component (comms.CommsBudget or None).
    # DISJOINT from components() by construction: these are bytes MOVED
    # per tick, not bytes resident, so they never enter the residency sum
    # (which tests pin as == sum(components)) — they get their own rows in
    # render_table and their own rule (comms-over-budget).
    comms: object = None

    def __post_init__(self):
        if self.budget_bytes <= 0:
            raise ValueError("budget_bytes must be positive")
        if not 0 <= self.headroom < 1:
            raise ValueError("headroom must be in [0, 1)")
        if self.prefix_blocks > self.num_blocks:
            raise ValueError(f"prefix_blocks {self.prefix_blocks} exceeds "
                             f"the pool ({self.num_blocks} blocks)")

    # ------------------------------------------------------------ geometry
    @property
    def num_blocks(self) -> int:
        return int(self.config.kv_signature[4])

    @property
    def per_block_bytes(self) -> int:
        return per_block_bytes(self.config.kv_signature, tp=self.tp)

    @property
    def usable_bytes(self) -> int:
        return int(self.budget_bytes * (1.0 - self.headroom))

    # ---------------------------------------------------------- components
    @property
    def params_component(self) -> int:
        return int(self.params_bytes) // max(1, int(self.tp))

    @property
    def kv_pool_component(self) -> int:
        return (self.num_blocks - self.prefix_blocks) * self.per_block_bytes

    @property
    def prefix_tier_component(self) -> int:
        return self.prefix_blocks * self.per_block_bytes

    @property
    def temps_component(self) -> int:
        temps = [p.temp_bytes for p in self.programs]
        return max([int(self.temps_bytes)] + temps)

    @property
    def adapter_bank_component(self) -> int:
        # the full fixed-shape banks (AdapterRegistry.bank_bytes()) — HBM
        # is paid for A_max slots up front whether or not they're loaded,
        # which is exactly why the plan must carry it (ISSUE-15)
        return int(self.adapter_bank_bytes)

    def components(self) -> dict:
        out = {
            "params": self.params_component,
            "kv_pool": self.kv_pool_component,
            "prefix_tier": self.prefix_tier_component,
            "temps": self.temps_component,
            "adapter_bank": self.adapter_bank_component,
        }
        if self.window_pool_bytes:      # a second pool, where a model has one
            out["window_pool"] = int(self.window_pool_bytes)
        return out

    @property
    def planned_total_bytes(self) -> int:
        return sum(self.components().values())

    # -------------------------------------------------------------- io/ui
    def to_json(self) -> dict:
        return {
            "config": self.config.to_json(),
            "budget_bytes": int(self.budget_bytes),
            "headroom": float(self.headroom),
            "params_bytes": int(self.params_bytes),
            "tp": int(self.tp),
            "prefix_blocks": int(self.prefix_blocks),
            "programs": [p.to_json() for p in self.programs],
            "temps_bytes": int(self.temps_bytes),
            "adapter_bank_bytes": int(self.adapter_bank_bytes),
            "window_pool_bytes": int(self.window_pool_bytes),
            "comms": self.comms.to_json() if self.comms else None,
            "components": self.components(),
            "planned_total_bytes": self.planned_total_bytes,
        }

    @classmethod
    def from_json(cls, obj) -> "DeploymentPlan":
        from .compilesurface import ServingConfig

        known = {f.name for f in dataclasses.fields(cls)}
        derived = {"components", "planned_total_bytes"}
        unknown = sorted(set(obj) - known - derived)
        if unknown:
            raise ValueError(f"unknown DeploymentPlan fields {unknown}; "
                             f"known: {sorted(known)}")
        kw = {k: v for k, v in obj.items() if k in known}
        kw["config"] = ServingConfig.from_json(kw["config"])
        kw["programs"] = tuple(ProgramEstimate.from_json(p)
                               for p in kw.get("programs", ()))
        if kw.get("comms") is not None:
            from .comms import CommsBudget

            kw["comms"] = CommsBudget.from_json(kw["comms"])
        return cls(**kw)

    def render_table(self) -> str:
        """The deploy-review artifact ``--hbm`` prints: one row per
        component with its share of the budget, then the per-program
        static/measured peaks."""
        total = self.planned_total_bytes
        fit = "FIT" if total <= self.usable_bytes else "OVER"
        lines = [
            f"== hbm residency: {self.config.name} ==",
            f"  budget {fmt_bytes(self.budget_bytes):>12s}   headroom "
            f"{self.headroom:.0%}   usable {fmt_bytes(self.usable_bytes)}"
            f"   tp={self.tp}",
        ]
        for comp, nbytes in self.components().items():
            pct = 100.0 * nbytes / self.budget_bytes
            lines.append(f"  {comp:12s} {fmt_bytes(nbytes):>12s}  "
                         f"{pct:5.1f}% of budget")
        lines.append(f"  {'total':12s} {fmt_bytes(total):>12s}  "
                     f"{100.0 * total / self.budget_bytes:5.1f}% -> {fit}")
        if self.comms is not None:
            share = self.comms.share_of_tick()
            wall_ms = self.comms.tick_wall_s * 1e3
            lines.append(
                f"  {'comms':12s} {fmt_bytes(self.comms.bytes_per_tick):>12s}"
                + ("  on wire/tick, interconnect unknown (un-gated)"
                   if share is None else
                   f"  on wire/tick = {share:6.1%} of the {wall_ms:.0f}ms "
                   "tick wall"))
        for p in self.programs:
            measured = (fmt_bytes(p.measured_peak_bytes)
                        if p.measured_peak_bytes else "n/a")
            lines.append(f"  program {p.name}: static peak "
                         f"{fmt_bytes(p.peak_bytes)} (temps "
                         f"{fmt_bytes(p.temp_bytes)}), measured {measured}")
        return "\n".join(lines)


# ================================================================ the rules
def _rule_over_budget(plan):
    total, usable = plan.planned_total_bytes, plan.usable_bytes
    if total <= usable:
        return
    comps = ", ".join(f"{k}={fmt_bytes(v)}"
                      for k, v in plan.components().items())
    yield Finding(
        "hbm-over-budget", HIGH,
        f"planned residency {fmt_bytes(total)} exceeds the usable budget "
        f"{fmt_bytes(usable)} ({fmt_bytes(plan.budget_bytes)} x "
        f"(1 - {plan.headroom:.0%}) headroom): {comps}",
        subject=f"{plan.config.name}:plan",
        remediation="shrink the pool (plan_kv_pool sizes it to fit), raise "
                    "tp, quantize the KV dtype, or declare a bigger chip")


def _rule_estimate_drift(plan, rel_tol=DRIFT_REL_TOL,
                         abs_floor=DRIFT_ABS_FLOOR):
    for p in plan.programs:
        real = p.measured_peak_bytes
        if not real:
            continue                    # no stats on this backend: ungated
        static = int(p.peak_bytes)
        real = int(real)
        lo = real / (1.0 + rel_tol)
        hi = real * (1.0 + rel_tol)
        if lo <= static <= hi or abs(static - real) <= abs_floor:
            continue
        yield Finding(
            "estimate-drift", HIGH,
            f"program {p.name!r}: static peak {fmt_bytes(static)} vs "
            f"compiled memory_stats peak {fmt_bytes(real)} — outside the "
            f"{rel_tol:+.0%} tolerance; the estimator (or this trace) is "
            "lying and every residency number downstream is suspect",
            subject=f"{plan.config.name}:{p.name}",
            remediation="re-derive the program estimate from the deployed "
                        "trace, or fix analysis/hbm.py estimate_peak")


def _rule_oversized_temp(plan, strict=False):
    sev = HIGH if strict else WARN
    cap = int(OVERSIZED_TEMP_FRACTION * plan.budget_bytes)
    for p in plan.programs:
        if p.largest_bytes <= cap:
            continue
        yield Finding(
            "oversized-temp", sev,
            f"program {p.name!r} materializes a single "
            f"{fmt_bytes(p.largest_bytes)} buffer ({p.largest_label}) at "
            f"its peak — over {OVERSIZED_TEMP_FRACTION:.0%} of the "
            f"{fmt_bytes(plan.budget_bytes)} budget",
            where=p.largest_where,
            subject=f"{plan.config.name}:{p.name}",
            remediation="chunk or remat the producing op (a broadcast this "
                        "size usually wants to stay fused or be tiled)")


def _rule_pool_misfit(plan, strict=False):
    sev = HIGH if strict else WARN
    cfg = plan.config
    live_blocks = plan.num_blocks - plan.prefix_blocks
    if cfg.max_seq_len:
        need = cfg.slots * blocks_for(cfg.max_seq_len, cfg.block_size)
        if need > live_blocks:
            yield Finding(
                "pool-misfit", sev,
                f"{cfg.slots} slots x blocks_for({cfg.max_seq_len}) = "
                f"{need} blocks exceed the {live_blocks} live pool blocks "
                f"({plan.num_blocks} - {plan.prefix_blocks} parked) — full "
                "concurrency at max length queues on blocks",
                subject=f"{cfg.name}:pool",
                remediation="grow num_blocks, shrink max_seq_len/slots, or "
                            "accept admission-time deferrals")
            return
    reachable = cfg.slots * cfg.table_width + plan.prefix_blocks
    unreachable = max(0, plan.num_blocks - reachable)
    if unreachable > POOL_WASTE_FRACTION * plan.num_blocks:
        yield Finding(
            "pool-misfit", sev,
            f"{unreachable} of {plan.num_blocks} pool blocks "
            f"({unreachable / plan.num_blocks:.0%}) are unreachable by any "
            f"admissible request ({cfg.slots} slots x table_width "
            f"{cfg.table_width} + {plan.prefix_blocks} parked) — HBM "
            "bought, never used",
            subject=f"{cfg.name}:pool",
            remediation="shrink num_blocks (plan_kv_pool clamps to the "
                        "reachable set), raise slots/max_seq_len, or park "
                        "the excess as prefix tier")


def analyze_hbm_plan(plan, *, strict=False, allowlist=None,
                     name=None) -> Report:
    """Run the four residency rules over one DeploymentPlan; returns the
    shared Report type (same gating as every other lint)."""
    import jax

    findings = []
    findings.extend(_rule_over_budget(plan))
    findings.extend(_rule_estimate_drift(plan))
    findings.extend(_rule_oversized_temp(plan, strict=strict))
    findings.extend(_rule_pool_misfit(plan, strict=strict))
    rules = tuple(HBM_RULES)
    if plan.comms is not None:
        # ISSUE-20: a plan that carries its interconnect component gets the
        # comms budget gate too — the deploy review reads ONE table
        from .comms import _rule_comms_over_budget

        findings.extend(_rule_comms_over_budget(
            plan.comms, subject=f"{plan.config.name}:comms"))
        rules += ("comms-over-budget",)
    al = allowlist if allowlist is not None else BUILTIN_HBM_ALLOWLIST
    try:
        backend = jax.default_backend()
    except Exception:
        backend = ""
    kept, suppressed = al.apply(findings, backend)
    return Report(name or f"hbm.residency[{plan.config.name}]", kept,
                  suppressed, rules)


# ============================================================= runtime half
def params_bytes_of(model) -> int:
    """Resident bytes of a model's parameters (the optimizer-free serving
    state): what the plan's params component and the scheduler's
    ``hbm_budget=`` sizing charge per replica (pre-tp)."""
    import jax.numpy as jnp

    total = 0
    for p in model.parameters():
        try:
            total += int(p.size) * jnp.dtype(str(p.dtype)).itemsize
        except Exception:
            total += int(getattr(getattr(p, "_value", None), "nbytes", 0))
    return total


def plan_kv_pool(budget_bytes, *, num_layers=None, num_kv_heads=None,
                 head_dim=None, cache_spec=None,
                 block_size, dtype="bfloat16", slots=8, max_seq_len=None,
                 params_bytes=0, tp=1, headroom=DEFAULT_HEADROOM,
                 prefix_blocks=0, temps_bytes=0, adapter_bank_bytes=0,
                 name="planned", prefill_chunk=16, decode_steps=4, spec_k=0,
                 eos_token_id=None, decode_kernel="pallas") -> dict:
    """Size a PagedKVCache pool from an HBM budget: the runtime half the
    continuous scheduler's ``hbm_budget=`` knob consults before building
    its pool. Returns ``{"num_blocks", "fit_blocks", "target_blocks",
    "per_block_bytes", "plan"}`` where ``plan`` is the DeploymentPlan the
    scheduler publishes through the ``paddle_hbm_planned_bytes`` gauges.

    num_blocks = min(what fits the usable budget after params/tp + temps,
    what the admissible requests can reach: slots x
    blocks_for(max_seq_len) + parked prefix blocks) — the second clamp is
    what keeps a generous budget from buying unreachable blocks
    (pool-misfit's waste arm). `cache_spec` (inference.kv_cache.CacheSpec)
    takes the place of the triple: layers that keep every row are counted
    a block, and the rings of layers that keep a window (all slots, sized
    by the largest launch) are a fixed second pool, `window_pool_bytes`."""
    import jax.numpy as jnp

    from ..inference.kv_cache import CacheSpec
    from .compilesurface import ServingConfig

    if cache_spec is None:
        cache_spec = CacheSpec.uniform(num_layers, num_kv_heads, head_dim)
    head = cache_spec.signature_head()
    window_bytes = cache_spec.window_bytes(
        block_size, jnp.dtype(dtype).itemsize, slots,
        max(int(prefill_chunk), int(spec_k) + 1))
    budget_bytes = int(budget_bytes)
    usable = int(budget_bytes * (1.0 - headroom))
    fixed = (int(params_bytes) // max(1, int(tp)) + int(temps_bytes)
             + int(adapter_bank_bytes) + window_bytes)
    sig = head + (int(block_size), 0, str(dtype))
    pbb = per_block_bytes(sig, tp=tp)
    fit = (usable - fixed) // pbb
    target = None
    if max_seq_len:
        target = (int(slots) * blocks_for(max_seq_len, block_size)
                  + int(prefix_blocks))
    num_blocks = int(min(fit, target) if target is not None else fit)
    floor = blocks_for(max_seq_len, block_size) if max_seq_len else 1
    if num_blocks < floor:
        raise ValueError(
            f"hbm budget {fmt_bytes(budget_bytes)} cannot fit a KV pool: "
            f"{fmt_bytes(max(0, usable - fixed))} left after params/temps "
            f"buys {max(0, fit)} blocks of {fmt_bytes(pbb)}, need at least "
            f"{floor}")
    config = ServingConfig(
        name=name, slots=int(slots), prefill_chunk=int(prefill_chunk),
        decode_steps=int(decode_steps), spec_k=int(spec_k),
        eos_token_id=eos_token_id, max_seq_len=max_seq_len,
        kv_signature=head + (int(block_size), num_blocks, str(dtype)),
        decode_kernel=decode_kernel)
    plan = DeploymentPlan(
        config=config, budget_bytes=budget_bytes, headroom=headroom,
        params_bytes=int(params_bytes), tp=int(tp),
        prefix_blocks=int(prefix_blocks), temps_bytes=int(temps_bytes),
        adapter_bank_bytes=int(adapter_bank_bytes),
        window_pool_bytes=window_bytes)
    return {"num_blocks": num_blocks, "fit_blocks": int(fit),
            "target_blocks": target, "per_block_bytes": pbb, "plan": plan}


# ============================================================ zoo residency
# The smoke residency the self-check/bench/tier-1 gate on: the zoo GPT's
# two default step programs against the zoo smoke pool and a 64 MiB budget
# (generous for a 2-layer smoke model — the gate is the RULES firing on
# real numbers, not a tight fit). max_seq_len=2048 makes the pool exactly
# reachable: 8 slots x blocks_for(2048) = 128 blocks = the pool.
SMOKE_BUDGET_BYTES = 64 << 20
SMOKE_MAX_SEQ_LEN = 2048


def smoke_budget_bytes() -> int:
    return SMOKE_BUDGET_BYTES


def _trace_step_program(model, kv, config, path):
    """Trace + (where the backend can) compile one continuous-scheduler
    step program at the config's geometry with fully idle inputs (the same
    write-free launches AOTWarmup uses); returns (ClosedJaxpr, measured
    memory_stats dict — empty when the backend has no real stats)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ..observability.xla import memory_stats

    S, C, T = config.slots, config.prefill_chunk, config.decode_steps
    W = config.table_width
    tbl = np.zeros((S, W), np.int32)
    zeros_i = np.zeros((S,), np.int64)
    idle = np.zeros((S,), bool)
    state = model._decode_state(jnp.bfloat16)
    temps = jnp.zeros((S,), jnp.float32)
    top_ks = jnp.zeros((S,), jnp.int32)
    pools = (tuple(kv.k_pages), tuple(kv.v_pages))
    key = jax.random.key(0)
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    if path == "prefill_chunk":
        ids = np.zeros((S, C), np.int64)
        model.prefill_chunk(ids, zeros_i, zeros_i, kv, tbl,
                            eos_token_id=config.eos_token_id, seed=0)
        run = model.compiled_prefill_chunk_runner(S, C)
        args = (state, jnp.asarray(ids), i32(zeros_i), i32(zeros_i),
                i32(tbl), temps, top_ks, *pools, key)
    elif path == "decode_step":
        model.decode_step(zeros_i, zeros_i, idle, kv, tbl, steps=T,
                          eos_token_id=config.eos_token_id, seed=0)
        run = model.compiled_decode_step_runner(S, T)
        args = (state, jnp.asarray(zeros_i), i32(zeros_i),
                jnp.asarray(idle), i32(zeros_i), i32(tbl),
                temps, top_ks, *pools, key)
    elif path == "verify_step":
        chunk = np.zeros((S, config.spec_k + 1), np.int64)
        model.verify_step(chunk, zeros_i, zeros_i, idle, kv, tbl, seed=0)
        run = model.compiled_verify_step_runner(S, config.spec_k + 1)
        args = (state, jnp.asarray(chunk), i32(zeros_i), i32(zeros_i),
                jnp.asarray(idle), i32(zeros_i), i32(tbl),
                temps, top_ks, *pools, key)
    else:
        raise ValueError(f"no residency trace for path {path!r}")
    closed = jax.make_jaxpr(run)(*args)
    try:
        measured = memory_stats(run.lower(*args).compile())
    except Exception:
        measured = {}
    if measured.get("estimated"):       # fallback stats are not a measurement
        measured = {}
    return closed, measured


def smoke_plan(*, budget_bytes=None, with_measured=True, config_name=None):
    """Build the zoo residency plan: smoke GPT + smoke pool + the default
    continuous paths, statically estimated and (where the backend has
    CompiledMemoryStats) measured. Shared by the zoo entry, the bench
    ``hbm_planning`` leg, and the tier-1 acceptance tests. ``config_name``
    picks one of the shipped serving configs (``--hbm NAME``); the default
    is the non-speculative shipped config."""
    import dataclasses as _dc

    from .compilesurface import default_serving_configs
    from .zoo import _gpt_smoke

    cfg_model, model = _gpt_smoke()
    model.eval()
    from ..inference.kv_cache import PagedKVCache

    shipped = default_serving_configs()
    if config_name is None:
        base = shipped[0]
    else:
        match = [c for c in shipped if c.name == config_name]
        if not match:
            raise ValueError(f"unknown serving config {config_name!r}; "
                             f"shipped: {[c.name for c in shipped]}")
        base = match[0]
    config = _dc.replace(base, name="hbm-smoke",
                         max_seq_len=SMOKE_MAX_SEQ_LEN)
    _, _, _, block_size, num_blocks, dtype = config.kv_signature
    kv = PagedKVCache.for_model(model, block_size=block_size,
                                num_blocks=num_blocks, dtype=dtype,
                                slots=config.slots)
    programs = []
    for path in config.active_paths():
        closed, measured = _trace_step_program(model, kv, config, path)
        est = estimate_peak(closed, name=path)
        real = measured.get("peak_bytes") if with_measured else None
        # a backend without donation keeps both pool copies: compare the
        # matching (undonated) walk so drift measures estimator error,
        # not the backend's donation support
        if real and not measured.get("alias_bytes"):
            est = PeakEstimate(
                est.name, est.peak_bytes_undonated,
                est.peak_bytes_undonated, est.argument_bytes,
                est.output_bytes, 0, est.temp_bytes, est.at_peak,
                est.eqn_count)
        programs.append(ProgramEstimate.from_estimate(
            est, measured=real or None))
    return DeploymentPlan(
        config=config,
        budget_bytes=int(budget_bytes or SMOKE_BUDGET_BYTES),
        params_bytes=params_bytes_of(model),
        programs=tuple(programs))


def analyze_hbm_residency(allowlist=None, *, budget_bytes=None,
                          name="hbm.residency") -> Report:
    """The ``hbm_residency`` zoo entry body: smoke plan -> the four rules.
    ``--self-check`` fails on any un-allowlisted HIGH here, which makes
    estimator drift against real backend stats a CI failure, not a shrug."""
    plan = smoke_plan(budget_bytes=budget_bytes)
    return analyze_hbm_plan(plan, allowlist=allowlist, name=name)


# ------------------------------------------------------------- fixture mode
def hbm_fixture_reports(path):
    """Seeded-violation mode for ``--hbm PATH`` (mirrors --surface): a
    ``.json`` file is a DeploymentPlan spec (``{"plan": {...}}`` or the
    plan object itself); a ``.py`` file is a PROGRAM fixture — it must
    define ``make_program()`` returning ``(fn, args)`` plus a
    ``BUDGET_BYTES`` int, and is estimated against that budget (the
    giant-broadcast-temp seed). Directories run every fixture inside.
    Everything is strict with an empty allowlist."""
    if os.path.isdir(path):
        names = sorted(n for n in os.listdir(path)
                       if n.endswith((".py", ".json")))
        out = []
        for n in names:
            out.extend(hbm_fixture_reports(os.path.join(path, n)))
        return out
    label = f"hbm[{os.path.basename(path)}]"
    if path.endswith(".json"):
        with open(path, "r") as fh:
            spec = json.load(fh)
        plan = DeploymentPlan.from_json(spec.get("plan", spec))
        return [analyze_hbm_plan(plan, strict=True, allowlist=Allowlist([]),
                                 name=label)]
    import runpy

    mod = runpy.run_path(path)
    if "make_program" not in mod or "BUDGET_BYTES" not in mod:
        raise ValueError(f"{path}: a .py hbm fixture must define "
                         "make_program() -> (fn, args) and BUDGET_BYTES")
    import jax

    from .compilesurface import ServingConfig

    fn, args = mod["make_program"]()
    closed = jax.make_jaxpr(fn)(*args)
    est = estimate_peak(closed, name=os.path.basename(path))
    budget = int(mod["BUDGET_BYTES"])
    # a program-only fixture: pool/params are zeroed out so the ONLY rules
    # with teeth are the per-program ones (oversized-temp, estimate-drift)
    config = ServingConfig(name=os.path.basename(path), slots=1,
                           max_seq_len=1,
                           kv_signature=(1, 1, 1, 1, 1, "bfloat16"))
    plan = DeploymentPlan(
        config=config, budget_bytes=budget,
        programs=(ProgramEstimate.from_estimate(est),))
    return [analyze_hbm_plan(plan, strict=True, allowlist=Allowlist([]),
                             name=label)]
