"""Sharding & collective lint — static SPMD layout contracts (ISSUE-20).

Since ISSUE-12 tensor-parallel-sharded the serving step programs, the repo
has DECLARED a layout (``distributed/mesh.py SpecLayout``) but nothing
verified that the compiled artifacts honor it: GSPMD is free to insert
resharding collectives wherever the declared layout and the program's real
dataflow disagree, and every such insertion is latency paid on every launch
of a program that runs thousands of times per second. This module is the
fifth lint leg (graph / thread / compile-surface / HBM / **comms**): a
static pass over the POST-SPMD compiled HLO of the serving step programs.

Why compiled HLO and not the lowered StableHLO: GSPMD partitions at
*compile* time. The pre-partitioning StableHLO of the tp=2 decode tick
carries only ``@Sharding`` custom-call annotations — zero collectives —
while the compiled module carries every all-reduce/all-gather/
collective-permute XLA actually inserted. The lowered module cannot answer
"what crosses the interconnect"; the compiled one is the ground truth the
deploy review needs, and jax hands it over for free
(``run.lower(*args).compile().as_text()`` + ``input_shardings``).

Two halves, five rules:

* **Collective inventory** — every ``all-gather`` / ``all-reduce`` /
  ``reduce-scatter`` / ``all-to-all`` / ``collective-permute`` in the
  compiled module, with shape, dtype, replica-group size and estimated
  bytes-on-wire (per participating chip, ring formulas: `bytes_on_wire`).
  Ops inside the decode scan (``/while/`` in their op_name metadata) count
  once per scanned step. Rules: ``implicit-reshard`` (HIGH — a collective
  kind no declared SpecLayout transition explains), ``comms-over-budget``
  (HIGH — per-tick wire bytes vs the per-chip ICI table in
  ``observability/xla.py``, the bandwidth sibling of ``device_peak_flops``).
* **Layout contract** — the compiled program's actual ``input_shardings`` /
  ``output_shardings`` against the declared ``SpecLayout.step_contract()``.
  Rules: ``layout-contract-drift`` (HIGH — a contract glob matches an
  argument whose compiled sharding disagrees, or matches nothing at all),
  ``replicated-large-buffer`` (WARN, strict-HIGH — a >=1 MiB input
  replicated over tp that a SpecLayout axis could shard; the LoRA adapter
  bank is the known candidate), ``dead-mesh-axis`` (WARN — a declared mesh
  axis nothing in the program set uses; ``dp`` trips it by design and is
  builtin-allowlisted with its reason).

What the first self-check caught (the linter's reason to exist, written up
in docs/ANALYSIS.md): the fused qkv projection's column shard does NOT land
on head boundaries — at tp=2 the 192-wide qkv splits at 96, straddling the
k and v head groups, so XLA patches the split with per-layer
collective-permutes (models/gpt.py ``split_qkv``); the fused swiglu
gate/up halves straddle the same way; and top-k sampling over the
vocab-sharded logits lowers to a distributed sort with all-to-alls. All
three are real cross-chip traffic nobody declared — carried in
``BUILTIN_COMMS_ALLOWLIST`` with reasons until the layouts are interleaved,
exactly the "clean or explained" bar the other lint legs hold.

Gating: the ``comms_surface`` zoo entry (``--self-check``), the CLI
``--comms [NAME|PATH]`` (per-program collective table, the deploy-review
artifact; PATH = strict fixture mode over tests/comms_fixtures/), the bench
``comms_lint`` leg, and the MULTICHIP dryrun's fleet phase. PR 5's narrower
``collective-axis`` rule stays: it checks axis *names* inside the traced
jaxpr; this pass checks the *compiled* artifact — different failure modes.
"""
from __future__ import annotations

import dataclasses
import fnmatch
import json
import os
import re

from .core import Report, fmt_bytes
from .findings import HIGH, WARN, Allowlist, AllowlistEntry, Finding

__all__ = [
    "COMMS_RULES", "BUILTIN_COMMS_ALLOWLIST", "CollectiveOp",
    "CommsEstimate", "CommsBudget", "collective_inventory", "bytes_on_wire",
    "compiled_comms_surface", "step_comms_surfaces", "render_comms_table",
    "analyze_comms_surfaces", "analyze_step_comms",
    "sampled_logits_gather_surface", "comms_fixture_reports",
    "DEFAULT_TPOT_BUDGET_S", "REPLICATED_BUFFER_MIN_BYTES",
]

COMMS_RULES = {
    "implicit-reshard":
        "a collective in the compiled module that no declared SpecLayout "
        "transition explains — GSPMD is resharding mid-program behind the "
        "layout contract's back, paid on every launch",
    "layout-contract-drift":
        "a compiled input/output sharding disagrees with the declared "
        "SpecLayout contract entry that names it (or a contract glob "
        "matches nothing — the contract rotted off the program)",
    "comms-over-budget":
        "per-tick collective bytes-on-wire cannot cross the per-chip ICI "
        "inside the tick wall budget at the configured tp (silent when the "
        "interconnect is unknown, e.g. CPU)",
    "replicated-large-buffer":
        "a >=1 MiB program input is fully replicated over tp though a mesh "
        "axis could shard one of its dimensions (HIGH in strict mode; the "
        "LoRA adapter bank is the known candidate)",
    "dead-mesh-axis":
        "a declared mesh axis that no input/output sharding in the program "
        "set uses — topology bought, never wired",
}

# tick wall budget: decode_steps tokens per tick, each owed the default
# p99 TPOT objective shipped in observability/slo.py (tpot_p99_ms: 50)
DEFAULT_TPOT_BUDGET_S = 0.050
REPLICATED_BUFFER_MIN_BYTES = 1 << 20

_STEP_PATHS = ("prefill_chunk", "decode_step", "verify_step")

# ============================================================== HLO parsing
# Post-SPMD HLO types print as e.g. ``f32[2,1,64]{2,1,0}`` (per-device
# shapes) — NOT the ``tensor<...>`` syntax rules.py parses out of StableHLO,
# hence a second tiny parser instead of reusing _tensor_bytes.
_HLO_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}
_HLO_TYPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")
_COLLECTIVE_RE = re.compile(
    r"=\s+(?P<result>\([^)]*\)|[a-z][a-z0-9]*\[[0-9,]*\](?:\{[^}]*\})?)\s+"
    r"(?P<kind>all-gather|all-reduce|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?\(")
_IOTA_GROUPS_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")
_LIST_GROUPS_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_PAIRS_RE = re.compile(r"source_target_pairs=\{(\{[0-9,]+\}(?:,\{[0-9,]+\})*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')
_FRAME_ID_RE = re.compile(r"stack_frame_id=(\d+)")


def _hlo_result_bytes(result: str):
    """(dtype, bytes) of a printed HLO result type — tuple types sum their
    elements and report the first element's dtype."""
    total, dtype = 0, ""
    for dt, dims in _HLO_TYPE_RE.findall(result):
        if dt not in _HLO_DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _HLO_DTYPE_BYTES[dt]
        dtype = dtype or dt
    return dtype, total


def bytes_on_wire(kind, buffer_bytes, group_size) -> int:
    """Bytes one participating chip puts on the ICI per execution of one
    collective, ring algorithms (docs/ANALYSIS.md tables them):

    * all-gather (printed result = the full gathered buffer G):  G(n-1)/n
    * all-reduce (printed result = the full buffer B):          2B(n-1)/n
    * reduce-scatter (printed result = the scattered shard Bs): Bs(n-1)
    * all-to-all (printed result = the per-chip buffer B):       B(n-1)/n
    * collective-permute:                                        B
    """
    n = max(1, int(group_size))
    b = int(buffer_bytes)
    if kind == "all-reduce":
        return 2 * b * (n - 1) // n
    if kind == "reduce-scatter":
        return b * (n - 1)
    if kind == "collective-permute":
        return b
    return b * (n - 1) // n            # all-gather / all-to-all


@dataclasses.dataclass(frozen=True)
class CollectiveOp:
    """One collective in a compiled module: what crosses chips, how big,
    how often per launch, and which source line put it there."""
    kind: str
    result: str                  # printed (per-device) result type
    dtype: str
    buffer_bytes: int
    group_size: int
    count: int                   # executions per program launch
    wire_bytes: int              # bytes-on-wire per launch (count folded in)
    op_name: str = ""
    where: str = ""

    def to_json(self) -> dict:
        return dataclasses.asdict(self)


def _short_where(source_file, source_line, op_name):
    path = source_file
    for anchor in ("paddle_tpu/", "site-packages/"):
        i = path.rfind(anchor)
        if i >= 0:
            path = path[i:]
            break
    tail = ""
    if op_name:
        tail = f" ({op_name.rsplit('/', 1)[-1]})"
    return f"{path}:{source_line}{tail}" if path else op_name


def _frame_sources(hlo_text) -> dict:
    """{stack_frame_id: (source_file, source_line)} from the module's header
    tables. XLA prints provenance as an index: ops carry `stack_frame_id=N`,
    and the FileNames / FileLocations / StackFrames blocks at the top of the
    module text resolve it (the frame's own location is the innermost user
    frame — where the op was traced)."""
    blocks = {}
    for block in hlo_text.split("\n\n"):
        name, _, body = block.strip().partition("\n")
        if name in ("FileNames", "FileLocations", "StackFrames"):
            blocks[name] = body
    files = dict(re.findall(r'^(\d+) "(.*)"$', blocks.get("FileNames", ""),
                            re.M))
    locations = {
        loc: (files[f], line) for loc, f, line in re.findall(
            r"^(\d+) \{file_name_id=(\d+) .*?\bline=(\d+)",
            blocks.get("FileLocations", ""), re.M)}
    return {frame: locations[loc] for frame, loc in re.findall(
        r"^(\d+) \{file_location_id=(\d+)", blocks.get("StackFrames", ""),
        re.M)}


def collective_inventory(hlo_text, *, loop_steps=1):
    """Parse every collective out of post-SPMD compiled HLO text.

    ``loop_steps`` is the launch multiplier for ops that live inside the
    program's while loop (the decode scan): XLA prints the loop body once
    but the op runs once per scanned step. Async ``-start``/``-done``
    pairs count once (the ``-start`` carries the transfer)."""
    ops = []
    sources = _frame_sources(hlo_text)
    for line in hlo_text.splitlines():
        m = _COLLECTIVE_RE.search(line)
        if m is None:
            continue
        kind, result = m.group("kind"), m.group("result")
        dtype, nbytes = _hlo_result_bytes(result)
        group = 1
        gm = _IOTA_GROUPS_RE.search(line)
        if gm:
            group = int(gm.group(2))
        else:
            gm = _LIST_GROUPS_RE.search(line)
            if gm:
                group = len(gm.group(1).split(","))
            elif kind == "collective-permute":
                pm = _PAIRS_RE.search(line)
                if pm:
                    group = pm.group(1).count("{")
        op_name = (_OP_NAME_RE.search(line) or [None, ""])[1]
        fm = _FRAME_ID_RE.search(line)
        where = (_short_where(*sources[fm.group(1)], op_name)
                 if fm and fm.group(1) in sources else op_name)
        count = int(loop_steps) if "/while/" in op_name else 1
        ops.append(CollectiveOp(
            kind=kind, result=result.split("{")[0], dtype=dtype,
            buffer_bytes=nbytes, group_size=group, count=count,
            wire_bytes=bytes_on_wire(kind, nbytes, group) * count,
            op_name=op_name, where=where))
    return ops


# ======================================================== sharding flatten
def _normalize_spec(entries) -> tuple:
    """A PartitionSpec-ish sequence as a canonical tuple: sub-tuples kept,
    trailing Nones dropped (jax prints P('tp') and P('tp', None) for the
    same placement)."""
    out = [tuple(e) if isinstance(e, (list, tuple)) else e
           for e in (entries or ())]
    while out and out[-1] is None:
        out.pop()
    return tuple(out)


def _spec_of(sharding) -> tuple:
    spec = getattr(sharding, "spec", None)
    return _normalize_spec(tuple(spec)) if spec is not None else ()


def _flat_labeled(labels, tree):
    """Flatten one level of top-level args against their labels, then each
    subtree by path — ``state.blocks.0.attn.qkv_proj.weight``,
    ``k_pages.1`` — dot-joined so contract globs never need fnmatch's
    bracket syntax."""
    import jax.tree_util as jtu

    out = []
    for label, sub in zip(labels, tree):
        for path, leaf in jtu.tree_flatten_with_path(sub)[0]:
            key = label
            for p in path:
                part = getattr(p, "key", getattr(p, "idx", None))
                key += f".{part}" if part is not None else ""
            out.append((key, leaf))
    return out


def compiled_comms_surface(compiled, *, name, labels=None, args=None,
                           mesh_axes=None, loop_steps=1) -> dict:
    """The comms view of one jax ``Compiled``: collective inventory +
    flattened input/output sharding specs + input sizes. Works on any
    compiled program (zoo step programs, fixtures, the sampled-logits
    probe) — everything downstream is pure data."""
    import jax.tree_util as jtu

    ops = collective_inventory(compiled.as_text(), loop_steps=loop_steps)
    in_specs, in_bytes = {}, {}
    try:
        ins, _kwargs = compiled.input_shardings
    except Exception:
        ins = None
    if ins is not None:
        if labels is None:
            labels = tuple(f"arg{i}" for i in range(len(ins)))
        for key, sh in _flat_labeled(labels, ins):
            in_specs[key] = _spec_of(sh)
        if args is not None:
            import numpy as np

            for key, leaf in _flat_labeled(labels, args):
                try:     # PRNG key arrays have no byte width — count as 0
                    nbytes = int(np.prod(leaf.shape)
                                 * np.dtype(leaf.dtype).itemsize)
                except Exception:
                    nbytes = 0
                in_bytes[key] = {"bytes": nbytes,
                                 "shape": tuple(getattr(leaf, "shape", ()))}
    out_specs = {}
    try:
        outs = compiled.output_shardings
        for path, sh in jtu.tree_flatten_with_path(outs)[0]:
            key = "out" + "".join(
                f".{getattr(p, 'key', getattr(p, 'idx', ''))}" for p in path)
            out_specs[key] = _spec_of(sh)
    except Exception:
        pass
    return {
        "name": name,
        "mesh_axes": dict(mesh_axes or {}),
        "loop_steps": int(loop_steps),
        "ops": ops,
        "bytes_per_launch": sum(op.wire_bytes for op in ops),
        "input_specs": in_specs,
        "input_bytes": in_bytes,
        "output_specs": out_specs,
    }


# ============================================================ the step zoo
def _build_step_program(path):
    """Build one continuous-scheduler step program at the zoo smoke
    geometry under the CURRENT mesh and return (model, args, name,
    loop_steps, slots, width) — the same construction the zoo report
    functions use, minus the jaxpr analysis."""
    import jax
    import numpy as np

    from .zoo import _continuous_smoke

    model, kv, tbl, ids, S, C, NEW, T, jnp = _continuous_smoke()
    pools = (tuple(kv.k_pages), tuple(kv.v_pages))
    temps = jnp.zeros((S,), jnp.float32)
    top_ks = jnp.zeros((S,), jnp.int32)
    state = model._decode_state(jnp.bfloat16)
    key = jax.random.key(0)
    i32 = lambda a: jnp.asarray(a, jnp.int32)  # noqa: E731
    if path == "prefill_chunk":
        offs = np.zeros(S, np.int64)
        lens = np.asarray([C, 0], np.int64)
        model.prefill_chunk(ids, offs, lens, kv, tbl)
        args = (state, jnp.asarray(ids), i32(offs), i32(lens), i32(tbl),
                temps, top_ks, *pools, key)
        return model, args, "gpt.decode.paged_prefill_chunk_tp", 1, S, C
    model.prefill_chunk(ids, np.zeros(S, np.int64),
                        np.asarray([C, 0], np.int64), kv, tbl)
    act = np.asarray([True, False])
    lmax = np.asarray([C + NEW, 0], np.int64)
    if path == "decode_step":
        tok = np.zeros(S, np.int64)
        lens = np.asarray([C, 0], np.int64)
        model.decode_step(tok, lens, act, kv, tbl, steps=T, max_lens=lmax)
        args = (state, jnp.asarray(tok), i32(lens), jnp.asarray(act),
                i32(lmax), i32(tbl), temps, top_ks, *pools, key)
        # the scan body's collectives run once per scanned token
        return model, args, "gpt.decode.paged_step_tp", T, S, T
    if path == "verify_step":
        K = 3
        chunk = np.zeros((S, K + 1), np.int64)
        chunk[0] = np.random.RandomState(1).randint(0, 512, K + 1)
        offs = np.asarray([C, 0], np.int64)
        dlens = np.asarray([K, 0], np.int64)
        model.verify_step(chunk, offs, dlens, act, kv, tbl, max_lens=lmax)
        args = (state, jnp.asarray(chunk), i32(offs), i32(dlens),
                jnp.asarray(act), i32(lmax), i32(tbl), temps, top_ks,
                *pools, key)
        return model, args, "gpt.decode.paged_verify_step_tp", 1, S, K + 1
    raise ValueError(f"no comms surface for step path {path!r}")


def step_comms_surfaces(paths=None):
    """Compile the serving step programs under the ("dp","tp") serving mesh
    and return their comms surfaces. tp=2 when the process has the devices
    (tier-1 forces 8 host devices; a TPU slice always qualifies), else the
    degenerate tp=1 surface — no collectives, nothing sharded — so the
    pass still runs everywhere."""
    import jax

    from ..distributed.mesh import get_mesh, serving_mesh, set_mesh
    from ..models.generation import step_arg_labels

    prev = get_mesh()
    tp = 2 if len(jax.devices()) >= 2 else 1
    serving_mesh(dp=1, tp=tp)
    try:
        surfaces = []
        for path in paths or _STEP_PATHS:
            model, args, name, loop, slots, width = _build_step_program(path)
            compiled = model.compiled_step_program(path, slots, width, args)
            s = compiled_comms_surface(
                compiled, name=name, labels=step_arg_labels(path),
                args=args, mesh_axes={"dp": 1, "tp": tp}, loop_steps=loop)
            s["path"] = path
            s["tp"] = tp
            surfaces.append(s)
        return surfaces
    finally:
        set_mesh(prev)


# declared OUTPUT layout per step path: the KV pool layers stay
# head-sharded on the way out (same SpecLayout.kv_pool placement the
# inputs declare); sampled tokens come back replicated to the host.
def _output_contract(pool) -> dict:
    step = {"out.0": (), "out.1.*": pool, "out.2.*": pool}
    return {"prefill_chunk": step, "decode_step": step,
            "verify_step": {"out.0": (), "out.1": (),
                            "out.2.*": pool, "out.3.*": pool}}


def render_comms_table(surfaces) -> str:
    """The deploy-review artifact ``--comms`` prints: one row per
    collective with its wire cost, per program."""
    lines = []
    for s in surfaces:
        tp = s.get("tp") or s.get("mesh_axes", {}).get("tp", "?")
        lines.append(f"== comms surface: {s['name']} (tp={tp}) ==")
        if not s["ops"]:
            lines.append("  no collectives")
        for op in s["ops"]:
            lines.append(
                f"  {op.kind:18s} {op.result:22s} group={op.group_size} "
                f"x{op.count:<3d} {fmt_bytes(op.wire_bytes):>10s} on wire"
                f"  @ {op.where}")
        lines.append(f"  per-launch total {fmt_bytes(s['bytes_per_launch'])}"
                     " on wire per chip")
    return "\n".join(lines)


# ================================================================ the rules
def _rule_implicit_reshard(surface, expected):
    """HIGH: a collective kind no declared layout transition explains."""
    for op in surface["ops"]:
        if op.kind in expected:
            continue
        yield Finding(
            "implicit-reshard", HIGH,
            f"{op.kind} {op.result} (group={op.group_size}, x{op.count} "
            f"per launch, {fmt_bytes(op.wire_bytes)} on wire) has no "
            f"declared layout transition — declared transitions: "
            f"{sorted(expected)}",
            where=op.where, subject=surface["name"],
            remediation="align the sharded axis with the producing layout "
                        "(interleave per-shard head groups for fused "
                        "projections), declare the transition in "
                        "SpecLayout.expected_collectives, or allowlist it "
                        "with the reason")


def _rule_layout_contract(surface, contract):
    """HIGH: compiled sharding disagrees with the declared contract."""
    actual = {}
    actual.update(surface.get("input_specs", {}))
    actual.update(surface.get("output_specs", {}))
    if not contract or not actual:
        return
    for glob, want in sorted(contract.items()):
        want_n = _normalize_spec(want)
        hits = [k for k in actual if fnmatch.fnmatch(k, glob)]
        if not hits:
            yield Finding(
                "layout-contract-drift", HIGH,
                f"contract entry {glob!r} -> {want_n} matches no input or "
                "output of the compiled program — the contract rotted off "
                "the argument names",
                subject=surface["name"],
                remediation="re-aim the contract glob at the current "
                            "argument labels (or delete the entry)")
            continue
        for k in hits:
            got = actual[k]
            if got != want_n:
                yield Finding(
                    "layout-contract-drift", HIGH,
                    f"{k}: compiled sharding {got} != declared {want_n} "
                    f"(contract entry {glob!r})",
                    where=k, subject=surface["name"],
                    remediation="fix the constraint at the declaration "
                                "site (distributed/mesh.py SpecLayout) or "
                                "update the contract if the new layout is "
                                "intended")


def _rule_replicated_large_buffer(surface, strict=False,
                                  min_bytes=REPLICATED_BUFFER_MIN_BYTES):
    """WARN (strict HIGH): a large input replicated over a shardable tp."""
    tp = int(surface.get("tp")
             or surface.get("mesh_axes", {}).get("tp", 1))
    if tp <= 1:
        return
    sev = HIGH if strict else WARN
    specs = surface.get("input_specs", {})
    for label, meta in sorted(surface.get("input_bytes", {}).items()):
        nbytes, shape = meta["bytes"], meta["shape"]
        if nbytes < min_bytes or _normalize_spec(specs.get(label)) != ():
            continue
        shardable = [i for i, d in enumerate(shape) if d and d % tp == 0]
        if not shardable:
            continue
        yield Finding(
            "replicated-large-buffer", sev,
            f"{label}: {fmt_bytes(nbytes)} {tuple(shape)} is fully "
            f"replicated over tp={tp} though dim(s) {shardable} divide tp "
            f"— {fmt_bytes(nbytes - nbytes // tp)} of HBM per chip bought "
            "back by sharding it",
            where=label, subject=surface["name"],
            remediation="give the buffer a SpecLayout axis (the adapter "
                        "bank shards on its rank or output dim) or record "
                        "here why replication is the better trade")


def _rule_dead_mesh_axis(mesh_axes, surfaces):
    """WARN: a declared axis no sharding in the program set uses."""
    if not mesh_axes:
        return
    used = set()
    for s in surfaces:
        for spec in list(s.get("input_specs", {}).values()) \
                + list(s.get("output_specs", {}).values()):
            for e in spec:
                for name in (e if isinstance(e, tuple) else (e,)):
                    if name:
                        used.add(name)
    names = ", ".join(s["name"] for s in surfaces)
    for axis in sorted(mesh_axes):
        if axis in used:
            continue
        yield Finding(
            "dead-mesh-axis", WARN,
            f"declared mesh axis {axis!r} (size {mesh_axes[axis]}) is used "
            f"by no input/output sharding across: {names}",
            subject=surfaces[0]["name"] if surfaces else "comms",
            remediation="drop the axis from the mesh, or wire it into a "
                        "SpecLayout placement (an axis that shards nothing "
                        "still fragments the device grid)")


def _rule_comms_over_budget(budget, subject="comms"):
    """HIGH: the tick's wire bytes cannot fit the tick wall at this ICI."""
    if budget is None or budget.ici_bytes_per_s is None:
        return                       # unknown interconnect: ungated, honest
    wire_s = budget.wire_time_s()
    if wire_s <= budget.tick_wall_s:
        return
    per = ", ".join(
        f"{e.name}={fmt_bytes(int(e.bytes_per_launch * e.launches_per_tick))}"
        for e in budget.estimates)
    yield Finding(
        "comms-over-budget", HIGH,
        f"{fmt_bytes(budget.bytes_per_tick)} on wire per tick needs "
        f"{wire_s * 1e3:.2f}ms at {fmt_bytes(int(budget.ici_bytes_per_s))}/s"
        f" per chip — over the {budget.tick_wall_s * 1e3:.2f}ms tick wall "
        f"before compute spends a FLOP ({per})",
        subject=subject,
        remediation="raise tp to shrink per-chip shards, cut the implicit "
                    "reshards above, or re-plan the tick "
                    "(fewer decode_steps per launch)")


# ====================================================== interconnect budget
@dataclasses.dataclass(frozen=True)
class CommsEstimate:
    """Per-launch wire bytes of one step program, and how often the
    scheduler launches it per tick."""
    name: str
    bytes_per_launch: int
    launches_per_tick: float = 1.0

    def to_json(self) -> dict:
        return {"name": self.name,
                "bytes_per_launch": int(self.bytes_per_launch),
                "launches_per_tick": float(self.launches_per_tick)}

    @classmethod
    def from_json(cls, obj) -> "CommsEstimate":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(obj) - known)
        if unknown:
            raise ValueError(f"unknown CommsEstimate fields {unknown}")
        return cls(**obj)


@dataclasses.dataclass(frozen=True)
class CommsBudget:
    """The DeploymentPlan's interconnect component (ISSUE-20): per-tick
    collective bytes against the chip's ICI and the tick wall. DISJOINT
    from the HBM residency components by construction — these are bytes
    *moved* per tick, not bytes *resident*, so they never enter
    ``components()`` or ``planned_total_bytes``."""
    tick_wall_s: float
    ici_bytes_per_s: float | None = None   # None = unknown (CPU): ungated
    estimates: tuple = ()

    @property
    def bytes_per_tick(self) -> int:
        return int(sum(e.bytes_per_launch * e.launches_per_tick
                       for e in self.estimates))

    def wire_time_s(self) -> float:
        if not self.ici_bytes_per_s:
            return 0.0
        return self.bytes_per_tick / float(self.ici_bytes_per_s)

    def share_of_tick(self):
        """Wire time as a fraction of the tick wall (None when the
        interconnect is unknown) — the bench ``comms_share_of_tick``."""
        if self.ici_bytes_per_s is None or not self.tick_wall_s:
            return None
        return self.wire_time_s() / self.tick_wall_s

    def to_json(self) -> dict:
        return {"tick_wall_s": float(self.tick_wall_s),
                "ici_bytes_per_s": self.ici_bytes_per_s,
                "estimates": [e.to_json() for e in self.estimates]}

    @classmethod
    def from_json(cls, obj) -> "CommsBudget":
        known = {f.name for f in dataclasses.fields(cls)}
        unknown = sorted(set(obj) - known)
        if unknown:
            raise ValueError(f"unknown CommsBudget fields {unknown}")
        kw = dict(obj)
        kw["estimates"] = tuple(CommsEstimate.from_json(e)
                                for e in kw.get("estimates", ()))
        return cls(**kw)


def smoke_comms_budget(surfaces, *, decode_steps=None,
                       ici_bytes_per_s=None) -> CommsBudget:
    """The zoo CommsBudget: every step surface launches once per tick; the
    tick wall is decode_steps x the default TPOT objective; the ICI is the
    running chip's (None on CPU, which un-gates the budget rule rather than
    inventing a number)."""
    if ici_bytes_per_s is None:
        import jax

        from ..observability.xla import device_ici_bandwidth

        ici_bytes_per_s = device_ici_bandwidth(jax.devices()[0])
    steps = decode_steps
    if steps is None:
        steps = max([s.get("loop_steps", 1) for s in surfaces] or [1])
    return CommsBudget(
        tick_wall_s=steps * DEFAULT_TPOT_BUDGET_S,
        ici_bytes_per_s=ici_bytes_per_s,
        estimates=tuple(CommsEstimate(s["name"], s["bytes_per_launch"])
                        for s in surfaces))


# ============================================================= entry points
def analyze_comms_surfaces(surfaces, *, contract=None, expected=None,
                           mesh_axes=None, budget=None, strict=False,
                           allowlist=None, name="comms.surface") -> Report:
    """Run the five comms rules over a set of surfaces; returns the shared
    Report type (same gating as every other lint leg)."""
    import jax

    findings = []
    for s in surfaces:
        findings.extend(_rule_implicit_reshard(
            s, expected if expected is not None else default_expected()))
        per_contract = dict(contract or {})
        per_contract.update(s.get("contract", {}))
        if int(s.get("tp") or s.get("mesh_axes", {}).get("tp", 1)) > 1:
            findings.extend(_rule_layout_contract(s, per_contract))
        findings.extend(_rule_replicated_large_buffer(s, strict=strict))
    findings.extend(_rule_dead_mesh_axis(mesh_axes, surfaces))
    findings.extend(_rule_comms_over_budget(
        budget, subject=surfaces[0]["name"] if surfaces else "comms"))
    al = allowlist if allowlist is not None else BUILTIN_COMMS_ALLOWLIST
    try:
        backend = jax.default_backend()
    except Exception:
        backend = ""
    kept, suppressed = al.apply(findings, backend)
    return Report(name, kept, suppressed, tuple(COMMS_RULES))


def default_expected() -> dict:
    from ..distributed.mesh import SpecLayout

    return SpecLayout().expected_collectives()


def analyze_step_comms(allowlist=None, *, paths=None,
                       name="comms.surface", _surfaces=None) -> Report:
    """The ``comms_surface`` zoo entry body: compile the serving step
    programs under the tp serving mesh, inventory their collectives, check
    the SpecLayout contract, and run all five rules. ``--self-check``
    fails on any un-allowlisted HIGH here — an implicit reshard in the
    decode tick is a deploy blocker, not a curiosity. ``_surfaces`` lets
    the CLI reuse surfaces it already compiled for the printed table
    (three tp=2 compiles are the whole cost of this pass)."""
    from ..distributed.mesh import SpecLayout

    surfaces = (_surfaces if _surfaces is not None
                else step_comms_surfaces(paths=paths))
    layout = SpecLayout()
    outputs = _output_contract(layout.kv_pool())
    for s in surfaces:
        s["contract"] = outputs.get(s.get("path"), {})
    return analyze_comms_surfaces(
        surfaces,
        contract=layout.step_contract(),
        expected=layout.expected_collectives(),
        mesh_axes=surfaces[0]["mesh_axes"] if surfaces else None,
        budget=smoke_comms_budget(surfaces),
        allowlist=allowlist, name=name)


def sampled_logits_gather_surface(S=2, V=512, tp=None) -> dict:
    """The ONE documented collective of the split-KV decode path, in
    isolation: [S, V] logits vocab-sharded by the tied lm_head
    (SpecLayout.logits()), forced back to replicated the way sampling
    consumes them. The compiled surface must contain exactly one
    all-gather whose bytes-on-wire match S*V*itemsize*(tp-1)/tp — the
    acceptance pin that keeps the inventory's byte arithmetic honest."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    from ..distributed.mesh import SpecLayout, serving_mesh

    if tp is None:
        tp = 2 if len(jax.devices()) >= 2 else 1
    mesh = serving_mesh(dp=1, tp=tp, set_global=False).jax_mesh
    layout = SpecLayout()

    @jax.jit
    def gather(logits):
        sharded = jax.lax.with_sharding_constraint(
            logits, NamedSharding(mesh, PartitionSpec(*layout.logits())))
        scaled = sharded * 2.0       # computed while vocab-sharded
        return jax.lax.with_sharding_constraint(
            scaled, NamedSharding(mesh, PartitionSpec()))

    args = (jnp.zeros((S, V), jnp.float32),)
    compiled = gather.lower(*args).compile()
    return compiled_comms_surface(
        compiled, name="sampled_logits_gather", labels=("logits",),
        args=args, mesh_axes={"dp": 1, "tp": tp})


# ------------------------------------------------------------- fixture mode
def comms_fixture_reports(path):
    """Seeded-violation mode for ``--comms PATH`` (mirrors --threads /
    --surface / --hbm): a ``.json`` file is a synthetic comms surface
    (keys: ``mesh_axes`` / ``contract`` / ``actual`` / ``collectives`` /
    ``buffers`` / ``budget`` / ``expected_collectives`` — all optional, a
    rule runs iff its section is present); a ``.py`` file is a PROGRAM
    fixture defining ``make_program() -> (fn, args)`` (optionally
    ``LOOP_STEPS``) that is compiled and inventoried for real. Directories
    run every fixture inside. Everything is strict with an empty
    allowlist: any HIGH exits 1."""
    if os.path.isdir(path):
        names = sorted(n for n in os.listdir(path)
                       if n.endswith((".py", ".json")))
        out = []
        for n in names:
            out.extend(comms_fixture_reports(os.path.join(path, n)))
        return out
    label = f"comms[{os.path.basename(path)}]"
    if path.endswith(".json"):
        with open(path, "r") as fh:
            spec = json.load(fh)
        return [_json_fixture_report(spec, label)]
    import runpy

    mod = runpy.run_path(path)
    if "make_program" not in mod:
        raise ValueError(f"{path}: a .py comms fixture must define "
                         "make_program() -> (fn, args)")
    import jax

    fn, args = mod["make_program"]()
    if not hasattr(fn, "lower"):
        fn = jax.jit(fn)
    compiled = fn.lower(*args).compile()
    surface = compiled_comms_surface(
        compiled, name=os.path.basename(path), args=args,
        loop_steps=int(mod.get("LOOP_STEPS", 1)))
    return [analyze_comms_surfaces(
        [surface], expected=mod.get("EXPECTED_COLLECTIVES", {}),
        strict=True, allowlist=Allowlist([]), name=label)]


def _json_fixture_report(spec, label) -> Report:
    name = spec.get("name", label)
    surface = {
        "name": name,
        "mesh_axes": dict(spec.get("mesh_axes", {})),
        "tp": spec.get("mesh_axes", {}).get("tp", 1),
        "ops": [],
        "input_specs": {k: _normalize_spec(v)
                        for k, v in spec.get("actual", {}).items()},
        "input_bytes": {},
        "output_specs": {},
        "contract": {},
        "bytes_per_launch": 0,
    }
    for c in spec.get("collectives", ()):
        dtype, nbytes = _hlo_result_bytes(c["result"])
        group = int(c.get("group_size", 1))
        count = int(c.get("count", 1))
        surface["ops"].append(CollectiveOp(
            kind=c["kind"], result=c["result"], dtype=dtype,
            buffer_bytes=nbytes, group_size=group, count=count,
            wire_bytes=bytes_on_wire(c["kind"], nbytes, group) * count,
            where=c.get("where", name)))
    surface["bytes_per_launch"] = sum(op.wire_bytes
                                      for op in surface["ops"])
    for b in spec.get("buffers", ()):
        import numpy as np

        nbytes = int(np.prod(b["shape"]) * np.dtype(b["dtype"]).itemsize)
        surface["input_bytes"][b["label"]] = {"bytes": nbytes,
                                              "shape": tuple(b["shape"])}
        surface["input_specs"].setdefault(
            b["label"], _normalize_spec(b.get("spec", ())))
    budget = None
    if "budget" in spec:
        budget = CommsBudget.from_json(spec["budget"])
    expected = spec.get("expected_collectives")
    if expected is not None:
        expected = {k: "declared by fixture" for k in expected}
    return analyze_comms_surfaces(
        [surface], contract=spec.get("contract"), expected=expected or {},
        mesh_axes=spec.get("mesh_axes") or None, budget=budget,
        strict=True, allowlist=Allowlist([]), name=label)


# Intentional, justified cross-chip traffic shipped with the repo — the
# lint's first catch, kept VISIBLE (Report.suppressed) until the layouts
# are fixed. Every entry is real wire traffic the declared SpecLayout does
# not explain; docs/ANALYSIS.md carries the full writeup.
BUILTIN_COMMS_ALLOWLIST = Allowlist([
    # The fused qkv projection is column-sharded as one 192-wide matrix
    # (q=64 | k=64 | v=64 at 4 heads x 16 dim): the tp=2 shard boundary at
    # 96 lands MID-k, so split_qkv's slices straddle shards and XLA patches
    # each layer with f32[S,1,hidden] collective-permutes (models/gpt.py
    # split_qkv). Known layout debt: the fix is interleaving per-shard head
    # groups so the shard boundary lands between heads, not inside them.
    AllowlistEntry(
        "implicit-reshard", subject="gpt.decode.*_tp",
        contains="models/gpt.py",
        reason="fused qkv column shard straddles the k/v head groups at "
               "tp=2 (shard boundary 96 falls inside k) — split_qkv's "
               "slices cross shards until per-shard head groups are "
               "interleaved; bounded, per-layer, hidden-sized traffic"),
    # Same straddle for the fused swiglu: gate|up halves of the 512-wide
    # gate_up projection each cross the 256-boundary column shard.
    AllowlistEntry(
        "implicit-reshard", subject="gpt.decode.*_tp",
        contains="incubate/nn/functional",
        reason="fused swiglu gate/up halves straddle the gate_up column "
               "shard at tp=2 — same head-group interleaving fix as "
               "split_qkv; bounded, per-layer, ffn-sized traffic"),
    # Top-k sampling over the vocab-sharded logits lowers to XLA's
    # distributed sort, which exchanges shard partitions with all-to-alls.
    # Intentional: sorting the shards in place moves O(S*k) bytes where
    # gathering the logits first would move O(S*V).
    AllowlistEntry(
        "implicit-reshard", subject="gpt.decode.*_tp", contains="sort",
        reason="top-k sampling sorts the vocab-sharded logits in place "
               "(distributed sort all-to-alls) — cheaper on wire than "
               "gathering [S, V] logits to every chip first"),
    # dp is the replica-FLEET axis: data parallelism lives at the
    # scheduler-replica level (ReplicaFleet), so no in-program sharding
    # ever names it — declared in the SpecLayout docstring, and kept
    # declared so fleet meshes and program meshes stay the same object.
    AllowlistEntry(
        "dead-mesh-axis", contains="'dp'",
        reason="dp is the replica-fleet axis (scheduler-level data "
               "parallelism, distributed/mesh.py SpecLayout): in-program "
               "shardings never use it by design"),
])
