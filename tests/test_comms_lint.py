"""Sharding & collective lint (ISSUE-20): the post-SPMD HLO collective
parser, the bytes-on-wire arithmetic, the five comms rules, the
interconnect budget dataclasses, the DeploymentPlan.comms arm, the seeded
fixtures, the CLI legs, and the metrics exposition.

The parser pins are HAND-COMPUTED on inline HLO lines — every wire-bytes
number below is derivable on paper from the printed buffer size, the group
size and the ring formulas (docs/ANALYSIS.md), which is the point: when one
breaks, the cost model's semantics changed, not a tolerance. The one REAL
compiled program in the non-slow tier is the sampled-logits gather probe —
the split-KV decode path's single documented collective — pinned to exactly
S*V*itemsize*(tp-1)/tp bytes; the full three-program zoo pass (three tp=2
compiles, ~20s) is slow-marked and rides ``--self-check`` in CI.
"""
import inspect
import json
import os

import numpy as np
import pytest

import jax

from paddle_tpu.analysis import comms as C
from paddle_tpu.analysis.__main__ import main as cli_main
from paddle_tpu.analysis.compilesurface import ServingConfig
from paddle_tpu.analysis.core import HIGH, WARN
from paddle_tpu.analysis.findings import (Allowlist, AllowlistEntry,
                                          stale_allowlist_findings)
from paddle_tpu.analysis import hbm as H

FIXTURES = os.path.join(os.path.dirname(__file__), "comms_fixtures")

multichip = pytest.mark.skipif(
    len(jax.devices()) < 2,
    reason="needs >=2 devices (tier-1 forces 8 CPU devices)")


# ---------------------------------------------------------------- the parser
# One line per collective kind, written the way XLA prints post-SPMD HLO:
# iota replica_groups on the gather, explicit-list groups on the reduce,
# source_target_pairs on the permute, a tuple-typed async -start, and a
# -done that must NOT be counted (the -start carries the transfer). The
# all-reduce lives inside the decode scan (``/while/`` in op_name) so it
# multiplies by loop_steps.
# Provenance is printed as an index: ops carry ``stack_frame_id=N`` and the
# FileNames / FileLocations / StackFrames header blocks resolve it.
_HLO = """\
HloModule jit_step

FileNames
1 "/w/paddle_tpu/models/generation.py"
2 "/w/paddle_tpu/nn/functional/common.py"

FunctionNames
1 "sample"
2 "linear"

FileLocations
1 {file_name_id=1 function_name_id=1 line=149 end_line=149 column=4 end_column=9}
2 {file_name_id=2 function_name_id=2 line=25 end_line=25 column=4 end_column=9}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=2}

ENTRY %main {
  %ag = f32[2,512]{1,0} all-gather(f32[2,256]{1,0} %p0), replica_groups=[1,2]<=[2], dimensions={1}, metadata={op_name="jit(step)/reduce" stack_frame_id=1}
  %ar = f32[8]{0} all-reduce(f32[8]{0} %x), replica_groups={{0,1}}, to_apply=%add, metadata={op_name="jit(step)/while/body/dot_general" stack_frame_id=2}
  %rs = f32[4]{0} reduce-scatter(f32[8]{0} %x), replica_groups={{0,1}}, dimensions={0}
  %aa = f32[8]{0} all-to-all(f32[8]{0} %x), replica_groups={{0,1}}, metadata={op_name="jit(sort)/sort"}
  %cp = f32[4]{0} collective-permute(f32[4]{0} %x), source_target_pairs={{0,1},{1,0}}
  %ags = (f32[4]{0}, f32[8]{0}) all-gather-start(f32[4]{0} %x), replica_groups=[1,2]<=[2]
  %agd = f32[8]{0} all-gather-done((f32[4]{0}, f32[8]{0}) %ags)
}
"""


def test_collective_inventory_hand_computed():
    ops = C.collective_inventory(_HLO, loop_steps=3)
    by_kind = {}
    for op in ops:
        by_kind.setdefault(op.kind, []).append(op)
    # 6 collectives: the -done is the completion token, not a transfer
    assert len(ops) == 6
    assert sorted(by_kind) == ["all-gather", "all-reduce", "all-to-all",
                               "collective-permute", "reduce-scatter"]

    ag, ags = by_kind["all-gather"]
    # gathered buffer G = 2*512*4 = 4096 B, ring: G(n-1)/n at n=2
    assert (ag.dtype, ag.buffer_bytes, ag.group_size) == ("f32", 4096, 2)
    assert ag.count == 1 and ag.wire_bytes == 2048
    assert ag.where == "paddle_tpu/models/generation.py:149 (reduce)"
    # async start: tuple type sums its elements (16 + 32 B)
    assert ags.buffer_bytes == 48 and ags.wire_bytes == 24

    (ar,) = by_kind["all-reduce"]
    # B = 32 B, 2B(n-1)/n = 32 per execution; /while/ -> x loop_steps
    assert ar.count == 3 and ar.wire_bytes == 3 * 32

    (rs,) = by_kind["reduce-scatter"]     # shard Bs = 16 B, Bs(n-1) = 16
    assert rs.buffer_bytes == 16 and rs.wire_bytes == 16
    (aa,) = by_kind["all-to-all"]         # B = 32 B, B(n-1)/n = 16
    assert aa.wire_bytes == 16
    (cp,) = by_kind["collective-permute"]  # B = 16 B, group from the pairs
    assert cp.group_size == 2 and cp.wire_bytes == 16


def test_bytes_on_wire_ring_formulas():
    assert C.bytes_on_wire("all-gather", 4096, 2) == 2048
    assert C.bytes_on_wire("all-gather", 4096, 4) == 3072
    assert C.bytes_on_wire("all-reduce", 1024, 4) == 1536
    assert C.bytes_on_wire("reduce-scatter", 256, 4) == 768
    assert C.bytes_on_wire("all-to-all", 1024, 4) == 768
    assert C.bytes_on_wire("collective-permute", 777, 8) == 777
    # a group of one moves nothing (except the permute, which is explicit)
    assert C.bytes_on_wire("all-gather", 4096, 1) == 0
    assert C.bytes_on_wire("all-reduce", 4096, 1) == 0


def test_normalize_spec_canonical_forms():
    # jax prints P('tp') and P('tp', None) for the same placement
    assert C._normalize_spec(["tp", None]) == ("tp",)
    assert C._normalize_spec([None, "tp"]) == (None, "tp")
    assert C._normalize_spec([["dp", "tp"]]) == (("dp", "tp"),)
    assert C._normalize_spec(None) == ()
    assert C._normalize_spec([]) == ()


# ----------------------------------------------------------------- the rules
def _op(kind="collective-permute", result="f32[4]", nbytes=16, group=2,
        count=1, where="w"):
    return C.CollectiveOp(kind=kind, result=result, dtype="f32",
                          buffer_bytes=nbytes, group_size=group, count=count,
                          wire_bytes=C.bytes_on_wire(kind, nbytes, group)
                          * count, where=where)


def _surface(**kw):
    s = {"name": "syn", "mesh_axes": {"tp": 2}, "tp": 2, "loop_steps": 1,
         "ops": [], "bytes_per_launch": 0, "input_specs": {},
         "input_bytes": {}, "output_specs": {}}
    s.update(kw)
    return s


def test_rule_implicit_reshard_flags_undeclared_kinds_only():
    s = _surface(ops=[_op("all-reduce", nbytes=32),
                      _op("collective-permute")])
    found = list(C._rule_implicit_reshard(s, {"all-reduce": "partial sums"}))
    assert [f.rule for f in found] == ["implicit-reshard"]
    assert found[0].severity == HIGH
    assert "collective-permute" in found[0].message
    assert not list(C._rule_implicit_reshard(
        s, {"all-reduce": "", "collective-permute": ""}))


def test_rule_layout_contract_mismatch_and_rotted_glob():
    s = _surface(input_specs={"state.w": (), "k_pages.0": (None, None, "tp")},
                 output_specs={"out.0": ()})
    # mismatch on a matched key
    found = list(C._rule_layout_contract(s, {"state.w": (None, "tp")}))
    assert [f.rule for f in found] == ["layout-contract-drift"]
    assert "state.w" in found[0].message
    # a glob matching nothing is drift too — the contract rotted
    found = list(C._rule_layout_contract(s, {"state.gone.*": ("tp",)}))
    assert len(found) == 1 and "matches no input" in found[0].message
    # agreement (including the out.* side) is silent
    assert not list(C._rule_layout_contract(
        s, {"k_pages.*": (None, None, "tp"), "out.0": ()}))


def test_rule_replicated_large_buffer_gates_and_strict():
    big = {"bytes": 2 << 20, "shape": (8, 64, 1024)}
    s = _surface(input_bytes={"bank": big}, input_specs={"bank": ()})
    (f,) = C._rule_replicated_large_buffer(s)
    assert f.rule == "replicated-large-buffer" and f.severity == WARN
    (f,) = C._rule_replicated_large_buffer(s, strict=True)
    assert f.severity == HIGH
    # sharded, small, tp=1, and tp-indivisible buffers are all silent
    assert not list(C._rule_replicated_large_buffer(
        _surface(input_bytes={"bank": big}, input_specs={"bank": ("tp",)})))
    assert not list(C._rule_replicated_large_buffer(
        _surface(input_bytes={"b": {"bytes": 100, "shape": (10, 10)}})))
    assert not list(C._rule_replicated_large_buffer(
        _surface(tp=1, mesh_axes={"tp": 1}, input_bytes={"bank": big})))
    odd = {"bytes": 2 << 20, "shape": (7, 9)}
    assert not list(C._rule_replicated_large_buffer(
        _surface(input_bytes={"b": odd}, input_specs={"b": ()})))


def test_rule_dead_mesh_axis():
    s = _surface(input_specs={"k_pages.0": (None, None, "tp")})
    found = list(C._rule_dead_mesh_axis({"dp": 2, "tp": 2}, [s]))
    assert [f.rule for f in found] == ["dead-mesh-axis"]
    assert "'dp'" in found[0].message and found[0].severity == WARN
    assert not list(C._rule_dead_mesh_axis({"tp": 2}, [s]))
    assert not list(C._rule_dead_mesh_axis(None, [s]))


def test_rule_comms_over_budget_pass_fail_and_ungated():
    est = (C.CommsEstimate("decode", 1_000_000),)
    over = C.CommsBudget(tick_wall_s=0.001, ici_bytes_per_s=1000.0,
                         estimates=est)
    (f,) = C._rule_comms_over_budget(over, subject="syn")
    assert f.rule == "comms-over-budget" and f.severity == HIGH
    ok = C.CommsBudget(tick_wall_s=0.1, ici_bytes_per_s=1e12, estimates=est)
    assert not list(C._rule_comms_over_budget(ok))
    # unknown interconnect (CPU) un-gates rather than inventing a number
    unknown = C.CommsBudget(tick_wall_s=0.1, ici_bytes_per_s=None,
                            estimates=est)
    assert not list(C._rule_comms_over_budget(unknown))
    assert not list(C._rule_comms_over_budget(None))


# ------------------------------------------------------ budget dataclasses
def test_comms_budget_arithmetic_and_json_round_trip():
    b = C.CommsBudget(
        tick_wall_s=0.1, ici_bytes_per_s=200e9,
        estimates=(C.CommsEstimate("prefill", 1000),
                   C.CommsEstimate("decode", 2048, launches_per_tick=2.0)))
    assert b.bytes_per_tick == 1000 + 4096
    assert b.wire_time_s() == pytest.approx(5096 / 200e9)
    assert b.share_of_tick() == pytest.approx(5096 / 200e9 / 0.1)
    rt = C.CommsBudget.from_json(json.loads(json.dumps(b.to_json())))
    assert rt == b
    assert C.CommsBudget(tick_wall_s=0.1).share_of_tick() is None


def test_comms_budget_json_rejects_unknown_fields():
    with pytest.raises(ValueError, match="unknown CommsBudget"):
        C.CommsBudget.from_json({"tick_wall_s": 0.1, "bytes_per_tick": 5})
    with pytest.raises(ValueError, match="unknown CommsEstimate"):
        C.CommsEstimate.from_json({"name": "x", "bytes_per_launch": 1,
                                   "wire_time": 2})


def test_smoke_comms_budget_from_surfaces():
    surfaces = [_surface(name="prefill", bytes_per_launch=100,
                         loop_steps=1),
                _surface(name="decode", bytes_per_launch=4096,
                         loop_steps=4)]
    b = C.smoke_comms_budget(surfaces, ici_bytes_per_s=1e9)
    # tick wall = decode scan steps x the default 50ms TPOT objective
    assert b.tick_wall_s == pytest.approx(4 * C.DEFAULT_TPOT_BUDGET_S)
    assert b.bytes_per_tick == 4196
    assert {e.name for e in b.estimates} == {"prefill", "decode"}


# --------------------------------------------------- DeploymentPlan.comms
def _plan(budget=8 << 20, comms=None):
    cfg = ServingConfig(name="syn", slots=4, max_seq_len=1024,
                        kv_signature=(2, 4, 16, 128, 32, "bfloat16"))
    return H.DeploymentPlan(config=cfg, budget_bytes=budget, comms=comms)


def test_plan_comms_is_disjoint_from_residency_components():
    comms = C.CommsBudget(tick_wall_s=0.1, ici_bytes_per_s=1e9,
                          estimates=(C.CommsEstimate("decode", 4096),))
    plan = _plan(comms=comms)
    bare = _plan()
    # bytes MOVED never enter bytes RESIDENT: same components, same sum
    assert plan.components() == bare.components()
    assert plan.planned_total_bytes == bare.planned_total_bytes
    assert "comms" not in plan.components()
    table = plan.render_table()
    assert "comms" in table and "on wire/tick" in table
    assert "comms" not in bare.render_table()


def test_plan_comms_json_round_trip_and_unknown_rejected():
    comms = C.CommsBudget(tick_wall_s=0.2, ici_bytes_per_s=None,
                          estimates=(C.CommsEstimate("decode", 77),))
    plan = _plan(comms=comms)
    rt = H.DeploymentPlan.from_json(json.loads(json.dumps(plan.to_json())))
    assert rt.comms == comms
    assert _plan().to_json()["comms"] is None
    obj = plan.to_json()
    obj["comms"]["made_up"] = 1
    with pytest.raises(ValueError, match="unknown CommsBudget"):
        H.DeploymentPlan.from_json(obj)


def test_analyze_hbm_plan_runs_comms_arm_pass_and_fail():
    est = (C.CommsEstimate("decode", 1_000_000),)
    over = C.CommsBudget(tick_wall_s=0.001, ici_bytes_per_s=1000.0,
                         estimates=est)
    report = H.analyze_hbm_plan(_plan(comms=over), allowlist=Allowlist([]))
    assert [f.rule for f in report.high()] == ["comms-over-budget"]
    assert "comms-over-budget" in report.rules_run
    ok = C.CommsBudget(tick_wall_s=0.1, ici_bytes_per_s=1e12, estimates=est)
    report = H.analyze_hbm_plan(_plan(comms=ok), allowlist=Allowlist([]))
    assert not [f for f in report.findings
                if f.rule == "comms-over-budget"]
    # a comms-less plan does not even advertise the rule
    bare = H.analyze_hbm_plan(_plan(), allowlist=Allowlist([]))
    assert "comms-over-budget" not in bare.rules_run


# ------------------------------------------------------- the acceptance pin
@multichip
def test_sampled_logits_gather_pinned_bytes():
    """The split-KV decode path's ONE documented collective, compiled in
    isolation: vocab-sharded [S, V] logits forced back to replicated must
    cost exactly one all-gather of S*V*itemsize*(tp-1)/tp bytes on wire —
    the pin that keeps the inventory's byte arithmetic honest against a
    REAL compiled program (the zoo-wide pass is slow-marked)."""
    S, V = 2, 512
    s = C.sampled_logits_gather_surface(S=S, V=V)
    tp = s["mesh_axes"]["tp"]
    assert tp >= 2
    gathers = [op for op in s["ops"] if op.kind == "all-gather"]
    assert len(gathers) == 1 and len(s["ops"]) == 1
    (ag,) = gathers
    want = S * V * 4 * (tp - 1) // tp
    assert ag.wire_bytes == want == s["bytes_per_launch"]
    assert ag.group_size == tp
    # the host hands the logits over replicated; the vocab shard lives
    # inside the program (with_sharding_constraint), which is exactly why
    # the gather shows up in the compiled module at all
    assert s["input_specs"]["logits"] == ()


# ------------------------------------------------------------ the fixtures
def _fixture_report(name):
    reports = C.comms_fixture_reports(os.path.join(FIXTURES, name))
    assert len(reports) == 1
    return reports[0]


@multichip
def test_fixture_forced_reshard_exactly_one_high():
    r = _fixture_report("forced_reshard.py")
    assert [f.rule for f in r.findings] == ["implicit-reshard"]
    assert [f.severity for f in r.findings] == [HIGH]
    assert "collective-permute" in r.findings[0].message


def test_fixture_contract_drift_exactly_one_high():
    r = _fixture_report("contract_drift.json")
    assert [f.rule for f in r.findings] == ["layout-contract-drift"]
    assert [f.severity for f in r.findings] == [HIGH]


def test_fixture_over_budget_exactly_one_high():
    r = _fixture_report("over_budget.json")
    assert [f.rule for f in r.findings] == ["comms-over-budget"]
    assert [f.severity for f in r.findings] == [HIGH]


def test_fixture_replicated_bank_exactly_one_strict_high():
    r = _fixture_report("replicated_bank.json")
    assert [f.rule for f in r.findings] == ["replicated-large-buffer"]
    assert [f.severity for f in r.findings] == [HIGH]   # fixture = strict


def test_fixture_dead_axis_warn_only():
    r = _fixture_report("dead_axis.json")
    assert [f.rule for f in r.findings] == ["dead-mesh-axis"]
    assert [f.severity for f in r.findings] == [WARN]
    assert r.high() == []


def test_fixture_clean_is_clean():
    r = _fixture_report("clean.json")
    assert r.findings == [] and r.suppressed == []


# ------------------------------------------------------------------ the CLI
def test_cli_comms_fixture_exit_codes(capsys):
    assert cli_main(["--comms", os.path.join(FIXTURES, "clean.json")]) == 0
    assert cli_main(["--comms",
                     os.path.join(FIXTURES, "dead_axis.json")]) == 0
    assert cli_main(["--comms",
                     os.path.join(FIXTURES, "over_budget.json")]) == 1
    # the directory runs every fixture; the seeded HIGHs gate it
    assert cli_main(["--comms", FIXTURES]) == 1
    out = capsys.readouterr().out
    assert "comms[over_budget.json]" in out
    assert "comms-over-budget" in out


def test_cli_comms_rejects_unknown_step_name(capsys):
    assert cli_main(["--comms", "no_such_step"]) == 2
    assert "unknown step path" in capsys.readouterr().err


def test_cli_list_rules_covers_comms(capsys):
    assert cli_main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in C.COMMS_RULES:
        assert rule in out
    assert "[comms]" in out


def test_self_check_audits_comms_allowlist_for_staleness():
    # functional: an entry that matched nothing is a WARN the self-check
    # prints; wiring: the CLI audit list includes the comms allowlist
    stale = stale_allowlist_findings([
        ("comms", Allowlist([AllowlistEntry("implicit-reshard",
                                            contains="never-matches",
                                            reason="stale on purpose")]))])
    assert len(stale) == 1 and stale[0].rule == "allowlist-stale"
    import paddle_tpu.analysis.__main__ as cli_mod

    src = inspect.getsource(cli_mod.main)
    assert '"comms", BUILTIN_COMMS_ALLOWLIST' in src


def test_builtin_comms_allowlist_entries_all_reasoned():
    entries = C.BUILTIN_COMMS_ALLOWLIST.entries
    assert len(entries) >= 4
    for e in entries:
        assert e.reason and len(e.reason) > 20


# ------------------------------------------------------- metrics exposition
def test_record_findings_exposes_comms_rules():
    from paddle_tpu.analysis.threads import record_findings
    from paddle_tpu.observability.metrics import (MetricsRegistry,
                                                  render_prometheus)

    s = _surface(ops=[_op("collective-permute")])
    report = C.analyze_comms_surfaces([s], expected={}, strict=True,
                                      allowlist=Allowlist([]))
    reg = MetricsRegistry()
    record_findings(report, reg)
    text = render_prometheus(reg)
    assert "paddle_analysis_findings_total" in text
    assert 'rule="implicit-reshard"' in text


# ------------------------------------------------------------ step programs
def test_step_arg_labels_match_signatures():
    from paddle_tpu.models.generation import step_arg_labels

    for kind in ("prefill_chunk", "decode_step", "verify_step"):
        labels = step_arg_labels(kind)
        assert labels[0] == "state" and labels[-1] == "rng_key"
        assert "k_pages" in labels and "v_pages" in labels
        with_lora = step_arg_labels(kind, adapters=True)
        assert len(with_lora) == len(labels) + 2
        i = with_lora.index("adapter_slots")
        assert with_lora[i + 1] == "bank"
        assert with_lora[-1] == "rng_key"
    with pytest.raises(KeyError):
        step_arg_labels("no_such_step")


# ------------------------------------------------------------- the zoo gate
@pytest.mark.slow
@multichip
def test_zoo_comms_surface_self_check_clean_with_visible_suppressions():
    """The full ``comms_surface`` zoo entry (three tp=2 compiles): zero
    un-allowlisted HIGHs, and the first-catch traffic — qkv/swiglu shard
    straddles, the top-k distributed sort — VISIBLE in suppressed with
    reasons, never silently absorbed."""
    from paddle_tpu.analysis.zoo import zoo_report

    r = zoo_report("comms_surface")
    assert r.high() == [], [f.render() for f in r.high()]
    assert len(r.suppressed) > 0
    rules = {f.rule for f, _ in r.suppressed}
    assert "implicit-reshard" in rules
    assert all(e.reason for _, e in r.suppressed)
    assert set(r.rules_run) == set(C.COMMS_RULES)
