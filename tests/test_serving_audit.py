"""Serving audit regression guard (ISSUE-1 satellite: CI/tooling).

The serving regression class (per-call cache allocation in the host
wrapper) is pinned by bench.py's scan-vs-e2e audit: the serving
section must emit `bN_tokens_per_sec` / `bN_scan_tokens_per_sec` AND the
derived gap fields, with the gap computed correctly. If someone rewires the
serving bench and drops the audit, these tests fail before the next bench run
silently loses the guard.
"""
import importlib
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
bench = importlib.import_module("bench")


def test_audit_fields_computed():
    out = {
        "b1_tokens_per_sec": 600.0, "b1_scan_tokens_per_sec": 625.0,
        "b8_tokens_per_sec": 3500.0, "b8_scan_tokens_per_sec": 3600.0,
    }
    bench.serving_audit_fields(out)
    assert out["b1_audit_gap_pct"] == pytest.approx(4.0)
    assert out["b1_audit"] == "ok"
    assert out["b8_audit_gap_pct"] == pytest.approx(100 * (100 / 3600), abs=0.01)
    assert out["b8_audit"] == "ok"


def test_audit_flags_regression_over_threshold():
    out = {"b1_tokens_per_sec": 300.0, "b1_scan_tokens_per_sec": 600.0}
    bench.serving_audit_fields(out)
    assert out["b1_audit_gap_pct"] == pytest.approx(50.0)
    assert out["b1_audit"] == "e2e-overhead"       # the r4 regression signature


def test_audit_faster_e2e_clamps_to_zero():
    # measurement noise can put e2e ABOVE scan; the gap clamps at 0, never
    # negative (a negative "gap" would hide a later real regression in deltas)
    out = {"b1_tokens_per_sec": 650.0, "b1_scan_tokens_per_sec": 600.0}
    bench.serving_audit_fields(out)
    assert out["b1_audit_gap_pct"] == 0.0
    assert out["b1_audit"] == "ok"


def test_audit_skips_missing_sections():
    out = {"b1_tokens_per_sec": 600.0}              # scan rate absent
    bench.serving_audit_fields(out)
    assert "b1_audit_gap_pct" not in out
    assert "b8_audit_gap_pct" not in out


def test_serving_bench_emits_audit_fields():
    """The serving section's field wiring itself: bench_serving must route its
    measurements through serving_audit_fields (source-level pin — running the
    full serving bench on CPU takes minutes)."""
    import inspect

    src = inspect.getsource(bench.bench_serving)
    assert "serving_audit_fields(" in src
    assert "scan_tokens_per_sec" in src


def test_pressure_fields_conservation_ok():
    out = {"accepted": 10, "completed": 7, "failed": 1, "timeouts": 2,
           "p50_ms": 10.0, "p99_ms": 40.0}
    bench.serving_pressure_fields(out)
    assert out["terminal_total"] == 10
    assert out["conservation"] == "ok"
    assert out["tail_ratio_p99_p50"] == pytest.approx(4.0)


def test_pressure_fields_flag_leaked_requests():
    # an accepted request that never reached a terminal outcome is the
    # serving-runtime bug class this PR exists to kill; the bench must name it
    out = {"accepted": 10, "completed": 9}
    bench.serving_pressure_fields(out)
    assert out["terminal_total"] == 9
    assert out["conservation"] == "leak"


def test_pressure_fields_skip_missing_sections():
    out = {"p50_ms": 10.0}
    bench.serving_pressure_fields(out)
    assert "conservation" not in out and "tail_ratio_p99_p50" not in out


def test_pressure_bench_wires_conservation_fields():
    """Source-level pin: bench_serving_pressure must route the predictor's
    metrics snapshot through serving_pressure_fields (running the pressure
    leg itself takes minutes on CPU)."""
    import inspect

    src = inspect.getsource(bench.bench_serving_pressure)
    assert "serving_pressure_fields(" in src
    assert "metrics.snapshot()" in src


def test_continuous_fields_speedup_and_gate():
    """ISSUE-6 acceptance wiring: the continuous_serving section derives
    `speedup_vs_fixed` from useful aggregate tok/s and gates it at 2x,
    with the serving_pressure conservation fields riding along."""
    out = {"fixed_tokens_per_sec": 400.0,
           "continuous_tokens_per_sec": 1000.0,
           "accepted": 64, "completed": 64,
           "p50_ms": 100.0, "p99_ms": 250.0}
    bench.continuous_serving_fields(out)
    assert out["speedup_vs_fixed"] == pytest.approx(2.5)
    assert out["audit"] == "ok"
    assert out["conservation"] == "ok"
    assert out["tail_ratio_p99_p50"] == pytest.approx(2.5)


def test_continuous_fields_flag_under_2x_and_leak():
    out = {"fixed_tokens_per_sec": 500.0,
           "continuous_tokens_per_sec": 800.0,
           "accepted": 64, "completed": 63}
    bench.continuous_serving_fields(out)
    assert out["speedup_vs_fixed"] == pytest.approx(1.6)
    assert out["audit"] == "under-2x"
    assert out["conservation"] == "leak"


def test_continuous_fields_skip_missing_sections():
    out = {"continuous_tokens_per_sec": 800.0}    # fixed leg absent
    bench.continuous_serving_fields(out)
    assert "speedup_vs_fixed" not in out and "audit" not in out


def test_continuous_bench_wires_fields_and_per_request_budgets():
    """Source-level pin: bench_continuous_serving must compare USEFUL
    tokens (per-request max_new_tokens on the continuous leg, the fixed leg
    decoding the full cap) and route through continuous_serving_fields."""
    import inspect

    src = inspect.getsource(bench.bench_continuous_serving)
    assert "continuous_serving_fields(" in src
    assert "max_new_tokens=wants[i]" in src
    assert "useful_tokens" in src


def test_speculative_fields_gate_and_audits():
    """ISSUE-10 acceptance wiring: the speculative_decode section derives
    `speedup_vs_baseline` from useful b1 tok/s and gates it at 2x, lifts
    acceptance/waste from the oracle and n-gram legs, and audits program-
    cache growth across accept patterns to zero."""
    out = {"baseline_tokens_per_sec": 500.0,
           "spec_tokens_per_sec": 1250.0,
           "oracle_stats": {"acceptance_rate": 1.0, "wasted": 0},
           "ngram_stats": {"acceptance_rate": 0.62},
           "programs_warm": 3, "programs_after": 3}
    bench.speculative_decode_fields(out)
    assert out["speedup_vs_baseline"] == pytest.approx(2.5)
    assert out["audit"] == "ok"
    assert out["acceptance_rate"] == pytest.approx(1.0)
    assert out["wasted_tokens"] == 0
    assert out["ngram_acceptance_rate"] == pytest.approx(0.62)
    assert out["recompile_audit"] == "ok"


def test_speculative_fields_flag_under_2x_and_recompiles():
    out = {"baseline_tokens_per_sec": 600.0,
           "spec_tokens_per_sec": 900.0,
           "programs_warm": 3, "programs_after": 5}
    bench.speculative_decode_fields(out)
    assert out["speedup_vs_baseline"] == pytest.approx(1.5)
    assert out["audit"] == "under-2x"
    assert out["recompile_audit"] == "recompiled-2"


def test_speculative_fields_skip_missing_sections():
    out = {"spec_tokens_per_sec": 900.0}          # baseline leg absent
    bench.speculative_decode_fields(out)
    assert "speedup_vs_baseline" not in out and "audit" not in out
    assert "recompile_audit" not in out and "acceptance_rate" not in out


def test_speculative_bench_wires_fields_and_recompile_audit():
    """Source-level pin: bench_speculative_decode must time the draft/
    verify driver against the per-token decode_step baseline over ONE
    shared pool, watch the model's program cache for accept-pattern
    recompiles, and route through speculative_decode_fields."""
    import inspect

    src = inspect.getsource(bench.bench_speculative_decode)
    assert "speculative_decode_fields(" in src
    assert "speculative_generate(" in src
    assert "_generate_cache" in src
    assert "decode_step(" in src


def test_prefix_fields_savings_ttft_and_gates():
    """ISSUE-11 acceptance wiring: the prefix_caching section derives
    `prefill_savings_pct` from index-skipped prompt tokens (gated >= 40),
    `ttft_ratio_cold_over_warm` from the final turn's first-flush timings
    (gated >= 1.5), and folds the bit-exactness parity flag into the
    audit."""
    out = {"prompt_tokens_total": 240, "prefix_hit_tokens": 192,
           "cold_final_ttft_ms": 18.0, "warm_final_ttft_ms": 5.0,
           "parity": "ok"}
    bench.prefix_caching_fields(out)
    assert out["prefill_savings_pct"] == pytest.approx(80.0)
    assert out["ttft_ratio_cold_over_warm"] == pytest.approx(3.6)
    assert out["audit"] == "ok"


def test_prefix_fields_flag_each_gate():
    base = {"prompt_tokens_total": 240, "prefix_hit_tokens": 192,
            "cold_final_ttft_ms": 18.0, "warm_final_ttft_ms": 5.0,
            "parity": "ok"}
    out = dict(base, parity="mismatch")
    bench.prefix_caching_fields(out)
    assert out["audit"] == "parity-mismatch"      # parity beats the others
    out = dict(base, prefix_hit_tokens=48)
    bench.prefix_caching_fields(out)
    assert out["prefill_savings_pct"] == pytest.approx(20.0)
    assert out["audit"] == "low-savings"
    out = dict(base, warm_final_ttft_ms=16.0)
    bench.prefix_caching_fields(out)
    assert out["ttft_ratio_cold_over_warm"] == pytest.approx(1.12)
    assert out["audit"] == "ttft-flat"


def test_prefix_fields_skip_missing_sections():
    out = {"prompt_tokens_total": 240}            # replay legs absent
    bench.prefix_caching_fields(out)
    assert "prefill_savings_pct" not in out and "audit" not in out
    assert "ttft_ratio_cold_over_warm" not in out


def test_prefix_bench_wires_replay_streaming_and_fields():
    """Source-level pin: bench_prefix_caching must measure TTFT through the
    streaming path (infer_stream first flush), replay a multi-turn
    conversation cold AND warm, and route through prefix_caching_fields."""
    import inspect

    src = inspect.getsource(bench.bench_prefix_caching)
    assert "prefix_caching_fields(" in src
    assert "infer_stream(" in src
    assert "prefix_cache=True" in src and "prefix_cache=False" in src
    assert "prefix_hit_tokens" in src


def test_decode_attention_bench_reports_vs_baseline():
    """The decode_attention sub-bench must report the Pallas-vs-XLA ratio
    under the contract key `vs_baseline` for every shape entry."""
    import inspect

    src = inspect.getsource(bench.bench_decode_attention)
    assert "vs_baseline" in src and "pallas_us_per_step" in src


# ----------------------------------------------------- mesh_serving (ISSUE-12)
def test_mesh_fields_speedup_gate_and_residency():
    """ISSUE-12 acceptance wiring: the mesh_serving section derives
    `fleet_speedup` from aggregate useful tok/s (dp=2 fleet vs one replica
    through the SAME router) and gates it at 1.6x; the recompile audit pins
    zero program-cache growth across replica admit/kill/retire; per-chip vs
    logical KV bytes fold to `kv_residency_ratio` (1/tp under the serving
    mesh); the serving_pressure conservation fields ride along."""
    out = {"single_tokens_per_sec": 500.0, "fleet_tokens_per_sec": 900.0,
           "programs_warm": 4, "programs_after": 4,
           "kv_pool_bytes_logical": 1 << 20,
           "kv_pool_bytes_per_chip": 1 << 19,
           "accepted": 48, "completed": 48,
           "p50_ms": 100.0, "p99_ms": 300.0}
    bench.mesh_serving_fields(out)
    assert out["fleet_speedup"] == pytest.approx(1.8)
    assert out["audit"] == "ok"
    assert out["recompile_audit"] == "ok"
    assert out["kv_residency_ratio"] == pytest.approx(0.5)
    assert out["conservation"] == "ok"
    assert out["tail_ratio_p99_p50"] == pytest.approx(3.0)


def test_mesh_fields_flag_under_gate_recompile_and_leak():
    out = {"single_tokens_per_sec": 500.0, "fleet_tokens_per_sec": 700.0,
           "programs_warm": 4, "programs_after": 6,
           "kv_pool_bytes_logical": 1 << 20,
           "kv_pool_bytes_per_chip": 1 << 20,
           "accepted": 48, "completed": 47}
    bench.mesh_serving_fields(out)
    assert out["fleet_speedup"] == pytest.approx(1.4)
    assert out["audit"] == "under-1.6x"
    assert out["recompile_audit"] == "recompiled-2"
    assert out["kv_residency_ratio"] == pytest.approx(1.0)
    assert out["conservation"] == "leak"


def test_mesh_fields_skip_missing_sections():
    out = {"fleet_tokens_per_sec": 700.0}     # single-replica leg absent
    bench.mesh_serving_fields(out)
    assert "fleet_speedup" not in out and "audit" not in out
    assert "recompile_audit" not in out and "kv_residency_ratio" not in out


def test_mesh_bench_wires_fleet_churn_and_fields():
    """Source-level pin: bench_mesh_serving must serve both legs through the
    SAME ReplicaFleet router, exercise admit/kill/retire churn under the
    recompile audit, and route through mesh_serving_fields."""
    import inspect

    src = inspect.getsource(bench.bench_mesh_serving)
    assert "mesh_serving_fields(" in src
    assert "ReplicaFleet.build(model, 1" in src
    assert "ReplicaFleet.build(model, 2" in src
    assert "add_replica(" in src and "retire_replica(" in src
    assert "ThreadDeath(" in src
    assert "_generate_cache" in src
    assert "per_chip_pool_bytes(" in src


def test_cold_start_fields_speedup_gate_and_audit():
    out = {
        "cold": {"ttft_from_start_s": 9.3, "post_ready_compiles": 0},
        "warm": {"ttft_from_start_s": 3.5, "post_ready_compiles": 0},
    }
    bench.cold_start_fields(out)
    assert out["warm_speedup"] == 2.66
    assert out["post_ready_compiles"] == 0
    assert out["audit"] == "ok"


def test_cold_start_fields_flag_warm_slow_and_post_ready_compiles():
    slow = {
        "cold": {"ttft_from_start_s": 5.0, "post_ready_compiles": 0},
        "warm": {"ttft_from_start_s": 4.0, "post_ready_compiles": 0},
    }
    bench.cold_start_fields(slow)
    assert slow["warm_speedup"] == 1.25 and slow["audit"] == "warm-slow"

    # a post-ready cold build outranks even a passing speedup: the manifest
    # missed a program the traffic hit
    leaky = {
        "cold": {"ttft_from_start_s": 9.0, "post_ready_compiles": 1},
        "warm": {"ttft_from_start_s": 3.0, "post_ready_compiles": 2},
    }
    bench.cold_start_fields(leaky)
    assert leaky["warm_speedup"] == 3.0
    assert leaky["post_ready_compiles"] == 3
    assert leaky["audit"] == "post-ready-compiles-3"


def test_cold_start_fields_skip_missing_sections():
    out = {"cold": {"ttft_from_start_s": 9.3}}     # warm child crashed
    bench.cold_start_fields(out)
    assert "warm_speedup" not in out and "audit" not in out


def test_cold_start_bench_wires_subprocess_children_and_fields():
    """Source-level pin: bench_cold_start must run each leg in a FRESH
    subprocess (in-process legs would share jax's live program cache and
    measure nothing), reuse ONE persistent cache dir across both, and
    route through cold_start_fields; the child must gate on ready() and
    time TTFT from the parent's spawn instant (PADDLE_T0)."""
    import inspect

    src = inspect.getsource(bench.bench_cold_start)
    assert "--cold-start-child" in src
    assert "PADDLE_T0" in src
    assert "cold_start_fields(" in src
    assert 'for leg in ("cold", "warm")' in src
    # one process per chip: the parent of the children never initialises a
    # JAX backend (own entry point, no device argument), and the cache is a
    # fixed directory, never a temp dir
    assert "jax.devices" not in src and "mkdtemp" not in src
    assert inspect.signature(bench.bench_cold_start).parameters == {}
    assert "bench_cold_start" not in inspect.getsource(bench.main)

    child = inspect.getsource(bench._cold_start_child_impl)
    assert "warmup=True" in child
    assert "compile_cache_dir=cache_dir" in child
    assert "pred.ready()" in child
    assert "infer_stream(" in child
    assert "PADDLE_T0" in child


# ---------------------------------------------------- hbm_planning (ISSUE-14)
def test_hbm_planning_fields_clean():
    out = {
        "components": {"params": 100, "kv_pool": 800, "prefix_tier": 50,
                       "temps": 50},
        "planned_total_bytes": 1000,
        "findings": [{"rule": "pool-misfit", "severity": "warn"}],
    }
    bench.hbm_planning_fields(out)
    assert out["components_sum_bytes"] == 1000
    assert out["findings_by_rule"] == {"pool-misfit": 1}
    assert out["high_total"] == 0
    assert out["audit"] == "ok"                 # warns alone do not gate


def test_hbm_planning_fields_flag_high():
    out = {
        "components": {"params": 1, "kv_pool": 1, "prefix_tier": 0,
                       "temps": 0},
        "planned_total_bytes": 2,
        "findings": [{"rule": "hbm-over-budget", "severity": "high"},
                     {"rule": "estimate-drift", "severity": "high"}],
    }
    bench.hbm_planning_fields(out)
    assert out["high_total"] == 2
    assert out["audit"] == "lint-high"


def test_hbm_planning_fields_flag_component_sum_mismatch():
    # components are DISJOINT by construction (prefix tier carved out of the
    # pool); a sum that misses planned_total means the plan arithmetic broke
    out = {
        "components": {"params": 10, "kv_pool": 10, "prefix_tier": 0,
                       "temps": 0},
        "planned_total_bytes": 30,
        "findings": [],
    }
    bench.hbm_planning_fields(out)
    assert out["components_sum_bytes"] == 20
    assert out["audit"] == "plan-inconsistent"


def test_hbm_planning_bench_wires_plan_and_fields():
    """Source-level pin: bench_hbm_planning must build the shared smoke plan
    (the same one the zoo hbm_residency entry gates), run the residency
    rules, and route through hbm_planning_fields — running the full leg
    compiles both step programs, too heavy for this unit file."""
    import inspect

    src = inspect.getsource(bench.bench_hbm_planning)
    assert "smoke_plan(" in src
    assert "analyze_hbm_plan(" in src
    assert "hbm_planning_fields(" in src
    assert "planned_total_bytes" in src


# ---------------------------------------------------- comms_lint (ISSUE-20)
def test_comms_lint_fields_clean():
    out = {
        "findings": [{"rule": "dead-mesh-axis", "severity": "warn"}],
        "comms_share_of_tick": None,     # unknown ICI (CPU) stays None
    }
    bench.comms_lint_fields(out)
    assert out["findings_by_rule"] == {"dead-mesh-axis": 1}
    assert out["high_total"] == 0
    assert out["audit"] == "ok"                 # warns alone do not gate
    assert out["comms_share_of_tick"] is None   # not coerced to a number


def test_comms_lint_fields_flag_high():
    out = {
        "findings": [{"rule": "implicit-reshard", "severity": "high"},
                     {"rule": "comms-over-budget", "severity": "high"},
                     {"rule": "replicated-large-buffer", "severity": "warn"}],
    }
    bench.comms_lint_fields(out)
    assert out["findings_by_rule"] == {"implicit-reshard": 1,
                                       "comms-over-budget": 1,
                                       "replicated-large-buffer": 1}
    assert out["high_total"] == 2
    assert out["audit"] == "lint-high"


def test_comms_lint_bench_wires_surfaces_and_fields():
    """Source-level pin: bench_comms_lint must compile the step surfaces
    once (shared with the printed table), run the five-rule pass, size the
    tick budget, and route through comms_lint_fields — running the full
    leg is three tp=2 compiles, too heavy for this unit file. main() must
    carry the section under the "comms_lint" key."""
    import inspect

    src = inspect.getsource(bench.bench_comms_lint)
    assert "step_comms_surfaces(" in src
    assert "analyze_step_comms(_surfaces=surfaces)" in src
    assert "smoke_comms_budget(" in src
    assert "comms_lint_fields(" in src
    assert "bytes_per_decode_launch" in src
    assert '"comms_lint"' in inspect.getsource(bench.main)


# ------------------------------------------------------------ ISSUE-15 lora
def test_multi_lora_fields_speedup_gate_and_audit():
    """ISSUE-15 acceptance wiring: the multi_lora section derives
    `speedup_batched_over_sequential` from the two walls (gated >= 2.0 —
    four adapters sharing ticks vs per-adapter draining), and the audit
    folds slot-0 parity and the zero-recompile churn invariant ahead of
    the speedup gate."""
    out = {"batched_s": 0.05, "sequential_s": 0.13,
           "program_cache_growth": 0, "slot0_parity": "ok"}
    bench.multi_lora_fields(out)
    assert out["speedup_batched_over_sequential"] == pytest.approx(2.6)
    assert out["audit"] == "ok"


def test_multi_lora_fields_flag_each_gate():
    base = {"batched_s": 0.05, "sequential_s": 0.13,
            "program_cache_growth": 0, "slot0_parity": "ok"}
    out = dict(base, slot0_parity="mismatch")
    bench.multi_lora_fields(out)
    assert out["audit"] == "slot0-parity-mismatch"   # parity beats the rest
    out = dict(base, program_cache_growth=2)
    bench.multi_lora_fields(out)
    assert out["audit"] == "recompiled-on-churn"
    out = dict(base, sequential_s=0.08)
    bench.multi_lora_fields(out)
    assert out["speedup_batched_over_sequential"] == pytest.approx(1.6)
    assert out["audit"] == "no-batching-win"


def test_multi_lora_fields_skip_missing_sections():
    out = {"batched_s": 0.05}                    # sequential leg absent
    bench.multi_lora_fields(out)
    assert "speedup_batched_over_sequential" not in out
    assert "audit" not in out


def test_multi_lora_bench_wires_churn_parity_and_fields():
    """Source-level pin: bench_multi_lora must drive heterogeneous-adapter
    ticks (concurrent per-adapter clients), churn the registry mid-serving
    while watching the runner cache, compare slot-0 traffic against a
    registry-free scheduler, and route through multi_lora_fields — the
    full leg compiles step programs, too heavy for this unit file."""
    import inspect

    src = inspect.getsource(bench.bench_multi_lora)
    assert "multi_lora_fields(" in src
    assert "AdapterRegistry(" in src
    assert "unregister(" in src and "register(" in src
    assert "_runner_cache()" in src
    assert "slot0_parity" in src


# ------------------------------------------------------------- ISSUE-17 qos
def test_tenant_fairness_fields_weight_share_math_and_gate():
    """ISSUE-17 starvation gate wiring: per-tenant delivered share of useful
    tokens vs weight/sum-of-weights, min ratio across tenants, tok/s from
    the window — audit "ok" iff every tenant keeps >= 90% of its share."""
    out = {"window_s": 4.0, "tenants": {
        "gold": {"weight": 3.0, "tokens_done": 450},
        "bronze": {"weight": 1.0, "tokens_done": 150},
    }}
    bench.tenant_fairness_fields(out)
    assert out["tenants"]["gold"]["fair_share"] == pytest.approx(0.75)
    assert out["tenants"]["gold"]["delivered_share"] == pytest.approx(0.75)
    assert out["tenants"]["bronze"]["fair_share_ratio"] == pytest.approx(1.0)
    assert out["min_fair_share_ratio"] == pytest.approx(1.0)
    assert out["useful_tokens_per_sec"] == pytest.approx(150.0)
    assert out["audit"] == "ok"


def test_tenant_fairness_fields_flags_worst_starved_tenant():
    # equal delivered tokens under 3:1 weights — the aggressor grabbed half
    # the fleet: gold's ratio 0.5/0.75 drops below the 0.9 floor
    out = {"tenants": {
        "gold": {"weight": 3.0, "tokens_done": 200},
        "flash": {"weight": 1.0, "tokens_done": 200},
    }}
    bench.tenant_fairness_fields(out)
    assert out["min_fair_share_ratio"] == pytest.approx(0.6667, abs=1e-3)
    assert out["tenants"]["flash"]["fair_share_ratio"] == pytest.approx(2.0)
    assert out["audit"] == "starved:gold"
    assert "useful_tokens_per_sec" not in out      # no window measured


def test_tenant_fairness_fields_skip_missing_sections():
    out = {}
    bench.tenant_fairness_fields(out)
    assert "audit" not in out
    out = {"tenants": {"gold": {"weight": 3.0, "tokens_done": 0}}}
    bench.tenant_fairness_fields(out)                # leg produced no tokens
    assert "audit" not in out


def test_tenant_fairness_bench_wires_ledger_overload_and_fields():
    """Source-level pin: bench_tenant_fairness must serve through a
    TenantLedger-armed scheduler (qos=), run the flash-crowd aggressor at
    4x the weighted tenants' client concurrency, drive closed-loop clients
    against a stop event, and route through tenant_fairness_fields — the
    full leg is a multi-second serving window, too heavy for this file."""
    import inspect

    src = inspect.getsource(bench.bench_tenant_fairness)
    assert "tenant_fairness_fields(" in src
    assert "TenantLedger(" in src
    assert "qos=ledger" in src
    assert '"flash": 16' in src
    assert "threading.Event()" in src


# ------------------------------------------------ slo_observability (ISSUE-18)
def test_slo_observability_fields_clean():
    """SLO-stack overhead gate wiring: instrumented vs plain wall ->
    overhead_pct (clamped at 0), audit ok iff <= 5% AND the flight
    recorder actually captured ticks."""
    out = {"instrumented_wall_sec": 2.04, "plain_wall_sec": 2.0,
           "flight_ticks_recorded": 37, "slo_alerting": []}
    bench.slo_observability_fields(out)
    assert out["overhead_pct"] == pytest.approx(2.0)
    assert out["audit"] == "ok"
    # noise put the instrumented leg ahead: clamp, never negative
    out = {"instrumented_wall_sec": 1.9, "plain_wall_sec": 2.0,
           "flight_ticks_recorded": 5}
    bench.slo_observability_fields(out)
    assert out["overhead_pct"] == 0.0
    assert out["audit"] == "ok"


def test_slo_observability_fields_flag_each_gate():
    out = {"instrumented_wall_sec": 2.2, "plain_wall_sec": 2.0,
           "flight_ticks_recorded": 10}
    bench.slo_observability_fields(out)
    assert out["overhead_pct"] == pytest.approx(10.0)
    assert out["audit"] == "slo-observability-overhead"
    # recorder captured nothing: the overhead number measured nothing
    out = {"instrumented_wall_sec": 2.0, "plain_wall_sec": 2.0,
           "flight_ticks_recorded": 0}
    bench.slo_observability_fields(out)
    assert out["audit"] == "flight-recorder-idle"


def test_slo_observability_fields_skip_missing_sections():
    out = {}
    bench.slo_observability_fields(out)
    assert "audit" not in out
    out = {"instrumented_wall_sec": 2.0}        # plain leg crashed
    bench.slo_observability_fields(out)
    assert "audit" not in out


def test_slo_observability_bench_wires_stack_and_fields():
    """Source-level pin: bench_slo_observability must run the CONTINUOUS
    scheduler with the full ISSUE-18 stack on its instrumented leg
    (SLOMonitor + flight_recorder + two-tenant ledger), take a throwaway
    compile pass, and route through slo_observability_fields — the real
    leg is a multi-second serving window, too heavy for this file."""
    import inspect

    src = inspect.getsource(bench.bench_slo_observability)
    assert "slo_observability_fields(" in src
    assert "SLOMonitor(" in src
    assert "flight_recorder=True" in src
    assert "qos=ledger" in src
    assert "ContinuousGenerateBatchingPredictor(" in src
    assert '"slo_observability"' in inspect.getsource(bench.main)


# --------------------------------------------- serving_utilization (ISSUE-19)
def _util_out(**over):
    """A clean measured dict for serving_utilization_fields: conserved
    flops, tenant sum closing on useful, ticks recorded, no recompiles."""
    out = {
        "instrumented_wall_sec": 2.04, "plain_wall_sec": 2.0,
        "utilization": {
            "flops": {"issued": 1000, "useful": 600, "pad_waste": 300,
                      "spec_waste": 100},
            "tenants": {"gold": 350, "bronze": 250},
            "ticks": 12,
        },
        "new_compiled_programs": 0,
    }
    out.update(over)
    return out


def test_serving_utilization_fields_clean():
    out = _util_out()
    bench.serving_utilization_fields(out)
    assert out["overhead_pct"] == pytest.approx(2.0)
    assert out["audit"] == "ok"
    # noise put the instrumented leg ahead: clamp, never negative
    out = _util_out(instrumented_wall_sec=1.9)
    bench.serving_utilization_fields(out)
    assert out["overhead_pct"] == 0.0 and out["audit"] == "ok"


def test_serving_utilization_fields_flag_each_gate():
    # ledger tax over the 5% gate
    out = _util_out(instrumented_wall_sec=2.2)
    bench.serving_utilization_fields(out)
    assert out["overhead_pct"] == pytest.approx(10.0)
    assert out["audit"] == "serving-utilization-overhead"
    # instrumented leg attributed nothing: overhead measured nothing
    out = _util_out()
    out["utilization"]["ticks"] = 0
    bench.serving_utilization_fields(out)
    assert out["audit"] == "utilization-idle"
    out = _util_out()
    out["utilization"]["flops"] = {"issued": 0, "useful": 0,
                                   "pad_waste": 0, "spec_waste": 0}
    out["utilization"]["tenants"] = {}
    bench.serving_utilization_fields(out)
    assert out["audit"] == "utilization-idle"
    # broken conservation: issued != useful + pad + spec_waste
    out = _util_out()
    out["utilization"]["flops"]["pad_waste"] = 299
    bench.serving_utilization_fields(out)
    assert out["audit"] == "utilization-conservation"
    # tenant sum drifting off useful is the SAME failure
    out = _util_out()
    out["utilization"]["tenants"] = {"gold": 350}
    bench.serving_utilization_fields(out)
    assert out["audit"] == "utilization-conservation"
    # the flops probe must trace, never compile
    out = _util_out(new_compiled_programs=1)
    bench.serving_utilization_fields(out)
    assert out["audit"] == "utilization-recompile"


def test_serving_utilization_fields_skip_missing_sections():
    out = {}
    bench.serving_utilization_fields(out)
    assert "audit" not in out
    out = {"instrumented_wall_sec": 2.0}        # plain leg crashed
    bench.serving_utilization_fields(out)
    assert "audit" not in out


def test_serving_utilization_bench_wires_ledger_and_fields():
    """Source-level pin: bench_serving_utilization must run the continuous
    scheduler with utilization=True on its instrumented leg over two-tenant
    traffic, take a throwaway compile pass, size the shared runner cache
    around the measured legs (the zero-recompile audit input), and route
    through serving_utilization_fields — the real leg is a multi-second
    serving window, too heavy for this file."""
    import inspect

    src = inspect.getsource(bench.bench_serving_utilization)
    assert "serving_utilization_fields(" in src
    assert "utilization=bool(instrumented)" in src
    assert "qos=ledger" in src
    assert "ContinuousGenerateBatchingPredictor(" in src
    assert "_generate_cache" in src
    assert ".snapshot()" in src
    assert '"serving_utilization"' in inspect.getsource(bench.main)
