"""Thread lint (ISSUE-8 tentpole): every rule proven live on a seeded
violation, the real tree proven clean (or visibly allowlisted) and the CLI
gate.

The fixtures in tests/thread_lint_fixtures/ are analyzed as SOURCE (pure
AST — never imported), so the deadlocks and races they seed can never
actually run.
"""
import os

import pytest

from paddle_tpu.analysis.threads import (
    BUILTIN_THREAD_ALLOWLIST,
    RUNTIME_MODULES,
    THREAD_RULES,
    analyze_threads,
    lock_order_graph,
    record_findings,
    thread_lint_paths,
)

FIXTURES = os.path.join(os.path.dirname(__file__), "thread_lint_fixtures")


def _fixture(name):
    return os.path.join(FIXTURES, name)


def _lint(*names, runtime=("*",), allowlist=None):
    return analyze_threads(paths=[_fixture(n) for n in names],
                           runtime_modules=runtime, allowlist=allowlist)


def _rules(report, severity=None):
    return {f.rule for f in report.findings
            if severity is None or f.severity == severity}


# ------------------------------------------------------- seeded violations
def test_lock_order_cycle_fires_on_inverted_pair():
    r = _lint("bad_lock_order.py")
    highs = [f for f in r.findings if f.rule == "lock-order-cycle"]
    assert highs and all(f.severity == "high" for f in highs)
    # the cycle names both locks and at least one acquisition site
    msg = highs[0].message
    assert "_a" in msg and "_b" in msg and "TwoLocks" in msg
    # no collateral findings: the fixture isolates the rule
    assert _rules(r, "high") == {"lock-order-cycle"}


def test_lock_order_graph_exposes_both_edges():
    edges = lock_order_graph(paths=[_fixture("bad_lock_order.py")])
    names = {(a.split(".")[-1], b.split(".")[-1]) for a, b in edges}
    assert ("_a", "_b") in names        # via the _grab_b call (indirect)
    assert ("_b", "_a") in names        # direct nesting


def test_unguarded_write_fires_on_worker_thread_write():
    r = _lint("bad_unguarded.py")
    f = next(f for f in r.findings if f.rule == "unguarded-write")
    assert f.severity == "high"
    assert "counter" in f.message and "worker thread" in f.message
    assert "inconsistent lockset" in f.message   # snapshot() reads locked
    assert "bad_unguarded.py" in f.where


def test_unguarded_write_downgrades_to_warn_outside_runtime_modules():
    # same fixture, default runtime set (which it does not match)
    r = _lint("bad_unguarded.py", runtime=RUNTIME_MODULES)
    f = next(f for f in r.findings if f.rule == "unguarded-write")
    assert f.severity == "warn"


def test_blocking_under_lock_fires_for_get_sleep_and_io():
    r = _lint("bad_blocking.py")
    msgs = [f.message for f in r.findings if f.rule == "blocking-under-lock"]
    assert len(msgs) >= 3
    blob = "\n".join(msgs)
    assert "Queue.get() without timeout" in blob
    assert "sleep" in blob
    assert "open()" in blob


def test_raw_clock_and_non_daemon_thread_fire():
    r = _lint("bad_clock_daemon.py")
    assert "raw-clock" in _rules(r)
    nd = next(f for f in r.findings if f.rule == "non-daemon-thread")
    assert nd.severity == "high"        # runtime=("*",) strict mode
    rc = next(f for f in r.findings if f.rule == "raw-clock")
    assert rc.severity == "warn"        # raw-clock never gates by itself


def test_allowlist_suppression_is_visible_with_reason():
    from paddle_tpu.analysis.findings import Allowlist, AllowlistEntry

    allow = Allowlist([AllowlistEntry(
        "unguarded-write", subject="thread-lint", contains="Racy.counter",
        reason="seeded fixture: suppression-visibility test")])
    r = _lint("bad_unguarded.py", allowlist=allow)
    assert not any(f.rule == "unguarded-write" for f in r.findings)
    sup = [(f, e) for f, e in r.suppressed if f.rule == "unguarded-write"]
    assert sup and sup[0][1].reason.startswith("seeded fixture")


def test_allowlist_entry_requires_reason():
    from paddle_tpu.analysis.findings import AllowlistEntry

    with pytest.raises(ValueError):
        AllowlistEntry("unguarded-write", reason="")


# ------------------------------------------------------------ the real tree
def test_real_tree_is_clean_or_visibly_allowlisted():
    """The acceptance gate: zero un-allowlisted high findings over the
    installed paddle_tpu package, and every suppression carries a reason."""
    r = analyze_threads()
    assert r.high() == [], "\n".join(f.render() for f in r.high())
    assert r.suppressed, "the builtin allowlist should be exercised"
    for f, entry in r.suppressed:
        assert entry.reason
    # the deliberate suppressions are the ones we documented
    suppressed_rules = {f.rule for f, _ in r.suppressed}
    assert "unguarded-write" in suppressed_rules       # _busy flags
    assert "blocking-under-lock" in suppressed_rules   # Supervisor.heal


def test_real_tree_runtime_modules_all_present():
    """Every declared runtime module actually exists (a rename would
    silently drop it from the strict tier)."""
    paths = thread_lint_paths()
    for mod in RUNTIME_MODULES:
        assert any(p.replace(os.sep, "/").endswith(mod) for p in paths), mod


def test_builtin_thread_allowlist_reasons():
    for entry in BUILTIN_THREAD_ALLOWLIST:
        assert entry.reason and len(entry.reason) > 20


def test_static_lock_graph_real_tree_is_acyclic():
    from paddle_tpu.analysis.lockwitness import _find_cycles

    edges = lock_order_graph()
    adj = {}
    for a, b in edges:
        adj.setdefault(a, set()).add(b)
    assert _find_cycles(adj) == []


# --------------------------------------------------------------------- CLI
def test_cli_threads_fixture_dir_exits_nonzero():
    from paddle_tpu.analysis.__main__ import main

    assert main(["--self-check", "--threads", FIXTURES]) == 1


def test_cli_threads_clean_file_exits_zero():
    from paddle_tpu.analysis.__main__ import main
    import paddle_tpu.analysis.lockwitness as lw

    assert main(["--threads", lw.__file__]) == 0


def test_cli_threads_package_self_check_clean(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["--threads"]) == 0
    out = capsys.readouterr().out
    assert "thread-lint" in out and "allowlisted" in out


def test_cli_list_rules_includes_thread_rules(capsys):
    from paddle_tpu.analysis.__main__ import main

    assert main(["--list-rules"]) == 0
    out = capsys.readouterr().out
    for rule in THREAD_RULES:
        assert rule in out


def test_cli_json_shape_with_threads(tmp_path):
    import json

    from paddle_tpu.analysis.__main__ import main

    src = tmp_path / "bad.py"
    src.write_text(open(_fixture("bad_unguarded.py")).read())
    import contextlib
    import io

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = main(["--json", "--threads", str(src)])
    assert rc == 1
    payload = json.loads(buf.getvalue())
    assert payload["status"] == "lint-high"
    assert payload["high_total"] >= 1
    names = [p["program"] for p in payload["programs"]]
    assert "thread-lint" in names


# --------------------------------------------------------------- metrics
def test_record_findings_exposes_prometheus_series():
    from paddle_tpu.observability.metrics import (
        MetricsRegistry,
        render_prometheus,
    )

    reg = MetricsRegistry()
    r = _lint("bad_unguarded.py")
    record_findings(r, reg)
    text = render_prometheus(reg)
    assert "paddle_analysis_findings_total" in text
    assert 'rule="unguarded-write"' in text
