"""The last line: one builder for plain and traced runs, which refuses every
way of being malformed before anything is printed (PR 22 died here)."""
import copy

import pytest

from _tiny import bench  # noqa: F401 — also puts the repo on sys.path
from benchmarks.harness import lastline

CELL = "gpt2-medium-train-s1024"
DEVICE = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
          "memory_peak_bytes": 7_876_768_256}
COMPARED = {"loss_gap": {"value": 1e-5, "limit": 1e-3}}


def good(trace):
    b = bench()
    names = lastline.expected_metrics(b, CELL, trace)
    device = dict(DEVICE, busy_s=2.9, window_s=3.0) if trace else dict(DEVICE)
    return lastline.build(
        b, CELL, trace, correct=True, attempted=140, failed=0,
        metrics={n: 12.5 for n in names}, device=device, compared=COMPARED,
        breakdown={"device_ops": [["fusion", 1.5]], "idle_gaps": []}
        if trace else None)


@pytest.mark.parametrize("trace", [False, True])
def test_good_line_is_accepted(trace):
    line = good(trace)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics",
                              "device"]
    assert list(line)[-1] == "compared"
    assert lastline.validate(line, bench(), CELL, trace) is line
    assert "\n" not in lastline.dumps(line)
    assert all(set(m) == {"value", "unit"} for m in line["metrics"].values())


def _drop(key):
    return lambda line: line.pop(key)


def _device(key, value=None, drop=False):
    def change(line):
        if drop:
            line["device"].pop(key)
        else:
            line["device"][key] = value
    return change


def _metric(change):
    def apply(line):
        name = next(iter(line["metrics"]))
        change(line["metrics"], name)
    return apply


MALFORMED = {
    "missing_correct": (False, _drop("correct")),
    "missing_attempted": (False, _drop("attempted")),
    "missing_failed": (False, _drop("failed")),
    "missing_metrics": (False, _drop("metrics")),
    "missing_device": (False, _drop("device")),
    "metric_without_unit": (False, _metric(lambda m, n: m[n].pop("unit"))),
    "metric_without_value": (False, _metric(lambda m, n: m[n].pop("value"))),
    "metric_not_finite": (False, _metric(
        lambda m, n: m[n].update(value=float("nan")))),
    "metric_missing": (False, _metric(lambda m, n: m.pop(n))),
    "metric_of_another_cell": (False, _metric(
        lambda m, n: m.update(ttft_p999_ms={"value": 1.0, "unit": "ms"}))),
    "no_memory_peak": (False, _device("memory_peak_bytes", drop=True)),
    "memory_peak_zero": (False, _device("memory_peak_bytes", 0)),
    "traced_without_window_s": (True, _device("window_s", drop=True)),
    "traced_without_busy_s": (True, _device("busy_s", drop=True)),
    "busy_s_zero": (True, _device("busy_s", 0.0)),
    "busy_s_over_window_s": (True, _device("busy_s", 3.5)),
    "mfu_over_100": (True, lambda line: line["metrics"]["mfu.train"].update(
        value=104.0)),
    "roofline_zero": (True, lambda line: line["metrics"][
        "flash_roofline.train"].update(value=0.0)),
    "compared_not_last": (False, lambda line: line.update(
        device=line.pop("device"))),
    "failed_over_attempted": (False, lambda line: line.update(failed=141)),
    "breakdown_too_long": (True, lambda line: line["breakdown"].update(
        device_ops=[["op", 0.1]] * 11)),
}


@pytest.mark.parametrize("case", sorted(MALFORMED))
def test_malformed_line_is_refused(case):
    trace, change = MALFORMED[case]
    line = copy.deepcopy(good(trace))
    change(line)
    with pytest.raises(lastline.MalformedLine):
        lastline.validate(line, bench(), CELL, trace)


def test_reader_that_found_nothing_is_refused_in_its_own_cell():
    b = bench()
    names = lastline.expected_metrics(b, CELL, True)
    metrics = {n: 12.5 for n in names}
    metrics["flash_roofline.train"] = None      # the reader found nothing
    with pytest.raises(lastline.MalformedLine, match="flash_roofline.train"):
        lastline.build(b, CELL, True, correct=True, attempted=1, failed=0,
                       metrics=metrics,
                       device=dict(DEVICE, busy_s=1.0, window_s=2.0),
                       compared=COMPARED)
