"""tools/trace_gaps.py: idle gaps of the device split among the innermost
open host span, on made-up intervals (nanoseconds)."""
import importlib.util
import io
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "trace_gaps", os.path.join(ROOT, "tools", "trace_gaps.py"))
tg = importlib.util.module_from_spec(spec)
spec.loader.exec_module(tg)

MS = 1_000_000
OPS = [(0, 10 * MS), (5 * MS, 20 * MS), (60 * MS, 100 * MS),
       (100.5 * MS, 150 * MS)]                 # idle 20-60 and 100-100.5
SPANS = [("serve.tick", 0, 58 * MS), ("serve.decode.wait", 2 * MS, 21 * MS),
         ("serve.decode.absorb", 21 * MS, 40 * MS),
         ("serve.tick", 58 * MS, 160 * MS), ("serve.admit", 58 * MS, 59 * MS),
         ("serve.decode.dispatch", 59 * MS, 70 * MS),
         ("generate.decode_step", 59.5 * MS, 60 * MS)]


def test_idle_gaps_are_the_uncovered_stretches_over_the_floor():
    assert tg.idle_gaps(OPS, (0, 150 * MS), 1 * MS) == [(20 * MS, 60 * MS)]
    assert tg.idle_gaps(OPS, (0, 150 * MS), 0.1 * MS) == [
        (20 * MS, 60 * MS), (100 * MS, 100.5 * MS)]
    # clipped to the window at both ends, and a window past the last op
    assert tg.idle_gaps(OPS, (30 * MS, 170 * MS), 1 * MS) == [
        (30 * MS, 60 * MS), (150 * MS, 170 * MS)]
    assert tg.idle_gaps([], (0, 5 * MS), 1 * MS) == [(0, 5 * MS)]


def test_each_instant_goes_to_the_innermost_open_span():
    got = tg.innermost((20 * MS, 60 * MS), SPANS)
    assert got == {"serve.decode.wait": 1 * MS,
                   "serve.decode.absorb": 19 * MS,
                   "serve.tick": 18 * MS,           # 40-58, no child open
                   "serve.admit": 1 * MS,
                   "serve.decode.dispatch": 0.5 * MS,
                   "generate.decode_step": 0.5 * MS}
    assert sum(got.values()) == 40 * MS
    assert tg.innermost((200 * MS, 201 * MS), SPANS) == {tg.NO_SPAN: 1 * MS}


def test_report_rows_add_up_to_the_idle_time():
    out = io.StringIO()
    rows = tg.report(OPS, (0, 150 * MS), SPANS, min_ms=0.1, out=out)
    assert sum(ns for _, (ns, _) in rows) == pytest.approx(40.5 * MS)
    assert rows[0][0] == "serve.decode.absorb"
    assert dict(rows)["serve.tick"] == [18.5 * MS, 2]
    text = out.getvalue()
    assert "2 idle gaps over 0.1 ms: 0.0405s" in text
    assert "serve.decode.absorb" in text
    # the second table: spans wholly inside the window, by name
    assert tg.spans_in((0, 150 * MS), SPANS) == {
        "serve.tick": [1, 58 * MS], "serve.decode.wait": [1, 19 * MS],
        "serve.decode.absorb": [1, 19 * MS], "serve.admit": [1, 1 * MS],
        "serve.decode.dispatch": [1, 11 * MS],
        "generate.decode_step": [1, 0.5 * MS]}
    assert "span inside the window" in text


def test_kept_trace_counts_mosaic_instructions_by_kernel():
    """tools/keep_serve_trace.py: the paged kernel has one name in both step
    programs, told apart by the instruction's number alone."""
    spec = importlib.util.spec_from_file_location(
        "keep_serve_trace", os.path.join(ROOT, "tools",
                                         "keep_serve_trace.py"))
    keep = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(keep)
    mosaic = {"custom_call_target": "tpu_custom_call"}
    events = [["_paged_kernel.36", 0, 5, mosaic],
              ["_paged_kernel.36", 9, 5, mosaic],
              ["_paged_kernel.396", 20, 5, mosaic],
              ["_fwd_kernel.7", 30, 5, mosaic],
              ["fusion.12", 40, 5, {}]]
    assert keep.mosaic_names(events) == {"_paged_kernel": [2, 3],
                                         "_fwd_kernel": [1, 1]}

