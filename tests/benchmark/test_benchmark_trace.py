"""The reduction from a trace to numbers, on a small trace recorded on the
chip (two train steps of PR 25's first run) and on hand-made events."""
import os

import pytest

from _tiny import BENCH_DIR, ROOT, load_json
from benchmarks.harness import peaks, trace
from benchmarks.harness.job import View, layer_reader, load_family

RECORDED = os.path.join(BENCH_DIR, "testdata",
                        "train_two_steps.trace.json.gz")


@pytest.fixture(scope="module")
def reduced():
    return trace.reduce(trace.read_json(RECORDED))


def test_busy_is_a_union_not_a_sum():
    events = [["a", 0.0, 10e9, {}], ["b", 5e9, 10e9, {}], ["c", 20e9, 1e9, {}],
              ["d", 20.2e9, 0.1e9, {}]]
    assert trace.union_seconds(events) == pytest.approx(16.0)
    assert sum(e[2] for e in events) / 1e9 == pytest.approx(21.1)


def test_top_ops_count_a_loop_and_its_body_once():
    events = [["while.1", 0.0, 10e9, {}], ["fusion.2", 1e9, 3e9, {}],
              ["fusion.3", 5e9, 4e9, {}], ["inner.4", 6e9, 1e9, {}],
              ["copy.5", 10e9, 2e9, {}]]
    top = dict(trace.top_ops(events))
    assert top == pytest.approx({"while": 3.0, "fusion": 6.0, "inner": 1.0,
                                 "copy": 2.0})
    assert sum(top.values()) == pytest.approx(trace.union_seconds(events))


def test_events_are_clipped_to_the_window():
    plane = {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
        ["before", 0.0, 2e9, {}], ["across", 9e9, 2e9, {}],
        ["after", 30e9, 1e9, {}]]}]}
    clipped = trace.op_events(plane, (1e9, 10e9))
    assert [(e[0], e[2] / 1e9) for e in clipped] == [("before", 1.0),
                                                     ("across", 1.0)]


def test_recorded_trace_window_and_busy(reduced):
    assert 0.40 < reduced["window_s"] < 0.44          # two steps of ~207 ms
    assert 0 < reduced["busy_s"] <= reduced["window_s"]
    assert reduced["busy_s"] / reduced["window_s"] > 0.95
    ops = reduced["events"][0]
    assert trace.union_seconds(ops) <= sum(e[2] for e in ops) / 1e9 + 1e-9


def test_recorded_trace_holds_the_flash_kernels(reduced):
    seconds, calls = trace.mosaic_seconds(reduced["events"][0])
    assert calls == 2 * 24 * 3          # two steps, forward + dq + dkv a layer
    assert 0.05 < seconds < 0.12
    top = dict(trace.top_ops(reduced["events"][0]))
    assert any(name.endswith("(mosaic)") for name in top)
    assert all(seconds <= reduced["busy_s"] for seconds in top.values())


def test_layer_metrics_on_the_recorded_trace_are_shares(reduced):
    cfg = load_json("benchmarks", "configs", "gpt2-medium.json")
    view = View(cfg=cfg, mix={}, peaks=peaks.PEAKS["TPU v5 lite"], chips=1,
                records={"traced_steps": 2, "batch": 8, "seq": 1024},
                window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                events=reduced["events"])
    got = {name: layer_reader(ROOT, name)(view) for name in
           ("mfu.train", "flash_roofline.train", "device_idle.train")}
    assert 40 < got["mfu.train"] < 50           # 2 steps * 8192 * 2.27 GFLOP
    assert 10 < got["flash_roofline.train"] < 30
    assert 0 <= got["device_idle.train"] < 5
    assert got["device_idle.train"] == layer_reader(
        ROOT, "device_idle.serve")(view)    # one reader for both paths
    assert all(v <= 100 for v in got.values())
    assert got["mfu.train"] == pytest.approx(
        100 * 2 * 8192 * load_family(ROOT, "gpt2").counts.train_flops_per_token(
            cfg, 1024)
        / (reduced["window_s"] * 197e12))


def test_another_kernel_in_the_step_fails_the_attention_reader(reduced):
    ops = reduced["events"][0]
    assert trace.mosaic_calls(ops, (72,)) == trace.mosaic_seconds(ops)
    assert trace.mosaic_calls([], (72,)) == (0.0, 0)
    other = ["rope_kernel.1", ops[0][1], 1e6,
             {"custom_call_target": trace.MOSAIC_TARGET}]
    with pytest.raises(ValueError, match="73 different Mosaic calls"):
        trace.mosaic_calls(ops + [other], (72,))


def test_a_reader_that_finds_nothing_returns_nothing(reduced):
    view = View(cfg={}, mix={}, peaks={}, chips=1, records={}, window_s=1.0,
                busy_s=0.5, events=[[]])
    for name in ("mfu.train", "flash_roofline.train", "device_idle.train",
                 "mfu.serve", "paged_attn_roofline.serve",
                 "device_idle.serve"):
        assert layer_reader(ROOT, name)(view) is None, name


def test_a_trace_without_the_annotation_or_a_device_is_an_error():
    with pytest.raises(LookupError, match="no host event"):
        trace.reduce({"planes": [{"name": "/device:TPU:0", "lines": []}]})
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        [trace.WINDOW_ANNOTATION, 0.0, 1e9, {}]]}]}
    with pytest.raises(LookupError, match="no device plane"):
        trace.reduce({"planes": [host]})


def test_short_event_keeps_the_name_and_the_call_target():
    name = ('%jvp__.47 = (bf16[128,1024,64]{2,1,0}) custom-call(bf16[128,1024,'
            '64] %bitcast.3500), custom_call_target="tpu_custom_call", '
            'operand_layout_constraints={}')
    assert trace.short_event(name, 1, 2) == [
        "jvp__.47", 1.0, 2.0, {"custom_call_target": "tpu_custom_call"}]
    assert trace.short_event("%fusion.3 = bf16[8] fusion()", 1, 2)[3] == {}
