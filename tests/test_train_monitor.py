"""Training-side telemetry (ISSUE-4): StepMonitor over TrainStep — per-step
metrics + spans, live MFU from the compiled program's own cost_analysis, HBM
watermark gauges from memory_analysis, the recompilation sentinel (including
the AOT-fallback path), numerics anomaly detection, the hapi MonitorCallback /
ProgBarLogger surfacing."""
import importlib
import json
import os
import sys

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.jit.train import TrainStep
from paddle_tpu.observability import (
    MetricsRegistry,
    NumericsAnomalyDetector,
    StepMonitor,
    Tracer,
    export_joined_chrome,
    render_prometheus,
)
from paddle_tpu.observability.xla import cost_flops, memory_stats


def _build(in_dim=8, out_dim=4):
    paddle.seed(0)
    model = nn.Sequential(nn.Linear(in_dim, 16), nn.GELU(),
                          nn.Linear(16, out_dim))
    opt = paddle.optimizer.AdamW(learning_rate=1e-2,
                                 parameters=model.parameters())
    loss_fn = nn.CrossEntropyLoss()
    return model, TrainStep(model, lambda o, y: loss_fn(o, y), opt)


def _batch(b=16, in_dim=8, classes=4, seed=0):
    rs = np.random.RandomState(seed)
    return (paddle.to_tensor(rs.randn(b, in_dim).astype("float32")),
            paddle.to_tensor(rs.randint(0, classes, b).astype("int64")))


# ------------------------------------------------------------- xla helpers
def test_xla_introspection_normalizes_cost_and_memory():
    _, step = _build()
    x, y = _batch()
    compiled = step.aot_prime(x, y)
    assert cost_flops(compiled) > 0
    mem = memory_stats(compiled)
    for k in ("argument_bytes", "output_bytes", "temp_bytes",
              "generated_code_bytes", "alias_bytes", "peak_bytes"):
        assert k in mem and mem[k] >= 0
    assert mem["peak_bytes"] >= mem["temp_bytes"]

    class Broken:
        def cost_analysis(self):
            raise RuntimeError("backend says no")

        def memory_analysis(self):
            raise RuntimeError("backend says no")

    assert cost_flops(Broken()) == 0.0      # degrade, never raise
    assert memory_stats(Broken()) == {}


# ----------------------------------------------------------- monitored step
def test_step_monitor_metrics_spans_and_live_mfu():
    _, step = _build()
    x, y = _batch()
    step.aot_prime(x, y)
    mon = StepMonitor(samples_per_step=16, tokens_per_step=16 * 8,
                      peak_flops=1e9)       # fake peak: MFU computable on CPU
    mon.bind(step)
    for _ in range(3):
        loss = step(x, y)
    assert np.isfinite(float(loss))
    # gauges + counters landed
    text = mon.render()
    assert "paddle_train_steps_total 3" in text
    assert "paddle_train_step_seconds_count 3" in text
    assert "paddle_train_samples_per_sec" in text
    assert 'paddle_train_hbm_bytes{kind="peak"}' in text
    assert "paddle_train_model_flops_per_step" in text
    f = mon.last_fields
    assert f["step"] == 3 and f["step_time_s"] > 0
    assert f["ips"] == pytest.approx(16 / f["step_time_s"])
    assert f["tokens_per_sec"] == pytest.approx(128 / f["step_time_s"])
    assert f["mfu"] == pytest.approx(
        mon.flops_per_step / f["step_time_s"] / 1e9)
    assert "loss" in f
    assert mon.hbm_peak_bytes > 0
    # spans: h2d + step per call, on one trace
    names = [s.name for s in mon.tracer.spans()]
    assert names.count("step") == 3 and names.count("h2d") == 3
    assert names.count("compile") == 1      # first compile only
    assert mon.recompiles == 0
    mon.detach(step)
    assert step._monitor is None


def test_recompile_sentinel_detects_shape_change():
    _, step = _build()
    x, y = _batch(b=16)
    mon = StepMonitor(peak_flops=None)
    mon.bind(step)
    step(x, y)
    step(x, y)                               # same shape: no new compile
    assert mon.recompiles == 0
    x2, y2 = _batch(b=8, seed=1)
    step(x2, y2)                             # intentionally shape-changed
    assert mon.recompiles == 1
    step(x2, y2)                             # cached now: no double count
    assert mon.recompiles == 1
    text = mon.render()
    assert ('paddle_train_recompiles_total{reason="new_shape"} 1') in text
    compiles = [s for s in mon.tracer.spans() if s.name == "compile"]
    assert [c.tags["reason"] for c in compiles] == ["first", "new_shape"]


def test_recompile_sentinel_flags_aot_fallback():
    """The jitted-fallback path (train.py: AOT avals mismatch) is the silent
    recompile class the sentinel exists for."""
    _, step = _build()
    x, y = _batch(b=16)
    step.aot_prime(x, y)
    mon = StepMonitor(peak_flops=None)
    mon.bind(step)                           # AOT avals seed the seen-set
    step(x, y)                               # AOT hit — no compile event
    assert mon.recompiles == 0
    x2, y2 = _batch(b=4, seed=2)
    step(x2, y2)                             # falls back to jit + recompiles
    assert mon.recompiles == 1
    text = mon.render()
    assert 'paddle_train_recompiles_total{reason="aot_fallback"} 1' in text


def test_run_steps_monitored_counts_all_steps():
    _, step = _build()
    x, y = _batch()
    mon = StepMonitor(samples_per_step=16)
    mon.bind(step)
    losses = step.run_steps(3, x, y)
    assert tuple(losses.shape) == (3,)
    text = mon.render()
    assert "paddle_train_steps_total 3" in text
    names = [s.name for s in mon.tracer.spans()]
    assert "run_steps" in names
    assert mon.recompiles == 0               # first scan compile is "first"
    step.run_steps(2, x, y)                  # new scan length -> new program
    assert mon.recompiles == 1


def test_monitor_disabled_and_unbound_are_inert():
    _, step = _build()
    x, y = _batch()
    base = float(step(x, y))                 # unbound: plain step works
    mon = StepMonitor(enabled=False)
    mon.bind(step)
    float(step(x, y))
    assert mon.tracer.spans() == []
    # no step series recorded (family exists but has no children), and the
    # TYPE/HELP skeleton still renders — a disabled monitor scrapes cleanly
    text = mon.render()
    assert "# TYPE paddle_train_steps_total counter" in text
    assert "paddle_train_steps_total 0" not in text
    assert "\npaddle_train_steps_total " not in text
    assert mon.last_fields == {}
    assert np.isfinite(base)


# ------------------------------------------------------------- numerics
def test_anomaly_detector_nan_inf_and_spike():
    det = NumericsAnomalyDetector(window=16, spike_factor=10.0, min_history=4)
    for i in range(6):
        assert det.check(i, loss=1.0 + 0.01 * i) == []
    (ev,) = det.check(7, loss=float("nan"))
    assert ev.kind == "nan_loss"
    (ev,) = det.check(8, loss=float("inf"))
    assert ev.kind == "inf_loss"
    (ev,) = det.check(9, loss=50.0)          # > 10x the ~1.0 median
    assert ev.kind == "loss_spike"
    assert ev.threshold == pytest.approx(10.0 * 1.025)  # 10x rolling median
    # the spike did NOT poison the baseline: a second spike still fires
    (ev,) = det.check(10, loss=50.0)
    assert ev.kind == "loss_spike"
    assert det.check(11, loss=1.02) == []    # healthy value still healthy
    # grad-norm channel is independent
    for i in range(6):
        det.check(i, grad_norm=0.5)
    (ev,) = det.check(12, grad_norm=500.0)
    assert ev.kind == "grad_norm_spike"
    (ev,) = det.check(13, grad_norm=float("nan"))
    assert ev.kind == "nan_grad_norm"


def test_monitor_routes_anomalies_to_counter_and_trace():
    mon = StepMonitor(peak_flops=None)
    for i in range(8):
        mon.observe_scalars(step=i, loss=2.0)
    events = mon.observe_scalars(step=9, loss=float("nan"))
    assert [e.kind for e in events] == ["nan_loss"]
    assert list(mon.anomalies)[-1].kind == "nan_loss"
    assert ('paddle_train_anomalies_total{kind="nan_loss"} 1'
            in mon.render())
    assert any(s.name == "anomaly" and s.tags["kind"] == "nan_loss"
               for s in mon.tracer.spans())


def test_nan_loss_detected_from_a_real_training_step():
    """End-to-end: a step whose loss goes NaN (poisoned input) raises the
    anomaly counter without breaking the step itself."""
    _, step = _build()
    x, y = _batch()
    mon = StepMonitor(peak_flops=None)
    mon.bind(step)
    step(x, y)
    bad = paddle.to_tensor(np.full((16, 8), np.nan, "float32"))
    step(bad, y)
    assert any(e.kind == "nan_loss" for e in mon.anomalies)
    assert 'paddle_train_anomalies_total{kind="nan_loss"} 1' in mon.render()


# ------------------------------------------------- profiler-joined export
def test_joined_chrome_export_has_step_phases_next_to_profiler_events(
        tmp_path):
    """Acceptance: export_joined_chrome output contains step-phase spans
    alongside profiler host events, on one sorted timebase."""
    from paddle_tpu.profiler import Profiler, RecordEvent

    _, step = _build()
    x, y = _batch()
    mon = StepMonitor(peak_flops=None)
    mon.bind(step)
    p = Profiler()
    p.start()
    with mon.phase("data_wait"):
        pass
    with RecordEvent("host_marker"):
        step(x, y)
    p.step()
    p.stop()
    path = str(tmp_path / "joined.json")
    export_joined_chrome(path, tracer=mon.tracer, profiler=p)
    events = json.load(open(path))["traceEvents"]
    names = [e["name"] for e in events]
    for expected in ("data_wait", "h2d", "step", "host_marker"):
        assert expected in names, f"missing {expected}: {names}"
    assert any(n.startswith("ProfileStep#") for n in names)
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)                  # one shared timebase


# ----------------------------------------------------------- hapi surface
def _fit_model():
    X = np.random.default_rng(0).standard_normal((48, 8)).astype("float32")
    Y = np.random.default_rng(1).integers(0, 4, (48, 1))

    class DS(paddle.io.Dataset):
        def __len__(self):
            return 48

        def __getitem__(self, i):
            return X[i], Y[i]

    with paddle.utils.unique_name.guard():
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 4))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.Adam(1e-2, parameters=net.parameters()),
                  nn.CrossEntropyLoss())
    return model, DS()


def test_monitor_callback_binds_streams_and_spans(tmp_path):
    from paddle_tpu.hapi.callbacks import MonitorCallback, ProgBarLogger
    from paddle_tpu.utils.log_writer import scalars

    model, ds = _fit_model()
    cb = MonitorCallback(log_dir=str(tmp_path / "vdl"), samples_per_step=16,
                         loss_every=1, log_freq=1)
    model.fit(ds, batch_size=16, epochs=2, verbose=0,
              callbacks=[cb, ProgBarLogger(verbose=0)])
    mon = cb.monitor
    assert model._step_monitor is mon
    assert "paddle_train_steps_total 6" in mon.render()   # 3 batches x 2
    names = [s.name for s in mon.tracer.spans()]
    for expected in ("data_wait", "h2d", "step", "callbacks"):
        assert expected in names, f"missing {expected}: {names}"
    # LogWriter sink got the per-step scalar series
    logdir = str(tmp_path / "vdl")
    fname = [f for f in os.listdir(logdir) if f.startswith("vdlrecords")][0]
    series = scalars(os.path.join(logdir, fname))
    assert "train/loss" in series and len(series["train/loss"]) == 6
    assert "train/ips" in series
    # fit-created TrainStep was the bind target
    assert model._train_step is not None
    assert model._train_step._monitor is None  # detached at on_end


def test_progbar_surfaces_monitor_fields_only_when_active(capsys):
    from paddle_tpu.hapi.callbacks import ProgBarLogger

    class FakeModel:
        _step_monitor = None

    pb = ProgBarLogger(log_freq=1, verbose=2)
    pb.set_model(FakeModel())
    pb.on_epoch_begin(0)
    pb.on_batch_end("train", 0, {"loss": 0.5})
    plain = capsys.readouterr().out
    assert "mfu" not in plain and "ips:" not in plain   # absent: unchanged

    class FakeMon:
        last_fields = {"ips": 123.4, "tokens_per_sec": 2048.0, "mfu": 0.415}

    FakeModel._step_monitor = FakeMon()
    pb.on_batch_end("train", 1, {"loss": 0.5})
    live = capsys.readouterr().out
    assert "ips: 123.4" in live and "mfu: 41.5%" in live
    assert "tok/s: 2048" in live


# ----------------------------------------------- merged exposition with serving
def test_train_registry_merges_with_serving_registries():
    """render_prometheus over (serving, training) registries: one valid
    exposition, no series collisions by construction."""
    from paddle_tpu.inference.resilience import ServingMetrics

    sm = ServingMetrics(component="generator")
    sm.inc("accepted")
    mon = StepMonitor(peak_flops=None)
    reg2 = MetricsRegistry()
    text = render_prometheus(sm.registry, mon.registry, reg2)
    assert "# TYPE paddle_serving_events_total counter" in text
    assert "# TYPE paddle_train_steps_total counter" in text
    assert text.count("# TYPE paddle_train_steps_total counter") == 1


# ------------------------------------------------------- graph lint (ISSUE-5)
def test_monitor_lints_step_once_at_first_compile():
    """StepMonitor(lint=True, the default) runs paddle_tpu.analysis over the
    bound step at FIRST launch only: a Report lands on the monitor, findings
    count into paddle_analysis_findings_total{rule,severity}, and a
    graph_lint span joins the step timeline."""
    _, step = _build()
    x, y = _batch()
    mon = StepMonitor(samples_per_step=16).bind(step)
    step(x, y)
    rep = mon.lint_report
    assert rep is not None and rep.name == "train_step:Sequential"
    assert rep.high() == []                 # the in-repo step is clean
    names = [s.name for s in mon.tracer.spans()]
    assert names.count("graph_lint") == 1
    step(x, y)                              # second step: no re-lint
    assert [s.name for s in mon.tracer.spans()].count("graph_lint") == 1


def test_monitor_lint_counts_findings_and_renders_metric():
    """A step whose program violates a rule (host-sync via debug_callback in
    the loss) must show up in the findings counter exposition."""
    import paddle_tpu.analysis  # noqa: F401 - exercised through the monitor
    model, step = _build()

    def noisy_loss(o, y):
        import jax

        jax.debug.print("o={o}", o=o.sum() if hasattr(o, "sum") else o)
        loss = nn.CrossEntropyLoss()(o, y)
        return loss

    step_noisy = TrainStep(model, noisy_loss, step.optimizer)
    mon = StepMonitor().bind(step_noisy)
    x, y = _batch()
    step_noisy(x, y)
    rep = mon.lint_report
    assert rep is not None
    assert any(f.rule == "host-sync" for f in rep.findings)
    text = mon.render()
    assert 'paddle_analysis_findings_total{rule="host-sync"' in text


def test_monitor_lint_opt_out_and_disabled():
    _, step = _build()
    x, y = _batch()
    mon = StepMonitor(lint=False).bind(step)
    step(x, y)
    assert mon.lint_report is None
    _, step2 = _build()
    mon2 = StepMonitor(enabled=False).bind(step2)
    step2(x, y)
    assert mon2.lint_report is None


def test_monitor_lints_run_steps_path():
    _, step = _build()
    x, y = _batch()
    mon = StepMonitor().bind(step)
    step.run_steps(2, x, y)
    assert mon.lint_report is not None
    assert mon.lint_report.high() == []
