"""Observability layer (ISSUE-3): request-scoped tracing, the typed metrics
registry + Prometheus exposition, serving-lifecycle spans joined to the
terminal-outcome CAS, X-Trace-Id on every HTTP path, and the exposition-lint
contract (valid text format, no duplicate series, counter monotonicity,
conservation sum) scraped off a live InferenceServer."""
import io
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.faults import FaultInjector
from paddle_tpu.inference.resilience import AdmissionController, ServingMetrics
from paddle_tpu.inference.serving import (
    BatchingPredictor,
    GenerateBatchingPredictor,
    InferenceServer,
)
from paddle_tpu.observability import (
    MetricsRegistry,
    RequestTrace,
    Tracer,
    export_joined_chrome,
    render_prometheus,
)


# ----------------------------------------------------------------- Tracer unit
class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def test_tracer_contextvar_nesting_and_parenting():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    with tr.span("outer") as tid:
        clk.tick(0.001)
        with tr.span("inner", shard=3):
            clk.tick(0.002)
        clk.tick(0.001)
    spans = tr.trace(tid)
    assert [s.name for s in spans] == ["outer", "inner"]
    outer, inner = spans
    assert inner.parent_id == outer.span_id
    assert inner.trace_id == outer.trace_id == tid
    assert inner.tags == {"shard": 3}
    assert inner.duration_us == pytest.approx(2000.0)
    assert outer.duration_us == pytest.approx(4000.0)
    # nesting is per-context: after exit there is no current trace
    from paddle_tpu.observability import current_trace_id

    assert current_trace_id() is None


def test_tracer_span_tags_exception_and_reraises():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("boom") as tid:
            raise RuntimeError("injected")
    (s,) = tr.trace(tid)
    assert "injected" in s.tags["error"]


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer(capacity=8, clock=FakeClock())
    for i in range(20):
        tr.record(f"s{i}", 0.0, 1.0, trace_id="t")
    assert len(tr.spans()) == 8
    assert tr.dropped == 12
    assert [s.name for s in tr.spans()] == [f"s{i}" for i in range(12, 20)]


def test_tracer_sampling_is_per_trace_and_disabled_is_noop():
    tr = Tracer(clock=FakeClock(), sample_rate=0.0)
    assert tr.should_sample() is False
    rt = RequestTrace(tr)
    rt.child("x", 0, 1)
    rt.finish("result")
    assert tr.spans() == []            # unsampled trace records nothing
    assert rt.trace_id                 # ...but still has an id for logs
    off = Tracer(enabled=False)
    assert off.should_sample() is False
    assert off.record("x", 0, 1, "t") is None
    assert off.spans() == []


def test_request_trace_cross_thread_and_terminal_idempotence():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    rt = RequestTrace(tr, trace_id="req-1")
    clk.tick(0.001)
    t0 = tr.now_us()
    clk.tick(0.005)

    def worker():
        rt.child("queue_wait", t0, tr.now_us())
        rt.finish("timeout", cas="timeout")

    t = threading.Thread(target=worker)
    t.start()
    t.join(timeout=5)
    assert rt.finish("result") is False     # CAS loser records nothing
    spans = tr.trace("req-1")
    names = [s.name for s in spans]
    assert names == ["request", "queue_wait", "timeout"]
    root = spans[0]
    assert root.tags["outcome"] == "timeout"
    terminal = spans[-1]
    assert terminal.parent_id == root.span_id
    assert terminal.tags["cas"] == "timeout"


def test_chrome_export_monotonic_and_joined_with_profiler(tmp_path):
    import json

    from paddle_tpu.profiler import Profiler, RecordEvent

    tr = Tracer()
    p = Profiler()
    p.start()
    with RecordEvent("model_call"):
        with tr.span("serving_request"):
            time.sleep(0.002)
    p.stop()
    path = str(tmp_path / "joined.json")
    export_joined_chrome(path, tracer=tr, profiler=p)
    events = json.load(open(path))["traceEvents"]
    names = [e["name"] for e in events]
    assert "model_call" in names and "serving_request" in names
    ts = [e["ts"] for e in events]
    assert ts == sorted(ts)                       # one shared timebase
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in events)


# -------------------------------------------------------------- metrics unit
def test_registry_counter_gauge_histogram_and_exposition():
    reg = MetricsRegistry()
    c = reg.counter("demo_requests_total", "requests", labels=("route",))
    c.labels("a").inc()
    c.labels(route="a").inc(2)
    c.labels("b").inc()
    g = reg.gauge("demo_depth", "queue depth")
    g.set(7)
    reg.gauge("demo_cb", "callback").set_function(lambda: 41 + 1)
    h = reg.histogram("demo_seconds", "lat", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    text = reg.render()
    assert "# HELP demo_requests_total requests" in text
    assert "# TYPE demo_requests_total counter" in text
    assert 'demo_requests_total{route="a"} 3' in text
    assert 'demo_requests_total{route="b"} 1' in text
    assert "demo_depth 7" in text
    assert "demo_cb 42" in text
    assert 'demo_seconds_bucket{le="0.1"} 1' in text
    assert 'demo_seconds_bucket{le="1"} 2' in text
    assert 'demo_seconds_bucket{le="+Inf"} 3' in text
    assert "demo_seconds_count 3" in text
    assert "demo_seconds_sum 5.55" in text


def test_registry_type_safety():
    reg = MetricsRegistry()
    c = reg.counter("x_total", "x")
    assert reg.counter("x_total", "x") is c       # get-or-create
    with pytest.raises(ValueError):
        reg.gauge("x_total", "x")                 # type flip forbidden
    with pytest.raises(ValueError):
        reg.counter("x_total", "x", labels=("a",))  # label flip forbidden
    with pytest.raises(ValueError):
        c.inc(-1)                                 # counters are monotonic
    with pytest.raises(TypeError):
        c.set(3)
    with pytest.raises(ValueError):
        reg.counter("bad name", "x")
    g = reg.gauge("g", "g")
    g.inc()
    g.dec(3)
    assert g.value == -2


def test_render_merges_registries_once_and_flags_conflicts():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("shared_total", "s", labels=("component",)).labels("x").inc()
    b.counter("shared_total", "s", labels=("component",)).labels("y").inc(2)
    text = render_prometheus(a, b, a)              # dup registry deduped
    assert text.count("# TYPE shared_total counter") == 1
    assert 'shared_total{component="x"} 1' in text
    assert 'shared_total{component="y"} 2' in text
    b2 = MetricsRegistry()
    b2.gauge("shared_total", "s", labels=("component",))
    with pytest.raises(ValueError):
        render_prometheus(a, b2)                   # type conflict
    b3 = MetricsRegistry()
    b3.counter("shared_total", "s", labels=("component",)).labels("x").inc()
    with pytest.raises(ValueError):
        render_prometheus(a, b3)                   # duplicate series


def test_label_values_escaped():
    reg = MetricsRegistry()
    reg.counter("esc_total", "e", labels=("msg",)).labels(
        'he said "hi"\nback\\slash').inc()
    line = [l for l in reg.render().splitlines()
            if l.startswith("esc_total{")][0]
    assert '\\"hi\\"' in line and "\\n" in line and "\\\\slash" in line


# ------------------------------------------- ServingMetrics reservoir (fix)
def test_latency_reservoir_tracks_late_tail():
    """Satellite fix: the old reservoir dropped every sample after the first
    4096, freezing p99 early in a long run. Uniform reservoir sampling keeps
    late-arriving tail latencies moving the percentiles."""
    m = ServingMetrics()
    for _ in range(4096):
        m.observe_latency(0.010)                  # a quiet first minute
    assert m.snapshot()["p99_ms"] == pytest.approx(10.0)
    for _ in range(4096):
        m.observe_latency(1.000)                  # then the incident
    snap = m.snapshot()
    # ~half the reservoir is now incident-era samples; p99 must have moved
    assert snap["p99_ms"] == pytest.approx(1000.0)
    assert snap["p50_ms"] > 10.0


def test_serving_metrics_mirror_into_registry():
    m = ServingMetrics(component="generator")
    m.inc("accepted", 3)
    m.inc("completed", 2)
    m.inc("timeouts")
    m.observe_latency(0.02)
    text = m.registry.render()
    assert ('paddle_serving_events_total{component="generator",'
            'event="accepted"} 3') in text
    assert ('paddle_serving_events_total{component="generator",'
            'event="timeouts"} 1') in text
    assert "paddle_serving_request_latency_seconds_count" in text
    # legacy snapshot shape unchanged
    snap = m.snapshot()
    assert snap["accepted"] == 3 and "p50_ms" in snap


# ------------------------------------------------- serving lifecycle spans
class Doubler:
    def run(self, stacked):
        return [stacked[0] * 2.0]


def test_predictor_completed_request_trace_covers_lifecycle():
    bp = BatchingPredictor(Doubler(), max_batch_size=2, max_delay_ms=1)
    try:
        bp.infer(np.ones(2), timeout=10, trace_id="life-1")
        names = [s.name for s in bp.tracer.trace("life-1")]
        for expected in ("request", "admission", "queue_wait",
                         "batch_assembly", "decode_launch", "decode",
                         "result"):
            assert expected in names, f"missing span {expected}: {names}"
        root = bp.tracer.trace("life-1")[0]
        assert root.name == "request" and root.tags["cas"] == "result"
    finally:
        bp.close()


def test_predictor_timeout_trace_reaches_terminal_with_outcome():
    """Acceptance criterion: a request that dies by timeout yields a
    retrievable trace covering admission → terminal, terminal tagged with
    the CAS outcome."""
    f = FaultInjector()
    bp = BatchingPredictor(Doubler(), max_batch_size=1, max_delay_ms=1,
                           faults=f)
    try:
        f.install("predictor.run", delay=0.4, times=1)
        done = {}
        t = threading.Thread(
            target=lambda: done.update(r=bp.infer(np.ones(2), timeout=10)))
        t.start()
        deadline = time.monotonic() + 5
        while not bp._busy and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(TimeoutError):
            bp.infer(np.ones(2), timeout=0.05, trace_id="t-504")
        t.join(timeout=10)
        spans = bp.tracer.trace("t-504")
        names = [s.name for s in spans]
        assert names[0] == "request" and "admission" in names
        terminal = [s for s in spans if s.tags.get("cas")]
        assert {s.tags["cas"] for s in terminal} == {"timeout"}
        assert any(s.name == "timeout" and s.tags["outcome"] == "timeout"
                   for s in spans)
    finally:
        bp.close()


def test_predictor_door_rejection_trace_and_disabled_tracer_records_nothing():
    bp = BatchingPredictor(
        Doubler(), max_batch_size=1, max_delay_ms=1,
        admission=AdmissionController(max_queue_depth=0))
    try:
        from paddle_tpu.inference.resilience import ServerBusy

        with pytest.raises(ServerBusy):
            bp.infer(np.ones(2), timeout=5, trace_id="shed-1")
        spans = bp.tracer.trace("shed-1")
        names = [s.name for s in spans]
        assert "admission" in names and "rejected" in names
        assert spans[0].tags["outcome"] == "rejected"
    finally:
        bp.close()
    off = BatchingPredictor(Doubler(), max_batch_size=1, max_delay_ms=1,
                            tracer=Tracer(enabled=False))
    try:
        off.infer(np.ones(2), timeout=10)
        assert off.tracer.spans() == []
        assert off.metrics.get("completed") == 1   # metrics still flow
    finally:
        off.close()


# --------------------------------------------------- generator + HTTP legs
@pytest.fixture(scope="module")
def small_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    with paddle.utils.unique_name.guard():
        paddle.seed(11)
        m = GPTForCausalLM(GPTConfig(vocab_size=128, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     num_kv_heads=2, max_position=64,
                                     dropout=0.0))
    m.eval()
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, 128, 5).astype("int64")
    return m, prompt


def test_generator_trace_includes_kv_reserve_and_decode(small_gpt):
    m, prompt = small_gpt
    gp = GenerateBatchingPredictor(m, max_batch_size=2, max_delay_ms=5,
                                   max_new_tokens=3, decode_kernel="xla",
                                   block_size=8, num_blocks=16)
    try:
        gp.infer(prompt, timeout=120, trace_id="gen-1")
        names = [s.name for s in gp.tracer.trace("gen-1")]
        for expected in ("request", "admission", "queue_wait", "kv_reserve",
                         "decode_launch", "decode", "result"):
            assert expected in names, f"missing span {expected}: {names}"
        # decode-launch timing hook fed the registry
        text = gp.metrics.registry.render()
        assert "paddle_decode_launch_seconds_count" in text
        assert ('paddle_generated_tokens_total{component="generator"} 3'
                in text)
        # pool gauges partition the pool
        assert 'paddle_kv_pool_blocks{pool="generator",state="free"} 16' \
            in text
    finally:
        gp.close()


def _get(base, path, headers=None):
    req = urllib.request.Request(base + path, headers=headers or {})
    try:
        r = urllib.request.urlopen(req, timeout=10)
        return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _post_npz(base, path, ids, headers=None):
    buf = io.BytesIO()
    np.savez(buf, ids=ids)
    req = urllib.request.Request(base + path, data=buf.getvalue(),
                                 headers=headers or {})
    try:
        r = urllib.request.urlopen(req, timeout=60)
        return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def test_server_every_terminal_path_carries_trace_and_retry_headers(
        small_gpt):
    """Satellite: 200/429/503/504/400 (and GETs) all carry X-Trace-Id;
    the load-shed statuses (429/503) always carry Retry-After."""
    m, prompt = small_gpt
    f = FaultInjector()
    gp = GenerateBatchingPredictor(m, max_batch_size=2, max_delay_ms=5,
                                   max_new_tokens=3, decode_kernel="xla",
                                   block_size=8, num_blocks=16, faults=f)
    srv = InferenceServer(None, batching=False, generator=gp).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        ids = prompt.astype("int64")
        # 200 + client-supplied trace id is echoed AND joins the server trace
        status, _, hdrs = _post_npz(base, "/generate", ids,
                                    headers={"X-Trace-Id": "client-abc"})
        assert status == 200 and hdrs["X-Trace-Id"] == "client-abc"
        names = [s.name for s in gp.tracer.trace("client-abc")]
        assert "request" in names and "result" in names

        # 429 queue-full: X-Trace-Id + Retry-After
        gp.admission = AdmissionController(max_queue_depth=0, retry_after=0.5)
        status, _, hdrs = _post_npz(base, "/generate", ids)
        assert status == 429
        assert "X-Trace-Id" in hdrs and int(hdrs["Retry-After"]) >= 1
        gp.admission = AdmissionController()

        # 400 oversized-for-pool: X-Trace-Id, no retry hint needed
        status, _, hdrs = _post_npz(base, "/generate",
                                    np.arange(300).astype("int64"))
        assert status == 400 and "X-Trace-Id" in hdrs

        # 504 deadline expiry: X-Trace-Id, and the trace reached its terminal
        f.install("predictor.generate", delay=0.5, times=1)
        status, _, hdrs = _post_npz(base, "/generate", ids,
                                    headers={"X-Timeout-Ms": "100",
                                             "X-Trace-Id": "slow-1"})
        assert status == 504 and hdrs["X-Trace-Id"] == "slow-1"
        spans = gp.tracer.trace("slow-1")
        assert any(s.tags.get("cas") == "timeout" for s in spans)

        # 503 draining: X-Trace-Id + Retry-After on POST and readyz
        srv._draining.set()
        status, _, hdrs = _post_npz(base, "/generate", ids)
        assert status == 503
        assert "X-Trace-Id" in hdrs and "Retry-After" in hdrs
        status, _, hdrs = _get(base, "/readyz")
        assert status == 503 and "X-Trace-Id" in hdrs
        srv._draining.clear()

        # GETs and 404s carry the header too
        for path, want in (("/health", 200), ("/metrics", 200),
                           ("/nope", 404)):
            status, _, hdrs = _get(base, path)
            assert status == want and "X-Trace-Id" in hdrs
    finally:
        srv.stop(drain_timeout=5)


# ---------------------------------------------------------- exposition lint
_SERIES_RE = re.compile(
    r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{([^}]*)\})? ([^ ]+)$')


def _parse_exposition(text):
    """Parse a text exposition -> (types, helps, {series_key: value}).
    Asserts structural validity along the way."""
    types, helps, series = {}, {}, {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# TYPE "):
            _, _, name, typ = line.split(" ", 3)
            assert name not in types, f"duplicate TYPE for {name}"
            assert typ in ("counter", "gauge", "histogram")
            types[name] = typ
            continue
        if line.startswith("# HELP "):
            parts = line.split(" ", 3)
            helps[parts[2]] = parts[3] if len(parts) > 3 else ""
            continue
        assert not line.startswith("#"), f"unknown comment {line!r}"
        mm = _SERIES_RE.match(line)
        assert mm, f"malformed series line {line!r}"
        name, _, labels, value = mm.groups()
        base = re.sub(r"_(bucket|sum|count)$", "", name) \
            if name.endswith(("_bucket", "_sum", "_count")) else name
        assert base in types or name in types, \
            f"series {name} has no TYPE line"
        key = (name, labels or "")
        assert key not in series, f"duplicate series {key}"
        series[key] = float(value.replace("+Inf", "inf"))
    for name in types:
        assert name in helps, f"TYPE without HELP for {name}"
    return types, helps, series


def _events(series, component, event):
    return series.get(
        ("paddle_serving_events_total",
         f'component="{component}",event="{event}"'), 0.0)


def test_metrics_exposition_lint_and_conservation(small_gpt):
    """Satellite (CI/tooling): boot the server, scrape /metrics?format=prom
    twice with traffic in between — valid format, no duplicate series,
    counters monotone, and the PR 2 conservation sum holds as rendered."""
    m, prompt = small_gpt
    pred = Doubler()
    gp = GenerateBatchingPredictor(m, max_batch_size=2, max_delay_ms=5,
                                   max_new_tokens=3, decode_kernel="xla",
                                   block_size=8, num_blocks=16)
    srv = InferenceServer(pred, batching=True, max_batch_size=2,
                          max_delay_ms=1, generator=gp).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        ids = prompt.astype("int64")
        assert _post_npz(base, "/generate", ids)[0] == 200
        assert _post_npz(base, "/predict", np.ones(2))[0] == 200

        status, body, hdrs = _get(base, "/metrics?format=prom")
        assert status == 200
        assert hdrs["Content-Type"].startswith("text/plain")
        types1, _, series1 = _parse_exposition(body.decode())

        # a JSON scrape still works (legacy default) and more traffic lands
        status, body_json, hdrs = _get(base, "/metrics")
        assert status == 200 and hdrs["Content-Type"] == "application/json"
        import json

        snap = json.loads(body_json)
        assert snap["generator"]["completed"] == 1
        assert _post_npz(base, "/generate", ids)[0] == 200

        # Accept-header negotiation reaches the same exposition
        status, body2, _ = _get(base, "/metrics",
                                headers={"Accept": "text/plain"})
        assert status == 200
        types2, _, series2 = _parse_exposition(body2.decode())

        # counter monotonicity across the two scrapes
        assert types1 == types2
        for (name, labels), v1 in series1.items():
            base_name = re.sub(r"_(bucket|sum|count)$", "", name)
            if types1.get(base_name, types1.get(name)) == "counter" \
                    or name.endswith(("_bucket", "_count")):
                v2 = series2.get((name, labels))
                assert v2 is not None and v2 >= v1, \
                    f"counter {name}{{{labels}}} went backwards"

        # PR 2 conservation sum AS RENDERED in the exposition
        for component in ("batcher", "generator"):
            acc = _events(series2, component, "accepted")
            assert acc >= 1
            terminal = (_events(series2, component, "completed")
                        + _events(series2, component, "failed")
                        + _events(series2, component, "timeouts"))
            assert acc == terminal, f"{component} leaked requests"

        # KV pool gauges partition the pool
        pool = {st: series2.get(
            ("paddle_kv_pool_blocks", f'pool="generator",state="{st}"'))
            for st in ("live", "free", "evictable")}
        assert None not in pool.values()
        assert sum(pool.values()) == series2[
            ("paddle_kv_pool_size_blocks", 'pool="generator"')] == 16
        # HTTP layer counted every response we made
        assert series2[("paddle_http_responses_total",
                        'path="/generate",status="200"')] == 2

        # ISSUE-18 absent-iff-off contract: no SLOMonitor / flight recorder
        # wired here, so none of their gauges may render (a dead gauge is
        # noise); the tracer-drop counter, by contrast, is always-on
        assert not any(n.startswith("paddle_slo_") for n in types2)
        assert "paddle_flightrec_ticks" not in types2
        # ISSUE-19: the utilization ledger's series ride the same contract —
        # no ledger wired here, so none of them may render
        assert "paddle_serving_flops_total" not in types2
        assert "paddle_tenant_flops_total" not in types2
        assert "paddle_serving_host_gap_seconds" not in types2
        assert "paddle_serving_mfu" not in types2
        assert "paddle_trace_dropped_spans_total" in types2
        for (name, labels), v in series2.items():
            if name == "paddle_trace_dropped_spans_total":
                assert 'component="' in labels and v == 0.0
    finally:
        srv.stop(drain_timeout=5)


# ------------------------------------------- training-series exposition lint
def test_train_series_exposition_lint_with_merged_registries():
    """ISSUE-4 satellite: the paddle_train_* series hold the same exposition
    contract as the serving ones — HELP/TYPE for every family, no duplicate
    series when the training registry is merged with serving registries, and
    histogram buckets cumulative + +Inf-terminated AS RENDERED."""
    from paddle_tpu.observability import StepMonitor

    clk = FakeClock()
    mon = StepMonitor(peak_flops=None, samples_per_step=4, clock=clk,
                      tracer=Tracer(clock=clk))
    # three steps at different durations so several buckets fill
    for dt in (0.003, 0.04, 0.8):
        t0 = mon.step_begin()
        clk.tick(dt)
        mon.step_end(None, 1.0, t0)
    for i in range(8):
        mon.observe_scalars(step=i, loss=1.0)
    mon.observe_scalars(step=9, loss=float("nan"))      # anomaly family

    sm = ServingMetrics(component="generator")
    sm.inc("accepted")
    sm.inc("completed")
    sm.observe_latency(0.02)
    text = render_prometheus(sm.registry, mon.registry)
    types, helps, series = _parse_exposition(text)      # no-dup + HELP/TYPE

    for fam, typ in (("paddle_train_steps_total", "counter"),
                     ("paddle_train_step_seconds", "histogram"),
                     ("paddle_train_samples_per_sec", "gauge"),
                     ("paddle_train_mfu", "gauge"),
                     ("paddle_train_loss", "gauge"),
                     ("paddle_train_hbm_bytes", "gauge"),
                     ("paddle_train_recompiles_total", "counter"),
                     ("paddle_train_anomalies_total", "counter")):
        assert types.get(fam) == typ, f"{fam} missing/mistyped in exposition"
        assert helps[fam], f"{fam} rendered without HELP text"
    assert series[("paddle_train_steps_total", "")] == 3
    assert series[("paddle_train_anomalies_total", 'kind="nan_loss"')] == 1

    # histogram bucket counts cumulative and +Inf-terminated as rendered
    buckets = [(labels, v) for (name, labels), v in series.items()
               if name == "paddle_train_step_seconds_bucket"]
    assert buckets, "step-seconds histogram rendered no buckets"

    def le_of(labels):
        mm = re.search(r'le="([^"]+)"', labels)
        return float(mm.group(1).replace("+Inf", "inf"))

    buckets.sort(key=lambda kv: le_of(kv[0]))
    counts = [v for _, v in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert le_of(buckets[-1][0]) == float("inf"), "missing +Inf bucket"
    assert counts[-1] == 3
    assert series[("paddle_train_step_seconds_count", "")] == counts[-1]
    # the serving side of the merge is intact too
    assert series[("paddle_serving_events_total",
                   'component="generator",event="accepted"')] == 1
