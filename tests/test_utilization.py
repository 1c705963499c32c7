"""ISSUE-19 fleet utilization ledger: per-tick FLOPs attribution.

Pure legs drive ``attribute_launch`` / ``UtilizationLedger`` on a fake
clock and pin the integer conservation law (issued == useful + pad +
spec_waste, sum(tenant bills) == useful — EXACT, not approx) per program
shape, the host-gap split, the warmup/clamp guards, and the rolling-window
MFU math with an injected peak. Live legs boot the continuous scheduler
with ``utilization=True`` and sweep mixed greedy/sampled/spec traffic,
asserting conservation after EVERY tick (tick_end is wrapped, not
sampled), that priority preemption never bills a paused tenant, that the
exported series obey the absent-iff-off/label-hygiene/monotonicity lint,
and the /utilization + /debug/profile endpoint taxonomy end to end.
"""
import json
import random
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.qos import TenantLedger
from paddle_tpu.inference.scheduler import (
    ContinuousGenerateBatchingPredictor,
)
from paddle_tpu.inference.serving import PROFILE_MS_CAP, InferenceServer
from paddle_tpu.inference.speculative import SpecStats
from paddle_tpu.observability import UtilizationLedger, attribute_launch
from paddle_tpu.observability.metrics import (
    MetricsRegistry,
    render_prometheus,
)


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def small_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    with paddle.utils.unique_name.guard():
        paddle.seed(19)
        m = GPTForCausalLM(GPTConfig(vocab_size=160, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     num_kv_heads=2, max_position=96,
                                     dropout=0.0))
    m.eval()
    return m


def _make(m, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_steps", 2)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("decode_kernel", "xla")
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_seq_len", 40)
    kw.setdefault("utilization", True)
    return ContinuousGenerateBatchingPredictor(m, **kw)


def _get(base, path):
    try:
        r = urllib.request.urlopen(base + path, timeout=30)
        return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _post_ids(base, path, ids):
    import io

    buf = io.BytesIO()
    np.savez(buf, ids=ids)
    req = urllib.request.Request(base + path, data=buf.getvalue())
    try:
        r = urllib.request.urlopen(req, timeout=60)
        return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def _conserved(issued, useful, pad, spec, bills):
    assert issued == useful + pad + spec
    assert sum(bills.values()) == useful
    assert min([issued, useful, pad, spec] + list(bills.values()),
               default=0) >= 0


# ------------------------------------------------------- attribute_launch
def test_attribute_launch_exact_shares_per_program_shape():
    # prefill_chunk [S=4, C=8] = 32 units: two live picks, 8 and 3 tokens
    issued, useful, pad, spec, bills = attribute_launch(
        3200, 32, [("gold", 8), ("bronze", 3)])
    assert (issued, useful, pad, spec) == (3200, 1100, 2100, 0)
    assert bills == {"gold": 800, "bronze": 300}
    # decode_step [S=4] x T=2 = 8 units: three live rows absorbing 2, 2, 1
    issued, useful, pad, spec, bills = attribute_launch(
        800, 8, [(None, 2), (None, 2), ("gold", 1)])
    assert (issued, useful, pad, spec) == (800, 500, 300, 0)
    assert bills == {"default": 400, "gold": 100}
    # verify_step [S=2, K+1=4] = 8 units: slot A emitted 3 (2 accepted),
    # slot B emitted 1 with 3 rejected drafts -> spec_units 3
    issued, useful, pad, spec, bills = attribute_launch(
        8000, 8, [("a", 3), ("b", 1)], spec_units=3)
    assert (issued, useful, pad, spec) == (8000, 4000, 1000, 3000)
    assert bills == {"a": 3000, "b": 1000}


def test_attribute_launch_conservation_property_sweep():
    rng = random.Random(0x19)
    for _ in range(500):
        total = rng.randint(1, 64)
        n_slots = rng.randint(0, 6)
        budget = total
        slots = []
        for i in range(n_slots):
            u = rng.randint(0, max(0, budget))
            budget -= u
            slots.append((rng.choice([None, "a", "b", "c"]), u))
        spec = rng.randint(0, max(0, budget))
        flops = rng.choice([0, 1, rng.randint(1, 10**9),
                            float(rng.randint(0, 10**12))])
        issued, useful, pad, sp, bills = attribute_launch(
            flops, total, slots, spec_units=spec)
        _conserved(issued, useful, pad, sp, bills)
        assert issued == max(0, int(round(flops)))


def test_attribute_launch_guards():
    # no flops / no units -> all-zero, never a division error
    assert attribute_launch(None, 8, [("a", 3)]) == (0, 0, 0, 0, {})
    assert attribute_launch(0.0, 8, [("a", 3)]) == (0, 0, 0, 0, {})
    assert attribute_launch(-5.0, 8, [("a", 3)]) == (0, 0, 0, 0, {})
    # zero total units: the flops WERE issued — all of them are pad
    assert attribute_launch(100, 0, [("a", 3)]) == (100, 0, 100, 0, {})
    # zero-unit and sub-unit slots never appear in the bills
    issued, useful, pad, spec, bills = attribute_launch(
        3, 8, [("a", 0), ("b", 4)])
    assert bills == {"b": 1} and (useful, pad) == (1, 2)
    _conserved(issued, useful, pad, spec, bills)


def test_spec_stats_unit_split_matches_ledger_convention():
    st = SpecStats()
    st.launches, st.emitted, st.drafted, st.accepted = 3, 7, 9, 4
    useful, spec, pad = st.unit_split(4)     # 3 launches x width 4 = 12
    assert (useful, spec, pad) == (7, 5, 0)
    assert useful + spec + pad == st.launches * 4
    st2 = SpecStats()
    assert st2.unit_split(4) == (0, 0, 0)


# ------------------------------------------------- ledger fake-clock math
def test_ledger_tick_math_on_fake_clock():
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=10_000.0, clock=clk)
    led.tick_begin()
    led.record_launch("prefill_chunk", 3200, 0.25, 32,
                      [("gold", 8), ("bronze", 3)])
    led.record_launch("decode_step", 800, 0.15, 8, [("gold", 2)])
    clk.tick(1.0)
    t = led.tick_end()
    assert t["issued"] == 4000 and t["useful"] == 1300
    assert t["issued"] == t["useful"] + t["pad"] + t["spec_waste"]
    assert t["tenants"] == {"gold": 1000, "bronze": 300}
    assert t["wall_s"] == pytest.approx(1.0)
    assert t["launch_s"] == pytest.approx(0.40)
    assert t["host_gap_s"] == pytest.approx(0.60)
    assert set(t["programs"]) == {"prefill_chunk", "decode_step"}
    assert t["programs"]["prefill_chunk"]["launches"] == 1
    assert led.last_tick is t
    # MFU: 1300 useful flops over 1.0s at peak 10k FLOP/s
    assert led.mfu() == pytest.approx(1300 / 10_000.0)
    snap = led.snapshot()
    assert snap["flops"] == {"issued": 4000, "useful": 1300,
                             "pad_waste": 2700, "spec_waste": 0}
    assert snap["ticks"] == 1 and snap["launches"] == 2
    assert snap["tenants"] == {"gold": 1000, "bronze": 300}
    assert snap["useful_ratio"] == pytest.approx(1300 / 4000)
    assert snap["host_gap_p50_s"] == pytest.approx(0.60)
    assert snap["mfu"] == pytest.approx(0.13)
    blk = led.metrics_block()
    assert blk["flops"]["issued"] == 4000
    assert blk["host_gap_p99_s"] == pytest.approx(0.60)


def test_ledger_warmup_and_clamp_guards():
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    # a launch OUTSIDE any tick (compile warmup) must not count
    led.record_launch("prefill_chunk", 999, 0.1, 8, [(None, 8)])
    assert led.snapshot()["flops"]["issued"] == 0 and led.last_tick is None
    # launch wall can exceed tick wall on clock jitter: gap clamps to 0
    led.tick_begin()
    led.record_launch("decode_step", 100, 5.0, 8, [(None, 2)])
    clk.tick(0.5)
    t = led.tick_end()
    assert t["host_gap_s"] == 0.0
    # tick_end without tick_begin is a no-op
    assert led.tick_end() is None
    # peak unknown -> mfu 0.0 and snapshot reports None, never a made-up
    # number (the gauge is unregistered too, pinned by the lint test)
    assert led.mfu() == 0.0
    assert led.snapshot()["mfu"] is None


def test_ledger_mfu_window_prunes_old_ticks():
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=1000.0, clock=clk, mfu_window_s=10.0)
    led.tick_begin()
    led.record_launch("decode_step", 500, 0.1, 8, [(None, 8)])
    clk.tick(1.0)
    led.tick_end()
    assert led.mfu() == pytest.approx(500 / (1.0 * 1000.0))
    clk.tick(5.0)   # tick still inside the window; elapsed now spans 6s
    assert led.mfu() == pytest.approx(500 / (6.0 * 1000.0))
    clk.tick(20.0)  # window passed: nothing retained -> 0.0
    assert led.mfu() == 0.0
    # lifetime totals are NOT windowed
    assert led.snapshot()["flops"]["useful"] == 500
    assert led.snapshot()["flops"]["issued"] == 500


# ------------------------------------------------------- exposition lint
def test_ledger_series_render_and_mfu_gauge_absent_iff_no_peak():
    clk = FakeClock()
    reg = MetricsRegistry()
    led = UtilizationLedger(peak_flops=2000.0, clock=clk)
    led.bind_metrics(reg, component="continuous")
    led.tick_begin()
    led.record_launch("verify_step", 1000, 0.2, 8, [("gold", 3)],
                      spec_units=2)
    clk.tick(0.5)
    led.tick_end()
    text1 = render_prometheus(reg)
    assert ('paddle_serving_flops_total{component="continuous",'
            'kind="useful"} 375') in text1
    assert ('paddle_serving_flops_total{component="continuous",'
            'kind="spec_waste"} 250') in text1
    assert ('paddle_tenant_flops_total{component="continuous",'
            'tenant="gold"} 375') in text1
    assert 'paddle_serving_mfu{component="continuous"}' in text1
    assert ('paddle_serving_host_gap_seconds_count'
            '{component="continuous"} 1') in text1
    # conservation AS RENDERED: kinds sum to issued
    vals = {}
    for line in text1.splitlines():
        if line.startswith("paddle_serving_flops_total{"):
            k = line.split('kind="', 1)[1].split('"', 1)[0]
            vals[k] = float(line.rsplit(" ", 1)[1])
    assert sum(vals.values()) == led.snapshot()["flops"]["issued"] == 1000

    # counter monotonicity across scrapes
    led.tick_begin()
    led.record_launch("verify_step", 1000, 0.2, 8, [("gold", 3)],
                      spec_units=2)
    clk.tick(0.5)
    led.tick_end()
    text2 = render_prometheus(reg)
    for line in text1.splitlines():
        if line.startswith(("paddle_serving_flops_total{",
                            "paddle_tenant_flops_total{")):
            name, v1 = line.rsplit(" ", 1)
            v2 = [ln for ln in text2.splitlines()
                  if ln.startswith(name + " ")]
            assert v2 and float(v2[0].rsplit(" ", 1)[1]) >= float(v1), \
                f"counter went backwards: {name}"

    # peak-less ledger (this suite's CPU device has no peak): everything
    # renders EXCEPT the MFU gauge
    reg2 = MetricsRegistry()
    UtilizationLedger(peak_flops=None, clock=clk) \
        .bind_metrics(reg2, component="c2")
    text3 = render_prometheus(reg2)
    assert "paddle_serving_flops_total" in text3
    assert "paddle_serving_mfu" not in text3


# ------------------------------------------------ live scheduler sweeps
def _record_ticks(sched):
    """Wrap the ledger's tick_end so EVERY tick's decomposition (and the
    paused-tenant set at tick close) lands in a list the test can sweep."""
    seen = []
    orig = sched.util.tick_end

    def wrapped():
        paused = {s.tenant for s in sched._paused}
        t = orig()
        if t is not None:
            seen.append((t, paused))
        return t

    sched.util.tick_end = wrapped
    return seen


def test_scheduler_conservation_after_every_tick_mixed_traffic(small_gpt):
    """Tentpole acceptance: seeded mixed greedy/sampled/spec traffic on a
    real scheduler; conservation must hold EXACTLY after every tick, the
    tenant sum must close on useful, spec traffic must produce spec_waste,
    and greedy output must be bit-identical with speculation on and off
    (the ledger reads the launches, it never steers them)."""
    ledger = TenantLedger()
    ledger.register("gold", weight=2.0)
    ledger.register("bronze", weight=1.0)
    sched = _make(small_gpt, spec_k=3, qos=ledger, flight_recorder=16)
    ticks = _record_ticks(sched)
    rng = np.random.RandomState(19)
    prompts = [rng.randint(0, 160, (rng.randint(3, 9),)).astype("int64")
               for _ in range(8)]
    try:
        outs = {}

        def client(i):
            kw = {"tenant": "gold" if i % 2 else "bronze"}
            if i % 3 == 1:
                kw.update(temperature=0.8, top_k=5)
            if i % 4 == 3:
                kw["spec"] = False
            outs[i] = sched.infer(prompts[i], timeout=120, **kw)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert ticks, "scheduler never closed a utilization tick"
        for t, _paused in ticks:
            assert t["issued"] == (t["useful"] + t["pad"]
                                   + t["spec_waste"])
            assert sum(t["tenants"].values()) == t["useful"]
            for p in t["programs"].values():
                assert p["issued"] == (p["useful"] + p["pad"]
                                       + p["spec_waste"])
                # ISSUE-26: the same law in positions, off no FLOPs probe
                assert p["issued_positions"] == (
                    p["useful_positions"] + p["pad_positions"]
                    + p["spec_positions"])
                assert min(p["useful_positions"], p["pad_positions"],
                           p["spec_positions"]) >= 0
                assert 0 <= p["live_rows"] <= p["table_rows"]
            assert t["wall_s"] >= 0 and t["host_gap_s"] >= 0
            assert t["launch_s"] == pytest.approx(
                t["dispatch_s"] + t["wait_s"])
        snap = sched.util.snapshot()
        fl = snap["flops"]
        assert fl["issued"] == sum(t["issued"] for t, _ in ticks)
        assert fl["issued"] == (fl["useful"] + fl["pad_waste"]
                                + fl["spec_waste"])
        assert sum(snap["tenants"].values()) == fl["useful"]
        assert set(snap["tenants"]) <= {"gold", "bronze"}
        assert fl["useful"] > 0 and fl["pad_waste"] > 0
        assert fl["spec_waste"] > 0, \
            "spec traffic ran but no rejected-draft FLOPs were attributed"
        progs = snap["programs"]
        assert set(progs) == {"prefill_chunk", "verify_step"}
        ver = progs["verify_step"]
        assert ver["spec_positions"] > 0 and ver["pad_positions"] > 0
        assert 0 < ver["live_rows"] < ver["table_rows"]
        assert sum(p["issued_positions"] for p in progs.values()) == sum(
            q["issued_positions"] for t, _ in ticks
            for q in t["programs"].values())
        assert snap["launches"] == sum(p["launches"] for p in progs.values())
        assert snap["mfu"] is None          # CPU: no peak, no made-up MFU
        # flight-recorder snapshots carry the tick decomposition
        d = sched.flight.dump()
        utils = [tk["util"] for tk in d["ticks"] if "util" in tk]
        assert utils and all(
            u["issued"] == u["useful"] + u["pad"] + u["spec_waste"]
            for u in utils)
        # ledger-on bit parity: same greedy prompt, spec on vs off
        a = sched.infer(prompts[0], timeout=120, spec=True)
        b = sched.infer(prompts[0], timeout=120, spec=False)
        np.testing.assert_array_equal(a, b)
    finally:
        sched.close()


def test_preemption_pause_never_bills_the_paused_tenant(small_gpt):
    """Acceptance: a priority-preempted (paused) sequence is off-slot — no
    tick that closes while it is parked may bill its tenant."""
    ledger = TenantLedger()
    ledger.register("low", weight=1.0, priority=2)
    ledger.register("high", weight=1.0, priority=0)
    sched = _make(small_gpt, max_slots=1, max_new_tokens=16, max_seq_len=64,
                  qos=ledger)
    ticks = _record_ticks(sched)
    rng = np.random.RandomState(7)
    try:
        done = {}

        def run(name):
            done[name] = sched.infer(
                rng.randint(0, 160, (6,)).astype("int64"),
                timeout=120, tenant=name)

        t_low = threading.Thread(target=run, args=("low",))
        t_low.start()
        deadline = time.monotonic() + 10.0
        while (not any(s is not None for s in sched._slots)
               and time.monotonic() < deadline):
            time.sleep(0.005)
        t_high = threading.Thread(target=run, args=("high",))
        t_high.start()
        t_low.join()
        t_high.join()
        assert sched.metrics.get("preempted_seqs") > 0, \
            "the high-priority arrival never preempted — test is vacuous"
        paused_ticks = [(t, paused) for t, paused in ticks if paused]
        assert paused_ticks, "no tick closed while a sequence was paused"
        for t, paused in ticks:
            assert not (set(t["tenants"]) & paused), \
                f"tick billed paused tenant(s): {t['tenants']} ∩ {paused}"
        snap = sched.util.snapshot()
        assert sum(snap["tenants"].values()) == snap["flops"]["useful"]
        # both tenants DID get billed for the work they actually ran
        assert snap["tenants"]["low"] > 0 and snap["tenants"]["high"] > 0
    finally:
        sched.close()


def test_scheduler_off_means_off(small_gpt):
    """utilization=False (the default): nothing shown and nothing probed —
    `util` is None, no wants_flops hook, none of the series in the
    exposition. The tick ledger every scheduler keeps (ISSUE-26) counts
    on, off plain integers, and carries no FLOPs."""
    sched = _make(small_gpt, utilization=False)
    try:
        assert sched.util is None
        assert not getattr(sched._timing_hook, "wants_flops", False)
        sched.infer(np.arange(4, dtype="int64"), timeout=60)
        text = render_prometheus(sched.metrics.registry)
        assert "paddle_serving_flops_total" not in text
        assert "paddle_tenant_flops_total" not in text
        assert "paddle_serving_mfu" not in text
        assert "paddle_serving_host_gap_seconds" not in text
        snap = sched._ledger.snapshot()
        assert snap["ticks"] > 0 and snap["flops"]["issued"] == 0
        assert snap["programs"]["decode_step"]["issued_positions"] > 0
    finally:
        sched.close()


# ------------------------------------------------------ server endpoints
def test_server_utilization_endpoint_and_metrics_block(small_gpt):
    sched = _make(small_gpt)
    srv = InferenceServer(None, generator=sched).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        assert _post_ids(base, "/generate",
                         np.arange(5, dtype="int64"))[0] == 200
        # the answer leaves when its last token is absorbed; the tick
        # thread accounts that tick's launch AFTER it. Read only once every
        # slot is free and two reads of the ledger's `launches` agree, so
        # both reads below see one state
        free = sched.metrics.registry.gauge(
            "paddle_sched_slots", labels=("component", "phase")).labels(
                "continuous", "free")
        deadline, seen = time.monotonic() + 30, None
        while time.monotonic() < deadline:
            now = (free.value, sched.util.snapshot()["launches"])
            if now == seen and now[0] == sched.max_slots and now[1] > 0:
                break
            seen = now
            time.sleep(0.1)
        status, body, hdrs = _get(base, "/utilization")
        assert status == 200
        assert hdrs["Content-Type"] == "application/json"
        snaps = json.loads(body)
        assert list(snaps) == ["continuous"]
        fl = snaps["continuous"]["flops"]
        assert fl["issued"] == (fl["useful"] + fl["pad_waste"]
                                + fl["spec_waste"]) > 0
        assert sum(snaps["continuous"]["tenants"].values()) == fl["useful"]
        # compact block rides the JSON /metrics snapshot
        status, body, _ = _get(base, "/metrics")
        assert status == 200
        snap = json.loads(body)
        assert snap["utilization"]["flops"]["issued"] == fl["issued"]
        assert "mfu" in snap["utilization"]
        # and the same block is in the generator's own metrics snapshot
        assert snap["generator"]["utilization"]["flops"]["issued"] \
            == fl["issued"]
    finally:
        srv.stop()
        sched.close()


def test_server_utilization_404_without_ledger(small_gpt):
    sched = _make(small_gpt, utilization=False)
    srv = InferenceServer(None, generator=sched).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        status, body, _ = _get(base, "/utilization")
        assert status == 404 and b"no utilization ledger" in body
        status, body, _ = _get(base, "/metrics")
        assert "utilization" not in json.loads(body)
    finally:
        srv.stop()
        sched.close()


def test_server_debug_profile_taxonomy_and_capture(tmp_path):
    """/debug/profile: 400 on missing/malformed/zero/oversized ms, 409 on a
    concurrent capture, 200 with on-disk artifacts for a real one."""
    import os

    srv = InferenceServer(None, profile_dir=str(tmp_path)).start()
    base = f"http://127.0.0.1:{srv.port}"
    try:
        assert _get(base, "/debug/profile")[0] == 400
        assert _get(base, "/debug/profile?ms=soon")[0] == 400
        assert _get(base, "/debug/profile?ms=0")[0] == 400
        assert _get(base, f"/debug/profile?ms={PROFILE_MS_CAP + 1}")[0] \
            == 400
        # single-flight: while a capture holds the lock, a second is 409
        assert srv._profile_lock.acquire(blocking=False)
        try:
            status, body, hdrs = _get(base, "/debug/profile?ms=50")
            assert status == 409 and b"already in flight" in body
            assert hdrs["Retry-After"] == "1"
        finally:
            srv._profile_lock.release()
        status, body, _ = _get(base, "/debug/profile?ms=50")
        assert status == 200
        out = json.loads(body)
        assert out["ms"] == 50
        assert out["trace_dir"].startswith(str(tmp_path))
        assert os.path.isdir(out["trace_dir"])
        # the device trace landed on disk (CPU backend still writes xplane)
        captured = [f for _, _, fs in os.walk(out["trace_dir"]) for f in fs]
        assert captured, "profiler capture produced no artifacts"
    finally:
        srv.stop()
