"""ISSUE-26: the serving tick loop says where its own time goes.

A fake clock drives a real scheduler's ticks (a launch is timed through its
read-back; the tick's wall takes admission in and parked time out); the
per-launch positions and K,V rows are checked against a hand count for two
slots; a CPU ``jax.profiler`` session round some ticks finds the
``serve.*`` spans on ``/host:CPU`` with their stats, and exactly those
ticks in ``snapshot()["profiled"]``; ``ledgers()`` keeps a closed
scheduler's ledger and the ledger refers to nothing of the server."""
import collections
import gc
import glob
import threading
import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.scheduler import (
    ContinuousGenerateBatchingPredictor,
)
from paddle_tpu.observability import UtilizationLedger, utilization
from paddle_tpu.observability.metrics import render_prometheus
from paddle_tpu.profiler import profiler as prof


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt


def _model():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    with paddle.utils.unique_name.guard():
        paddle.seed(26)
        m = GPTForCausalLM(GPTConfig(vocab_size=160, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     num_kv_heads=2, max_position=96,
                                     dropout=0.0))
    m.eval()
    return m


@pytest.fixture(scope="module")
def small_gpt():
    return _model()


def _make(m, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("decode_steps", 2)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("decode_kernel", "xla")
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_seq_len", 40)
    return ContinuousGenerateBatchingPredictor(m, **kw)


def _record_ticks(sched):
    seen = []
    orig = sched._ledger.tick_end

    def wrapped():
        t = orig()
        if t is not None:
            seen.append(t)
        return t

    sched._ledger.tick_end = wrapped
    return seen


def _quiet(sched, timeout=10.0):
    """Wait until the tick that served the last answer has closed."""
    deadline = time.monotonic() + timeout
    while sched._busy and time.monotonic() < deadline:
        time.sleep(0.001)
    assert not sched._busy


def _together(sched, prompts):
    """Serve the prompts so that ONE admission takes them all: the tick
    thread is held in `_admit` until every request is queued."""
    gate, entered, orig = threading.Event(), threading.Event(), sched._admit

    def held():
        entered.set()
        gate.wait(10.0)
        return orig()

    sched._admit = held
    assert entered.wait(10.0)       # the parked pass in flight has ended
    outs = [None] * len(prompts)

    def client(i):
        outs[i] = sched.infer(prompts[i], timeout=120)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10.0
    while sched._queue.qsize() < len(prompts) and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    for t in threads:
        t.join()
    sched._admit = orig
    _quiet(sched)
    return outs


# ------------------------------------------------------- the span primitive
def test_record_event_is_a_no_op_annotation_outside_a_session():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    with prof.RecordEvent("span.outside", slots=3) as ev:
        ev.set_stats(late=1)
    assert ev._annotation is None and ev._start is None
    ev.end()        # a second end is harmless


def test_record_event_still_lands_in_a_recording_profiler():
    p = prof.Profiler().start()
    with prof.RecordEvent("span.kept", rows=2):
        pass
    p.stop()
    assert [e.name for e in p.events] == ["span.kept"]


def test_timing_hook_names_its_interval_dispatch(small_gpt):
    """No field called launch_s leaves the wait out: the hook's interval is
    dispatch_s, and per_token_s (which nobody read) is gone. The record
    always carries the model's counts of the launch (`stats`, {} here)."""
    sched = _make(small_gpt)
    seen = []
    inner = sched._timing_hook
    sched._timing_hook = lambda info: (seen.append(dict(info)), inner(info))
    try:
        sched.infer(np.arange(5, dtype="int64"), timeout=60)
    finally:
        sched.close()
    assert seen and all(
        set(i) == {"path", "batch", "prompt_len", "new_tokens", "compiled",
                   "dispatch_s", "flops", "stats"} for i in seen)
    assert all(i["stats"] == {} for i in seen)


# ------------------------------------------------- pure ledger, fake clock
def test_ledger_window_opens_where_the_last_tick_closed():
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    led.tick_begin()
    clk.tick(1.0)
    first = led.tick_end()
    clk.tick(0.25)                  # admission of the next pass
    led.tick_begin(contiguous=True)
    clk.tick(1.0)
    second = led.tick_end()
    clk.tick(5.0)                   # parked on an empty queue
    led.tick_begin()                # a pass that found no live slot
    clk.tick(1.0)
    third = led.tick_end()
    assert first["wall_s"] == pytest.approx(1.0)
    assert second["wall_s"] == pytest.approx(1.25)
    assert third["wall_s"] == pytest.approx(1.0)
    assert led.snapshot()["wall_s"] == pytest.approx(3.25)
    # contiguous with nothing before it is just "now"
    led2 = UtilizationLedger(peak_flops=None, clock=clk)
    led2.tick_begin(contiguous=True)
    clk.tick(0.5)
    assert led2.tick_end()["wall_s"] == pytest.approx(0.5)


def test_ledger_launch_is_dispatch_plus_wait_and_positions_conserve():
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    led.tick_begin()
    led.record_launch("prefill_chunk", None, 0.65, 4096,
                      [("a", 128), (None, 60)], wait_s=0.6)
    led.record_launch("verify_step", 1000, 0.30, 12, [("a", 2), ("b", 1)],
                      spec_units=3, wait_s=0.25, live_rows=70,
                      walked_rows=96, table_rows=4 * 5 * 8 * 3)
    clk.tick(1.0)
    t = led.tick_end()
    assert t["launch_s"] == pytest.approx(0.95)
    assert t["launch_s"] == pytest.approx(t["dispatch_s"] + t["wait_s"])
    assert t["wait_s"] == pytest.approx(0.85)
    assert t["host_gap_s"] == pytest.approx(0.05)
    pre, ver = t["programs"]["prefill_chunk"], t["programs"]["verify_step"]
    assert (pre["issued_positions"], pre["useful_positions"],
            pre["pad_positions"], pre["spec_positions"]) == (4096, 188,
                                                             3908, 0)
    assert (ver["issued_positions"], ver["useful_positions"],
            ver["pad_positions"], ver["spec_positions"]) == (12, 3, 6, 3)
    assert (ver["live_rows"], ver["walked_rows"],
            ver["table_rows"]) == (70, 96, 480)
    assert pre["walked_rows"] == 0
    assert pre["dispatch_s"] == pytest.approx(0.05)
    snap = led.snapshot()
    assert snap["programs"]["verify_step"] == ver
    assert snap["wait_s"] == pytest.approx(0.85)
    assert snap["launch_wall_s"] == pytest.approx(
        snap["dispatch_s"] + snap["wait_s"])
    assert snap["profiled"]["ticks"] == 0 and not t["profiled"]


# ------------------------------------- one profiled account a session
class _Session:
    """Stands in for `jax.profiler.TraceAnnotation` in the ledger's module:
    the test says when a session runs."""
    on = False

    @classmethod
    def is_enabled(cls):
        return cls.on


def _one_tick(led, clk, during=()):
    """One tick of one launch; `during` sets the session's state before
    the launch and before the end."""
    states = list(during)
    led.tick_begin()
    clk.tick(0.5)
    if states:
        _Session.on = states.pop(0)
    led.record_launch("decode_step", None, 0.4, 8, [(None, 3)], wait_s=0.3)
    if states:
        _Session.on = states.pop(0)
    return led.tick_end()


@pytest.fixture
def session(monkeypatch):
    monkeypatch.setattr(utilization, "TraceAnnotation", _Session)
    monkeypatch.setattr(_Session, "on", False)
    return _Session


def test_a_second_session_starts_the_profiled_account_afresh(session):
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    _one_tick(led, clk)                             # no session
    session.on = True
    _one_tick(led, clk), _one_tick(led, clk)        # the first capture
    session.on = False
    assert led.snapshot()["profiled"]["ticks"] == 2     # read after it
    _one_tick(led, clk)
    session.on = True
    assert _one_tick(led, clk)["profiled"]          # the second capture
    snap = led.snapshot()
    assert snap["ticks"] == 5 and snap["launches"] == 5
    acc = snap["profiled"]
    assert (acc["ticks"], acc["launches"]) == (1, 1)
    assert acc["programs"]["decode_step"]["useful_positions"] == 3
    assert acc["wall_s"] == pytest.approx(0.5)
    session.on = False
    _one_tick(led, clk)
    assert led.snapshot()["profiled"] == acc        # kept until the next


@pytest.mark.parametrize("during", [(False, True), (True, False),
                                    (False, False)])
def test_a_tick_that_saw_no_session_anywhere_inside_it_is_not_profiled(
        session, during):
    """Stop and restart inside one tick: both ends read "on", a launch or
    the end in between read "off" — the tick belongs to neither session,
    and the restart is a new one."""
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    session.on = True
    _one_tick(led, clk)
    t = _one_tick(led, clk, during=during)
    assert not t["profiled"]
    session.on = True
    _one_tick(led, clk)
    assert led.snapshot()["profiled"]["ticks"] == 1
    assert led.snapshot()["ticks"] == 3


def test_a_parked_pass_tells_two_sessions_apart(session):
    """No tick between one capture's stop and the next one's start: the
    parked loop's poll is what sees the first one end."""
    clk = FakeClock()
    led = UtilizationLedger(peak_flops=None, clock=clk)
    session.on = True
    _one_tick(led, clk)
    session.on = False
    led.poll_session()              # the tick loop, parked on the queue
    session.on = True
    _one_tick(led, clk)
    assert led.snapshot()["profiled"]["ticks"] == 1


def test_span_stats_cost_nothing_outside_a_capture(small_gpt, monkeypatch):
    """`_tick_stats` takes the slot lock and walks the slots: only a
    capture keeps a range's stats, so only a capture pays for them."""
    assert not prof.RecordEvent.capturing()
    sched = _make(small_gpt)
    calls = []
    monkeypatch.setattr(sched, "_tick_stats",
                        lambda: calls.append(1) or {})
    monkeypatch.setattr(sched, "_compiled_now",
                        lambda program: calls.append(program) or 0)
    try:
        sched.infer(np.arange(5, dtype="int64"), timeout=60)
        _quiet(sched)
    finally:
        sched.close()
    assert sched._ledger.snapshot()["ticks"] >= 3 and not calls


# ------------------------------------------- a real scheduler, fake clock
class _Late:
    """A launch's result that takes `dt` of the fake clock to read back."""

    def __init__(self, value, clk, dt):
        self.value, self.clk, self.dt = value, clk, dt

    def __array__(self, dtype=None, copy=None):
        self.clk.tick(self.dt)
        return np.asarray(self.value)


def test_fake_clock_tick_wall_has_admission_in_and_parking_out(small_gpt):
    clk = FakeClock()
    ADMIT, WAIT = 0.1, 0.5
    sched = _make(small_gpt, utilization=UtilizationLedger(
        peak_flops=None, clock=clk))
    ticks = _record_ticks(sched)
    admits = []
    orig_admit = sched._admit

    def slow_admit():
        clk.tick(ADMIT)             # every pass's admission costs 0.1
        admits.append(clk())
        return orig_admit()

    sched._admit = slow_admit
    read_back = sched._read_back

    def late_read_back(phase, tokens, *rest):
        # a launch's tokens take WAIT of the fake clock to come back (what
        # stays on the device, a launch run ahead's input, takes nothing)
        return read_back(phase, _Late(tokens._value, clk, WAIT), *rest)

    sched._read_back = late_read_back
    t_start = clk()
    try:
        out = sched.infer(np.arange(7, dtype="int64"), timeout=60)
        _quiet(sched)
    finally:
        sched.close()
    assert len(out) == 7 + 6
    assert len(ticks) >= 3
    launches = [sum(p["launches"] for p in t["programs"].values())
                for t in ticks]
    for i, (t, n) in enumerate(zip(ticks, launches)):
        assert n >= 1
        assert t["launch_s"] == pytest.approx(t["dispatch_s"] + t["wait_s"])
        assert t["wait_s"] == pytest.approx(WAIT * n)
        # the first tick opens after the admission that ended the park;
        # every later one opens where its predecessor closed
        assert t["wall_s"] == pytest.approx(
            WAIT * n + (ADMIT if i else 0.0))
        # (dispatch_s is the hook's, on the real clock: a few ms)
        assert t["host_gap_s"] == pytest.approx(
            max(0.0, t["wall_s"] - t["launch_s"]), abs=1e-9)
    snap = sched._ledger.snapshot()
    in_ticks = WAIT * sum(launches) + ADMIT * (len(ticks) - 1)
    assert snap["wall_s"] == pytest.approx(in_ticks)
    # the clock also ran through every parked pass's admission: in no tick
    assert clk() - t_start == pytest.approx(
        WAIT * sum(launches) + ADMIT * len(admits))
    assert len(admits) > len(ticks) - 1
    # the launch histogram is observed after the read-back: it holds the
    # wait, where the hook's dispatch alone is a few milliseconds
    text = render_prometheus(sched.metrics.registry)
    sums = [float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
            if ln.startswith("paddle_decode_launch_seconds_sum")]
    assert sum(sums) >= WAIT * sum(launches)


# ------------------------------------------------- positions and K,V rows
def test_positions_and_rows_against_a_hand_count_for_two_slots(small_gpt):
    """Two prompts of 4 admitted together, 6 new tokens each, chunk 4,
    2 token steps a tick, 4 slots, tables of 5 pages of 8 rows. One prefill
    launch takes both prompts (16 positions, 8 useful) and emits each
    first token; three decode launches of 4 x 2 positions absorb 2, 2 and
    1 token a slot, at 4, 6 and 8 rows in the pool."""
    sched = _make(small_gpt)
    ticks = _record_ticks(sched)
    try:
        outs = _together(sched, [np.arange(4, dtype="int64"),
                                 np.arange(10, 14, dtype="int64")])
        assert sched.table_width == 5
    finally:
        sched.close()
    assert [len(o) for o in outs] == [10, 10]
    progs = sched._ledger.snapshot()["programs"]
    pre, dec = progs["prefill_chunk"], progs["decode_step"]
    assert (pre["launches"], pre["issued_positions"],
            pre["useful_positions"], pre["pad_positions"]) == (1, 16, 8, 8)
    assert (pre["live_rows"], pre["table_rows"]) == (0, 0)
    assert (dec["launches"], dec["issued_positions"],
            dec["useful_positions"], dec["pad_positions"]) == (3, 24, 10, 14)
    # a slot at L rows attends over L+1 and L+2 in a tick of two steps
    hand = sum(2 * ((L + 1) + (L + 2)) for L in (4, 6, 8))
    assert dec["live_rows"] == hand == 90
    assert dec["table_rows"] == 3 * (4 * 5 * 8 * 2) == 960
    # a table of 40 rows is under one block of the kernel's walk: each of
    # the 2 active slots walks all of it at each of a launch's 2 steps
    assert dec["walked_rows"] == 3 * (2 * 2 * 40) == 480
    assert len(ticks) == 3 and not any(t["profiled"] for t in ticks)
    assert sched._kv_rows(np.array([7, 20]), 2) == (2 * 27 + 2 * 3, 160, 320)


def test_walked_rows_are_the_kernels_own_trip_counts(monkeypatch):
    """The ledger's `walked_rows` cannot drift from the kernel: `_kv_rows`
    and the kernel's wrapper take their blocks from one helper, so the trip
    counts the wrapper hands the kernel's call (caught here, at the width of
    a decode step and of a verify launch) times the block's rows ARE the
    count, on every side of a page and a block edge."""
    from types import SimpleNamespace

    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import decode_attention as da

    BS, NB, Hkv, D = 32, 12, 2, 64
    caught = []
    real = da._paged_pallas

    def spy(q, k_pages, v_pages, tables, lengths, trips, pages, **static):
        caught.append(np.asarray(trips))
        return real(q, k_pages, v_pages, tables, lengths, trips, pages,
                    **static)

    monkeypatch.setattr(da, "_paged_pallas", spy)
    pool = jnp.zeros((2, BS, Hkv * D), jnp.bfloat16)
    tables = jnp.zeros((4, NB), jnp.int32)
    lengths = np.array([0, 255, 256, 300])
    T = 3

    def trips(S, at):
        da.paged_decode_attention(jnp.zeros((4, S, Hkv, D), jnp.bfloat16),
                                  pool, pool, tables, at)
        return int(caught[-1].sum())

    block = BS * da.paged_tiling(Hkv, NB, BS, D, 1, 2)[1]
    assert block == 256
    sched = SimpleNamespace(max_slots=6, table_width=NB,
                            kv_cache=SimpleNamespace(block_size=BS))
    rows = ContinuousGenerateBatchingPredictor._kv_rows
    live, walked, table = rows(sched, lengths, T)
    assert walked == block * sum(trips(1, lengths + t) for t in range(T))
    # blocks at step 0, 1, 2 for lengths 0 / 255 / 256 / 300
    assert walked == block * ((1 + 1 + 2 + 2) + (1 + 2 + 2 + 2) * 2)
    assert live <= walked <= table == 6 * NB * BS * T
    # a verify launch is ONE call whose T rows all walk length + T
    assert rows(sched, lengths, T, one_call=True)[1] == (
        T * block * trips(T, lengths))
    # a slot with no valid new row is not walked; a table the block does not
    # divide is walked to the block's end (the kernel fetches its last live
    # page again for the columns past it, masked)
    da.paged_decode_attention(jnp.zeros((4, 1, Hkv, D), jnp.bfloat16), pool,
                              pool, tables, lengths,
                              new_rows=np.array([1, 0, 1, 0]))
    assert list(caught[-1]) == [1, 0, 2, 0]
    assert rows(sched, np.array([NB * BS - 1]), 1)[1] == 2 * block


# ------------------------------------------------ spans on the trace's clock
def _host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[0]
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                       dict(e.stats)) for e in line.events
                      if e.name.startswith(("serve.", "generate."))]
    return sorted(spans, key=lambda s: s[1])


def test_profiler_session_holds_the_tick_spans_and_only_its_ticks(
        small_gpt, tmp_path):
    sched = _make(small_gpt)
    try:
        sched.infer(np.arange(5, dtype="int64"), timeout=60)    # before
        _quiet(sched)
        before = sched._ledger.snapshot()
        assert before["ticks"] >= 3 and before["profiled"]["ticks"] == 0
        jax.profiler.start_trace(str(tmp_path))
        try:
            _together(sched, [np.arange(4, dtype="int64"),
                              np.arange(10, 14, dtype="int64")])
        finally:
            jax.profiler.stop_trace()
        sched.infer(np.arange(5, dtype="int64"), timeout=60)    # after
        _quiet(sched)
    finally:
        sched.close()
    snap = sched._ledger.snapshot()
    acc = snap["profiled"]
    assert acc["ticks"] == 3 < snap["ticks"] == 2 * before["ticks"] + 3
    assert acc["programs"]["decode_step"]["live_rows"] == 90
    assert acc["programs"]["decode_step"]["walked_rows"] == 480
    assert snap["programs"]["decode_step"]["walked_rows"] > 480
    assert acc["programs"]["prefill_chunk"]["useful_positions"] == 8
    assert 0 < acc["wait_s"] < acc["wall_s"]
    assert acc["launch_wall_s"] == pytest.approx(
        acc["dispatch_s"] + acc["wait_s"], abs=2e-6)
    spans = _host_spans(tmp_path)
    ticks = [s for s in spans if s[0] == "serve.tick"]
    assert len(ticks) == 3
    assert ticks[0][3] == {"live": 2, "prefill": 2, "decode": 0,
                           "pending": 0}
    assert ticks[1][3]["decode"] == 2
    names = {s[0] for s in spans}
    assert names >= {"serve.tick", "serve.admit", "serve.retire",
                     "serve.prefill.assemble", "serve.prefill.dispatch",
                     "serve.prefill.wait", "serve.prefill.absorb",
                     "serve.decode.assemble", "serve.decode.dispatch",
                     "serve.decode.wait", "serve.decode.absorb",
                     "generate.prefill_chunk", "generate.decode_step"}
    # children nest inside their tick, on the same clock, and the tick is
    # longer than the wait it holds
    for name, a, b, stats in spans:
        if name != "serve.tick":
            assert any(t[1] <= a and b <= t[2] for t in ticks), name
    waits = [s for s in spans if s[0] == "serve.decode.wait"]
    assert len(waits) == 3
    for w in waits:
        tick = [t for t in ticks if t[1] <= w[1] and w[2] <= t[2]][0]
        assert tick[2] - tick[1] > w[2] - w[1] > 0
    absorbs = [s[3] for s in spans if s[0] == "serve.decode.absorb"]
    assert [a["useful"] for a in absorbs] == [4, 4, 2]
    assert [a["rows"] for a in absorbs] == [22, 30, 38]
    assert all(a["issued"] == 8 for a in absorbs)
    pre = [s[3] for s in spans if s[0] == "serve.prefill.absorb"]
    assert pre == [{"useful": 8, "issued": 16}]
    assert all(s[3] == {"compiled": 0} for s in spans
               if s[0].endswith(".dispatch"))


def test_a_second_capture_reads_its_own_ticks_only(small_gpt, tmp_path):
    """A long-lived server, two /debug/profile-like captures: each reads
    the tick accounting of its own ticks, not the sum."""
    sched = _make(small_gpt)
    accounts = []
    try:
        for i, prompt in enumerate((4, 9)):
            jax.profiler.start_trace(str(tmp_path / f"capture{i}"))
            try:
                sched.infer(np.arange(prompt, dtype="int64"), timeout=60)
                _quiet(sched)
            finally:
                jax.profiler.stop_trace()
            accounts.append(sched._ledger.snapshot()["profiled"])
            sched.infer(np.arange(5, dtype="int64"), timeout=60)
            _quiet(sched)
    finally:
        sched.close()
    first, second = (a["programs"]["prefill_chunk"] for a in accounts)
    assert (first["launches"], first["useful_positions"]) == (1, 4)
    assert (second["launches"], second["useful_positions"]) == (3, 9)
    assert accounts[1]["ticks"] < sched._ledger.snapshot()["ticks"]


# ------------------------------------------------ read after the server
def _reaches(root, targets, limit=200000):
    """Whether a breadth-first walk of gc referents from `root` meets one
    of `targets`; None if it gave up at `limit` objects before the end."""
    want = {id(t) for t in targets}
    seen, queue = set(), collections.deque([root])
    while queue:
        obj = queue.popleft()
        if id(obj) in seen:
            continue
        seen.add(id(obj))
        if id(obj) in want:
            return True
        if len(seen) >= limit:
            return None
        if isinstance(obj, (type, type(gc))):
            continue                # classes and modules lead everywhere
        queue.extend(gc.get_referents(obj))
    return False


def test_ledgers_keeps_a_closed_schedulers_ledger_and_nothing_of_it():
    model = _model()
    sched = _make(model)
    led = sched._ledger
    assert sched.util is None       # utilization= off: nothing exported
    assert led in utilization.ledgers()
    sched.infer(np.arange(5, dtype="int64"), timeout=60)
    _quiet(sched)
    sched.close()
    pool = sched.kv_cache
    # walked to the end: False, not the None of a walk that gave up
    assert _reaches(led, [sched, model, pool]) is False
    assert _reaches(sched, [led])   # the walk does find what is there
    ticks = led.snapshot()["ticks"]
    del sched, model, pool
    gc.collect()
    kept = [x for x in utilization.ledgers() if x is led]
    assert len(kept) == 1 and kept[0].snapshot()["ticks"] == ticks >= 3
    # the list of closed ledgers is bounded
    for _ in range(8):
        UtilizationLedger(peak_flops=None).close()
    assert len(utilization._CLOSED) == utilization._CLOSED.maxlen
