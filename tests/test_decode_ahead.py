"""Decode ahead: the tick loop dispatches each decode launch before it
reads back the launch before it, taking the input tokens that launch makes
from the device: in a stretch of pure decode behind the last decode
launch, in a prefill tick behind the chunk.

Every case serves the same traffic through the loop as it is and through
the synchronous loop (the same scheduler, `_decode_ahead` and
`_decode_behind_chunk` switched off) and
compares what the clients get, token for token, and the tick ledger's
`ahead` / `ahead_dropped` counts against a hand count. Traffic is admitted
in one tick (the tick thread is held in `_admit` until every request is
queued), so both loops see the same schedule."""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.qos import TenantLedger
from paddle_tpu.inference.resilience import Deadline
from paddle_tpu.inference.scheduler import ContinuousGenerateBatchingPredictor

T = 2       # token steps a decode launch


@pytest.fixture(scope="module")
def small_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    with paddle.utils.unique_name.guard():
        paddle.seed(40)
        m = GPTForCausalLM(GPTConfig(vocab_size=160, hidden_size=64,
                                     num_layers=2, num_heads=4,
                                     num_kv_heads=2, max_position=96,
                                     dropout=0.0))
    m.eval()
    return m


def _make(m, ahead=True, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("prefill_token_budget", 16)
    kw.setdefault("decode_steps", T)
    kw.setdefault("max_new_tokens", 9)
    kw.setdefault("decode_kernel", "xla")
    kw.setdefault("block_size", 8)
    kw.setdefault("num_blocks", 32)
    kw.setdefault("max_seq_len", 40)
    sched = ContinuousGenerateBatchingPredictor(m, **kw)
    if not ahead:
        sched._decode_ahead = lambda launch: (None, None)
        sched._decode_behind_chunk = lambda picks, tk: (None, None)
    return sched


def _quiet(sched, timeout=10.0):
    deadline = time.monotonic() + timeout
    while sched._busy and time.monotonic() < deadline:
        time.sleep(0.001)
    assert not sched._busy


def _serve(sched, prompts, knobs=None):
    """Serve the prompts, all admitted by one admission; outputs in order
    (an exception where the request failed)."""
    knobs = knobs or [{}] * len(prompts)
    gate, entered, orig = threading.Event(), threading.Event(), sched._admit

    def held():
        entered.set()
        gate.wait(10.0)
        return orig()

    sched._admit = held
    assert entered.wait(10.0)       # the parked pass in flight has ended
    outs = [None] * len(prompts)

    def client(i):
        try:
            outs[i] = np.asarray(sched.infer(prompts[i], timeout=120,
                                             **knobs[i]))
        except Exception as e:     # noqa: BLE001 - the case looks at it
            outs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    deadline = time.monotonic() + 10.0
    while sched._queue.qsize() < len(prompts) and time.monotonic() < deadline:
        time.sleep(0.001)
    gate.set()
    for t in threads:
        t.join(timeout=120)
    sched._admit = orig
    _quiet(sched)
    return outs


def _run(m, prompts, ahead, knobs=None, setup=None, **kw):
    """(outputs, decode_step's ledger account, scheduler) of one server."""
    sched = _make(m, ahead=ahead, **kw)
    try:
        if setup is not None:
            setup(sched)
        outs = _serve(sched, prompts, knobs)
    finally:
        sched.close()
    assert sched.kv_cache.blocks_in_use == 0
    sched.kv_cache.check_conservation()
    progs = sched._ledger.snapshot()["programs"]
    return outs, progs.get("decode_step"), sched


def _prompts(seed, lens):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 160, n).astype("int64") for n in lens]


def _assert_same(got, want):
    assert len(got) == len(want)
    for i, (g, w) in enumerate(zip(got, want)):
        assert isinstance(g, np.ndarray), (i, g)
        np.testing.assert_array_equal(g, w, err_msg=f"request {i}")


# ------------------------------------------------------------------ parity
@pytest.mark.parametrize("lens,budget", [((3, 4, 2, 4), 16),
                                         ((3, 9, 13, 5), 4)],
                         ids=["one-prefill-tick", "chunks-interleaved"])
def test_greedy_tokens_match_the_synchronous_loop(small_gpt, lens, budget):
    prompts = _prompts(1, lens)
    want, sync, _ = _run(small_gpt, prompts, False,
                         prefill_token_budget=budget)
    got, dec, _ = _run(small_gpt, prompts, True,
                       prefill_token_budget=budget)
    _assert_same(got, want)
    assert sync["ahead"] == sync["ahead_dropped"] == 0
    assert dec["ahead_dropped"] == 0
    # every launch goes out before the one before it is read back: a
    # prefill tick's behind its chunk, a pure decode tick's behind the
    # launch in flight; and none is added: a slot that finishes by count is
    # left out of the launch after
    assert dec["launches"] == dec["ahead"] == sync["launches"]
    if budget == 16:
        # one prefill tick, then 9 new tokens: a first from the chunk and
        # four launches of 2
        assert dec["launches"] == 4
    # a launch run ahead takes its tokens from the device, of the dtype and
    # shape the host would give: the same compiled program, no second trace
    runs = [run for key, run in small_gpt._generate_cache.items()
            if key[0] == "decode_step"]
    assert runs and all(run._cache_size() == 1 for run in runs)


def test_mixed_sampled_and_greedy_tokens_match_when_none_finishes_early(
        small_gpt):
    """A seed is drawn at each dispatch: ahead or not, the launches go out
    in the same order and draw the same seeds, so sampled slots match too
    where no slot leaves before its count."""
    prompts = _prompts(2, (4, 3, 4, 2))
    knobs = [{}, dict(temperature=0.8, top_k=5), {},
             dict(temperature=1.0)]
    want, _, _ = _run(small_gpt, prompts, False, knobs=knobs)
    got, dec, _ = _run(small_gpt, prompts, True, knobs=knobs)
    _assert_same(got, want)
    assert dec["sampler_drawn"] == dec["launches"] == 4
    assert (dec["ahead"], dec["ahead_dropped"]) == (4, 0)


def test_the_warm_up_compiles_what_a_launch_run_ahead_needs(small_gpt):
    """The tokens of a launch that goes out before the launch before it is
    read back are made on the device by two small programs: the AOT
    warm-up compiles them, and serving compiles nothing more of them."""
    from paddle_tpu.inference import scheduler as sch

    prompts = _prompts(1, (3, 4, 2, 4))
    sch._carry_tokens.clear_cache()
    sch._chunk_tokens.clear_cache()
    sizes = []

    def after_warm_up(sched):
        deadline = time.monotonic() + 60.0
        while not sched.ready() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.ready() and not sched.warm_errors()
        sizes.append((sch._carry_tokens._cache_size(),
                      sch._chunk_tokens._cache_size()))

    got, dec, _ = _run(small_gpt, prompts, True, warmup=True,
                       setup=after_warm_up)
    assert all(isinstance(o, np.ndarray) for o in got)
    assert dec["ahead"] == dec["launches"] > 0
    assert (sch._carry_tokens._cache_size(),
            sch._chunk_tokens._cache_size()) == sizes[0] == (1, 1)


# ------------------------------------------- slots that leave mid-flight
def _first_eos(gen, eos):
    hits = np.flatnonzero(np.asarray(gen) == eos)
    return int(hits[0]) if len(hits) else None


def _dropped_by_hand(outs, prompts, eos, max_new):
    """Slot-steps the loop runs ahead for a slot whose EOS came in the
    launch before: its index e among the new tokens lies in decode launch
    j = ceil(e / T) (j = 0: the chunk's first token), and launch j + 1 was
    dispatched before j's read-back, carrying it, if the count left it room
    then."""
    drops = 0
    for out, p in zip(outs, prompts):
        e = _first_eos(out[len(p):], eos)
        if e is None:
            continue
        j = -(-e // T)
        if 1 + T * j < max_new:
            drops += T
    return drops


def test_eos_inside_a_launch_in_flight_drops_that_launchs_tokens(small_gpt):
    prompts = _prompts(17, (4, 4))
    plain, _, _ = _run(small_gpt, prompts, False)
    gen = plain[0][4:]
    # an EOS in the first slot's second decode launch (new tokens 3..4),
    # when its third is already in flight
    eos = next(int(gen[e]) for e in (3, 4) if gen[e] not in gen[:e])
    want, _, _ = _run(small_gpt, prompts, False, eos_token_id=eos)
    got, dec, _ = _run(small_gpt, prompts, True, eos_token_id=eos)
    _assert_same(got, want)
    drops = _dropped_by_hand(want, prompts, eos, 9)
    assert drops >= T
    assert dec["ahead_dropped"] == drops


def test_max_new_reached_mid_launch_is_never_carried_ahead(small_gpt):
    prompts = _prompts(4, (4, 4, 4))
    knobs = [dict(max_new_tokens=n) for n in (4, 6, 9)]
    want, sync, _ = _run(small_gpt, prompts, False, knobs=knobs)
    got, dec, _ = _run(small_gpt, prompts, True, knobs=knobs)
    _assert_same(got, want)
    assert [len(o) - 4 for o in got] == [4, 6, 9]
    assert dec["ahead_dropped"] == 0
    assert (dec["launches"], dec["useful_positions"]) == (
        sync["launches"], sync["useful_positions"])


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_cancel_and_deadline_while_a_launch_is_in_flight(small_gpt, how):
    """The first request leaves while the launch after the one being read
    back already carries it: its slot and pages go at the next tick
    boundary, that launch's tokens for it are dropped, and its batchmate
    decodes on unchanged."""
    prompts = _prompts(5, (4, 4))

    def leave_at_first_landing(sched):
        orig, landed = sched._land_decode, []

        def land(launch):
            landed.append(launch.ahead)
            if len(landed) == 1:
                assert sched._ahead is None     # the next one is in hand
                req = next(s.req for _, s in launch.picks
                           if np.array_equal(s.ids, prompts[0]))
                if how == "cancel":
                    req.cancel()
                else:
                    req.deadline = Deadline(0.0)
            return orig(launch)

        sched._land_decode = land

    want, _, _ = _run(small_gpt, prompts, False)
    got, dec, sched = _run(small_gpt, prompts, True,
                           setup=leave_at_first_landing)
    if how == "cancel":
        assert got[0].item() is None        # infer() of a cancelled request
    else:
        assert isinstance(got[0], Exception)
    _assert_same(got[1:], want[1:])
    assert dec["ahead_dropped"] == T
    assert sched.metrics.get("retired_seqs") == 2


def test_admission_while_a_launch_is_in_flight_lands_it_before_the_chunk(
        small_gpt, monkeypatch):
    first, second = _prompts(6, (4, 7))
    want, _, _ = _run(small_gpt, [first, second], False)
    late = {}

    def admit_at_first_landing(sched):
        orig, landed = sched._land_decode, []

        def land(launch):
            landed.append(1)
            if len(landed) == 1:
                t = threading.Thread(target=lambda: late.update(
                    out=np.asarray(sched.infer(second, timeout=120))))
                t.start()
                late["thread"] = t
                deadline = time.monotonic() + 10.0
                while sched._queue.empty() and time.monotonic() < deadline:
                    time.sleep(0.001)
            return orig(launch)

        sched._land_decode = land
        real = small_gpt.prefill_chunk

        def chunk(*a, **k):
            assert sched._ahead is None     # nothing runs ahead of a chunk
            return real(*a, **k)

        monkeypatch.setattr(small_gpt, "prefill_chunk", chunk)

    got, dec, _ = _run(small_gpt, [first], True, setup=admit_at_first_landing)
    late["thread"].join(timeout=120)
    _assert_same(got + [late["out"]], want)
    assert dec["ahead"] >= 2 and dec["ahead_dropped"] == 0


def test_a_retired_slots_pages_go_at_once_to_a_new_request(small_gpt):
    """A pool of five pages of 8 rows holds two requests of 4 + 12 tokens:
    the third waits. The first ends by EOS while the launch after it is in
    flight, and its pages go back at the read-back; the third takes them
    in the next tick, whose chunk lands the launch in flight first. The
    launch writes no row of them after the chunk has (it runs before it on
    the device, which runs launches in the pool's order): the third's
    answer is the synchronous loop's."""
    prompts = _prompts(17, (4, 4, 4))
    kw = dict(num_blocks=5, max_seq_len=24, max_new_tokens=12)
    plain, _, _ = _run(small_gpt, prompts, False, **kw)
    gen = plain[0][4:]
    eos = next(int(gen[e]) for e in (3, 4)
               if gen[e] not in gen[:e]
               and gen[e] not in plain[1][4:4 + e + T])
    pages = {}

    def watch_pages(sched):
        orig = sched._land_decode

        def land(launch):
            for _, s in launch.picks:
                pages.setdefault(s.rid, (s.ids, set(s.table[:2].tolist())))
            return orig(launch)

        sched._land_decode = land

    want, _, _ = _run(small_gpt, prompts, False, eos_token_id=eos, **kw)
    got, dec, _ = _run(small_gpt, prompts, True, eos_token_id=eos,
                       setup=watch_pages, **kw)
    _assert_same(got, want)
    assert dec["ahead_dropped"] >= T
    owner = {tuple(ids): held for ids, held in pages.values()}
    assert owner[tuple(prompts[2])] & owner[tuple(prompts[0])]


# ------------------------------------------------------------ fall-backs
def test_speculative_ticks_never_run_ahead(small_gpt):
    prompts = _prompts(8, (4, 3))
    got, dec, sched = _run(small_gpt, prompts, True, spec_k=2)
    assert all(isinstance(o, np.ndarray) for o in got)
    assert dec is None
    progs = sched._ledger.snapshot()["programs"]
    assert progs["verify_step"]["launches"] > 0
    assert all(p["ahead"] == p["ahead_dropped"] == 0
               for p in progs.values())


def test_a_qos_pause_lands_the_launch_in_flight_and_runs_none_ahead(
        small_gpt):
    """One slot: a background request decodes with a launch ahead; a more
    urgent one arrives while it is in flight. The admission that pauses the
    background sequence lands that launch first, and while the sequence
    waits paused nothing runs ahead; both answers are the synchronous
    loop's."""
    victim, urgent = _prompts(9, (4, 5))

    def tiers():
        led = TenantLedger()
        led.register("bg", weight=1.0, priority=2)
        led.register("fg", weight=1.0, priority=0)
        return led

    want, _, _ = _run(small_gpt, [victim, urgent], False)
    late, paused_ahead = {}, []

    def urgent_at_first_landing(sched):
        orig, landed = sched._land_decode, []

        def land(launch):
            landed.append(1)
            if len(landed) == 1:
                t = threading.Thread(target=lambda: late.update(
                    out=np.asarray(sched.infer(urgent, timeout=120,
                                               tenant="fg"))))
                t.start()
                late["thread"] = t
                deadline = time.monotonic() + 10.0
                while sched._queue.empty() and time.monotonic() < deadline:
                    time.sleep(0.001)
            return orig(launch)

        sched._land_decode = land
        pause, ahead = sched._pause_slot, sched._decode_ahead

        def pause_slot(i, s):
            assert sched._ahead is None
            return pause(i, s)

        def decode_ahead(launch):
            out = ahead(launch)
            if sched._paused:
                paused_ahead.append(out[0] is not None)
            return out

        sched._pause_slot, sched._decode_ahead = pause_slot, decode_ahead

    got, dec, sched = _run(small_gpt, [victim], True, qos=tiers(),
                           max_slots=1, knobs=[dict(tenant="bg")],
                           setup=urgent_at_first_landing)
    late["thread"].join(timeout=120)
    _assert_same(got + [late["out"]], want)
    assert sched.metrics.get("preempted_seqs") == 1
    assert sched.metrics.get("resumed_seqs") == 1
    assert paused_ahead and not any(paused_ahead)
    assert dec["ahead"] >= 1 and dec["ahead_dropped"] == 0
