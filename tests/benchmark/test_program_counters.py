"""The three per-layer readers of source `program_counter`, run on a tiny
CPU serve: the benchmark's own `Server`, a `jax.profiler` session round
three requests sent one at a time, and each reader's value against a hand
count of what those requests make the tick loop issue."""
import time

import jax
import numpy as np
import pytest

import _tiny
from benchmarks.harness import counters, program
from benchmarks.harness.job import View, layer_reader

READERS = ("host_gap_share.serve", "pad_positions_share.serve",
           "kv_live_rows_share.serve")
# (prompt, new tokens) sent one after the other, each alone on the server
REQUESTS = ((20, 9), (5, 6), (33, 4))


def hand_count(geometry, requests):
    """Positions and K,V rows of requests served one at a time: a prompt
    goes through in chunks of `prefill_chunk`, the last chunk emits the
    first token, and the rest come `decode_steps` a launch."""
    S, C, T = (geometry[k] for k in ("max_slots", "prefill_chunk",
                                     "decode_steps"))
    width = -(-geometry["max_seq_len"] // geometry["block_size"])
    table = S * width * geometry["block_size"] * T
    pre = {"launches": 0, "issued": 0, "useful": 0}
    dec = {"launches": 0, "issued": 0, "useful": 0, "live": 0, "table": 0}
    for prompt, new in requests:
        chunks = -(-prompt // C)
        pre["launches"] += chunks
        pre["issued"] += chunks * S * C
        pre["useful"] += prompt
        length, owed = prompt, new - 1
        while owed > 0:
            dec["launches"] += 1
            dec["issued"] += S * T
            dec["useful"] += min(T, owed)
            dec["live"] += sum(length + t + 1 for t in range(T))
            dec["table"] += table
            length, owed = length + T, owed - T
    return pre, dec


def quiet(pred):
    deadline = time.monotonic() + 10.0
    while pred._busy and time.monotonic() < deadline:
        time.sleep(0.001)
    assert not pred._busy


def ask(server, prompt, new):
    ids = (np.arange(prompt) * 7 % 250).astype(np.int64)
    got = [t for chunk in server.stream(ids, new, 120.0) for t in chunk]
    assert len(got) == new
    quiet(server.pred)      # the tick that served the last token has closed


@pytest.fixture(scope="module")
def traced_serve(tmp_path_factory):
    """Values of the three readers after a traced tiny serve, with the
    server closed and freed as the driver leaves it; the geometry."""
    from paddle_tpu.observability import utilization

    utilization._CLOSED.clear()     # other tests' servers, long closed
    cfg, mix = _tiny.tiny_cfg("gpt2-large"), _tiny.tiny_serve_mix()
    geometry = dict(mix["geometry"], decode_kernel="xla")
    server = program.Server(_tiny.family(), cfg, geometry, seed=26)
    try:
        server.wait_ready(300)
        ask(server, 12, 5)                      # before the session
        jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
        try:
            for prompt, new in REQUESTS:
                ask(server, prompt, new)
        finally:
            jax.profiler.stop_trace()
        ask(server, 12, 5)                      # after it
    finally:
        server.close()
    del server
    view = View(cfg=cfg, mix=mix, peaks=None, chips=1, records={},
                window_s=1.0, busy_s=0.5, events=[[]])
    values = {name: layer_reader(_tiny.ROOT, name)(view) for name in READERS}
    return values, counters.profiled(), geometry


def test_profiled_account_is_the_sessions_requests(traced_serve):
    _, acc, geometry = traced_serve
    pre, dec = hand_count(geometry, REQUESTS)
    got_pre = acc["programs"]["prefill_chunk"]
    got_dec = acc["programs"]["decode_step"]
    assert (got_pre["launches"], got_pre["issued_positions"],
            got_pre["useful_positions"]) == (pre["launches"], pre["issued"],
                                             pre["useful"])
    assert (got_dec["launches"], got_dec["issued_positions"],
            got_dec["useful_positions"], got_dec["live_rows"],
            got_dec["table_rows"]) == (dec["launches"], dec["issued"],
                                       dec["useful"], dec["live"],
                                       dec["table"])
    assert acc["launches"] == pre["launches"] + dec["launches"]


def test_pad_positions_share_is_the_hand_count(traced_serve):
    values, _, geometry = traced_serve
    pre, dec = hand_count(geometry, REQUESTS)
    issued = pre["issued"] + dec["issued"]
    useful = pre["useful"] + dec["useful"]
    assert values["pad_positions_share.serve"] == pytest.approx(
        100.0 * (1 - useful / issued))
    assert 0 < values["pad_positions_share.serve"] < 100


def test_kv_live_rows_share_is_the_hand_count(traced_serve):
    values, _, geometry = traced_serve
    _, dec = hand_count(geometry, REQUESTS)
    assert values["kv_live_rows_share.serve"] == pytest.approx(
        100.0 * dec["live"] / dec["table"])
    assert 0 < values["kv_live_rows_share.serve"] < 100


def test_host_gap_share_is_one_less_the_waits_share_of_the_wall(traced_serve):
    values, acc, _ = traced_serve
    assert values["host_gap_share.serve"] == pytest.approx(
        100.0 * (1 - acc["wait_s"] / acc["wall_s"]))
    assert 0 < values["host_gap_share.serve"] < 100
    assert acc["launch_wall_s"] == pytest.approx(
        acc["dispatch_s"] + acc["wait_s"], abs=3e-6)
    assert acc["launch_wall_s"] <= acc["wall_s"]


@pytest.mark.parametrize("name", READERS)
def test_reader_raises_where_no_ledger_holds_profiled_ticks(
        name, monkeypatch):
    from paddle_tpu.observability import utilization

    empty = utilization.UtilizationLedger(peak_flops=None)
    monkeypatch.setattr(utilization, "ledgers", lambda: [empty])
    with pytest.raises(LookupError, match="0 of the program's 1"):
        layer_reader(_tiny.ROOT, name)(None)


@pytest.mark.parametrize("name", READERS)
def test_reader_raises_on_several_and_without_the_counters(
        name, monkeypatch):
    from paddle_tpu.observability import utilization

    class Held:
        def snapshot(self):
            return {"profiled": {"ticks": 2}}

    monkeypatch.setattr(utilization, "ledgers", lambda: [Held(), Held()])
    with pytest.raises(LookupError, match="2 of the program's 2"):
        layer_reader(_tiny.ROOT, name)(None)
    # a program from before the ledgers came has nothing to read: an error
    # too, never a line that silently lacks the metric
    monkeypatch.delattr(utilization, "ledgers")
    with pytest.raises(AttributeError, match="ledgers"):
        layer_reader(_tiny.ROOT, name)(None)
