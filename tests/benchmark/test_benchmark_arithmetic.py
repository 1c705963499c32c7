"""The benchmark's own arithmetic against numbers worked out by hand."""
import numpy as np
import pytest

from _tiny import ROOT, load_json
from benchmarks.harness import peaks, serve_driver, traffic
from benchmarks.harness.job import load_family

MEDIUM = load_json("benchmarks", "configs", "gpt2-medium.json")
LARGE = load_json("benchmarks", "configs", "gpt2-large.json")
GPT2 = load_family(ROOT, "gpt2")
flops = GPT2.counts


@pytest.mark.parametrize("cfg,seq,want,about", [
    # 3 * (L * (24 h^2 + 4 h (S + 1) / 2) + 2 h V)
    (MEDIUM, 1024, 3 * (24 * (24 * 1024 ** 2 + 2 * 1024 * 1025)
                        + 2 * 1024 * 50257), 2.27e9),
    (LARGE, 1024, 3 * (36 * (24 * 1280 ** 2 + 2 * 1280 * 1025)
                       + 2 * 1280 * 50257), 4.92e9),
])
def test_train_flops_per_token(cfg, seq, want, about):
    assert flops.train_flops_per_token(cfg, seq) == want
    assert want == pytest.approx(about, rel=2e-3)


def test_flash_cost_at_the_train_cells_shape():
    # B=8, 16 heads of 64, S=1024: one product is 2*S*S*D/2 = 67,108,864
    # operations a head; 7 products, 128 heads; 12 tensors of B*H*S*D bf16
    work, nbytes = flops.flash_train_cost(MEDIUM, 8, 1024)
    assert work == 7 * 67_108_864 * 128 == 60_129_542_144
    assert nbytes == 12 * 8 * 16 * 1024 * 64 * 2 == 201_326_592
    least, bound = peaks.roofline_seconds(work, nbytes, peaks.PEAKS["TPU v5 lite"])
    assert bound == "compute" and least == pytest.approx(work / 197e12)


@pytest.mark.parametrize("cfg,want", [(MEDIUM, 98_304), (LARGE, 184_320)])
def test_kv_bytes_per_row(cfg, want):
    assert flops.kv_bytes_per_row(cfg) == want


def test_serve_flops_add_up_to_the_prompts():
    plen = 5
    by_token = sum(flops.serve_token_flops(LARGE, p, sampled=(p == plen))
                   for p in range(1, plen + 1))
    assert flops.prompt_flops(LARGE, plen) == by_token


def test_unknown_device_kind_raises():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks on record"):
        peaks.peaks_for("TPU v9")


def test_traffic_is_the_same_from_the_same_seed_and_clipped():
    mix = load_json("benchmarks", "traffic", "serve-chat.json")
    seed = 3_000_000_019        # more than 32 signed bits hold
    a = traffic.open_loop_requests(mix, LARGE, seed, 30.0)
    b = traffic.open_loop_requests(mix, LARGE, seed, 30.0)
    assert len(a) == round(mix["rate_per_s"] * (30.0 + mix["preroll_seconds"]))
    assert sum(1 for r in a if r.due_s >= 0) >= round(mix["rate_per_s"] * 30.0) - 1
    assert all(x.due_s == y.due_s and x.max_new == y.max_new
               and np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    for r in a:
        assert 16 <= len(r.prompt) <= 768 and 1 <= r.max_new <= 256
        assert len(r.prompt) + r.max_new <= 1024
        assert -mix["preroll_seconds"] <= r.due_s < 30.0
        assert r.prompt.min() >= 0 and r.prompt.max() < LARGE["vocab_size"]


def test_every_seed_gets_the_same_sizes_and_gaps_in_another_order():
    mix = dict(load_json("benchmarks", "traffic", "serve-chat.json"),
               rate_per_s=4.0)
    assert "order_seed" not in mix      # the schedule is the seed's
    a, b = (traffic.open_loop_requests(mix, LARGE, s, 30.0) for s in (1, 2))
    for length in (lambda r: len(r.prompt), lambda r: r.max_new):
        assert sorted(map(length, a)) == sorted(map(length, b))
        assert list(map(length, a)) != list(map(length, b))
    every = np.sort(traffic.exponential_gaps(4.0, len(a)))
    for rs in (a, b):       # each schedule is the one set of gaps, permuted
        gaps = np.diff([r.due_s for r in rs])
        assert set(np.round(gaps, 9)) <= set(np.round(every, 9))
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert not np.array_equal(a[0].prompt[:8], b[0].prompt[:8])


def test_length_quantiles_hit_the_median_and_the_clip():
    spec = {"dist": "lognormal", "median": 192, "sigma": 0.7, "min": 16,
            "max": 768}
    xs = traffic.quantile_lengths(spec, 1001)
    assert xs[500] == 192 and xs.min() >= 16 and xs.max() == 768
    assert list(traffic.quantile_lengths({"dist": "fixed", "value": 512}, 3)
                ) == [512, 512, 512]
    gaps = traffic.exponential_gaps(4.0, 200)
    assert gaps.sum() == pytest.approx(50.0) and (gaps > 0).all()


def test_train_batches_are_fresh_rows_that_all_differ():
    mix = load_json("benchmarks", "traffic", "train-s1024.json")
    gen = traffic.train_batches(mix, MEDIUM, 2_147_483_659)
    (x0, y0), (x1, _) = next(gen), next(gen)
    assert x0.shape == y0.shape == (8, 1024) and x0.dtype == np.int64
    assert np.array_equal(x0[:, 1:], y0[:, :-1])        # labels: next token
    assert len({row.tobytes() for row in np.concatenate([x0, x1])}) == 16


def answer(due, flushes, error=None):
    a = serve_driver.Answer(
        traffic.Request(0, due, np.zeros(4, np.int64),
                        sum(n for _, n in flushes)),
        sent=due + 0.25, flushes=flushes, error=error, done=True)
    a.tokens = [0] * sum(n for _, n in flushes)
    return a


def test_open_loop_latency_counts_from_the_due_time():
    # due at 1.0 s, sent late at 1.25 s, first token at 1.5 s: ttft 500 ms
    clients = [answer(1.0, [(1.5, 1), (2.5, 4)]),
               answer(2.0, [(2.25, 1), (12.25, 10)]),      # ends outside
               answer(3.0, [], error=RuntimeError("shed"))]
    clients.append(answer(-1.0, [(0.5, 2), (11.0, 3)]))    # a lead-in request
    got, due, failed = serve_driver.request_metrics(clients, seconds=10.0)
    assert (due, failed) == (3, 1)
    # sorted ttft: 0.25, 0.5, 10.0 (the failure counts as the window)
    assert got["ttft_p95_ms"] == pytest.approx(1e3 * (0.5 + 0.9 * 9.5))
    assert got["ttft_p50_ms"] == pytest.approx(500.0)
    # tpot: 1.0/4 and 10.0/10 a request; 11 s of decoding over 14 tokens
    assert got["tpot_p95_ms"] == pytest.approx(1e3 * (0.25 + 0.95 * 0.75))
    assert got["tpot_p50_ms"] == pytest.approx(625.0)
    assert got["tpot_mean_ms"] == pytest.approx(1e3 * 11.0 / 14)
    # the gaps between arrivals at one client: 1.0 and 10.0
    assert got["itl_p50_ms"] == pytest.approx(5500.0)
    # tokens that reached a client inside the window: 5 + 1, and the
    # lead-in's 2
    assert got["serve_output_tokens_per_s"] == pytest.approx(0.8)


def test_pool_in_use_follows_the_request_records():
    # one request of 4 + 5 tokens from 0.25 s to 2.5 s, pages of 4 rows
    c = answer(0.0, [(1.5, 1), (2.5, 4)])
    got = serve_driver.pool_occupancy([c], {"block_size": 4}, 4.0, step=1.0)
    # t = 0: not sent; t = 1: 4 rows, 1 page; t = 2: 5 rows, 2 pages; t = 3: gone
    assert got == {"slots_mean": 0.5, "slots_peak": 1, "pages_mean": 0.75,
                   "pages_peak": 2}


def test_traced_work_counts_rows_and_tokens_inside_the_window():
    c = answer(0.0, [(1.0, 1), (2.0, 2), (9.0, 2)])
    c.sent = 0.0
    work = serve_driver.traced_work(GPT2, LARGE, [c], 0.5, 3.0)
    # half of the 4-token prompt's forward, and output tokens 1 and 2 at
    # contexts 5 and 6
    assert work["prompt_tokens"] == pytest.approx(2.0)
    assert work["output_tokens"] == 2
    assert work["kv_rows"] == pytest.approx(0.5 * 4 + 5 + 6)
    decode = (flops.serve_token_flops(LARGE, 5, True)
              + flops.serve_token_flops(LARGE, 6, True))
    assert work["model_flops"] == pytest.approx(
        0.5 * flops.prompt_flops(LARGE, 4) + decode)
    # the prompt's span [0, 1] straddles the window's start: all of it may
    # lie inside, or none
    assert work["model_flops_low"] == pytest.approx(decode)
    assert work["model_flops_high"] == pytest.approx(
        flops.prompt_flops(LARGE, 4) + decode)
    assert (work["kv_rows_low"], work["kv_rows_high"]) == (11, 15)


def test_percentile_is_linear_between_ranks():
    assert traffic.percentile([1, 2, 3, 4, 5], 50) == 3
    assert traffic.percentile(range(101), 95) == 95
    with pytest.raises(ValueError):
        traffic.percentile([], 95)
