"""The `dots3_note` family and its cell as the harness finds them: the
files, the family contract at the real size (shapes only), the counts
against the hand arithmetic of the cut, the two readers of the new counters,
and one tiny run of the cell's own driver on the CPU that comes out correct,
with the fp8 control and an altered token above the limit."""
import json
import math

import numpy as np
import pytest

import _tiny
import _tiny_dots3 as T
from benchmarks import run as bench_run
from benchmarks.harness import compare, lastline, serve_driver
from benchmarks.harness.job import layer_reader

REAL = _tiny.load_json("benchmarks", "configs", "dots3-note-prev-ep8.json")
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}
# the tiny model in bfloat16 on the CPU: sound runs read 0.0-0.05 here (the
# cell's own limit and the chip's readings are in benchmarks/limits/)
TINY_LIMITS = {"served_logit_gap": 0.12, "wrong_token_counts": 0}
NEW = ("moe_useful_rows_share.serve", "attn_needed_rows_share.serve")


def test_the_cell_finds_its_configuration_mix_limits_and_readers():
    job = bench_run.load_job(T.CELL, 3_000_000_011, 30.0, 1)
    assert job.family.name == "dots3_note" == job.cfg["model_type"]
    assert job.mix["kind"] == "open_loop" and job.chips == 1
    g = job.mix["geometry"]
    assert g["max_seq_len"] == job.mix["max_total"] == 16384
    assert g["num_blocks"] * g["block_size"] == g["max_slots"] * 16384
    assert job.mix["prompt"]["max"] + job.mix["output"]["max"] <= 16384
    assert abs(job.mix["rate_per_s"] / job.mix["knee_per_s"] - 0.8) < 0.011
    traced = lastline.expected_metrics(job.bench, T.CELL, trace=True)
    assert set(NEW) < set(traced) and "mfu.serve" in traced
    assert "paged_attn_roofline.serve" not in traced    # GPT-2's kernel
    assert lastline.expected_metrics(job.bench, T.CELL, trace=False) == {
        "setup_s": "s", "itl_p95_ms": "ms"}
    assert set(job.limits) == {"served_logit_gap", "wrong_token_counts"}
    # the accepted entries stand first and as they were
    assert [c["name"] for c in job.bench["configs"]][:2] == [
        "gpt2-medium", "gpt2-large"]
    assert [w["name"] for w in job.bench["workloads"]][-1] == T.CELL


def test_every_published_key_is_in_the_file_and_the_cut_is_named():
    import os

    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "dots3-note-prev")
    assert REAL["source"] == row["source_url"]
    differ = [k for k, v in row["config"].items() if REAL.get(k) != v]
    assert sorted(differ) == sorted(REAL["reduced"]) == [
        "layer_types", "n_routed_experts", "num_hidden_layers", "vocab_size"]
    assert REAL["layer_types"] == row["config"]["layer_types"][:5]
    assert (REAL["published_n_routed_experts"], REAL["ep_size"],
            REAL["published_vocab_size"]) == (256, 8, 152064)
    assert REAL["n_routed_experts"] * REAL["ep_size"] == 256
    assert REAL["vocab_size"] * 8 == 152064


def test_the_cuts_arithmetic_is_the_leaves():
    """Parameters by layer, as the issue reckons the cut (millions)."""
    model = T.family().model
    leaves = model.leaves(REAL)

    def millions(prefix, names=None):
        return sum(math.prod(s) for k, (s, _, _) in leaves.items()
                   if k.startswith(prefix)
                   and (names is None or k.split(".")[1] in names)) / 1e6
    attention = ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o",
                 "gate", "idx_q", "idx_k", "idx_k_norm_w", "idx_k_norm_b",
                 "idx_w")
    assert millions("L0.", attention) == pytest.approx(144.05, abs=0.01)
    assert millions("L2.", attention) == pytest.approx(90.83, abs=0.01)
    assert millions("L0.") == pytest.approx(356.4, abs=0.05)
    assert millions("L1.") == pytest.approx(923.9, abs=0.05)
    assert millions("L4.") == pytest.approx(870.7, abs=0.05)
    assert sum(math.prod(s) for s, _, _ in leaves.values()) / 1e6 == \
        pytest.approx(4087, abs=1)
    assert model.layer_kinds(REAL) == [
        "dense_full", "moe_full", "moe_window", "moe_window", "moe_window"]
    counts = T.family().counts
    assert counts.kv_bytes_per_row(REAL) == 2 * (2 * (576 + 128) + 3 * 1088)
    # a token's operations: twice the parameters it meets (one routed expert
    # of 32 held expected a layer, the shared one, no absent expert), the
    # attention over what it may attend, the indexer over all its context
    token = counts.token_work(REAL, 8192)
    met = (2 * 144.05e6 + 3 * 90.83e6 + 212.34e6
           + 4 * (2 * 23.59e6 + 1.31e6) + 5 * 2 * 5120 / 1e6 * 0 + 97.32e6)
    attend = (2 * 2 * 128 * 320 * 2048 + 3 * 2 * 64 * 384 * 513
              + 2 * 2 * 64 * 129 * 8192)
    assert token["attention_flops"] == attend
    assert token["model_flops"] == pytest.approx(2 * met + attend, rel=2e-3)
    assert token["kv_rows"] == 2048
    assert counts.token_work(REAL, 100)["kv_rows"] == 100
    prompt = counts.prompt_work(REAL, 300)
    assert prompt["model_flops"] == pytest.approx(sum(
        counts.token_work(REAL, c)["model_flops"] for c in range(1, 301))
        - 299 * counts.lm_head_flops_per_token(REAL), rel=1e-9)


def test_the_new_readers_read_the_counters_and_are_silent_without_them(
        monkeypatch):
    from benchmarks.harness import counters

    account = {"ticks": 3, "programs": {
        "prefill_chunk": {"launches": 2, "moe_rows_issued": 400,
                          "moe_rows_useful": 100,
                          "moe_assignments_elsewhere": 700,
                          "moe_expert_tokens": [60, 40],
                          "attn_rows_needed": 50, "attn_rows_read": 1000,
                          "indexer_rows_scored": 9},
        "decode_step": {"launches": 1, "moe_rows_issued": 100,
                        "moe_rows_useful": 25, "moe_expert_tokens": [5, 20],
                        "attn_rows_needed": 150, "attn_rows_read": 1000}}}
    monkeypatch.setattr(counters, "profiled", lambda: account)
    moe, attn = (layer_reader(_tiny.ROOT, name) for name in NEW)
    assert moe(None) == pytest.approx(25.0)
    assert attn(None) == pytest.approx(10.0)
    # a program that lacks the counters (the parent's): nothing, no raise
    bare = {"ticks": 3, "programs": {"decode_step": {"launches": 1}}}
    monkeypatch.setattr(counters, "profiled", lambda: bare)
    assert moe(None) is None and attn(None) is None


@pytest.fixture(scope="module")
def ran():
    """(job, outcome) of one run of the cell's driver at the tiny size."""
    cell = next(w for w in _tiny.bench()["workloads"] if w["name"] == T.CELL)
    job = _tiny.make_job(cell, T.tiny_cfg(), T.tiny_mix(), TINY_LIMITS,
                         seed=3_000_000_013, seconds=1.0)
    return job, serve_driver.run(job)


def test_the_tiny_cell_runs_and_is_correct(ran):
    job, outcome = ran
    outcome.memory_peak_bytes = outcome.memory_peak_bytes or 1
    line = bench_run.finish(job, outcome, DEVICE)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "itl_p95_ms"}


def test_the_control_and_an_altered_token_are_not_correct(ran):
    job, outcome = ran
    answers = outcome.records["answers"]
    both = serve_driver.served_logit_gaps(
        job.family, job.cfg, job.seed, answers,
        job.mix["geometry"]["max_seq_len"], control=True)
    assert both["served_logit_gap"] == outcome.numbers["served_logit_gap"]
    assert both["control_logit_gap"] > job.limits["served_logit_gap"]
    altered = [(p, t[:2] + [(t[2] + 5) % job.cfg["vocab_size"]] + t[3:])
               for p, t in answers]
    numbers = serve_driver.served_logit_gaps(
        job.family, job.cfg, job.seed, altered,
        job.mix["geometry"]["max_seq_len"])
    assert not compare.judge(dict(numbers, wrong_token_counts=0),
                             job.limits)[0]


@pytest.fixture(scope="module")
def both_routes(ran):
    """The serve check's two routes over one served answer (a fixture: the
    suite's budget guard times a test's call, and this is two forwards)."""
    from benchmarks.harness import reference

    job, outcome = ran
    prompt, tokens = outcome.records["answers"][0]
    ids = np.zeros((1, 64), np.int64)
    ids[0, :len(prompt)] = prompt
    ids[0, len(prompt):len(prompt) + len(tokens)] = tokens
    rows = [(ids, slice(len(prompt) - 1, len(prompt) - 1 + len(tokens)))]
    whole = reference.ServeCheck(job.family, job.cfg, job.seed, 64)
    assert whole.whole_image_fits()
    tight = reference.ServeCheck(job.family, job.cfg, job.seed, 64,
                                 hbm_bytes=1)
    assert not tight.whole_image_fits()
    return whole.gaps(rows)[0], tight.gaps(rows)[0]


def test_the_layer_at_a_time_route_agrees_with_the_whole_image(ran,
                                                               both_routes):
    """The cell's reference runs a layer at a time on the chip (the float32
    image of 8.2 GB of bfloat16 does not fit): both routes give one
    answer."""
    from benchmarks.harness import reference

    a, b = both_routes
    assert np.allclose(a["served_gap"], b["served_gap"], atol=2e-5)
    real = reference.ServeCheck(ran[0].family, REAL, 1, 16384,
                                hbm_bytes=16 * 2 ** 30)
    assert not real.whole_image_fits()
