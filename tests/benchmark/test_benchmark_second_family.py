"""A second family that exists only as added files. `benchmarks/` is copied
to a temporary root; there a family directory, a configuration, a mix, a
limits file and the BENCHMARK.json entries of `second_family/` are ADDED,
for a block the program builds and the `gpt2` family does not describe
(rotary positions, RMSNorm, SwiGLU, an untied head; leaves named a layer
each). `load_job` and the serve driver run on that root, a sound run comes
out correct and an altered answer not, and no file that was there has
changed. A fixture: never an
entry of the real BENCHMARK.json."""
import hashlib
import json
import os
import shutil

import numpy as np
import pytest

import _tiny
from benchmarks import run as bench_run
from benchmarks.harness import (compare, lastline, program, serve_driver,
                                weights)
from benchmarks.harness.job import load_family

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "second_family")
CELL = "rope-swiglu-tiny-serve-tiny"
DEVICE = {"platform": "cpu", "kind": "cpu", "count": 1}


def tree_hashes(root):
    out = {}
    for folder, dirs, files in os.walk(root):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for name in files:
            path = os.path.join(folder, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """(root, hashes before): the copy with the second family added."""
    root = str(tmp_path_factory.mktemp("second_family_root"))
    shutil.copytree(_tiny.BENCH_DIR, os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(_tiny.ROOT, "BENCHMARK.json"), root)
    before = tree_hashes(root)
    shutil.copytree(os.path.join(FIXTURE, "benchmarks"),
                    os.path.join(root, "benchmarks"), dirs_exist_ok=True)
    with open(os.path.join(FIXTURE, "entries.json")) as f:
        entries = json.load(f)
    bench = _tiny.bench()
    for key, more in entries.items():
        bench[key] = bench[key] + more          # appended, nothing edited
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)
    return root, before


def test_adding_a_family_edits_no_file_that_was_there(added):
    root, before = added
    after = tree_hashes(root)
    new = sorted(set(after) - set(before))
    assert new == sorted(
        os.path.relpath(os.path.join(folder, name), FIXTURE)
        for folder, _, files in os.walk(os.path.join(FIXTURE, "benchmarks"))
        for name in files if not name.endswith(".pyc"))
    assert len(new) == 6 and set(before) <= set(after)
    changed = [p for p in before if after[p] != before[p]]
    assert changed == ["BENCHMARK.json"]       # appended to, see below
    old, now = _tiny.bench(), _tiny.load_json(os.path.join(root,
                                                           "BENCHMARK.json"))
    for key, value in old.items():
        assert now[key] == value or now[key][:len(value)] == value, key
    # the real benchmark knows nothing of the fixture
    assert "rope-swiglu" not in json.dumps(old)
    with pytest.raises(FileNotFoundError, match="no family 'rope-swiglu'"):
        load_family(_tiny.ROOT, "rope-swiglu")


def test_the_added_family_gives_every_piece_and_its_parts_are_the_whole(added):
    root, _ = added
    job = bench_run.load_job(CELL, 5, 1.0, 0, root=root)
    family, cfg = job.family, job.cfg
    assert family is load_family(root, "rope-swiglu")
    assert family.folder.startswith(root)
    whole = weights.make_weights(family, cfg, 5)
    assert sorted(whole) == sorted(family.model.leaves(cfg))
    parts = {}
    for part in program.state_parts(family, cfg, 5):
        parts.update(part)
    assert len(parts) == len(whole) == 3 + 6 * cfg["num_hidden_layers"]
    for name, value in whole.items():
        assert np.array_equal(np.asarray(value), np.asarray(
            parts[family.program.state_key(name, None)])), name
    assert lastline.expected_metrics(job.bench, CELL, trace=False) == {
        "setup_s": "s", "itl_p50_ms": "ms"}
    assert lastline.expected_metrics(job.bench, CELL, trace=True) == {
        "mfu.serve.fixture": "%"}


@pytest.fixture(scope="module")
def ran(added):
    """(job, outcome) of one run of the added cell on a sound server."""
    job = bench_run.load_job(CELL, 13, 1.0, 0, root=added[0])
    return job, serve_driver.run(job)


def test_the_added_cell_runs_and_is_correct(added, ran):
    job, outcome = ran
    outcome.memory_peak_bytes = outcome.memory_peak_bytes or 1
    line = bench_run.finish(job, outcome, DEVICE)
    assert line["correct"] is True, line["compared"]
    assert line["attempted"] >= 4 and line["failed"] == 0
    assert set(line["metrics"]) == {"setup_s", "itl_p50_ms"}
    root, before = added
    after = tree_hashes(root)
    assert all(after[p] == before[p] for p in before if p != "BENCHMARK.json")


def test_a_token_altered_in_the_added_cells_answers_is_not_correct(ran):
    """What a broken server would have sent (the broken servers themselves
    are any family's: test_benchmark_reference.py): the third token of every
    answer another one. The added family's reference has to read it."""
    job, outcome = ran
    answers = [(prompt, tokens[:2] + [(tokens[2] + 101) % job.cfg["vocab_size"]]
                + tokens[3:]) for prompt, tokens in outcome.records["answers"]]
    numbers = serve_driver.served_logit_gaps(
        job.family, job.cfg, job.seed, answers,
        job.mix["geometry"]["max_seq_len"])
    sound = outcome.numbers["served_logit_gap"]
    assert sound <= job.limits["served_logit_gap"] < numbers["served_logit_gap"]
    assert numbers["served_logit_gap"] > 10 * max(sound, 1e-3)
    correct, _ = compare.judge(dict(numbers, wrong_token_counts=0), job.limits)
    assert not correct
