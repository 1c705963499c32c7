"""BENCHMARK.json against the contract's letter, and against the files the
harness finds by name."""
import os
import re

import pytest

from _tiny import BENCH_DIR, ROOT, bench, load_json
from benchmarks.harness import traffic
from benchmarks.harness.job import Family, layer_reader, load_family

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "proj", "n_embd",
               "n_inner", "head_dim")


def test_top_level_keys_and_limits():
    b = bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(b["run_seconds"], int) and 1 <= b["run_seconds"] <= 51
    assert 1 <= len(b["paths"]) <= 16 and all(PATH.match(p) for p in b["paths"])
    assert 1 <= len(b["command"]) <= 32
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    cells = len(b["workloads"])
    # a full check with all 24 cells must fit the driver's 43,200 s
    assert (2 + 14 * 24) * (b["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    four = sum(1 for w in b["workloads"] if w["chips"] == 4)
    assert four <= max(1, cells // 4)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024


def test_names_units_and_entries():
    b = bench()
    names = []
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["source"].startswith("https://") and len(c["source"]) <= 200
        assert len(c["reduced"]) <= 16
        assert not any(w in k for k in c["reduced"] for w in WIDTH_WORDS)
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
        names.append(c["name"])
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        names.append(w["name"])
    for m in b["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        names.append(m["name"])
    for m in b["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in SOURCES and "\n" not in m["layer"]
        names.append(m["name"])
    for m in b["end_to_end"] + b["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    assert len({(w["config"], w["traffic"]) for w in b["workloads"]}) == len(
        b["workloads"])
    assert "setup_s" in {m["name"] for m in b["end_to_end"]}


def test_every_cell_finds_its_files():
    b = bench()
    configs = {c["name"]: c for c in b["configs"]}
    used = set()
    for w in b["workloads"]:
        c = configs[w["config"]]
        used.add(c["name"])
        assert c["file"].startswith(tuple(p + "/" for p in b["paths"]))
        cfg = load_json(c["file"])
        assert cfg["reduced"] == c["reduced"] and cfg["source"] == c["source"]
        mix = load_json("benchmarks", "traffic", f"{w['traffic']}.json")
        assert mix["kind"] in traffic.KINDS
        limits = load_json("benchmarks", "limits", f"{w['name']}.json")
        assert limits["numbers"] and all(
            "limit" in v for v in limits["numbers"].values())
    assert used == set(configs), "a configuration without a cell"
    assert len({c["file"] for c in b["configs"]}) == len(b["configs"])
    for m in b["per_layer"]:
        assert callable(layer_reader(ROOT, m["name"])), m["name"]


def test_every_configuration_finds_its_family_and_every_piece_of_it():
    import inspect

    import jax

    b = bench()
    for c in b["configs"]:
        cfg = load_json(c["file"])
        family = load_family(ROOT, cfg["model_type"])
        assert family is load_family(ROOT, cfg["model_type"])   # one a process
        assert os.path.samefile(family.folder, os.path.join(
            BENCH_DIR, "families", cfg["model_type"]))
        for half, names in ((family.model, Family.MODEL),
                            (family.counts, Family.COUNTS),
                            (family.program, Family.PROGRAM)):
            assert os.path.dirname(half.__file__) == family.folder
            assert all(callable(getattr(half, n)) for n in names)
        # only the program's half may import the program, and none the harness
        for half in (family.model, family.counts):
            source = inspect.getsource(half)
            assert "paddle_tpu" not in source and "benchmarks" not in source
        model = family.model
        leaves = model.leaves(cfg)
        kinds = model.layer_kinds(cfg)
        pieces = [model.embed_leaves(cfg), model.head_leaves(cfg)] + [
            model.layer_leaves(cfg, i) for i in range(len(kinds))]
        named = {e if isinstance(e, str) else e[0]
                 for piece in pieces for e in piece.values()}
        assert named == set(leaves)         # every leaf is some piece's
        for shape, mean, std in leaves.values():
            assert all(isinstance(n, int) and n > 0 for n in shape) and std > 0
        # every compared leaf is a leaf, and has a place in the program
        for name, part, layer in model.leaf_names(cfg):
            assert name in leaves and isinstance(
                family.program.state_key(name, layer), str)
        assert model.forward_bytes(cfg, 1024) > 0
        # the three pieces fit together, by shapes alone
        from benchmarks.harness import reference
        tree = {k: jax.ShapeDtypeStruct(s, "float32")
                for k, (s, _, _) in leaves.items()}
        ids = jax.numpy.zeros((1, 8), "int32")
        logits = jax.eval_shape(
            lambda t: reference.logits_fn(family, cfg, t, ids), tree)
        assert logits.shape == (1, 8, cfg["vocab_size"])
        counts = family.counts
        for work in (counts.prompt_work(cfg, 7), counts.token_work(cfg, 7)):
            assert set(work) == {"model_flops", "attention_flops", "kv_rows"}
            assert 0 < work["attention_flops"] < work["model_flops"]
        assert counts.kv_bytes_per_row(cfg) > 0
        assert counts.train_flops_per_token(cfg, 1024) > 0


def test_a_family_that_lacks_a_piece_or_a_directory_is_refused(tmp_path):
    import shutil

    with pytest.raises(FileNotFoundError, match="no family 'mamba9'"):
        Family(ROOT, "mamba9")
    there = tmp_path / "benchmarks" / "families" / "half-a-family"
    shutil.copytree(os.path.join(BENCH_DIR, "families", "gpt2"), there)
    text = (there / "counts.py").read_text().replace("def token_work",
                                                     "def token_work_")
    (there / "counts.py").write_text(text)
    with pytest.raises(AttributeError, match=r"lacks \['token_work'\]"):
        Family(str(tmp_path), "half-a-family")


def test_every_layer_metric_moves_a_metric_its_cells_report():
    b = bench()
    cells = {w["name"] for w in b["workloads"]}

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    end = {m["name"]: m for m in b["end_to_end"]}
    for m in b["per_layer"]:
        assert m["moves"] in end, m
        assert cells_of(m) <= cells
        assert cells_of(m) <= cells_of(end[m["moves"]]), m["name"]
    for cell in cells:
        e2e = [m["name"] for m in b["end_to_end"] if cell in cells_of(m)]
        assert "setup_s" in e2e and len(e2e) >= 2, cell
        assert any(cell in cells_of(m) for m in b["per_layer"]), cell
    # beside each kernel's roofline, the whole step's share of the peak
    for m in b["per_layer"]:
        if "roofline" in m["name"]:
            assert any("mfu" in o["name"] and o["moves"] == m["moves"]
                       and cells_of(m) <= cells_of(o) for o in b["per_layer"])


def test_files_under_paths_are_named_from_the_allowed_characters():
    for path in bench()["paths"]:
        for folder, dirs, files in os.walk(os.path.join(ROOT, path)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(folder, f), ROOT)
                assert PATH.match(rel), rel


@pytest.mark.parametrize("name", ["run.py", "sweep.py"])
def test_no_test_or_entry_describes_a_topology_or_sets_libtpu_env(name):
    text = open(os.path.join(BENCH_DIR, name)).read()
    assert "ALLOW_MULTIPLE_LIBTPU_LOAD" not in text
    assert "get_topology_desc" not in text
