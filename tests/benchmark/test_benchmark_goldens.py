"""What the move behind the family seam must not move: weights from a seed,
whole and drawn in parts, the traffic and the counts are what the parent
harness (PR 27's tree) made, by digests recorded there (`goldens.json`).
XLA:CPU's last bit of a float depends on its optimisation level (and may on
the machine), so values recorded there are held to rounding; what is drawn
in parts is held against the whole tree of the same process bit for bit."""
import hashlib

import numpy as np
import pytest

import _tiny
from benchmarks.harness import program, traffic, weights

GOLD = _tiny.load_json("tests", "benchmark", "goldens.json")
GPT2 = _tiny.family()
SEEDS = (7, 2_800_000_011)
TINY = {"tiny-medium": _tiny.tiny_cfg, "tiny-large": _tiny.tiny_large_cfg}
KINDS = {"float32": {}, "served": {"round_to": "bfloat16",
                                   "out_dtype": "bfloat16"}}
REAL = {name: _tiny.load_json("benchmarks", "configs", f"{name}.json")
        for name in ("gpt2-medium", "gpt2-large")}


def sha(array):
    return hashlib.sha256(np.asarray(array).tobytes()).hexdigest()[:16]


def assert_is_golden(tree, gold):
    assert sorted(tree) == sorted(gold)
    for k, v in tree.items():
        v = np.asarray(v, np.float32)
        assert np.sum(v, dtype=np.float32) == pytest.approx(
            gold[k]["sum"], rel=1e-5, abs=1e-4), k
        assert v.ravel()[:4] == pytest.approx(gold[k]["head"], rel=1e-5,
                                              abs=1e-7), k


@pytest.mark.parametrize("kind", sorted(KINDS))
@pytest.mark.parametrize("seed", SEEDS)
@pytest.mark.parametrize("size", sorted(TINY))
def test_whole_tree_is_the_parent_harnesss(size, seed, kind):
    tree = weights.make_weights(GPT2, TINY[size](), seed, **KINDS[kind])
    assert_is_golden(tree, GOLD["weights"][f"{size}/{seed}/{kind}"])
    assert {str(v.dtype) for v in tree.values()} == {
        "bfloat16" if kind == "served" else "float32"}


# The seed is data to the one compiled program, so one seed does here; a
# program a leaf is a compile a leaf, so a sample of the leaves does: an
# embedding, a gain, a bias, a fused and a plain matrix, top and stacked.
@pytest.mark.parametrize("size,kind", [("tiny-large", "served")])
def test_a_leaf_drawn_alone_is_what_the_whole_tree_holds(size, kind):
    cfg, seed = TINY[size](), SEEDS[1]
    whole = weights.make_weights(GPT2, cfg, seed, **KINDS[kind])
    for name in ("wte", "ln_f_w", "ln1_w", "qkv_b", "down_w"):
        alone = weights.make_weights(GPT2, cfg, seed, only=[name],
                                     **KINDS[kind])
        assert list(alone) == [name] and sha(alone[name]) == sha(whole[name])


@pytest.mark.parametrize("size,kind", [("tiny-medium", "float32")])
def test_a_layer_drawn_alone_is_what_the_whole_tree_holds(size, kind):
    cfg, seed, model = TINY[size](), SEEDS[1], GPT2.model
    whole = weights.make_weights(GPT2, cfg, seed, **KINDS[kind])
    for index in range(len(model.layer_kinds(cfg))):
        entries = model.layer_leaves(cfg, index)
        layer = weights.make_weights(GPT2, cfg, seed,
                                     only=list(entries.values()),
                                     **KINDS[kind])
        assert set(layer) == set(entries.values())
        for (name, i), value in layer.items():
            assert np.array_equal(np.asarray(value),
                                  np.asarray(whole[name][i])), (name, i)
        assert {k: sha(v) for k, v in weights.pick(layer, entries).items()
                } == {k: sha(v) for k, v in
                      weights.pick(whole, entries).items()}
    # a leaf and two layers of another in one call
    mixed = weights.make_weights(GPT2, cfg, seed,
                                 only=[("qkv_w", 1), "wte", ("qkv_w", 0)],
                                 **KINDS[kind])
    assert list(mixed) == [("qkv_w", 1), ("qkv_w", 0), "wte"]
    assert sha(mixed["wte"]) == sha(whole["wte"])
    assert np.array_equal(np.asarray(mixed["qkv_w", 0]),
                          np.asarray(whole["qkv_w"][0]))


@pytest.mark.parametrize("name", sorted(REAL))
def test_real_shapes_are_the_parent_harnesss(name):
    leaves = GPT2.model.leaves(REAL[name])
    assert {k: list(v[0]) for k, v in leaves.items()} == GOLD["shapes"][name]
    for k, (_, mean, std) in leaves.items():
        assert std == 0.02 and mean == (1.0 if k in ("ln1_w", "ln2_w",
                                                      "ln_f_w") else 0.0)
    assert weights.image_bytes(GPT2, REAL[name]) == 4 * sum(
        int(np.prod(s)) for s in GOLD["shapes"][name].values())


@pytest.mark.slow
@pytest.mark.parametrize("name", sorted(REAL))
def test_real_size_weights_are_the_parent_harnesss(name):
    """Minutes on the CPU, gigabytes of host memory: run by hand."""
    model = GPT2.model
    for seed in SEEDS:
        for kind in sorted(KINDS):
            gold = GOLD["real"][f"{name}/{seed}/{kind}"]
            tree = weights.make_weights(GPT2, REAL[name], seed, **KINDS[kind])
            assert sorted(tree) == sorted(gold)
            for k, v in tree.items():
                assert np.sum(np.asarray(v, np.float32), dtype=np.float32
                              ) == pytest.approx(gold[k], rel=1e-4, abs=0.1)
            last = len(model.layer_kinds(REAL[name])) - 1
            part = weights.make_weights(
                GPT2, REAL[name], seed, **KINDS[kind],
                only=["wte", *model.layer_leaves(REAL[name], last).values()])
            assert sha(part["wte"]) == sha(tree["wte"])
            for (k, i), v in ((e, v) for e, v in part.items() if e != "wte"):
                assert np.array_equal(np.asarray(v), np.asarray(tree[k][i]))


class _Model:
    """Stands in for the program's model: a state_dict of settable leaves."""

    class Leaf:
        def __init__(self, value):
            self._value, self.shape = value, value.shape

    def __init__(self, by_key):
        self.state = {k: self.Leaf(v) for k, v in by_key.items()}

    def state_dict(self):
        return self.state


def test_the_load_goes_part_by_part_and_gives_the_whole_trees_values(
        monkeypatch):
    cfg, seed, serve = _tiny.tiny_large_cfg(), 2_800_000_011, True
    kind = "served"
    whole = weights.make_weights(GPT2, cfg, seed, **KINDS[kind])
    assert_is_golden(whole, GOLD["weights"][f"tiny-large/{seed}/{kind}"])
    want = {GPT2.program.state_key(k, i): np.asarray(whole[k][i])
            for k in GPT2.model.BLOCK_KINDS for i in range(cfg["n_layer"])}
    want.update({GPT2.program.state_key(k, None): np.asarray(whole[k])
                 for k in GPT2.model.TOP_KINDS})
    model = _Model({k: np.zeros_like(v) for k, v in want.items()})
    size = 2 if serve else 4
    largest = size * max(int(np.prod(s)) for s, _, _ in
                         GPT2.model.leaves(cfg).values())
    monkeypatch.setattr(program, "PART_BYTES", largest)
    calls, real = [], weights.make_weights

    def counted(family, cfg, seed, *, only=None, **kw):
        drawn = real(family, cfg, seed, only=only, **kw)
        calls.append(sum(v.nbytes for v in drawn.values()))
        return drawn
    monkeypatch.setattr(weights, "make_weights", counted)
    program.load_weights(GPT2, model, cfg, seed, serve=serve)
    assert len(calls) > 3 and max(calls) <= largest
    assert sum(calls) == size * weights.image_bytes(GPT2, cfg) // 4
    got = {k: np.asarray(leaf._value) for k, leaf in model.state.items()}
    assert set(got) == set(want)
    assert all(np.array_equal(got[k], want[k]) and got[k].dtype == want[k].dtype
               for k in want)
    # and the leaves a Trainer starts from are the float32 draws, in the
    # order the comparison lists them
    start = program.start_leaves(GPT2, cfg, seed)
    names = GPT2.model.leaf_names(cfg)
    assert len(start) == len(names)
    float32 = weights.make_weights(GPT2, cfg, seed)
    for value, (name, part, layer) in zip(start, names):
        leaf = float32[name] if layer is None else float32[name][layer]
        assert np.array_equal(np.asarray(value), np.asarray(
            GPT2.model.parts(name, leaf)[part])), (name, part, layer)


def test_a_model_with_other_leaves_than_the_benchmarks_is_refused():
    cfg = _tiny.tiny_cfg()
    keys = [GPT2.program.state_key(k, None) for k in GPT2.model.TOP_KINDS]
    keys += [GPT2.program.state_key(k, i) for k in GPT2.model.BLOCK_KINDS
             for i in range(cfg["n_layer"])]
    shapes = {key: value.shape for part in program.state_parts(GPT2, cfg, 1)
              for key, value in part.items()}
    short = _Model({k: np.zeros(shapes[k]) for k in keys[1:]})
    with pytest.raises(KeyError, match="none of the model's"):
        program.load_weights(GPT2, short, cfg, 1, serve=False)
    more = _Model({**{k: np.zeros(shapes[k]) for k in keys},
                   "gpt.extra.weight": np.zeros(3)})
    with pytest.raises(KeyError, match="did not make"):
        program.load_weights(GPT2, more, cfg, 1, serve=False)
    other = _Model({k: np.zeros(shapes[k] + (1,)) for k in keys})
    with pytest.raises(ValueError, match="against"):
        program.load_weights(GPT2, other, cfg, 1, serve=False)


@pytest.mark.parametrize("seed", SEEDS)
def test_traffic_is_the_parent_harnesss(seed):
    mix = _tiny.load_json("benchmarks", "traffic", "serve-chat.json")
    reqs = traffic.open_loop_requests(mix, REAL["gpt2-large"], seed, 30.0)
    h = hashlib.sha256()
    for r in reqs:
        h.update(r.prompt.tobytes())
        h.update(np.int64(r.max_new).tobytes())
    assert {"requests": len(reqs), "sha": h.hexdigest()[:16],
            "first_due": pytest.approx(reqs[0].due_s, abs=1e-9),
            "due_sum": pytest.approx(sum(r.due_s for r in reqs), abs=1e-9),
            "prompt_tokens": int(sum(len(r.prompt) for r in reqs)),
            "max_new": int(sum(r.max_new for r in reqs))} == GOLD[
                "traffic"][f"serve-chat/{seed}"]
    x, y = next(traffic.train_batches(
        _tiny.load_json("benchmarks", "traffic", "train-s1024.json"),
        REAL["gpt2-medium"], seed))
    assert hashlib.sha256(x.tobytes() + y.tobytes()).hexdigest()[:16] == GOLD[
        "traffic"][f"train-s1024/{seed}"]["sha"]


@pytest.mark.parametrize("name", sorted(REAL))
def test_counts_are_the_parent_harnesss(name):
    cfg, c = REAL[name], GPT2.counts
    assert {
        "train_flops_per_token_1024": c.train_flops_per_token(cfg, 1024),
        "block_matmul_flops_per_token": c.block_matmul_flops_per_token(cfg),
        "attention_flops_per_token_77": c.attention_flops_per_token(cfg, 77),
        "lm_head_flops_per_token": c.lm_head_flops_per_token(cfg),
        "flash_train_cost_8_1024": list(c.flash_train_cost(cfg, 8, 1024)),
        "kv_bytes_per_row": c.kv_bytes_per_row(cfg),
        "serve_token_flops_300_sampled": c.serve_token_flops(cfg, 300, True),
        "serve_token_flops_300_plain": c.serve_token_flops(cfg, 300, False),
        "prompt_flops_192": c.prompt_flops(cfg, 192)} == GOLD["counts"][name]
    # the two every family gives, in the same numbers
    assert c.prompt_work(cfg, 192)["model_flops"] == c.prompt_flops(cfg, 192)
    assert c.token_work(cfg, 300) == {
        "model_flops": c.serve_token_flops(cfg, 300, True),
        "attention_flops": cfg["n_layer"] * c.attention_flops_per_token(
            cfg, 300), "kv_rows": 300}
    assert c.prompt_work(cfg, 5)["attention_flops"] == sum(
        c.token_work(cfg, p)["attention_flops"] for p in range(1, 6))
    assert c.prompt_work(cfg, 5)["kv_rows"] == 5
