"""The dots3-note block against its plain reference
(`benchmarks/families/dots3_note/model.py`) at a tiny size on the CPU,
seeded weights, logits and never sampled tokens: the whole model with all
three kinds of layer, the share of experts and of the vocabulary tied to the
uncut model, the indexer's selection in both forms, and a mechanism taken
out of the PROGRAM being caught."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import _tiny_dots3 as T  # noqa: E402

import paddle_tpu as paddle  # noqa: E402
from benchmarks.harness import reference, weights  # noqa: E402
from paddle_tpu.models import dots3 as D  # noqa: E402

# context 5 times the tiny index_topk (6) and 8 times the tiny window (5)
SEQ = 40
TOL = 2e-4      # float32 both sides, sums in another order


@pytest.fixture(scope="module")
def tiny():
    cfg = T.tiny_cfg()
    model, tree = T.built(cfg, 3)
    ids = T.ids(0, 2, SEQ)
    ref = np.asarray(jax.jit(lambda i: reference.logits_fn(
        T.family(), cfg, tree, i))(jnp.asarray(ids)))
    return cfg, model, tree, ids, ref


def program_logits(model, ids):
    """One compiled program, as the step programs are (a new one a call:
    a fault patched in must be traced)."""
    return np.asarray(jax.jit(lambda i: model(paddle.Tensor(i))._value)(
        jnp.asarray(ids)))


def test_whole_model_logits_are_the_references(tiny):
    cfg, model, _, ids, ref = tiny
    kinds = T.family().model.layer_kinds(cfg)
    assert kinds == ["dense_full", "moe_full", "moe_window"]
    assert SEQ >= 5 * cfg["index_topk"] and SEQ >= 8 * cfg["sliding_window_size"]
    got = program_logits(model, ids)
    assert np.abs(got - ref).max() < TOL * np.abs(ref).max()


def _no_gate(monkeypatch):
    monkeypatch.setattr(jax.nn, "sigmoid", lambda x: jnp.ones_like(x))


def _no_rescale(monkeypatch):
    monkeypatch.setattr(D.math, "sqrt", lambda x: 1.0 if x > 1.5 else x ** 0.5)


def _bias_weighs(monkeypatch):
    from paddle_tpu.incubate.distributed.models.moe import held_experts as H

    def route(y, router, bias, top_k, *, norm_topk=True, scale=1.0):
        z = jax.nn.sigmoid(jnp.dot(y, router)) + bias     # weighed with b
        w, chosen = jax.lax.top_k(z, top_k)
        return chosen.astype(jnp.int32), w / jnp.sum(w, -1, keepdims=True)
    monkeypatch.setattr(H, "route_sigmoid", route)


def _no_selection(monkeypatch):
    monkeypatch.setattr(D, "kth_largest_mask",
                        lambda score, k: jnp.ones(score.shape, bool))


def _window_one_short(monkeypatch):
    real = D.Dots3Attention.selected

    def selected(self, q_index, w_index, k_index, pos, key_pos):
        allowed = real(self, q_index, w_index, k_index, pos, key_pos)
        if self.full:
            return allowed
        return allowed & (key_pos[None, :] > pos[:, None]
                          - (self.config.sliding_window_size - 1))
    monkeypatch.setattr(D.Dots3Attention, "selected", selected)


@pytest.mark.parametrize("fault", [_no_gate, _no_rescale, _bias_weighs,
                                   _no_selection, _window_one_short],
                         ids=lambda f: f.__name__.strip("_"))
def test_a_mechanism_taken_out_of_the_program_is_caught(tiny, monkeypatch,
                                                        fault):
    """The head-wise gate, the low-rank rescale, the bias that chooses and
    does not weigh, the indexer's selection, the window's edge: without any
    one of them the program's logits leave the reference's by far more than
    the tolerance of the test above."""
    cfg, model, _, ids, ref = tiny
    fault(monkeypatch)
    got = program_logits(model, ids[:1])
    assert np.abs(got - ref[:1]).max() > 50 * TOL * np.abs(ref).max()


def test_the_shares_add_up_to_the_uncut_expert_layer():
    """What all `ep_size` shares give for a layer, with what every chip
    computes alike (the shared expert) counted once, is what the uncut
    reference gives: share r holds experts [4r, 4r + 4) of 8."""
    fam, uncut = T.family(), T.tiny_cfg()
    model_of = fam.model
    tree = weights.make_weights(fam, uncut, 5)
    p = weights.pick(tree, model_of.layer_leaves(uncut, 1))
    mm = reference.product(False)
    y = jnp.asarray(np.random.default_rng(1).standard_normal((24, 64)),
                    jnp.float32)
    whole, alike = jax.jit(lambda p: model_of.feed_forward(
        uncut, "moe_full", p, y, mm))(p)
    parts = []
    for rank in range(2):
        share = T.tiny_cfg(n_routed_experts=4, published_n_routed_experts=8,
                           ep_size=2, ep_rank=rank)
        held = dict(p, experts_gate_up=p["experts_gate_up"][4 * rank:][:4],
                    experts_down=p["experts_down"][4 * rank:][:4])
        routed, same = jax.jit(lambda p: model_of.feed_forward(
            share, "moe_full", p, y, mm))(held)
        assert np.allclose(same, alike)
        parts.append(np.asarray(routed))
        # and the program's share is the reference's share
        blk = D.Dots3Block(D.Dots3Config(**dict(share, dtype="float32")), 1)
        for name, value in held.items():
            where = blk.experts if name in ("router", "router_bias",
                                            "experts_gate_up",
                                            "experts_down") else blk
            if hasattr(where, name):
                getattr(where, name)._value = value
        got, _, counts = jax.jit(blk.feed_forward)(y)
        assert np.abs(np.asarray(got) - parts[-1]).max() < 1e-4
        assert int(counts["expert_tokens"].sum()
                   + counts["elsewhere"]) == 24 * 2
    assert np.abs(sum(parts) - np.asarray(whole)).max() < 1e-4
    assert np.abs(np.asarray(whole)).max() > 0.01


def test_the_expert_walk_is_the_dense_product_masked():
    """The grouped walk against every held expert on every token, weighed by
    the routing: an oracle of another form than the walk's."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        held_experts_ffn, route_sigmoid)

    rng = np.random.default_rng(2)
    y = jnp.asarray(rng.standard_normal((50, 16)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((16, 12)), jnp.float32)
    bias = jnp.asarray(0.3 * rng.standard_normal(12), jnp.float32)
    gate_up = jnp.asarray(0.3 * rng.standard_normal((4, 16, 20)), jnp.float32)
    down = jnp.asarray(0.3 * rng.standard_normal((4, 10, 16)), jnp.float32)
    chosen, w = route_sigmoid(y, router, bias, 3)
    valid = jnp.asarray(rng.random(50) < 0.8)
    out, counts = jax.jit(lambda *a: held_experts_ffn(
        *a, first=4, valid=valid, tile=8))(y, chosen, w, gate_up, down)
    dense = 0
    for e in range(4):
        g, u = jnp.split(y @ gate_up[e], 2, axis=-1)
        weight = jnp.sum(jnp.where(chosen == 4 + e, w, 0.0), -1) * valid
        dense = dense + ((jax.nn.silu(g) * u) @ down[e]) * weight[:, None]
    assert np.abs(np.asarray(out) - np.asarray(dense)).max() < 1e-5
    here = (np.asarray(chosen) >= 4) & (np.asarray(chosen) < 8) \
        & np.asarray(valid)[:, None]
    assert counts["expert_tokens"].tolist() == [
        int((here & (np.asarray(chosen) == 4 + e)).sum()) for e in range(4)]
    assert int(counts["rows_issued"]) == 8 * sum(
        -(-n // 8) for n in counts["expert_tokens"].tolist())
    # the walk follows the load: three tokens walk a tile an expert that got
    # one, and an expert nobody chose costs nothing
    few, walked = jax.jit(lambda *a: held_experts_ffn(
        *a, first=4, valid=valid & (jnp.arange(50) < 3), tile=8))(
            y, chosen, w, gate_up, down)
    got = walked["expert_tokens"].tolist()
    assert 0 in got and int(walked["rows_issued"]) == 8 * sum(
        n > 0 for n in got)
    assert np.abs(np.asarray(few)[:3] - np.asarray(dense)[:3]).max() < 1e-5
    assert not np.asarray(few)[3:].any()
    # choosing by z + b, weighing by z: the weights are the chosen scores'
    z = np.asarray(jax.nn.sigmoid(y @ router))
    pick = np.take_along_axis(z, np.asarray(chosen), -1)
    assert np.allclose(np.asarray(w), pick / pick.sum(-1, keepdims=True),
                       atol=1e-6)


@pytest.mark.parametrize("rows, top_k, published, tile", [
    (16, 8, 256, 16),       # a decode step: half an assignment an expert
    (1024, 8, 256, 64),     # two chunks of 512: 32 an expert
    (2048, 8, 256, 128),    # two chunks of 1,024: 64 an expert
    (65536, 8, 256, 256),   # never over 256 rows
    (24, 2, 8, 16)])
def test_a_tile_is_twice_an_experts_expected_share(rows, top_k, published,
                                                   tile):
    """The tile comes from the row count alone: a power of two from 16 to
    256 that an expert's whole expected load fits twice."""
    from paddle_tpu.incubate.distributed.models.moe.held_experts import (
        tile_for)
    assert tile_for(rows, top_k, published) == tile


def test_the_sliced_heads_logits_are_the_uncut_heads_rows(tiny):
    """A slice of the vocabulary is a smaller vocabulary: the head of the
    share gives the uncut head's logits of the ids it holds."""
    cfg, _, tree, _, _ = tiny
    fam = T.family()
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, 5, 64)),
                    jnp.float32)
    mm = reference.product(False)
    p = weights.pick(tree, fam.model.head_leaves(cfg))
    whole = fam.model.head(cfg, p, x, mm)
    cut = dict(cfg, vocab_size=24)
    held = dict(p, lm_head=p["lm_head"][:, 24:48])
    assert np.allclose(fam.model.head(cut, held, x, mm), whole[..., 24:48],
                       atol=1e-6)


def test_the_selected_set_is_the_exact_top_k_and_both_forms_agree(tiny):
    """The program's selection (the bits of the k-th largest, no sort) is
    the reference's exact top-k by sorting, in float32; and one query's
    output through the gather form (a decode step) is its output through
    the mask form (a chunk)."""
    cfg, model, tree, ids, _ = tiny
    score = jnp.asarray(np.random.default_rng(6).standard_normal((9, 50)),
                        jnp.float32)
    score = jnp.where(jnp.arange(50)[None] <= 5 * jnp.arange(9)[:, None] + 3,
                      score, -jnp.inf)
    mask = np.asarray(D.kth_largest_mask(score, 6))
    order = np.argsort(-np.asarray(score), axis=-1)[:, :6]
    want = np.zeros_like(mask)
    np.put_along_axis(want, order, True, -1)
    assert (mask & np.isfinite(score) == want & np.isfinite(score)).all()
    assert D.kth_largest_mask(score[:, :5], 6).all()
    # the reference's own selection of a whole row, and the program's
    fam = T.family().model
    p = weights.pick(tree, fam.layer_leaves(cfg, 1))
    attn = model.model.layers[1].attn
    u = jnp.asarray(np.random.default_rng(7).standard_normal((1, SEQ, 64)),
                    jnp.float32)
    pos = jnp.arange(SEQ, dtype=jnp.int32)[None]
    mm = reference.product(False)

    @jax.jit
    def reference_selection(p, u):
        c_q = fam._rms_norm(mm(u[0], p["q_a"]), p["q_a_norm"],
                            cfg["rms_norm_eps"]) * (64 / 24) ** 0.5
        return fam.allowed_keys(cfg, True, fam.index_scores(
            cfg, p, u[0], c_q, mm), 0, SEQ, SEQ)

    @jax.jit
    def program_selection(u):
        q_i, w_i, k_i = attn._index(u, pos)
        return attn.selected(q_i[0], w_i[0], k_i[0], pos[0], pos[0])
    ref_sel, got_sel = reference_selection(p, u), program_selection(u)
    assert (np.asarray(got_sel) == np.asarray(ref_sel)).all()
    assert np.asarray(ref_sel).sum(-1).max() == cfg["index_topk"]


def test_the_real_size_model_is_built_without_parameter_bytes():
    """4.09 G parameters as abstract leaves: shapes and dtypes, no array."""
    import _tiny

    cfg = _tiny.load_json("benchmarks", "configs", "dots3-note-prev-ep8.json")
    model = T.family().program.build_model(cfg)
    state = model.state_dict()
    assert all(isinstance(t._value, jax.ShapeDtypeStruct)
               for t in state.values())
    assert {str(t.dtype) for t in state.values()} == {"bfloat16"}
    count = sum(int(np.prod(t.shape)) for t in state.values())
    assert 4.08e9 < count < 4.10e9
    leaves = T.family().model.leaves(cfg)
    assert count == sum(int(np.prod(s)) for s, _, _ in leaves.values())
    for name, (shape, _, _) in leaves.items():
        key = T.family().program.state_key(name, None)
        assert tuple(state[key].shape) == tuple(shape), name
    # outside the guard a parameter is an array again
    assert not isinstance(paddle.nn.Linear(2, 2).weight._value,
                          jax.ShapeDtypeStruct)
    spec = model._decode_cache_spec()
    assert [(c.row, c.index_row, c.window) for c in spec.layers] == [
        (576, 128, None), (576, 128, None), (1088, 0, 513), (1088, 0, 513),
        (1088, 0, 513)]
