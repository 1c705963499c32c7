"""dots3-note through the continuous scheduler at a tiny size on the CPU:
prefill in chunks (the mask form) and then decode (the gather form) over both
pools against the reference's full forward, what a window layer keeps, and
the tick ledger's counts of the expert layer and of the attention's rows."""
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmark"))
import _tiny_dots3 as T  # noqa: E402

from benchmarks.harness import serve_driver  # noqa: E402
from paddle_tpu.inference.kv_cache import (CacheSpec, LayerCache,  # noqa: E402
                                           PagedKVCache)
from paddle_tpu.inference.scheduler import (  # noqa: E402
    ContinuousGenerateBatchingPredictor)

# what `Dots3ForCausalLM._launch_counts` puts on a program's account
MODEL_KEYS = ("moe_rows_issued", "moe_rows_useful",
              "moe_assignments_elsewhere", "moe_expert_tokens",
              "attn_rows_needed", "attn_rows_read", "indexer_rows_scored")

# three slots, two lanes: a chunk launch walks the slots that hold a chunk,
# and the default budget (two chunks a tick, none cut) makes a launch one group
GEOMETRY = dict(max_slots=3, prefill_chunk=8, decode_steps=4, max_seq_len=64,
                decode_kernel="xla", max_new_tokens=12)


@pytest.fixture(scope="module")
def served():
    """Three requests of unequal length through one predictor on float32
    pools: (cfg, prompts, answers, the pool, the ledger's snapshot)."""
    cfg = T.tiny_cfg()
    model, _ = T.built(cfg, 3, served=True)
    kv = PagedKVCache.for_model(model, block_size=4, num_blocks=48,
                                dtype="float32", slots=3, launch_rows=8)
    pred = ContinuousGenerateBatchingPredictor(model, kv_cache=kv, **GEOMETRY)
    prompts = [T.ids(5, 37), T.ids(6, 21), T.ids(7, 30)]
    answers, most = [None, None, None], []

    def ask(i):
        out = []
        for tokens in pred.infer_stream(prompts[i], timeout=300,
                                        max_new_tokens=12 - 3 * (i % 2)):
            out.extend(int(t) for t in tokens)
            most.append(max(int(p.shape[1]) for p in kv.k_pages[2:]))
        answers[i] = out
    threads = [threading.Thread(target=ask, args=(i,)) for i in range(3)]
    [t.start() for t in threads]
    [t.join() for t in threads]
    try:
        audit = kv.check_conservation()
        return cfg, prompts, answers, kv, pred._ledger.snapshot(), audit
    finally:
        pred.close()


def test_chunked_prefill_then_decode_is_the_references_full_forward(served):
    """Every served token is the reference's own first choice at its
    position (float32: the gap is rounding), so the mask form of the chunks,
    the gather form of the steps and the rows both pools held agree with a
    forward that has no cache."""
    cfg, prompts, answers, *_ = served
    assert [len(a) for a in answers] == [12, 9, 12]
    gaps = serve_driver.served_logit_gaps(
        T.family(), cfg, 3, list(zip(prompts, answers)), 64)
    assert gaps["served_logit_gap"] < 1e-4
    altered = [(p, a[:2] + [(a[2] + 7) % 96] + a[3:])
               for p, a in zip(prompts, answers)]
    assert serve_driver.served_logit_gaps(
        T.family(), cfg, 3, altered, 64)["served_logit_gap"] > 0.01


def test_a_fault_planted_in_the_reference_reads_far_over_the_served_gap(
        served, capsys):
    """`tools/plant_reference_faults.py`, the way a fault is read at the
    cell's own load on the chip: the served tokens against the reference
    with one mechanism altered. Each of the selection, the window and the
    gate moves the gap by orders of magnitude."""
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    import plant_reference_faults as planted

    cfg, prompts, answers, *_ = served
    names = ["selection_bottom_k", "window_257", "no_gate"]
    planted.with_faults(serve_driver.served_logit_gaps, names, 3, None)(
        T.family(), cfg, 3, list(zip(prompts, answers)), 64)
    read = {line.split()[1]: float(line.split()[3])
            for line in capsys.readouterr().err.splitlines()
            if line.startswith("FAULT ")}
    assert set(read) == set(names) | {"none"} and read["none"] < 1e-4
    assert all(read[name] > 100 * read["none"] for name in names), read


def test_a_window_layer_keeps_a_ring_and_no_pages(served):
    """What is kept, pinned: a window layer's slot holds ceil((window +
    chunk) / page) + 1 pages' worth of rows in a ring of its own, whatever
    the length, and takes no page of the pool; the full layers' pages are
    conserved. (Pages are not freed behind a window: there are none.)"""
    cfg, _, _, kv, _, audit = served
    window, chunk, page = cfg["sliding_window_size"], 8, 4
    ring = (-(-(window + chunk) // page) + 1) * page
    assert [None if p is None else tuple(p.shape) for p in kv.k_pages] == [
        (48, 4, 20), (48, 4, 20), (3, ring, 28)]
    assert [None if p is None else tuple(p.shape) for p in kv.v_pages] == [
        (48, 4, 8), (48, 4, 8), None]
    assert audit["live"] == 0 and audit["free"] == 48
    assert kv.spec.block_bytes(4, 4) == 4 * 4 * 2 * (20 + 8)
    assert kv.spec.window_bytes(4, 4, 3, 8) == 3 * 4 * 28 * ring
    assert kv.pool_bytes() == 48 * kv.spec.block_bytes(4, 4) \
        + kv.spec.window_bytes(4, 4, 3, 8)


def test_the_ledger_counts_the_expert_layer_and_the_attentions_rows(served):
    cfg, prompts, answers, kv, snap, _ = served
    programs = snap["programs"]
    pre, dec = programs["prefill_chunk"], programs["decode_step"]
    for p in (pre, dec):
        assert set(MODEL_KEYS) <= set(p)
        assert p["moe_rows_useful"] == sum(p["moe_expert_tokens"])
        assert p["moe_rows_useful"] <= p["moe_rows_issued"]
        assert p["attn_rows_needed"] <= p["attn_rows_read"]
    # every real token is routed to 2 of the 8 experts, all held here, in
    # each of the two expert layers; padding is routed nowhere
    assert pre["moe_rows_useful"] == 2 * 2 * pre["useful_positions"]
    assert pre["moe_assignments_elsewhere"] == 0
    # rows the prompts' queries need: min(context, 6) on the two full
    # layers, min(context, 5) on the window layer
    want = sum(2 * min(c, 6) + min(c, 5)
               for p in prompts for c in range(1, len(p) + 1))
    assert pre["attn_rows_needed"] == want
    # a chunk launch carries one group of two lanes, a decode tick all
    # three slots; the budget of two chunks, never cut, gives no tick of
    # these prompts three slots (their tails are over half a chunk)
    span, ring = 16 * 4, kv.k_pages[2].shape[1]
    assert pre["issued_positions"] == pre["launches"] * 2 * 8
    assert dec["issued_positions"] == dec["launches"] * 3 * 4
    assert pre["attn_rows_read"] == pre["launches"] * 2 * 8 * (2 * span
                                                               + ring)
    assert dec["attn_rows_read"] == dec["launches"] * 4 * 3 * (2 * 6 + ring)
    assert pre["indexer_rows_scored"] == pre["launches"] * 2 * 2 * 8 * span
    assert snap["profiled"]["ticks"] == 0


def test_a_launch_of_more_chunks_than_lanes_is_walked_in_groups():
    """Three slots hold a chunk, two lanes: two groups, the second half
    full; the logits of each slot's last position are the whole forward's,
    and an idle slot's rows are not touched."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as paddle

    cfg = T.tiny_cfg()
    model, _ = T.built(cfg, 3)
    seq = T.ids(9, 4, 8)
    full = np.asarray(jax.jit(lambda i: model(paddle.Tensor(i))._value)(
        jnp.asarray(seq)))
    kv = PagedKVCache.for_model(model, block_size=4, num_blocks=16,
                                dtype="float32", slots=4, launch_rows=8)
    tables = np.arange(16, dtype=np.int32).reshape(4, 4)
    lens = np.array([8, 0, 5, 8])
    valid = np.arange(8)[None, :] < lens[:, None]

    @jax.jit
    def launch(state, pools):
        return model._decode_call(
            state, jnp.asarray(seq), pools, jnp.zeros(4, jnp.int32), "xla",
            paged_tables=jnp.asarray(tables), cache_valid=jnp.asarray(valid),
            logits_at=jnp.asarray(np.maximum(lens - 1, 0)))
    logits, caches, stats = launch(model.model_state_raw(),
                                   list(zip(kv.k_pages, kv.v_pages)))
    for slot in (0, 2, 3):
        assert np.abs(np.asarray(logits)[slot, 0]
                      - full[slot, lens[slot] - 1]).max() < 1e-4
    assert not np.asarray(caches[2][0])[1].any()         # slot 1's ring
    assert np.asarray(caches[2][0])[[0, 2, 3]].any(axis=(1, 2)).all()
    # 21 real tokens, 2 experts each, in each of the two expert layers
    assert int(stats["moe_expert_tokens"].sum()) == 2 * 2 * 21


def test_one_cache_spec_is_read_in_one_place():
    """GPT and LLaMA return the spec too, and unpack as the old triple; a
    spec of other rows does not pass for K,V rows."""
    from paddle_tpu.models import gpt_tiny, GPTForCausalLM, llama_tiny
    from paddle_tpu.models import LlamaForCausalLM

    for model in (GPTForCausalLM(gpt_tiny()), LlamaForCausalLM(llama_tiny())):
        spec = model._decode_cache_spec()
        assert isinstance(spec, CacheSpec) and spec.is_uniform_kv()
        layers, heads, dim = spec
        kv = PagedKVCache.for_model(model, block_size=8, num_blocks=4)
        assert kv.signature() == (layers, heads, dim, 8, 4, "bfloat16")
        assert kv.k_pages[0].shape == (4, 8, heads * dim)
        assert PagedKVCache(*spec, block_size=8,
                            num_blocks=4).signature() == kv.signature()
    mixed = CacheSpec((LayerCache("latent", row=20, index_row=8),
                       LayerCache("latent", row=28, window=5)))
    with pytest.raises(TypeError, match="not .layers, kv_heads, head_dim"):
        tuple(mixed)
    with pytest.raises(ValueError, match="pass slots="):
        PagedKVCache(spec=mixed, block_size=4, num_blocks=4)
    kv = PagedKVCache(spec=mixed, block_size=4, num_blocks=4, slots=3,
                      launch_rows=8)
    assert kv.signature()[0] is mixed and kv.signature()[-2:] == (3, 8)


def test_the_residency_plan_counts_both_pools():
    from paddle_tpu.analysis.hbm import plan_kv_pool

    mixed = CacheSpec((LayerCache("latent", row=20, index_row=8),
                       LayerCache("latent", row=28, window=5)))
    got = plan_kv_pool(1 << 20, cache_spec=mixed, block_size=4, slots=2,
                       max_seq_len=64, prefill_chunk=8, dtype="float32")
    ring = (-(-(5 + 8) // 4) + 1) * 4
    assert got["per_block_bytes"] == 4 * 4 * 28
    assert got["plan"].window_pool_bytes == 2 * 4 * 28 * ring
    assert got["plan"].components()["window_pool"] == 2 * 4 * 28 * ring
    assert got["num_blocks"] == 2 * 16
    old = plan_kv_pool(1 << 20, num_layers=2, num_kv_heads=2, head_dim=8,
                       block_size=4, slots=2, max_seq_len=64)
    assert "window_pool" not in old["plan"].components()
