"""Outside the list of families: a model this repository has never heard of,
written here from the pieces under `nn/`, is served by the stack as it
stands, and a count of its own (`toy_rows`) reaches the tick ledger with no
edit to a shared module. The ledger sums whatever keys arrive, and refuses
one that is its own."""
import math

import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.scheduler import ContinuousGenerateBatchingPredictor
from paddle_tpu.models.generation import GenerationMixin
from paddle_tpu.nn import functional as F
from paddle_tpu.nn.functional.cached_attention import (AttnCache, CacheSpec,
                                                       cached_attention)
from paddle_tpu.observability.utilization import UtilizationLedger

VOCAB, HIDDEN, HEADS = 50, 32, 2


class ToyDecoder(nn.Layer):
    """One attention layer and a head; it counts the rows it writes."""

    def __init__(self):
        super().__init__()
        self.embed = nn.Embedding(VOCAB, HIDDEN)
        self.qkv = nn.Linear(HIDDEN, 3 * HIDDEN)
        self.head = nn.Linear(HIDDEN, VOCAB)

    def forward(self, ids, caches=None, cache_offset=None, decode_kernel=None,
                paged_tables=None, cache_valid=None):
        batch, rows = ids.shape
        q, k, v = (t.reshape([batch, rows, HEADS, HIDDEN // HEADS])
                   for t in paddle.split(self.qkv(self.embed(ids)), 3, -1))
        if caches is None:
            out, _ = F.flash_attention(q, k, v, causal=True)
            return self.head(out.reshape([batch, rows, HIDDEN]))
        (first, second), = caches
        out, kept = cached_attention(
            q, k, v,
            AttnCache(first, second, cache_offset, paged_tables, cache_valid),
            scale=1.0 / math.sqrt(HIDDEN // HEADS),
            decode_kernel=decode_kernel)
        written = jnp.sum(cache_valid).astype(jnp.int32)     # a raw array
        return (self.head(out.reshape([batch, rows, HIDDEN])), [kept],
                {"toy_rows": written})


class ToyForCausalLM(nn.Layer, GenerationMixin):
    own_key = "toy_rows"

    def __init__(self):
        super().__init__()
        self.toy = ToyDecoder()

    def forward(self, ids):
        return self.toy(ids)

    def _decode_layer(self):
        return self.toy

    def _decode_cache_spec(self):
        return CacheSpec.uniform(1, HEADS, HIDDEN // HEADS)

    def _decode_validate(self, prompt_len, max_new_tokens):
        pass

    def _launch_counts(self, program, stats, positions, kv_cache,
                       table_width, steps=1, holding=0):
        return {self.own_key: int(stats["toy_rows"]), "toy_launches": 1}


def _predictor(model):
    kv = PagedKVCache.for_model(model, block_size=4, num_blocks=16,
                                dtype="float32")
    return ContinuousGenerateBatchingPredictor(
        model, kv_cache=kv, max_slots=2, prefill_chunk=4, decode_steps=2,
        max_seq_len=32, max_new_tokens=5, decode_kernel="xla")


def _serve(model, prompt):
    pred = _predictor(model)
    try:
        return pred.infer(prompt, timeout=120), pred._ledger.snapshot()
    finally:
        pred.close()


@pytest.fixture(scope="module")
def toy():
    paddle.seed(3)
    model = ToyForCausalLM()
    model.eval()
    return model


def test_a_models_own_count_reaches_the_ledger(toy):
    prompt = np.arange(7, dtype="int64")
    out, snap = _serve(toy, prompt)
    assert len(out) == 7 + 5
    pre = snap["programs"]["prefill_chunk"]
    dec = snap["programs"]["decode_step"]
    assert pre["toy_rows"] == pre["useful_positions"] == 7
    assert pre["toy_launches"] == pre["launches"] == 2
    assert 0 < dec["toy_rows"] <= dec["issued_positions"]
    assert dec["toy_launches"] == dec["launches"]
    # the served tokens are the plain forward's greedy ones
    logits = np.asarray(toy(paddle.to_tensor(out[None]))._value)[0]
    np.testing.assert_array_equal(logits[6:-1].argmax(-1), out[7:])


def test_a_count_under_one_of_the_ledgers_own_names_is_refused(toy):
    """The scheduler asks the ledger about every `_launch_counts` answer in
    the tick, outside its guard around the telemetry: the request that
    launched fails with the ValueError that names the key, and nothing of
    that launch is accounted."""
    clash = ToyForCausalLM()
    clash.eval()
    clash.own_key = "live_rows"
    pred = _predictor(clash)
    try:
        with pytest.raises(ValueError, match="live_rows"):
            pred.infer(np.arange(5, dtype="int64"), timeout=120)
        snap = pred._ledger.snapshot()
        assert snap["programs"] == {} and snap["launches"] == 0
        assert pred.kv_cache.blocks_in_use == 0     # the slot gave its pages back
    finally:
        pred.close()


def test_the_ledger_sums_whatever_keys_arrive():
    clock = iter(range(100))
    led = UtilizationLedger(peak_flops=None, clock=lambda: next(clock))
    led.tick_begin()
    led.record_launch("decode_step", None, 1.0, 8, [(None, 3)], counts=dict(
        moe_rows_issued=32, moe_expert_tokens=[1, 4], never_heard_of=1))
    led.record_launch("decode_step", None, 1.0, 8, [(None, 3)], counts=dict(
        moe_rows_issued=16, moe_expert_tokens=[3, 0], never_heard_of=2))
    led.record_launch("prefill_chunk", None, 1.0, 8, [(None, 3)])
    led.tick_end()
    got = led.snapshot()["programs"]
    assert got["decode_step"]["moe_rows_issued"] == 48
    assert got["decode_step"]["moe_expert_tokens"] == [4, 4]
    assert got["decode_step"]["never_heard_of"] == 3
    assert "never_heard_of" not in got["prefill_chunk"]
    assert led.expert_load_skew() == 1.0
    led.tick_begin()
    for key in ("issued", "useful_positions", "wait_s", "launches"):
        with pytest.raises(ValueError, match=key):
            led.record_launch("decode_step", None, 1.0, 8, [(None, 3)],
                              counts={key: 1})
