"""What every servable model must pass: the contract at the head of
`paddle_tpu/models/generation.py`, checked from outside.

`FAMILIES` is the one list. Each family has a test file of its own (so that
`--dist loadfile` spreads them) that runs every entry of `CHECKS` as the
case `<family>-<check>`. A new architecture adds a line to `FAMILIES` and a
three-line file; a new requirement adds a function to `CHECKS`.

Everything runs in float32 (weights and pools): a served token has to be
the plain forward's own first choice, to rounding. The heavy work (the step
programs driven by hand, a served run) is done once a family and cached on
its `Family`; a check reads the results.
"""
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference.kv_cache import PagedKVCache
from paddle_tpu.inference.scheduler import ContinuousGenerateBatchingPredictor
from paddle_tpu.nn.functional.cached_attention import CacheSpec

# one geometry for the hand-driven step programs and the predictor, so that
# both run the SAME compiled programs: 3 slots, pages of 4 rows, a table of
# 16 pages a slot (the whole pool: 48), chunks of 8, ticks of 4 steps
SLOTS, BLOCK, TABLE, CHUNK, STEPS, NEW = 3, 4, 16, 8, 4, 9
MAX_SEQ = BLOCK * TABLE
GEOMETRY = dict(max_slots=SLOTS, prefill_chunk=CHUNK, decode_steps=STEPS,
                max_seq_len=MAX_SEQ, max_new_tokens=NEW + 3,
                decode_kernel="xla")
PROMPT_LENS = (13, 8, 19)       # under, on and over a chunk boundary


def _gpt(**cfg):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    return GPTForCausalLM(GPTConfig(vocab_size=160, hidden_size=64,
                                    num_layers=2, num_heads=4,
                                    max_position=96, **cfg))


def _gpt_learned_mha():
    """GPT-2's shape: learned positions, LayerNorm, GELU, a tied head."""
    return _gpt(use_rope=False, use_rms_norm=False, use_swiglu=False,
                tie_embeddings=True)


def _gpt_rope_gqa():
    """Rope, RMS norm, SwiGLU, fewer K,V heads than query heads."""
    return _gpt(num_kv_heads=2)


def _llama_tiny():
    from paddle_tpu.models.llama import LlamaForCausalLM, llama_tiny

    return LlamaForCausalLM(llama_tiny())


def _dots3_tiny():
    """Latent attention on pages and on a ring, holding 4 of 8 experts."""
    from paddle_tpu.models.dots3 import Dots3ForCausalLM, dots3_tiny

    model = Dots3ForCausalLM(dots3_tiny(n_routed_experts=4,
                                        published_n_routed_experts=8))
    # the model's own spread (0.02) at this width leaves logits a rounding
    # apart: draw the matrices wider, as the benchmark's tiny file does
    rng = np.random.default_rng(7)
    for p in model.parameters():
        if len(p.shape) >= 2:
            p._value = jnp.asarray(rng.normal(0.0, 0.2, p.shape),
                                   p._value.dtype)
    return model


# family -> (builder, vocabulary). Every family here is served with
# speculation and passes `verify_step_...`; one that cannot says so here.
FAMILIES = {
    "gpt-learned-mha": (_gpt_learned_mha, 160),
    "gpt-rope-gqa": (_gpt_rope_gqa, 160),
    "llama-tiny": (_llama_tiny, 512),
    "dots3-tiny": (_dots3_tiny, 96),
}


def _np(t):
    return np.asarray(t._value if hasattr(t, "_value") else t)


class Family:
    """One family's model and what was run on it, each piece once."""

    def __init__(self, name):
        self.name = name
        build, self.vocab = FAMILIES[name]
        with paddle.utils.unique_name.guard():
            paddle.seed(11)
            self.model = build()
        self.model.eval()
        rng = np.random.default_rng(5)
        self.prompts = [rng.integers(0, self.vocab, n).astype("int64")
                        for n in PROMPT_LENS]
        self.spec = self.model._decode_cache_spec()
        self.windowed = any(c.window is not None for c in self.spec.layers)

    # ---------------------------------------------------------------- pieces
    def pool(self, **over):
        kw = dict(block_size=BLOCK, num_blocks=SLOTS * TABLE,
                  dtype="float32", slots=SLOTS, launch_rows=CHUNK)
        kw.update(over)
        return PagedKVCache.for_model(self.model, **kw)

    def gaps(self, tokens):
        """How far under the plain forward's best choice each slot's tokens
        lie, at most: 0 where each is the greedy continuation of what came
        before it. ONE forward over the prompts with their tokens, padded
        behind to one length (causal: what follows moves nothing)."""
        rows = [np.concatenate([p, t]) for p, t in zip(self.prompts, tokens)]
        ids = np.zeros((len(rows), max(map(len, rows))), np.int64)
        for i, row in enumerate(rows):
            ids[i, :len(row)] = row
        logits = np.asarray(jax.jit(
            lambda x: self.model(paddle.Tensor(x))._value)(jnp.asarray(ids)))
        out = []
        for i, (p, t) in enumerate(zip(self.prompts, tokens)):
            at = np.arange(len(p) - 1, len(p) - 1 + len(t))
            out.append(float((logits[i, at].max(-1)
                              - logits[i, at, t]).max()))
        return out

    def prefill(self, kv, chunk=CHUNK, idle=()):
        """Every live slot's prompt through `prefill_chunk` in chunks of
        `chunk`, in lockstep: (the slots' tables, the prompts' lengths, the
        first token each prompt's last chunk sampled)."""
        live = [i for i in range(SLOTS) if i not in idle]
        tables = np.stack([kv.reserve(f"slot{i}", MAX_SEQ)
                           for i in range(SLOTS)])
        plens = np.array([len(self.prompts[i]) if i in live else 0
                          for i in range(SLOTS)])
        first = np.zeros(SLOTS, np.int64)
        for start in range(0, int(plens.max()), chunk):
            ids = np.zeros((SLOTS, chunk), np.int64)
            lens = np.clip(plens - start, 0, chunk)
            for i in live:
                ids[i, :lens[i]] = self.prompts[i][start:start + lens[i]]
            tok = _np(self.model.prefill_chunk(
                ids, np.where(lens > 0, start, 0), lens, kv, tables,
                decode_kernel="xla"))
            done = (lens > 0) & (start + lens >= plens)
            first[done] = tok[done]
        return tables, plens, first

    def drive(self, chunk=CHUNK, steps=STEPS, new=NEW, idle=(), ceiling=None,
              keep_pool=False):
        """The step programs by hand: every slot's prompt in chunks of
        `chunk`, in lockstep, then decode ticks of `steps` for all. `idle`:
        slots that hold nothing. `ceiling`: {slot: rows past the prompt
        that `max_lens` lets it write}. Returns the tokens a slot (and the
        pool, its tables and the rows written, with `keep_pool`)."""
        m = self.model
        kv = self.pool(launch_rows=chunk)
        tables, plens, first = self.prefill(kv, chunk, idle)
        active = plens > 0
        maxlens = plens + MAX_SEQ
        for slot, rows in (ceiling or {}).items():
            maxlens[slot] = plens[slot] + rows
        out, tok, lengths = [first], first, plens.copy()
        for _ in range(-(-(new - 1) // steps)):
            toks = _np(m.decode_step(tok, lengths, active, kv, tables,
                                     steps=steps, max_lens=maxlens,
                                     decode_kernel="xla"))
            out.append(toks)
            tok, lengths = toks[:, -1], lengths + steps * active
        tokens = np.concatenate([out[0][:, None]] + out[1:], axis=1)[:, :new]
        if keep_pool:
            return tokens, kv, tables, lengths
        return tokens

    def rows(self, kv, tables, slot, positions):
        """What the pool holds of `slot` at `positions`, every layer's
        arrays side by side: [len(positions), numbers]."""
        positions = np.asarray(positions)
        got = []
        for cache, pair in zip(self.spec.layers,
                               zip(kv.k_pages, kv.v_pages)):
            for arr in pair:
                if arr is None:
                    continue
                arr = np.asarray(arr)
                if cache.window is None:
                    got.append(arr[tables[slot][positions // BLOCK],
                                   positions % BLOCK])
                else:
                    got.append(arr[slot, positions % arr.shape[1]])
        return np.concatenate([g.reshape(len(positions), -1) for g in got], 1)

    @functools.cached_property
    def decode_shapes(self):
        """What the decode layer returns for one token a slot, as shapes."""
        kv = self.pool()

        def call(state, pools):
            return self.model._decode_call(
                state, jnp.zeros((SLOTS, 1), jnp.int64), pools,
                jnp.zeros(SLOTS, jnp.int32), "xla",
                paged_tables=jnp.zeros((SLOTS, TABLE), jnp.int32),
                cache_valid=jnp.ones((SLOTS, 1), bool))
        return jax.eval_shape(call, self.model.model_state_raw(),
                              list(zip(kv.k_pages, kv.v_pages)))

    @functools.cached_property
    def driven(self):
        return self.drive(keep_pool=True)

    @functools.cached_property
    def served(self):
        """Three requests at once through one predictor with `warmup=True`,
        two greedy and one sampled between them: the answers, the ledger,
        the launch records the timing hook got, the keys `_launch_counts`
        returned a program, and what was compiled when."""
        m = self.model
        pred = ContinuousGenerateBatchingPredictor(
            m, kv_cache=self.pool(), warmup=True, **GEOMETRY)
        seen, records, real = {}, [], m._launch_counts

        def counted(program, stats, *a, **k):
            got = real(program, stats, *a, **k)
            seen.setdefault(program, set()).update(got)
            return dict(got)

        hook = pred._timing_hook

        def recorded(info):
            records.append(dict(info))
            hook(info)
        m._launch_counts = counted
        pred._timing_hook = recorded
        try:
            deadline = time.monotonic() + 300
            while not pred.ready() and time.monotonic() < deadline:
                time.sleep(0.02)
            warm = pred.warm_stats()
            programs = set(m._runner_cache())
            answers = {}

            def ask(i, **kw):
                out = []
                for tokens in pred.infer_stream(self.prompts[i], timeout=300,
                                                max_new_tokens=NEW, **kw):
                    out.extend(int(t) for t in tokens)
                answers[i] = np.asarray(out)
            # a sampler of its own in the middle slot, in the same ticks
            # as its two greedy neighbours
            threads = [threading.Thread(target=ask, args=(i,), kwargs=kw)
                       for i, kw in ((0, {}), (2, {}),
                                     (1, dict(temperature=0.9, top_k=5)))]
            [t.start() for t in threads]
            [t.join(300) for t in threads]
            sampled = answers.pop(1)
            return dict(
                answers=answers, sampled=sampled, warm=warm,
                ready=pred.ready(), snapshot=pred._ledger.snapshot(),
                built_after_ready=set(m._runner_cache()) - programs,
                seen=seen, records=records,
                audit=pred.kv_cache.check_conservation())
        finally:
            del m._launch_counts
            pred.close()


@functools.lru_cache(maxsize=None)
def family(name):
    """The family, its step programs driven once (in the first case's
    set-up: the compile is no check's own time). What fails there fails
    again, by name, in the checks that read it."""
    f = Family(name)
    try:
        f.driven
    except Exception:       # noqa: BLE001 - the checks raise it themselves
        pass
    return f


# --------------------------------------------------------------- the checks
def pool_has_the_arrays_the_spec_says(f):
    assert isinstance(f.spec, CacheSpec)
    kv = f.pool()
    assert len(kv.k_pages) == len(kv.v_pages) == len(f.spec.layers)
    for cache, first, second in zip(f.spec.layers, kv.k_pages, kv.v_pages):
        if cache.kind == "kv":
            want = (SLOTS * TABLE, BLOCK, cache.heads * cache.head_dim)
            assert first.shape == second.shape == want
        elif cache.window is None:
            assert first.shape == (SLOTS * TABLE, BLOCK, cache.row)
            assert (second is None) == (cache.index_row == 0)
            if second is not None:
                assert second.shape == (SLOTS * TABLE, BLOCK,
                                        cache.index_row)
        else:
            ring = f.spec.ring_rows(cache, BLOCK, CHUNK)
            assert first.shape == (SLOTS, ring, cache.row) and second is None
    assert kv.pool_bytes() == SLOTS * TABLE * f.spec.block_bytes(BLOCK, 4) \
        + f.spec.window_bytes(BLOCK, 4, SLOTS, CHUNK)


def cache_format_comes_from_below_the_models(f):
    """The format is declared under `nn/`, the model's module imports
    nothing of `inference/` for it, and the serving layer re-exports it."""
    import ast
    import inspect
    import sys

    from paddle_tpu.inference import kv_cache

    assert type(f.spec).__module__ == \
        "paddle_tpu.nn.functional.cached_attention"
    assert kv_cache.CacheSpec is CacheSpec
    module = sys.modules[type(f.model).__module__]
    tree = ast.parse(inspect.getsource(module))
    upward = [n.module for n in ast.walk(tree)
              if isinstance(n, ast.ImportFrom) and "inference" in
              (n.module or "") and n.level > 0]
    assert upward == [], upward


def decode_layer_returns_logits_caches_and_a_dict(f):
    out = f.decode_shapes
    assert isinstance(out, tuple) and len(out) == 3
    logits, caches, counts = out
    assert logits.shape == (SLOTS, 1, f.vocab)
    assert len(caches) == len(f.spec.layers)
    assert isinstance(counts, dict)
    assert all(np.prod(v.shape) <= 4096 for v in counts.values())


def optional_parts_have_their_defaults(f):
    """`_decode_logits_at` is a class attribute and `_launch_counts` takes
    the contract's arguments and returns a dict, whatever the model."""
    assert isinstance(type(f.model)._decode_logits_at, bool)
    got = f.model._launch_counts("decode_step", {}, np.array([3, 4]),
                                 f.pool(), TABLE, steps=STEPS, holding=0)
    assert isinstance(got, dict)
    assert all(isinstance(k, str) for k in got)


def chunked_prefill_then_decode_is_the_plain_forward(f):
    tokens = f.driven[0]
    assert tokens.shape == (SLOTS, NEW)
    assert max(f.gaps(tokens)) < 1e-4


def another_chunk_width_gives_the_same_tokens(f):
    np.testing.assert_array_equal(f.drive(chunk=CHUNK // 2), f.driven[0])


def one_step_a_tick_gives_the_same_tokens(f):
    np.testing.assert_array_equal(f.drive(steps=1, new=5),
                                  f.driven[0][:, :5])


def pool_holds_a_row_a_position_and_nothing_past_the_length(f):
    _, kv, tables, lengths = f.driven
    for slot in range(SLOTS):
        held = f.rows(kv, tables, slot, np.arange(lengths[slot]))
        assert np.abs(held).sum(axis=1).all(), slot
    # a window layer's ring is reused, pages are not: past the length the
    # pages hold what they were made with
    paged = [i for i, c in enumerate(f.spec.layers) if c.window is None]
    for slot in range(SLOTS):
        past = np.arange(lengths[slot], MAX_SEQ)
        for layer in paged:
            arr = np.asarray(kv.k_pages[layer])
            assert not arr[tables[slot][past // BLOCK], past % BLOCK].any()


def idle_slot_is_untouched_by_a_launch(f):
    """Slot 1 holds nothing: its pages and its ring stay as made, its
    output repeats its input, and the others' tokens do not change."""
    tokens, kv, tables, _ = f.drive(idle=(1,), keep_pool=True)
    assert not f.rows(kv, tables, 1, np.arange(MAX_SEQ)).any()
    assert (tokens[1] == 0).all()
    np.testing.assert_array_equal(tokens[[0, 2]], f.driven[0][[0, 2]])


def max_lens_stops_a_retiring_sequences_writes(f):
    """Slot 0 may write 2 rows past its prompt: a tick of 4 steps leaves
    the next rows as made, where slot 2 (no ceiling) wrote them."""
    tokens, kv, tables, _ = f.drive(new=STEPS + 1, ceiling={0: 2},
                                    keep_pool=True)
    p0, p2 = len(f.prompts[0]), len(f.prompts[2])
    assert np.abs(f.rows(kv, tables, 0, [p0, p0 + 1])).sum(axis=1).all()
    paged = [i for i, c in enumerate(f.spec.layers) if c.window is None]
    for layer in paged:
        arr = np.asarray(kv.k_pages[layer])
        for slot, at, written in ((0, p0 + 2, False), (0, p0 + 3, False),
                                  (2, p2 + 2, True), (2, p2 + 3, True)):
            row = arr[tables[slot][at // BLOCK], at % BLOCK]
            assert bool(row.any()) == written, (layer, slot, at)
    np.testing.assert_array_equal(tokens[[1, 2]],
                                  f.driven[0][[1, 2], :STEPS + 1])


def served_requests_give_the_step_programs_tokens(f):
    """Through `ContinuousGenerateBatchingPredictor.infer_stream`, two
    greedy requests in the ticks of a sampled third: what the step programs
    gave by hand, greedy in every slot, and every page comes home."""
    got = f.served
    for i in (0, 2):
        np.testing.assert_array_equal(got["answers"][i], f.driven[0][i])
    assert got["audit"]["live"] == 0
    assert got["audit"]["free"] == SLOTS * TABLE


def ledger_conserves_positions_and_holds_the_models_keys(f):
    got = f.served
    programs = got["snapshot"]["programs"]
    assert {"prefill_chunk", "decode_step"} <= set(programs)
    for name, p in programs.items():
        assert p["launches"] > 0
        assert p["issued_positions"] == (p["useful_positions"]
                                         + p["pad_positions"]
                                         + p["spec_positions"]), name
    # the scheduler asked the model at every launch, and whatever the model
    # answered (but the positions it issued, which the ledger has a place
    # for) is on the program's account
    assert set(got["seen"]) == {"prefill_chunk", "decode_step"}
    for name, keys in got["seen"].items():
        assert keys - {"issued_positions"} <= set(programs[name]), name
    useful = sum(len(f.prompts[i]) for i in range(SLOTS))
    assert programs["prefill_chunk"]["useful_positions"] == useful


def launch_record_always_carries_stats(f):
    records = f.served["records"]
    assert {r["path"] for r in records} == {"prefill_chunk", "decode_step"}
    for r in records:
        assert isinstance(r["stats"], dict), r["path"]
    counted = {k for r in records for k in r["stats"]}
    assert counted == set(f.decode_shapes[2])


def warmup_builds_every_step_program_before_ready(f):
    got = f.served
    assert got["ready"] and got["warm"]["missing"] == []
    assert got["warm"]["programs"] == 2
    assert set(got["warm"]["fingerprints"]) == {"prefill_chunk",
                                                "decode_step"}
    assert got["built_after_ready"] == set()


def mixed_samplers_build_no_second_program(f):
    """Temperature and top-k are traced inputs a slot: the sampled request
    ran in the greedy ones' ticks and programs (nothing was built after
    ready; their tokens are checked in `served_requests_...`), stayed in
    the vocabulary, and one program of each kind serves this geometry
    whatever was driven by hand with other samplers."""
    got = f.served
    assert got["built_after_ready"] == set()
    assert len(got["sampled"]) == NEW
    assert ((got["sampled"] >= 0) & (got["sampled"] < f.vocab)).all()
    # fewer decode launches than three requests alone would take: the
    # sampled slot was live in its greedy neighbours' ticks
    alone = SLOTS * -(-(NEW - 1) // STEPS)
    assert got["snapshot"]["programs"]["decode_step"]["launches"] < alone
    m = f.model
    ids = np.zeros((SLOTS, CHUNK), np.int64)
    ids[:, :4] = f.prompts[0][:4]

    def launch(temps, top_ks):
        kv = f.pool()
        tables = np.stack([kv.reserve(f"s{i}", MAX_SEQ)
                           for i in range(SLOTS)])
        tok = _np(m.prefill_chunk(ids, np.zeros(SLOTS, np.int64),
                                  np.full(SLOTS, 4), kv, tables,
                                  temperature=temps, top_k=top_ks,
                                  decode_kernel="xla"))
        return tok, _np(m.decode_step(
            tok, np.full(SLOTS, 4), np.ones(SLOTS, bool), kv, tables,
            steps=STEPS, temperature=temps, top_k=top_ks,
            decode_kernel="xla"))
    before = set(m._runner_cache())
    tok, toks = launch(np.asarray([0.0, 1.5, 0.7], np.float32),
                       np.asarray([0, 4, 1], np.int32))
    assert set(m._runner_cache()) == before
    # the greedy slot is not moved by its sampled neighbours
    tok2, toks2 = launch(np.zeros(SLOTS, np.float32),
                         np.zeros(SLOTS, np.int32))
    assert tok[0] == tok2[0]
    np.testing.assert_array_equal(toks[0], toks2[0])


def verify_step_under_greedy_gives_the_greedy_tokens(f):
    """`verify_step` at `spec_k` 2: the greedy continuation drafted is
    accepted whole, a wrong draft is rejected at its place, and the token
    after the accepted prefix is the greedy one."""
    m, greedy = f.model, f.driven[0]
    kv = f.pool(launch_rows=3)
    tables, plens, _ = f.prefill(kv)
    chunk = greedy[:, :3].copy()        # the token to feed and two drafts
    chunk[1, 2] = (chunk[1, 2] + 1) % f.vocab       # slot 1: a wrong draft
    accepted, nxt = m.verify_step(
        chunk, plens, np.full(SLOTS, 2), np.ones(SLOTS, bool), kv, tables,
        decode_kernel="xla")
    np.testing.assert_array_equal(_np(accepted), [2, 1, 2])
    np.testing.assert_array_equal(_np(nxt), [greedy[0, 3], greedy[1, 2],
                                             greedy[2, 3]])


def prefix_cache_gives_the_same_tokens_or_is_refused(f):
    """A model whose layers keep every row serves a repeated prompt from
    shared pages with the same tokens; one that keeps a window is refused
    at the door (a ring belongs to a slot and shares nothing)."""
    if f.windowed:
        with pytest.raises(ValueError, match="keep a window"):
            ContinuousGenerateBatchingPredictor(
                f.model, kv_cache=f.pool(), prefix_cache=True, **GEOMETRY)
        return
    pred = ContinuousGenerateBatchingPredictor(
        f.model, kv_cache=f.pool(), prefix_cache=True, **GEOMETRY)
    try:
        prompt = f.prompts[2]
        first = pred.infer(prompt, timeout=300, max_new_tokens=NEW)
        again = pred.infer(prompt, timeout=300, max_new_tokens=NEW)
        np.testing.assert_array_equal(first[len(prompt):], f.driven[0][2])
        np.testing.assert_array_equal(again, first)
        assert pred.prefix_cache.hits > 0
    finally:
        pred.close()


def hbm_budget_plan_sizes_a_pool_from_the_spec(f):
    from paddle_tpu.analysis.hbm import params_bytes_of, plan_kv_pool

    budget = 8 << 20
    got = plan_kv_pool(budget, cache_spec=f.spec, block_size=BLOCK,
                       slots=SLOTS, max_seq_len=MAX_SEQ, dtype="float32",
                       params_bytes=params_bytes_of(f.model),
                       prefill_chunk=CHUNK, decode_steps=STEPS)
    assert got["num_blocks"] == got["target_blocks"] == SLOTS * TABLE
    assert got["per_block_bytes"] == f.spec.block_bytes(BLOCK, 4)
    kv = f.pool(num_blocks=got["num_blocks"])
    assert kv.pool_bytes() == (got["num_blocks"] * got["per_block_bytes"]
                               + got["plan"].window_pool_bytes)
    assert kv.pool_bytes() + params_bytes_of(f.model) <= budget
    with pytest.raises(ValueError, match="cannot fit a KV pool"):
        plan_kv_pool(params_bytes_of(f.model), cache_spec=f.spec,
                     block_size=BLOCK, slots=SLOTS, max_seq_len=MAX_SEQ,
                     dtype="float32", params_bytes=params_bytes_of(f.model))


def per_request_budget_retires_early_with_the_same_prefix(f):
    """A request that asks for fewer tokens gets the prefix of the longer
    answer and leaves its pages; one that asks for more than the server
    gives is cut to the server's cap, not refused."""
    pred = ContinuousGenerateBatchingPredictor(
        f.model, kv_cache=f.pool(), **GEOMETRY)
    try:
        prompt = f.prompts[0]
        short = pred.infer(prompt, timeout=300, max_new_tokens=3)
        np.testing.assert_array_equal(short[:len(prompt)], prompt)
        np.testing.assert_array_equal(short[len(prompt):],
                                      f.driven[0][0][:3])
        capped = pred.infer(prompt, timeout=300, max_new_tokens=999)
        assert len(capped) == len(prompt) + GEOMETRY["max_new_tokens"]
        np.testing.assert_array_equal(capped[len(prompt):][:NEW],
                                      f.driven[0][0])
        assert pred.kv_cache.blocks_in_use == 0
    finally:
        pred.close()


def eos_freezes_the_rest_of_the_answer(f):
    """With the sequence's own first token as the end-of-sequence token,
    every later position is that token and the slot's pages come home."""
    eos = int(f.driven[0][1][0])
    pred = ContinuousGenerateBatchingPredictor(
        f.model, kv_cache=f.pool(), eos_token_id=eos, **GEOMETRY)
    try:
        prompt = f.prompts[1]
        out = pred.infer(prompt, timeout=300, max_new_tokens=6)
        assert list(out[len(prompt):]) == [eos] * 6
        assert pred.kv_cache.blocks_in_use == 0
    finally:
        pred.close()


def a_prompt_longer_than_the_server_allows_is_refused(f):
    pred = ContinuousGenerateBatchingPredictor(
        f.model, kv_cache=f.pool(), **GEOMETRY)
    try:
        with pytest.raises(ValueError):
            pred.infer(np.zeros(MAX_SEQ, np.int64), timeout=30)
        assert pred.metrics.get("rejected_invalid") == 1
        assert pred.metrics.get("accepted") == 0
    finally:
        pred.close()


CHECKS = [
    pool_has_the_arrays_the_spec_says,
    cache_format_comes_from_below_the_models,
    decode_layer_returns_logits_caches_and_a_dict,
    optional_parts_have_their_defaults,
    chunked_prefill_then_decode_is_the_plain_forward,
    another_chunk_width_gives_the_same_tokens,
    one_step_a_tick_gives_the_same_tokens,
    pool_holds_a_row_a_position_and_nothing_past_the_length,
    idle_slot_is_untouched_by_a_launch,
    max_lens_stops_a_retiring_sequences_writes,
    served_requests_give_the_step_programs_tokens,
    ledger_conserves_positions_and_holds_the_models_keys,
    launch_record_always_carries_stats,
    warmup_builds_every_step_program_before_ready,
    mixed_samplers_build_no_second_program,
    verify_step_under_greedy_gives_the_greedy_tokens,
    prefix_cache_gives_the_same_tokens_or_is_refused,
    hbm_budget_plan_sizes_a_pool_from_the_spec,
    per_request_budget_retires_early_with_the_same_prefix,
    eos_freezes_the_rest_of_the_answer,
    a_prompt_longer_than_the_server_allows_is_refused,
]


def cases(name):
    """The parametrisation of one family's file: `<family>-<check>`."""
    return pytest.mark.parametrize(
        "check", CHECKS, ids=[f"{name}-{c.__name__}" for c in CHECKS])
