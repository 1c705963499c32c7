"""Speculative decoding: pluggable drafters + the single-stream driver.

Single-stream decode is the serving shape that wastes the chip: each step
launches one token of work, so b1 runs at dispatch speed, not math
speed. Speculative decoding (Leviathan et
al., "Fast Inference from Transformers via Speculative Decoding", ICML
2023; Stern et al., NeurIPS 2018) converts the idle width into useful
tokens: a cheap DRAFTER proposes K tokens, and the target model scores all
K in ONE forward (`GenerationMixin.verify_step`, a prefill_chunk-shaped
call over the split-KV paged attention) that also runs the accept/reject
sampler in-program. Accepted tokens are free; the rejection resample is
corrected so the output distribution is EXACTLY the target model's —
greedy speculative output is token-identical to dense `generate()`
(pinned in tests/test_speculative.py).

Drafters implement one method and are deliberately dumb-simple:

    draft(history, k) -> up to k proposed continuation tokens (np.ndarray)

They must be DETERMINISTIC (a point-mass draft distribution): that is the
condition under which verify_step's acceptance test p(d_j) and masked-
residual resample are exact (min(1, p/q) with q a point mass is p(d),
and max(p - q, 0) renormalized is p with d removed). A stochastic draft
model would need its per-token proposal probabilities threaded into the
verify program; the `Drafter` protocol is where that hook would land.

Shipped drafters:

* ``NGramDrafter`` — prompt-lookup decoding: find the most recent earlier
  occurrence of the longest suffix n-gram of the history and propose the
  tokens that followed it. Host-only, model-free, zero launches; shines on
  self-repetitive text (code, summaries quoting their source, chat with
  retrieval) and degrades to acceptance ~0 (never below plain decode
  throughput-per-launch) on incompressible text.
* ``DraftModelDrafter`` — the draft-model hook point: greedy proposals
  from ANY model exposing the GenerationMixin `generate()` interface,
  drafting from a FIXED-width suffix window so the draft program compiles
  once per (window, k) and never again.
* ``SelfSpeculativeDrafter`` — shallow-prefix reuse of the TARGET model:
  DraftModelDrafter with draft_model == target. The draft only attends the
  last `window` tokens, so a draft launch costs O(window) attention
  instead of O(full prefix) — profitable once the accepted-token value
  beats the extra small launches (cost model: docs/DEPLOYMENT.md).

The continuous scheduler (scheduler.py, ``spec_k=`` knob) drives the same
verify program at S slots; this module's `speculative_generate` is the
single-stream (S=1) driver behind `model.generate_speculative(...)`.
"""
from __future__ import annotations

import itertools

import numpy as np

from .kv_cache import PagedKVCache

__all__ = ["Drafter", "NGramDrafter", "DraftModelDrafter",
           "SelfSpeculativeDrafter", "make_drafter", "SpecStats",
           "speculative_generate"]


class Drafter:
    """Protocol for draft-token proposers (duck-typed; subclassing is
    optional — anything with this method works).

    ``history`` is the full 1-D int sequence so far (prompt + generated);
    return up to ``k`` proposed continuation tokens as a 1-D array (empty
    = no proposal, the driver degrades to plain one-token decode through
    the same compiled program). Proposals must be deterministic given
    `history` — see the module docstring for why."""

    def draft(self, history: np.ndarray, k: int) -> np.ndarray:
        raise NotImplementedError


class NGramDrafter(Drafter):
    """Prompt-lookup drafter: longest-suffix n-gram match against the
    sequence's own past, proposing the tokens that followed the match.

    max_n..min_n are tried longest-first; the most RECENT earlier match
    wins (recent context predicts better than distant context). O(L * n)
    host work per draft — microseconds at serving lengths, and exactly
    zero device launches."""

    def __init__(self, max_n: int = 3, min_n: int = 1):
        if not 1 <= min_n <= max_n:
            raise ValueError(f"need 1 <= min_n <= max_n, got "
                             f"({min_n}, {max_n})")
        self.max_n = int(max_n)
        self.min_n = int(min_n)

    def draft(self, history, k):
        h = np.asarray(history).reshape(-1)
        L = len(h)
        k = int(k)
        if k < 1:
            return h[:0]
        for n in range(min(self.max_n, L - 1), self.min_n - 1, -1):
            pat = h[L - n:]
            # latest occurrence strictly before the suffix itself, with at
            # least one continuation token available
            for i in range(L - n - 1, -1, -1):
                if np.array_equal(h[i:i + n], pat):
                    return h[i + n:i + n + k]
        return h[:0]


class DraftModelDrafter(Drafter):
    """Draft-model hook point: greedy proposals from any GenerationMixin
    model, conditioned on a FIXED-width suffix window of the history.

    The fixed window is the recompile discipline: the draft program's
    shape is (1, window) + k new tokens, compiled once. Histories shorter
    than the window propose nothing (the driver plain-decodes those early
    tokens) rather than compiling a program per prompt length. `k_fixed`
    pins the drafted width too — the driver may ask for fewer near a
    sequence's budget and truncates host-side, so the tail of a sequence
    never forks a narrower draft program."""

    def __init__(self, draft_model, window: int = 16, k_fixed: int | None
                 = None, dtype="bfloat16", decode_kernel=None):
        self.model = draft_model
        self.window = int(window)
        self.k_fixed = None if k_fixed is None else int(k_fixed)
        self.dtype = dtype
        self.decode_kernel = decode_kernel

    def draft(self, history, k):
        h = np.asarray(history).reshape(-1)
        k = int(k)
        if k < 1 or len(h) < self.window:
            return h[:0]
        kk = self.k_fixed if self.k_fixed is not None else k
        if kk < k:
            k = kk
        ctx = np.asarray(h[-self.window:], np.int64)[None]
        out = self.model.generate(
            ctx, max_new_tokens=kk, temperature=0.0, dtype=self.dtype,
            decode_kernel=self.decode_kernel)
        out = np.asarray(out._value if hasattr(out, "_value") else out)
        return out[0, self.window:self.window + k]


class SelfSpeculativeDrafter(DraftModelDrafter):
    """Self-speculation (shallow-prefix reuse): the TARGET model drafts
    its own continuation from a short suffix window. No second model to
    deploy; the draft is cheap because it attends `window` tokens, not the
    full prefix — and wrong exactly where truncated context misleads,
    which the verify step then charges as rejections."""

    def __init__(self, model, window: int = 16, k_fixed: int | None = None,
                 dtype="bfloat16", decode_kernel=None):
        super().__init__(model, window=window, k_fixed=k_fixed, dtype=dtype,
                         decode_kernel=decode_kernel)


def make_drafter(spec, model=None) -> Drafter:
    """Resolve a drafter knob: 'ngram' | 'self' | a Drafter instance."""
    if spec is None:
        return NGramDrafter()
    if isinstance(spec, str):
        if spec == "ngram":
            return NGramDrafter()
        if spec == "self":
            if model is None:
                raise ValueError("drafter='self' needs the target model")
            return SelfSpeculativeDrafter(model)
        raise ValueError(f"unknown drafter {spec!r} "
                         "(expected 'ngram', 'self', or a Drafter)")
    if hasattr(spec, "draft"):
        return spec
    raise ValueError(f"not a drafter: {spec!r} (needs .draft(history, k))")


class SpecStats:
    """Per-run speculation accounting. wasted = drafted - accepted is the
    draft compute (and verify width) spent on rejected tokens; the
    acceptance rate is THE number that decides whether speculation pays
    (docs/DEPLOYMENT.md, "Cost model")."""

    __slots__ = ("drafted", "accepted", "launches", "emitted")

    def __init__(self):
        self.drafted = 0        # draft tokens submitted to verify
        self.accepted = 0       # draft tokens accepted by the target
        self.launches = 0       # verify launches (each also emits 1 token)
        self.emitted = 0        # total tokens produced (accepted + emitted)

    @property
    def wasted(self) -> int:
        return self.drafted - self.accepted

    @property
    def acceptance_rate(self) -> float:
        return self.accepted / self.drafted if self.drafted else 0.0

    def to_dict(self) -> dict:
        return {"drafted": self.drafted, "accepted": self.accepted,
                "wasted": self.wasted, "launches": self.launches,
                "emitted": self.emitted,
                "acceptance_rate": round(self.acceptance_rate, 4)}

    def unit_split(self, width) -> tuple[int, int, int]:
        """(useful, spec_waste, pad) verify-row units for the utilization
        ledger (ISSUE-19), out of ``launches * width`` total rows: every
        emitted token was a useful row, every rejected draft was a
        spec-waste row, the rest of each fixed-width launch was padding.
        Same convention as the scheduler's per-tick attribution, so a
        single-stream speculative run decomposes its FLOPs identically."""
        total = self.launches * int(width)
        useful = min(self.emitted, total)
        spec = min(self.wasted, total - useful)
        return useful, spec, total - useful - spec

    def __repr__(self):
        return f"SpecStats({self.to_dict()})"


_RID = itertools.count(1)   # process-unique reservation ids (atomic draw)


def speculative_generate(model, input_ids, max_new_tokens=32, spec_k=4,
                         drafter="ngram", temperature=0.0, top_k=0,
                         eos_token_id=None, seed=0, dtype="bfloat16",
                         decode_kernel="pallas", kv_cache=None, stats=None,
                         timing_hook=None):
    """Single-stream draft/verify decode loop (the b1 fast path).

    Semantics match `generate()`: returns prompt + max_new_tokens ids
    (same leading shape as the input), EOS freezes the remainder, greedy
    output is token-identical to the dense scan. Mechanics: prefill the
    prompt in one `prefill_chunk` launch, then per iteration draft up to
    `spec_k` tokens on the host and score/accept them in one
    `verify_step` launch (1 + accepted tokens per launch; a draft drought
    degrades to 1 token/launch through the SAME compiled program).

    `kv_cache`: optional shared PagedKVCache; by default a private pool
    sized for this request is used. `stats`: optional SpecStats
    accumulated in place (acceptance-rate observability).
    """
    ids = np.asarray(input_ids._value if hasattr(input_ids, "_value")
                     else input_ids)
    batched = ids.ndim == 2
    if batched and ids.shape[0] != 1:
        raise ValueError("speculative_generate is the single-stream path "
                         f"(got batch {ids.shape[0]}); batched service goes "
                         "through the continuous scheduler's spec_k knob")
    flat = ids.reshape(-1).astype(np.int64)
    plen = len(flat)
    max_new = int(max_new_tokens)
    K = int(spec_k)
    if K < 1:
        raise ValueError("spec_k must be >= 1")
    model._decode_validate(plen, max_new)
    d = make_drafter(drafter, model)
    st = stats if stats is not None else SpecStats()
    eos = None if eos_token_id is None else int(eos_token_id)
    seed_iter = itertools.count(int(seed))

    total = plen + max_new
    own_pool = kv_cache is None
    if own_pool:
        bs = 32
        kv_cache = PagedKVCache.for_model(
            model, block_size=bs, num_blocks=(total + bs - 1) // bs + 1,
            dtype="float32" if dtype is None else dtype, slots=1,
            launch_rows=max(plen, int(spec_k) + 1))
    rid = ("spec", next(_RID))
    kv_cache.reserve(rid, total)
    nb = kv_cache.blocks_for(total)
    table = np.asarray(kv_cache.block_table(rid, pad_to=nb),
                       np.int32)[None]

    generated: list[int] = []
    done = False

    def absorb(toks):
        nonlocal done
        for t in toks:
            if len(generated) >= max_new:
                break
            t = int(t)
            generated.append(t)
            if eos is not None and t == eos:
                generated.extend([eos] * (max_new - len(generated)))
                done = True
                break
        if len(generated) >= max_new:
            done = True

    try:
        tok = model.prefill_chunk(
            flat[None], np.zeros(1, np.int64), np.asarray([plen], np.int64),
            kv_cache, table, temperature=temperature, top_k=top_k,
            eos_token_id=eos_token_id, seed=next(seed_iter),
            decode_kernel=decode_kernel, timing_hook=timing_hook)
        cur = int(np.asarray(tok._value if hasattr(tok, "_value")
                             else tok)[0])
        kv_cache.append_tokens(rid, plen)
        length = plen
        absorb([cur])

        chunk = np.zeros((1, K + 1), np.int64)
        while not done:
            history = np.concatenate([flat, np.asarray(generated, np.int64)])
            remaining = max_new - len(generated)
            proposal = np.asarray(d.draft(history, K),
                                  np.int64).reshape(-1)[:K]
            dlen = min(len(proposal), remaining - 1)
            chunk[:] = 0
            chunk[0, 0] = cur
            if dlen > 0:
                chunk[0, 1:1 + dlen] = proposal[:dlen]
            acc, nxt = model.verify_step(
                chunk, np.asarray([length], np.int64),
                np.asarray([dlen], np.int64), np.asarray([True]),
                kv_cache, table, max_lens=np.asarray([total], np.int64),
                temperature=temperature, top_k=top_k, seed=next(seed_iter),
                decode_kernel=decode_kernel, timing_hook=timing_hook)
            a = int(np.asarray(acc._value if hasattr(acc, "_value")
                               else acc)[0])
            nx = int(np.asarray(nxt._value if hasattr(nxt, "_value")
                                else nxt)[0])
            st.drafted += dlen
            st.accepted += a
            st.launches += 1
            # rollback by bookkeeping: only the accepted prefix + the
            # emitted token become committed rows; rejected rows get
            # overwritten by the next launch's full-width write window
            length += 1 + a
            try:
                kv_cache.append_tokens(rid, 1 + a)
            except (KeyError, ValueError):  # pragma: no cover - audit-only
                pass
            cur = nx
            absorb([int(t) for t in chunk[0, 1:1 + a]] + [nx])
        st.emitted += len(generated)
    finally:
        try:
            kv_cache.mark_done(rid)
            kv_cache.release(rid)
        except KeyError:    # pragma: no cover - already released
            pass

    out = np.concatenate([flat, np.asarray(generated, np.int64)])
    out = out.astype(ids.dtype)
    return out[None] if batched else out
