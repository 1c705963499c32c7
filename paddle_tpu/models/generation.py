"""Shared autoregressive decoding for the causal-LM models (GPT, LLaMA,
dots3), and the contract a model keeps to be served.

TPU-native shape: prefill is one compiled program; the ENTIRE decode loop is
a second compiled program (`lax.scan` over steps) — no per-token host
round-trips. KV caches materialize INSIDE the program instead of being
allocated on the host per call.

Two cache layouts:
  * dense — per-request [B, max_len, Hkv, D] caches allocated in-program
    (the `generate()` path; one contiguous cache per batch slot).
  * paged — a shared page pool [num_pages, block_size, Hkv * D] addressed
    through per-request block tables (the `generate_paged()` path; serving
    hands in a paddle_tpu.inference.kv_cache.PagedKVCache so mixed-length
    requests share cache memory instead of each padding to max length).

Attention over the cache goes through ops/pallas/decode_attention behind the
`decode_kernel` flag: "xla" (grouped-GQA einsum — the correctness reference)
or "pallas" (split-KV flash-decode kernel). Dense defaults to "xla" (the
measured serving baseline); paged defaults to "pallas" (the XLA paged path
re-gathers the pool into a dense cache every step).

THE CONTRACT between a model and the serving stack. A causal model is
served (`inference/`: the paged pool, the continuous scheduler, speculation,
the prefix cache) by mixing in `GenerationMixin` and giving:

  _decode_layer()      -> the Layer whose `functional_call` accepts
                          (ids, caches=, cache_offset=, decode_kernel=,
                          paged_tables=, cache_valid=) and ALWAYS returns
                          `(logits, new_caches, counts)`: `caches` a pair of
                          arrays a layer (the second None where the layer
                          keeps one), `counts` a dict of small device
                          arrays, `{}` for a model that counts nothing. A
                          step program returns them beside its tokens, a
                          scan summed over its steps; the launch record the
                          timing hook gets always carries them as `stats`.
  _decode_cache_spec() -> `nn.functional.cached_attention.CacheSpec`: what
                          each layer keeps of a token (K,V rows of
                          [kv_heads, head_dim], or a latent row) and for how
                          long (all rows, or a window). The pool is built
                          from it (`PagedKVCache.for_model`) and a layer is
                          handed its arrays as an `AttnCache`; layers of K,V
                          rows share one attention-with-cache
                          (`cached_attention`).
  _decode_validate(prompt_len, max_new_tokens) -> None (raise on invalid)

and, optionally, over the defaults `GenerationMixin` declares:

  _decode_logits_at    class attribute, False: the head runs over every
                          position. True: the decode layer also accepts
                          `logits_at=` [B] and returns [B, 1, V], the logits
                          of that one position a row.
  _launch_counts(program, stats, positions, kv_cache, table_width,
                 steps=1, holding=0) -> dict, `{}`: what the model adds to
                          the tick ledger for one launch, under the ledger's
                          key names; `stats` are the launch's `counts` read
                          back with its tokens. The ledger sums whatever
                          keys arrive (a list place by place); none may be
                          one of its own: the scheduler fails the launch's
                          requests with the ValueError that names such a
                          key. `issued_positions`, where given, says how
                          many positions the program carried.

The arrows point one way: `ops/pallas` <- `nn/functional` (the cache format
and the attend call) <- `models/*` <- this module (the step programs) <-
`inference/*` <- the benchmark.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

from ..analysis.lockwitness import make_lock
from ..profiler.profiler import RecordEvent
from ..tensor import Tensor

# serializes COLD runner builds only (see _runner_for): fleet replicas share
# one model, and a shared lock beats per-model lazy-lock creation, which
# would itself race
_TRACE_LOCK = make_lock("generation._TRACE_LOCK")


# Canonical flattened-argument labels of the three continuous-scheduler
# step programs, in call order — the single naming the zoo lint entries,
# the comms pass (analysis/comms.py) and SpecLayout.step_contract() share,
# so a signature change breaks ONE table instead of silently desyncing
# three. The LoRA variants insert ("adapter_slots", "bank") before
# "rng_key" (step_arg_labels(adapters=True)).
STEP_ARG_LABELS = {
    "prefill_chunk": ("state", "chunk", "offsets", "chunk_lens", "tables",
                      "temperatures", "top_ks", "k_pages", "v_pages",
                      "rng_key"),
    "decode_step": ("state", "tokens", "lengths", "active", "max_lens",
                    "tables", "temperatures", "top_ks", "k_pages",
                    "v_pages", "rng_key"),
    "verify_step": ("state", "chunk", "offsets", "draft_lens", "active",
                    "max_lens", "tables", "temperatures", "top_ks",
                    "k_pages", "v_pages", "rng_key"),
}


def step_arg_labels(kind, *, adapters=False):
    """Argument labels for one step program path (see STEP_ARG_LABELS)."""
    base = STEP_ARG_LABELS[kind]
    if not adapters:
        return base
    return base[:-1] + ("adapter_slots", "bank", "rng_key")


def bucket_new_tokens(max_new_tokens):
    """The dense decode path's DECLARED max_new_tokens bucket set: the next
    power of two. The cache key used to carry the raw per-request budget, so
    mixed-budget fixed-batch traffic compiled one whole prefill+scan program
    per distinct value — the compile-surface lint's `unbounded-key` rule
    (analysis/compilesurface.py) exists because of exactly that. Keying on
    the bucket bounds the inventory at log2(cap) programs per (B, P) shape;
    generate() runs the bucket-width scan and truncates back to the request
    (token-exact: sampling is a deterministic per-step key-split chain, so
    the wider program's first n tokens equal the n-token program's output).
    """
    n = int(max_new_tokens)
    if n <= 1:
        return 1
    return 1 << (n - 1).bit_length()


class GenerationMixin:
    # the optional parts of the contract (module docstring), with defaults
    _decode_logits_at = False

    def _launch_counts(self, program, stats, positions, kv_cache,
                       table_width, steps=1, holding=0) -> dict:
        return {}

    # ------------------------------------------------------------- state cast
    def _decode_state(self, dtype):
        """Model state cast (once) to the decode dtype, cached by parameter
        buffer identity. Decode at B<=8 is weight-streaming-bound: f32 weights
        cost ~2x the HBM traffic AND trigger the TPU's multi-pass f32 matmul
        (measured ~7 GB/token vs ~0.9 GB in bf16 — the round-3 9 tok/s decode
        was exactly this), so bf16 state is the serving default."""
        state = self.model_state_raw()
        if dtype is None:
            return state
        src = tuple(state.values())
        cached = getattr(self, "_decode_state_bf16", None)
        # identity check against RETAINED source arrays (an id()-only key
        # could collide after CPython recycles freed addresses post-update)
        if (cached is not None and cached[0] == dtype
                and len(cached[1]) == len(src)
                and all(a is b for a, b in zip(cached[1], src))):
            return cached[2]
        cast = {k: (v.astype(dtype) if v.dtype == jnp.float32 else v)
                for k, v in state.items()}
        self._decode_state_bf16 = (dtype, src, cast)
        return cast

    def model_state_raw(self):
        """raw state keyed as the decode layer sees it (functional_call)."""
        return self._decode_layer().raw_state()

    # ------------------------------------------------------------- internals
    def _decode_call(self, raw_state, tok_ids, caches, offset, decode_kernel,
                     paged_tables=None, cache_valid=None, logits_at=None):
        """One functional model call over raw jax values -> (logits, caches,
        counts). A layer's cache is a pair of arrays, the second None where
        the layer keeps one (`LayerCache`). `logits_at` [B] is for a model
        that says it takes it (`_decode_logits_at`): its logits are then
        [B, 1, V], of that one position. `counts` are the model's own of the
        call, a dict of small arrays ({}: it counts nothing)."""
        kwargs = dict(cache_offset=offset, decode_kernel=decode_kernel)
        if paged_tables is not None:
            kwargs.update(paged_tables=paged_tables, cache_valid=cache_valid)
        if logits_at is not None:
            kwargs["logits_at"] = logits_at

        def wrap(a):
            return None if a is None else Tensor(a)

        def raw(a):
            return a._value if isinstance(a, Tensor) else a
        logits, new_caches, counts = self._decode_layer().functional_call(
            raw_state, Tensor(tok_ids),
            caches=[(wrap(k), wrap(v)) for k, v in caches], **kwargs)
        return (raw(logits), [(raw(kc), raw(vc)) for kc, vc in new_caches],
                counts)

    @staticmethod
    def _make_sampler(greedy, temperature, top_k, eos, ids_dtype):
        def sample(lg, key, finished):
            if greedy:
                nxt = jnp.argmax(lg.astype(jnp.float32), axis=-1)
            else:
                lg = lg.astype(jnp.float32) / jnp.float32(temperature)
                if top_k and top_k > 0:
                    kth = jax.lax.top_k(lg, top_k)[0][:, -1:]
                    lg = jnp.where(lg < kth, jnp.finfo(jnp.float32).min, lg)
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, lg, axis=-1)
            nxt = nxt.astype(ids_dtype)
            if eos >= 0:
                nxt = jnp.where(finished, eos, nxt)
                finished = finished | (nxt == eos)
            return nxt, key, finished

        return sample

    @staticmethod
    def _make_slot_sampler(eos, ids_dtype):
        """Per-SLOT sampler for the continuous-batching step programs:
        temperature/top-k arrive as TRACED [S] arrays, not trace constants,
        so mixed-sampler traffic runs ONE compiled program per step type
        (they used to ride the cache key and fork programs — ROADMAP item 1).

        Semantics per slot s: temps[s] <= 0 -> greedy argmax; else softmax
        sampling at temps[s] with optional top-k truncation (top_ks[s] <= 0
        -> no truncation). Traced top-k cannot use lax.top_k (static k), so
        the threshold is the k-th value of a descending sort — O(V log V)
        per slot, noise next to the model matmuls at serving vocab sizes."""

        def sample(lg, key, finished, temps, top_ks):
            lg32 = lg.astype(jnp.float32)
            greedy_tok = jnp.argmax(lg32, axis=-1)
            safe_t = jnp.where(temps > 0, temps, jnp.float32(1.0))
            scaled = lg32 / safe_t[:, None]
            vocab = scaled.shape[-1]
            sorted_desc = -jnp.sort(-scaled, axis=-1)
            k_idx = (jnp.clip(top_ks, 1, vocab) - 1).astype(jnp.int32)
            kth = jnp.take_along_axis(sorted_desc, k_idx[:, None], axis=-1)
            cut = jnp.where((top_ks > 0)[:, None] & (scaled < kth),
                            jnp.finfo(jnp.float32).min, scaled)
            key, sub = jax.random.split(key)
            sampled = jax.random.categorical(sub, cut, axis=-1)
            nxt = jnp.where(temps > 0, sampled, greedy_tok).astype(ids_dtype)
            if eos >= 0:
                nxt = jnp.where(finished, eos, nxt)
                finished = finished | (nxt == eos)
            return nxt, key, finished

        return sample

    def _runner_cache(self):
        cache = getattr(self, "_generate_cache", None)
        if cache is None:
            cache = self._generate_cache = {}
        return cache

    def _runner_for(self, cache_key, make_run):
        """Build-or-fetch a compiled runner; single-compile under concurrency.

        A ReplicaFleet runs N scheduler tick threads over ONE shared model —
        that sharing is what makes replica admit/retire/kill recompile-free —
        so two replicas cold-starting the same (shape, pool-signature) key
        must not trace it twice. Hit path stays lock-free (dict get is
        atomic); only the cold build serializes. Returns (run, compiled_now).
        """
        cache = self._runner_cache()
        run = cache.get(cache_key)
        if run is not None:
            return run, False
        with _TRACE_LOCK:
            run = cache.get(cache_key)
            if run is not None:
                return run, False
            run = cache[cache_key] = make_run()
            return run, True

    @staticmethod
    def _emit_timing(timing_hook, path, B, P, new_tokens, compiled, t0,
                     flops=None, stats=None):
        """Decode timing hook (observability layer): called once per launch,
        when the call that enqueued the program has RETURNED. ``dispatch_s``
        is that call alone: the device arrays made and the program handed to
        the runtime, which returns before the device has finished. Whoever
        reads the result back times the wait and adds it (the serving layer
        does: ``launch = dispatch + wait``). The same interval is the
        ``generate.<path>`` RecordEvent. ``flops`` (ISSUE-19) is the
        program's issued FLOPs per launch — present only when the hook
        asked for it (``wants_flops``), None otherwise. ``stats`` are the
        model's own counts of the launch, still on the device ({}: it counts
        nothing): whoever reads the tokens back reads them in the same
        wait."""
        if timing_hook is None:
            return
        info = {"path": path, "batch": int(B), "prompt_len": int(P),
                "new_tokens": int(new_tokens), "compiled": bool(compiled),
                "dispatch_s": time.perf_counter() - t0, "flops": flops,
                "stats": stats or {}}
        timing_hook(info)

    def _flops_of(self, cache_key, run, args):
        """Issued FLOPs of one execution of the step program behind
        ``cache_key`` (ISSUE-19 utilization ledger).

        jax.jit runners carry no cost analysis, but their LOWERED module
        does — ``run.lower(*args).cost_analysis()`` needs a trace, not an
        XLA compile, and agrees with the compiled executable's own number.
        The result is constant per cache key (fixed-width programs), so one
        trace per program lifetime, cached next to the runner cache; the
        post-ready compile sentinel is untouched because nothing here goes
        through _runner_for. Benign double-compute race under concurrency
        (same value lands twice). 0.0 when the backend reports nothing."""
        cache = getattr(self, "_flops_cache", None)
        if cache is None:
            cache = self._flops_cache = {}
        val = cache.get(cache_key)
        if val is None:
            from ..observability.xla import cost_flops

            try:
                val = cost_flops(run.lower(*args))
            except Exception:   # introspection must never break a launch
                val = 0.0
            cache[cache_key] = val
        return val

    @staticmethod
    def _wants_flops(timing_hook) -> bool:
        return bool(getattr(timing_hook, "wants_flops", False))

    @staticmethod
    def _check_deadline(deadline, where):
        """Deadline gate at the device-launch boundary: the compiled decode
        scan cannot be interrupted mid-flight, so a request whose budget is
        already spent must be refused BEFORE the launch burns a batch slot
        (serving propagates one Deadline from HTTP -> queue -> here)."""
        if deadline is not None and deadline.expired():
            from ..inference.resilience import DeadlineExceeded

            raise DeadlineExceeded(f"deadline expired before {where}")

    # ------------------------------------------------------------ dense path
    def generate(self, input_ids, max_new_tokens=32, temperature=0.0, top_k=0,
                 eos_token_id=None, seed=0, dtype="bfloat16",
                 decode_kernel=None, deadline=None, timing_hook=None):
        """Autoregressive decoding with dense per-layer KV caches.

        temperature==0 -> greedy; otherwise softmax sampling with optional
        top-k truncation; eos positions freeze once hit. Returns
        [B, prompt+new] ids.

        Sampling is FUSED into the compiled program (the scan body) with
        temperature/top_k as traced inputs (_make_slot_sampler): changing
        the sampler config re-runs the same program instead of recompiling
        the whole prefill+scan, and there is no host round-trip between
        logits and the sampled token (the registered `gpt_decode_dense`
        zoo program lints host-sync-clean with no allowlist entries).

        The budget is BUCKETED in the cache key (bucket_new_tokens): the
        compiled scan runs the next-power-of-two width and the result is
        truncated to the requested count, so mixed-budget traffic shares
        log2(cap) programs per shape instead of one per distinct value.
        Token-exact: each step's sample depends only on the prefix and the
        per-step key-split chain, so later (discarded) steps cannot affect
        the first n tokens.

        `dtype`: decode compute dtype for weights + KV caches ('bfloat16'
        default — decode is weight-streaming-bound, see _decode_state; pass
        None to keep the parameters' own dtype).
        `decode_kernel`: "xla" (default — grouped-GQA einsum) | "pallas"
        (split-KV flash-decode kernel, ops/pallas/decode_attention.py).
        `deadline`: optional inference.resilience.Deadline — raises
        DeadlineExceeded instead of launching an already-expired decode.
        `timing_hook`: optional fn(dict) receiving per-launch host timing
        (dispatch_s, compiled, ...) — the serving layer feeds the
        observability metrics/histograms through it.
        """
        ids = (input_ids._value if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids))
        B, P = ids.shape
        self._decode_validate(P, max_new_tokens)
        # dense caches are K,V rows of like layers: any other spec says so
        num_layers, kv_h, hd = self._decode_cache_spec().kv_triple()
        new_tokens = int(max_new_tokens)
        # the COMPILED scan width is the declared bucket, not the raw
        # per-request budget (compile-surface `unbounded-key`): mixed-budget
        # traffic shares one program per (B, P) shape and the output is
        # truncated back to the request below
        new_bucket = bucket_new_tokens(new_tokens)
        max_len = P + new_bucket
        decode_dtype = None if dtype is None else jnp.dtype(dtype)
        cache_dtype = decode_dtype or jnp.float32
        state = self._decode_state(decode_dtype)
        ids_dtype = ids.dtype  # closure must not pin the prompt array itself
        eos = -1 if eos_token_id is None else int(eos_token_id)
        # sampler params enter as TRACED [B] inputs (the PR 8 slot-sampler
        # math): every (greedy, temperature, top_k) config shares ONE
        # compiled program per shape instead of forking the runner cache
        sample = self._make_slot_sampler(eos, ids_dtype)
        temps = jnp.broadcast_to(
            jnp.asarray(0.0 if temperature is None else temperature,
                        jnp.float32), (B,))
        tks = jnp.broadcast_to(jnp.asarray(top_k or 0, jnp.int32), (B,))

        def make_run():
            @jax.jit
            def run(raw_state, prompt, stemps, stks, key):
                # head-leading [B, Hkv, T, D]: the decode kernel's
                # DMA-contiguous layout (ops/pallas/decode_attention.py)
                caches = [
                    (jnp.zeros((B, kv_h, max_len, hd), cache_dtype),
                     jnp.zeros((B, kv_h, max_len, hd), cache_dtype))
                    for _ in range(num_layers)
                ]
                logits, caches, _ = self._decode_call(
                    raw_state, prompt, caches, jnp.int32(0), decode_kernel)
                finished = jnp.zeros((B,), bool)
                tok0, key, finished = sample(logits[:, -1], key, finished,
                                             stemps, stks)

                def body(carry, t):
                    tok, caches, key, finished = carry
                    lg, caches, _ = self._decode_call(
                        raw_state, tok[:, None], caches,
                        (P + t).astype(jnp.int32), decode_kernel)
                    nxt, key, finished = sample(lg[:, -1], key, finished,
                                                stemps, stks)
                    return (nxt, caches, key, finished), nxt

                if new_bucket > 1:
                    (_, _, _, _), toks = jax.lax.scan(
                        body, (tok0, caches, key, finished),
                        jnp.arange(new_bucket - 1))
                    toks = jnp.concatenate([tok0[None], toks], axis=0)
                else:
                    toks = tok0[None]
                # prompt+new concatenated in-program: one result fetch, no
                # extra host-side dispatch per call
                return jnp.concatenate([prompt, jnp.swapaxes(toks, 0, 1)],
                                       axis=1)

            return run

        # jit caches on function identity: rebuilding the closure per call
        # would recompile prefill + the whole decode scan on every request.
        # Sampler params are traced inputs, so they are NOT in the key.
        cache_key = (B, P, bucket_new_tokens(max_new_tokens), eos,
                     str(ids.dtype), str(decode_dtype), decode_kernel)
        run, compiled_now = self._runner_for(cache_key, make_run)

        was_training = self.training
        self.eval()
        try:
            self._check_deadline(deadline, "dense decode launch")
            t0 = time.perf_counter()
            with RecordEvent("generate.dense"):
                full = run(state, ids, temps, tks, jax.random.key(seed))
                # truncate the bucket-width scan back to the request; the
                # slice is a device view, one result fetch as before
                out = Tensor(full[:, :P + new_tokens])
            self._emit_timing(timing_hook, "dense", B, P, new_tokens,
                              compiled_now, t0)
            return out
        finally:
            if was_training:
                self.train()

    def compiled_generate_runner(self, batch, prompt_len, max_new_tokens):
        """The cached compiled (state, prompt, temps, top_ks, key) -> ids
        program for a prior generate() shape, or None. Public so
        benches/audits can time the compiled program itself without
        depending on the cache-key layout. `max_new_tokens` resolves
        through the declared bucket set (bucket_new_tokens), mirroring
        what generate() keys on."""
        for k, run in (getattr(self, "_generate_cache", None) or {}).items():
            if k[:3] == (batch, prompt_len, bucket_new_tokens(max_new_tokens)):
                return run
        return None

    def compiled_generate_paged_runner(self, batch, prompt_len,
                                       max_new_tokens):
        """The cached compiled paged-decode program
        (state, prompt, lens, tables, k_pages, v_pages, key) -> toks for a
        prior generate_paged() shape, or None — the paged twin of
        compiled_generate_runner (benches and the graph linter analyze the
        program without re-deriving the cache-key layout)."""
        for k, run in (getattr(self, "_generate_cache", None) or {}).items():
            if k[:4] == ("paged", batch, prompt_len, max_new_tokens):
                return run
        return None

    # ------------------------------------------------------------ paged path
    def generate_paged(self, input_ids, prompt_lens, kv_cache, block_tables,
                       max_new_tokens=32, temperature=0.0, top_k=0,
                       eos_token_id=None, seed=0, decode_kernel="pallas",
                       deadline=None, timing_hook=None):
        """Autoregressive decoding over a SHARED paged KV pool.

        input_ids: [B, P] prompts right-padded to a common P; prompt_lens [B]
        gives each request's true length (padding rows are dropped from the
        cache by the out-of-bounds-scatter trick and masked from attention by
        per-request lengths). kv_cache: a PagedKVCache whose per-layer pools
        this program reads AND returns updated (committed back on exit).
        block_tables: [B, NB] page ids from the pool's allocator.

        Returns [B, max_new_tokens] new tokens (per request b the real
        continuation of input_ids[b, :prompt_lens[b]]).

        `deadline`: optional inference.resilience.Deadline, checked at the
        launch boundary — the compiled decode scan cannot be interrupted, so
        an expired budget raises DeadlineExceeded instead of launching.
        """
        ids = (input_ids._value if isinstance(input_ids, Tensor)
               else jnp.asarray(input_ids))
        B, P = ids.shape
        self._decode_validate(P, max_new_tokens)
        decode_dtype = (jnp.dtype(kv_cache.dtype)
                        if kv_cache.dtype != jnp.float32 else None)
        state = self._decode_state(decode_dtype)
        ids_dtype = ids.dtype
        greedy = not (temperature and temperature > 0)
        eos = -1 if eos_token_id is None else int(eos_token_id)
        sample = self._make_sampler(greedy, temperature, top_k, eos, ids_dtype)
        NB = int(block_tables.shape[1])

        def make_run():
            # donate the pools on accelerators: XLA aliases them in place so
            # the program never holds two copies of the page pool (donation is
            # unimplemented on CPU and would only warn there — the graph
            # linter's builtin allowlist carries the resulting CPU
            # donation-miss finding, see analysis/findings.py)
            donate = (4, 5) if jax.default_backend() != "cpu" else ()

            @functools.partial(jax.jit, donate_argnums=donate)
            def run(raw_state, prompt, plens, tables, k_pages, v_pages, key):
                plens = plens.astype(jnp.int32)
                caches = list(zip(k_pages, v_pages))
                valid = (jnp.arange(P, dtype=jnp.int32)[None, :]
                         < plens[:, None])
                # prefill at per-request offset 0; padding rows write nothing
                logits, caches, _ = self._decode_call(
                    raw_state, prompt, caches, jnp.zeros((B,), jnp.int32),
                    decode_kernel, paged_tables=tables, cache_valid=valid)
                last = jnp.take_along_axis(
                    logits, (plens - 1)[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0]
                finished = jnp.zeros((B,), bool)
                tok0, key, finished = sample(last, key, finished)
                lengths = plens

                def body(carry, _):
                    tok, caches, lengths, key, finished = carry
                    lg, caches, _ = self._decode_call(
                        raw_state, tok[:, None], caches, lengths,
                        decode_kernel, paged_tables=tables, cache_valid=None)
                    nxt, key, finished = sample(lg[:, -1], key, finished)
                    return (nxt, caches, lengths + 1, key, finished), nxt

                if max_new_tokens > 1:
                    # tok0 sits at position plens: the scan starts at the
                    # live length, not one past it (one past skips a cache
                    # slot and shifts every decode position by one — rope's
                    # relative scores hid it, learned positions did not)
                    (_, caches, _, _, _), toks = jax.lax.scan(
                        body, (tok0, caches, lengths, key, finished),
                        jnp.arange(max_new_tokens - 1))
                    toks = jnp.concatenate([tok0[None], toks], axis=0)
                else:
                    toks = tok0[None]
                new_k = [kc for kc, _ in caches]
                new_v = [vc for _, vc in caches]
                return jnp.swapaxes(toks, 0, 1), new_k, new_v

            return run

        cache_key = ("paged", B, P, max_new_tokens, NB, kv_cache.signature(),
                     greedy, float(temperature or 0.0), int(top_k or 0), eos,
                     str(ids.dtype), decode_kernel)
        run, compiled_now = self._runner_for(cache_key, make_run)

        was_training = self.training
        self.eval()
        try:
            self._check_deadline(deadline, "paged decode launch")
            t0 = time.perf_counter()
            with RecordEvent("generate.paged"):
                toks, new_k, new_v = run(
                    state, ids, jnp.asarray(prompt_lens, jnp.int32),
                    jnp.asarray(block_tables, jnp.int32),
                    tuple(kv_cache.k_pages), tuple(kv_cache.v_pages),
                    jax.random.key(seed))
                kv_cache.commit(new_k, new_v)
            self._emit_timing(timing_hook, "paged", B, P, max_new_tokens,
                              compiled_now, t0)
            return Tensor(toks)
        finally:
            if was_training:
                self.train()

    # --------------------------------------------- continuous-batching steps
    @staticmethod
    def _pool_donation():
        """donate_argnums gate shared by the paged step programs: donation is
        unimplemented on CPU (jax warns and keeps both copies), so the pools
        are aliased in place only on accelerators — the graph linter's builtin
        allowlist carries the resulting CPU donation-miss finding."""
        return jax.default_backend() != "cpu"

    @staticmethod
    def _adapter_extra(adapters, adapter_slots, S):
        """Launch-time LoRA args for the paged step programs: the traced
        [S] bank index plus the current bank pytree. Empty when no registry
        rides the call — the base programs keep their exact pre-LoRA
        signature (and jit cache keys)."""
        if adapters is None:
            return ()
        if adapter_slots is None:
            aidx = jnp.zeros((S,), jnp.int32)
        else:
            aidx = jnp.asarray(adapter_slots, jnp.int32)
        return (aidx, adapters.bank())

    def prefill_chunk(self, chunk_ids, offsets, chunk_lens, kv_cache,
                      block_tables, temperature=0.0, top_k=0,
                      eos_token_id=None, seed=0, decode_kernel="pallas",
                      adapters=None, adapter_slots=None, timing_hook=None):
        """One chunked-prefill step over the shared paged pool (fixed width).

        The continuous scheduler (inference/scheduler.py) splits long prompts
        into fixed-size chunks so prefill interleaves with decode ticks
        instead of stalling every in-flight decoder. One launch processes up
        to S slots' current chunks:

        chunk_ids:  [S, C] token chunk per slot, right-padded to the static
                    chunk width C (zeros in dead positions).
        offsets:    [S] int — each slot's cache length BEFORE this chunk (the
                    absolute position of its chunk's first token).
        chunk_lens: [S] int — valid tokens in each slot's chunk; 0 marks an
                    idle slot (its writes are dropped, its output ignored).
        block_tables: [S, NB] page ids (idle slots pad with page 0).

        KV rows for the chunk are scattered at [offset, offset+len) through
        the out-of-bounds-drop trick, exactly like generate_paged's prefill;
        attention masks cols <= offset + row so chunk N attends to chunks
        0..N-1 plus its own causal prefix. Returns [S] next-token samples
        from each chunk's LAST valid position — meaningful only for the slot
        whose chunk completes its prompt (the scheduler ignores the rest).
        Pools are committed back to `kv_cache`.

        `temperature` / `top_k` are scalars or per-slot [S] arrays and enter
        the program as TRACED inputs (see _make_slot_sampler): requests with
        different sampling params share the one compiled step program.

        `adapters` / `adapter_slots` (ISSUE-15): when an AdapterRegistry
        rides the call, the per-slot [S] bank index and the bank arrays are
        ALSO traced inputs — the cache key grows only the bank SHAPE
        (`adapters.signature()`), so adapter mix changes and load/unload
        never recompile."""
        ids = (chunk_ids._value if isinstance(chunk_ids, Tensor)
               else jnp.asarray(chunk_ids))
        S, C = ids.shape
        decode_dtype = (jnp.dtype(kv_cache.dtype)
                        if kv_cache.dtype != jnp.float32 else None)
        state = self._decode_state(decode_dtype)
        ids_dtype = ids.dtype
        eos = -1 if eos_token_id is None else int(eos_token_id)
        sample = self._make_slot_sampler(eos, ids_dtype)
        temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (S,))
        tks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (S,))
        NB = int(block_tables.shape[1])

        # the compile key carries the bank SHAPE only — adapter index and
        # bank values stay traced, so churn never lands here
        bank_sig = None if adapters is None else adapters.signature()
        # a model whose head can run over one position a row is asked for
        # the chunk's last only: the others' logits are never sampled
        head_at_last = self._decode_logits_at

        def make_run():
            donate = (7, 8) if self._pool_donation() else ()

            def step(raw_state, chunk, offs, lens, tables, stemps, stks,
                     k_pages, v_pages, key):
                offs = offs.astype(jnp.int32)
                lens = lens.astype(jnp.int32)
                caches = list(zip(k_pages, v_pages))
                valid = (jnp.arange(C, dtype=jnp.int32)[None, :]
                         < lens[:, None])
                last_at = jnp.maximum(lens - 1, 0)
                logits, caches, counts = self._decode_call(
                    raw_state, chunk, caches, offs, decode_kernel,
                    paged_tables=tables, cache_valid=valid,
                    logits_at=last_at if head_at_last else None)
                last = (logits[:, 0] if head_at_last else jnp.take_along_axis(
                    logits, last_at[:, None, None], axis=1)[:, 0])
                tok, _, _ = sample(last, key, jnp.zeros((S,), bool),
                                   stemps, stks)
                return (tok, [kc for kc, _ in caches],
                        [vc for _, vc in caches], counts)

            if bank_sig is None:
                return jax.jit(step, donate_argnums=donate)
            from ..inference.adapters import applied

            # aidx/bank slot in AFTER the pools, BEFORE the key: the
            # donated pool argnums above stay valid either way
            def lora_run(raw_state, chunk, offs, lens, tables, stemps,
                         stks, k_pages, v_pages, aidx, bank, key):
                with applied(bank, aidx):
                    return step(raw_state, chunk, offs, lens, tables,
                                stemps, stks, k_pages, v_pages, key)

            return jax.jit(lora_run, donate_argnums=donate)

        cache_key = ("prefill_chunk", S, C, NB, kv_cache.signature(), eos,
                     str(ids_dtype), decode_kernel, bank_sig)
        run, compiled_now = self._runner_for(cache_key, make_run)

        was_training = self.training
        self.eval()
        try:
            args = (state, ids, jnp.asarray(offsets, jnp.int32),
                    jnp.asarray(chunk_lens, jnp.int32),
                    jnp.asarray(block_tables, jnp.int32), temps, tks,
                    tuple(kv_cache.k_pages), tuple(kv_cache.v_pages),
                    *self._adapter_extra(adapters, adapter_slots, S),
                    jax.random.key(seed))
            # ISSUE-19: probe BEFORE the launch (donation deletes the pool
            # args after) and before t0 (the trace must not pollute dispatch_s)
            flops = (self._flops_of(cache_key, run, args)
                     if self._wants_flops(timing_hook) else None)
            t0 = time.perf_counter()
            with RecordEvent("generate.prefill_chunk"):
                tok, new_k, new_v, stats = run(*args)
                kv_cache.commit(new_k, new_v)
            self._emit_timing(timing_hook, "prefill_chunk", S, C, 0,
                              compiled_now, t0, flops=flops, stats=stats)
            return Tensor(tok)
        finally:
            if was_training:
                self.train()

    def decode_step(self, tokens, lengths, active, kv_cache, block_tables,
                    steps=1, max_lens=None, temperature=0.0, top_k=0,
                    eos_token_id=None, seed=0, decode_kernel="pallas",
                    adapters=None, adapter_slots=None, timing_hook=None):
        """`steps` decode iterations for a fixed-width slot batch (one tick).

        The continuous scheduler's steady-state program: S slots, each either
        an in-flight sequence or idle. Per scan iteration every ACTIVE slot
        writes its current token's KV at `lengths` and samples the next
        token; idle slots are fully masked (writes dropped via the cache
        valid mask, outputs held) so one compiled program serves every
        admit/retire configuration — no recompiles as sequences come and go.

        tokens:  [S] current input token per slot (last sampled, not yet in
                 the cache — same convention as generate_paged's scan body).
        lengths: [S] int — cache rows present per slot; advances by 1 per
                 step for active slots only.
        active:  [S] bool slot mask.
        block_tables: [S, NB] page ids (idle slots pad with page 0).
        max_lens: [S] int — per-slot KV write ceiling. The tick runs a FIXED
                 `steps` iterations, so a sequence retiring mid-tick would
                 otherwise keep writing past its reserved blocks and scatter
                 into the table's pad page (page 0 belongs to someone else);
                 writes at positions >= max_lens are dropped instead. None
                 means no ceiling (every step may write).

        Returns [S, steps] sampled tokens (idle slots repeat their input).
        Pools are committed back to `kv_cache`. The host syncs once per tick,
        not per token — `steps` amortizes dispatch exactly like the
        generate() scan does."""
        tokens = (tokens._value if isinstance(tokens, Tensor)
                  else jnp.asarray(tokens))
        S = int(tokens.shape[0])
        T = int(steps)
        decode_dtype = (jnp.dtype(kv_cache.dtype)
                        if kv_cache.dtype != jnp.float32 else None)
        state = self._decode_state(decode_dtype)
        ids_dtype = tokens.dtype
        eos = -1 if eos_token_id is None else int(eos_token_id)
        # temperature/top_k are TRACED per-slot inputs (scalars broadcast):
        # mixed-sampler traffic shares the one compiled tick program
        sample = self._make_slot_sampler(eos, ids_dtype)
        temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (S,))
        tks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (S,))
        NB = int(block_tables.shape[1])
        if max_lens is None:    # no ceiling: same program, permissive values
            max_lens = jnp.asarray(lengths, jnp.int32) + jnp.int32(T)
        bank_sig = None if adapters is None else adapters.signature()

        def make_run():
            donate = (8, 9) if self._pool_donation() else ()

            def step(raw_state, tok, lens, act, lmax, tables, stemps, stks,
                     k_pages, v_pages, key):
                lens = lens.astype(jnp.int32)
                lmax = lmax.astype(jnp.int32)
                caches = list(zip(k_pages, v_pages))
                adv = act.astype(jnp.int32)

                def body(carry, _):
                    tok, caches, lens, key, finished = carry
                    valid = (act & (lens < lmax))[:, None]
                    lg, caches, counts = self._decode_call(
                        raw_state, tok[:, None], caches, lens, decode_kernel,
                        paged_tables=tables, cache_valid=valid)
                    nxt, key, finished = sample(lg[:, -1], key, finished,
                                                stemps, stks)
                    nxt = jnp.where(act, nxt, tok)   # idle slots hold
                    return ((nxt, caches, lens + adv, key, finished),
                            (nxt, counts))

                (_, caches, _, _, _), (toks, stats) = jax.lax.scan(
                    body, (tok, caches, lens, key, jnp.zeros((S,), bool)),
                    jnp.arange(T))
                return (jnp.swapaxes(toks, 0, 1),
                        [kc for kc, _ in caches], [vc for _, vc in caches],
                        {k: jnp.sum(v, axis=0) for k, v in stats.items()})

            if bank_sig is None:
                return jax.jit(step, donate_argnums=donate)
            from ..inference.adapters import applied

            def lora_run(raw_state, tok, lens, act, lmax, tables, stemps,
                         stks, k_pages, v_pages, aidx, bank, key):
                with applied(bank, aidx):
                    return step(raw_state, tok, lens, act, lmax, tables,
                                stemps, stks, k_pages, v_pages, key)

            return jax.jit(lora_run, donate_argnums=donate)

        cache_key = ("decode_step", S, T, NB, kv_cache.signature(), eos,
                     str(ids_dtype), decode_kernel, bank_sig)
        run, compiled_now = self._runner_for(cache_key, make_run)

        was_training = self.training
        self.eval()
        try:
            args = (state, tokens, jnp.asarray(lengths, jnp.int32),
                    jnp.asarray(active, bool),
                    jnp.asarray(max_lens, jnp.int32),
                    jnp.asarray(block_tables, jnp.int32), temps, tks,
                    tuple(kv_cache.k_pages), tuple(kv_cache.v_pages),
                    *self._adapter_extra(adapters, adapter_slots, S),
                    jax.random.key(seed))
            flops = (self._flops_of(cache_key, run, args)
                     if self._wants_flops(timing_hook) else None)
            t0 = time.perf_counter()
            with RecordEvent("generate.decode_step"):
                toks, new_k, new_v, stats = run(*args)
                kv_cache.commit(new_k, new_v)
            self._emit_timing(timing_hook, "decode_step", S, 1, T,
                              compiled_now, t0, flops=flops, stats=stats)
            return Tensor(toks)
        finally:
            if was_training:
                self.train()

    def verify_step(self, chunk_ids, offsets, draft_lens, active, kv_cache,
                    block_tables, max_lens=None, temperature=0.0, top_k=0,
                    seed=0, decode_kernel="pallas", adapters=None,
                    adapter_slots=None, timing_hook=None):
        """Speculative draft verification over the paged pool (fixed width).

        One launch scores K drafted tokens per slot in a SINGLE forward
        through the same split-KV paged attention `prefill_chunk` uses (the
        chunk is a prefill-shaped call at per-slot offsets) and runs the
        Leviathan-et-al. rejection sampler entirely inside the traced
        program — no logits ever reach the host.

        chunk_ids:  [S, K+1] — position 0 is the slot's current input token
                    (last sampled, KV not yet written: the decode_step
                    convention); positions 1..K are its drafted tokens
                    (zeros past draft_lens).
        offsets:    [S] cache rows present per slot (the row position 0
                    writes).
        draft_lens: [S] valid drafts per slot; 0 degrades the slot to a
                    plain one-token decode THROUGH THE SAME PROGRAM, so
                    draft droughts and per-request spec-off never recompile.
        active:     [S] slot mask (idle slots write nothing, outputs held).
        max_lens:   [S] per-slot KV write ceiling (decode_step semantics):
                    rows >= max_lens are dropped by the OOB-scatter trick,
                    so over-speculation near a sequence's reserved budget
                    can never scatter into the table's pad page.

        Acceptance per slot, through the SAME traced temperature/top-k
        transform as _make_slot_sampler (temps <= 0 -> greedy): draft j is
        accepted iff every earlier draft was and — greedy — it equals the
        target argmax, or — sampled — u_j < p(d_j) under the target's
        (temperature/top-k-truncated) distribution. Our drafters are
        deterministic, so the draft distribution is a point mass and the
        paper's min(1, p/q) acceptance reduces to p(d_j). The token emitted
        after the accepted prefix is the corrected residual: the target
        distribution at the rejection position with the rejected draft
        masked out (exactly the renormalized max(p - q, 0) residual for a
        point-mass q — and in the greedy limit simply the argmax), or the
        bonus-position sample when every draft accepted. The output
        distribution is therefore EXACTLY the target model's — speculation
        changes latency, never the law of the tokens.

        Returns ([S] accepted_counts int32 in 0..K, [S] next tokens). KV
        rollback is length bookkeeping ONLY: the caller commits
        offsets + 1 + accepted rows. Rows beyond that hold rejected-draft
        KV, but every verify launch writes its FULL K+1-wide window, so the
        next launch for the slot overwrites the garbage before any
        in-budget position can attend to it — no block copies, ever."""
        ids = (chunk_ids._value if isinstance(chunk_ids, Tensor)
               else jnp.asarray(chunk_ids))
        S, W = ids.shape
        if W < 2:
            raise ValueError("verify_step needs at least one draft position "
                             f"(chunk width {W} = current token + K drafts)")
        K = W - 1
        decode_dtype = (jnp.dtype(kv_cache.dtype)
                        if kv_cache.dtype != jnp.float32 else None)
        state = self._decode_state(decode_dtype)
        ids_dtype = ids.dtype
        temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (S,))
        tks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (S,))
        NB = int(block_tables.shape[1])
        if max_lens is None:    # no ceiling: same program, permissive values
            max_lens = jnp.asarray(offsets, jnp.int32) + jnp.int32(W)
        bank_sig = None if adapters is None else adapters.signature()

        def make_run():
            donate = (9, 10) if self._pool_donation() else ()

            def step(raw_state, chunk, offs, dlens, act, lmax, tables,
                     stemps, stks, k_pages, v_pages, key):
                offs = offs.astype(jnp.int32)
                dlens = dlens.astype(jnp.int32)
                lmax = lmax.astype(jnp.int32)
                caches = list(zip(k_pages, v_pages))
                pos = jnp.arange(W, dtype=jnp.int32)[None, :]
                # the FULL chunk width writes (under the ceiling) — this is
                # what makes rollback pure bookkeeping: garbage rows from a
                # prior over-speculation sit inside the next launch's write
                # window and are overwritten before they become attendable
                valid = act[:, None] & ((offs[:, None] + pos) < lmax[:, None])
                logits, caches, _ = self._decode_call(
                    raw_state, chunk, caches, offs, decode_kernel,
                    paged_tables=tables, cache_valid=valid)
                lg32 = logits.astype(jnp.float32)            # [S, W, V]
                vocab = lg32.shape[-1]
                # per-POSITION temperature/top-k transform — the same math
                # as _make_slot_sampler, broadcast over the chunk axis, so
                # the verified distribution is the serving sampler's
                safe_t = jnp.where(stemps > 0, stemps, jnp.float32(1.0))
                scaled = lg32 / safe_t[:, None, None]
                sorted_desc = -jnp.sort(-scaled, axis=-1)
                k_idx = (jnp.clip(stks, 1, vocab) - 1).astype(jnp.int32)
                kth = jnp.take_along_axis(
                    sorted_desc,
                    jnp.broadcast_to(k_idx[:, None, None], (S, W, 1)),
                    axis=-1)
                cut = jnp.where((stks > 0)[:, None, None] & (scaled < kth),
                                jnp.finfo(jnp.float32).min, scaled)
                probs = jax.nn.softmax(cut, axis=-1)
                drafts = chunk[:, 1:].astype(jnp.int32)      # [S, K]
                p_draft = jnp.take_along_axis(
                    probs[:, :K, :], drafts[..., None], axis=-1)[..., 0]
                greedy_ok = drafts == jnp.argmax(lg32[:, :K, :], axis=-1)
                key, ku, ks = jax.random.split(key, 3)
                u = jax.random.uniform(ku, (S, K), jnp.float32)
                acc = jnp.where(stemps[:, None] > 0, u < p_draft, greedy_ok)
                live = (jnp.arange(K, dtype=jnp.int32)[None, :]
                        < dlens[:, None])
                acc = acc & live & act[:, None]
                prefix = jnp.cumprod(acc.astype(jnp.int32), axis=1)
                accepted = jnp.sum(prefix, axis=1)           # [S] in 0..K
                # logits at the accept point: position `accepted` saw the
                # accepted prefix as input, so its distribution is the
                # target's next-token law after those tokens
                nxt_lg = jnp.take_along_axis(
                    cut, accepted[:, None, None], axis=1)[:, 0]   # [S, V]
                # residual correction on a REAL rejection: zero out the
                # rejected draft token (for a point-mass draft distribution
                # the residual max(p - q, 0) is exactly p with p(d) removed,
                # renormalized — categorical over masked logits does that)
                rejected = accepted < dlens
                rej_tok = jnp.take_along_axis(
                    drafts, jnp.clip(accepted, 0, K - 1)[:, None],
                    axis=1)[:, 0]
                res_mask = (rejected[:, None]
                            & (jnp.arange(vocab, dtype=jnp.int32)[None, :]
                               == rej_tok[:, None]))
                nxt_lg = jnp.where(res_mask, jnp.finfo(jnp.float32).min,
                                   nxt_lg)
                sampled = jax.random.categorical(ks, nxt_lg, axis=-1)
                nxt = jnp.where(stemps > 0, sampled,
                                jnp.argmax(nxt_lg, axis=-1)).astype(ids_dtype)
                nxt = jnp.where(act, nxt, chunk[:, 0])   # idle slots hold
                accepted = jnp.where(act, accepted, 0)
                return (accepted, nxt, [kc for kc, _ in caches],
                        [vc for _, vc in caches])

            if bank_sig is None:
                return jax.jit(step, donate_argnums=donate)
            from ..inference.adapters import applied

            def lora_run(raw_state, chunk, offs, dlens, act, lmax, tables,
                         stemps, stks, k_pages, v_pages, aidx, bank, key):
                with applied(bank, aidx):
                    return step(raw_state, chunk, offs, dlens, act, lmax,
                                tables, stemps, stks, k_pages, v_pages,
                                key)

            return jax.jit(lora_run, donate_argnums=donate)

        cache_key = ("verify_step", S, W, NB, kv_cache.signature(),
                     str(ids_dtype), decode_kernel, bank_sig)
        run, compiled_now = self._runner_for(cache_key, make_run)

        was_training = self.training
        self.eval()
        try:
            args = (state, ids, jnp.asarray(offsets, jnp.int32),
                    jnp.asarray(draft_lens, jnp.int32),
                    jnp.asarray(active, bool),
                    jnp.asarray(max_lens, jnp.int32),
                    jnp.asarray(block_tables, jnp.int32), temps, tks,
                    tuple(kv_cache.k_pages), tuple(kv_cache.v_pages),
                    *self._adapter_extra(adapters, adapter_slots, S),
                    jax.random.key(seed))
            flops = (self._flops_of(cache_key, run, args)
                     if self._wants_flops(timing_hook) else None)
            t0 = time.perf_counter()
            with RecordEvent("generate.verify_step"):
                accepted, nxt, new_k, new_v = run(*args)
                kv_cache.commit(new_k, new_v)
            self._emit_timing(timing_hook, "verify_step", S, W, 1,
                              compiled_now, t0, flops=flops)
            return Tensor(accepted), Tensor(nxt)
        finally:
            if was_training:
                self.train()

    def generate_speculative(self, input_ids, max_new_tokens=32, spec_k=4,
                             drafter="ngram", temperature=0.0, top_k=0,
                             eos_token_id=None, seed=0, dtype="bfloat16",
                             decode_kernel="pallas", kv_cache=None,
                             stats=None):
        """Single-stream speculative decoding: draft K tokens on the host,
        verify them in ONE `verify_step` launch — the b1 fast path. Same
        return shape/semantics as `generate()` (prompt + new ids, EOS
        freeze) with provably the same output distribution; see
        inference/speculative.py for drafters and the driver."""
        from ..inference.speculative import speculative_generate

        return speculative_generate(
            self, input_ids, max_new_tokens=max_new_tokens, spec_k=spec_k,
            drafter=drafter, temperature=temperature, top_k=top_k,
            eos_token_id=eos_token_id, seed=seed, dtype=dtype,
            decode_kernel=decode_kernel, kv_cache=kv_cache, stats=stats)

    def compiled_prefill_chunk_runner(self, slots, chunk,
                                      adapter_signature=None):
        """The cached compiled prefill-chunk program
        (state, chunk, offsets, lens, tables, k_pages, v_pages, key) -> tok
        for a prior prefill_chunk() shape, or None (zoo lint + bench audit
        hook, the chunked twin of compiled_generate_paged_runner).
        `adapter_signature` selects the LoRA variant (bank-shape key);
        None matches the base program."""
        for k, run in (getattr(self, "_generate_cache", None) or {}).items():
            if (k[:3] == ("prefill_chunk", slots, chunk)
                    and k[-1] == adapter_signature):
                return run
        return None

    def compiled_decode_step_runner(self, slots, steps,
                                    adapter_signature=None):
        """The cached compiled decode-step program
        (state, tok, lens, active, tables, k_pages, v_pages, key) -> toks
        for a prior decode_step() shape, or None."""
        for k, run in (getattr(self, "_generate_cache", None) or {}).items():
            if (k[:3] == ("decode_step", slots, steps)
                    and k[-1] == adapter_signature):
                return run
        return None

    def compiled_verify_step_runner(self, slots, width,
                                    adapter_signature=None):
        """The cached compiled speculative verify program (state, chunk,
        offsets, draft_lens, active, max_lens, tables, temps, top_ks,
        k_pages, v_pages, key) -> (accepted, next) for a prior
        verify_step() shape, or None. `width` is the chunk width K+1."""
        for k, run in (getattr(self, "_generate_cache", None) or {}).items():
            if (k[:3] == ("verify_step", slots, width)
                    and k[-1] == adapter_signature):
                return run
        return None

    def compiled_step_program(self, kind, slots, width, args,
                              adapter_signature=None):
        """Lower + compile the cached step runner for `kind` (one of
        STEP_ARG_LABELS) at `args` and return the jax Compiled artifact,
        or None when the runner is not cached. This is the comms lint's
        window into the POST-SPMD program: `.as_text()` carries every
        collective GSPMD inserted and `input_shardings` the layouts it
        actually chose — neither exists on the traced/lowered forms."""
        runner = {
            "prefill_chunk": self.compiled_prefill_chunk_runner,
            "decode_step": self.compiled_decode_step_runner,
            "verify_step": self.compiled_verify_step_runner,
        }[kind](slots, width, adapter_signature)
        if runner is None:
            return None
        return runner.lower(*args).compile()
