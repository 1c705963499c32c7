"""Continuous (in-flight) batching scheduler over the paged KV pool.

Reference role: iteration-level scheduling from Orca (Yu et al., OSDI '22)
plus the chunked-prefill/decode interleaving of Sarathi-Serve (Agrawal et
al., OSDI '24), on the substrate PRs 1-3 built: block tables + atomic
reserve (kv_cache.py), deadline/shed/CAS semantics (resilience.py,
serving.py) and request-scoped tracing (observability/trace.py).

Shape of the thing — the fixed-batch `GenerateBatchingPredictor` runs one
compiled program per whole batch: a request arriving mid-cycle waits for the
next batch, a long prompt stalls every decoder batched with it, and a batch
is only as fast as its slowest member. `ContinuousGenerateBatchingPredictor`
replaces the per-batch launch with a persistent TICK loop over a fixed set
of S slots:

* admit  — each tick, queued requests take free slots by atomically
  reserving their blocks from the shared pool; a dry pool defers or sheds
  THAT request only (PR 2 semantics, `CacheOutOfBlocks` never touches
  batchmates).
* prefill — prompts are split into fixed-width chunks; each tick spends at
  most `prefill_token_budget` prompt tokens (across slots) in ONE
  `prefill_chunk` launch, so a 10k-token prompt never stalls in-flight
  decoders for more than a chunk's worth of compute (this is what bounds
  decode p99: docs/DEPLOYMENT.md, `prefill_token_budget`).
* decode — all decoding slots advance `decode_steps` tokens in ONE
  `decode_step` launch (a compiled scan: the host syncs per tick, not per
  token). Each launch goes out AHEAD of the read-back of the launch before
  it, taking the input tokens that launch makes from the device: in a
  prefill tick right behind the chunk (a slot the chunk completes starts
  from the chunk's token), in a stretch of pure decode behind the decode
  launch in flight (the last column of its tokens), so the host's
  dispatch, read-back bookkeeping and stream flushes run while the device
  works. A tick that is not a pure decode continuation (a prefill pick, a
  QoS pause, shutdown) lands a launch still in flight from the tick
  before first; `spec_k > 0` never runs ahead.
* retire — finished / EOS / deadline-expired / client-cancelled sequences
  free their blocks and slot at the next tick boundary; the freed slot is
  admissible on the same tick.

Both step programs are FIXED WIDTH (S slots, static chunk width, static
table width, per-slot active masks), so the scheduler runs exactly two
compiled programs forever — no shape-driven recompiles as sequences come
and go (the `recompile-hazard` lint rule gates this by construction;
analysis/zoo.py registers both programs). With ``spec_k > 0`` the decode
tick is replaced by the equally fixed-width speculative ``verify_step``
program (ISSUE-10): up to spec_k host-drafted tokens per slot are scored
in one prefill-shaped launch and accepted/rejected in-program, emitting
1 + accepted tokens per slot per tick with the output distribution
provably unchanged — still exactly two programs, still zero recompiles
across accept/reject/admit/retire patterns.

Everything the fixed-batch predictor guaranteed still holds per token-step:
one Deadline rides HTTP -> queue -> slot and expiry anywhere reaches exactly
ONE terminal outcome through the request CAS; a dying batcher thread
releases every slot's blocks and re-enqueues still-pending sequences before
the supervisor heals it; `close()` fails in-flight sequences with
ServiceUnavailable instead of stranding clients.
"""
from __future__ import annotations

import collections
import itertools
import queue
import threading

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis.lockwitness import make_lock
from ..models.generation import sampler_engages
from ..profiler.profiler import RecordEvent
from .faults import ThreadDeath
from .kv_cache import CacheOutOfBlocks
from .resilience import DeadlineExceeded, ServiceUnavailable
from .serving import _PENDING, GenerateBatchingPredictor
from .speculative import make_drafter
from .warmup import AOTWarmup
from .warmup import notify as _recompile_notify

__all__ = ["ContinuousGenerateBatchingPredictor", "phase_walls",
           "attribution_shares"]

_PREFILL, _DECODE = "prefill", "decode"


# The input tokens of a decode launch that goes out before the launch
# before it is read back, made on the device: one small program each, so
# the warm-up compiles them once and no first launch run ahead does.
@jax.jit
def _carry_tokens(toks, keep):
    """Each kept slot's last token of a decode launch's [S, T] tokens; 0
    for every other slot, as the host hands an idle one."""
    return jnp.where(keep, toks[:, -1], 0)


@jax.jit
def _chunk_tokens(first, chunk_tok, host_tok):
    """The chunk's token for a slot whose prompt it completes, the host's
    for every other."""
    return jnp.where(first, chunk_tok, host_tok)


def phase_walls(t0, t_admit, t_first, t_end, paused_s, paused_pre_s):
    """Decompose one request's wall time into phase walls (seconds).

    Pure function over the scheduler-clock stamps (ISSUE-18): acceptance
    (t0), slot admission (t_admit), first generated token (t_first, None if
    the request never produced one), terminal (t_end), plus total paused
    seconds and the portion paused before the first token. Returns
    (queue_s, prefill_s, paused_s, decode_s), each clamped >= 0:

    * queue   — acceptance to slot admission (never admitted: the whole
      life was queue wait).
    * prefill — admission to first token, minus pre-first-token pause time
      (no first token: everything after admission that wasn't a pause).
    * paused  — preemption park time, charged to its OWN phase: a paused
      sequence is neither prefilling nor decoding, and folding it into
      either would misattribute a scheduling decision as model latency.
    * decode  — first token to terminal, minus post-first-token pauses.
    """
    if t0 is None:
        return (0.0, 0.0, 0.0, 0.0)
    if t_admit is None:
        return (max(0.0, t_end - t0), 0.0, 0.0, 0.0)
    queue_s = max(0.0, t_admit - t0)
    paused_total = max(0.0, float(paused_s))
    paused_pre = min(paused_total, max(0.0, float(paused_pre_s)))
    if t_first is None:
        prefill_s = max(0.0, (t_end - t_admit) - paused_total)
        return (queue_s, prefill_s, paused_total, 0.0)
    prefill_s = max(0.0, (t_first - t_admit) - paused_pre)
    decode_s = max(0.0, (t_end - t_first) - (paused_total - paused_pre))
    return (queue_s, prefill_s, paused_total, decode_s)


def attribution_shares(queue_s, prefill_s, paused_s, decode_s):
    """Phase walls -> the terminal span's deadline-attribution tags.

    Shares are normalized by the walls' own sum so they add to 1.0 by
    construction (the property test's invariant); a zero-duration request
    (door rejection, instant shed) is all queue — the phase it died in."""
    total = queue_s + prefill_s + paused_s + decode_s
    if total <= 0.0:
        return {"queue_share": 1.0, "prefill_share": 0.0,
                "paused_share": 0.0, "decode_share": 0.0}
    return {"queue_share": round(queue_s / total, 6),
            "prefill_share": round(prefill_s / total, 6),
            "paused_share": round(paused_s / total, 6),
            "decode_share": round(decode_s / total, 6)}


class _SlotSeq:
    """One in-flight sequence bound to a scheduler slot."""

    __slots__ = ("req", "rid", "ids", "out_dtype", "plen", "pos", "tok",
                 "length", "generated", "table", "phase", "max_new", "order",
                 "temperature", "top_k", "spec", "prefix_hit", "digests",
                 "flushed", "adapter", "adapter_seed", "tenant", "priority",
                 "qos_held", "t_admit", "t_first", "t_last", "t_pause",
                 "paused_s", "paused_pre_s", "n_tok")

    def __init__(self, req, rid, ids, out_dtype, max_new, order):
        self.req = req
        self.rid = rid
        self.ids = ids              # int64 prompt (program input dtype)
        self.out_dtype = out_dtype  # client dtype, restored on finish
        self.plen = len(ids)
        self.pos = 0                # prefill progress (tokens in the cache)
        self.tok = 0                # next decode input (last sampled token)
        self.length = 0             # cache rows present
        self.generated: list[int] = []
        self.table = None           # np.int32 [table_width] page ids
        self.phase = _PREFILL
        self.max_new = int(max_new)
        self.order = order          # admit sequence number (FIFO fairness)
        # per-request sampling params: traced [S]-array inputs of the step
        # programs, so mixed-sampler slots share one compiled program
        self.temperature = float(req.temperature or 0.0)
        self.top_k = int(req.top_k or 0)
        # per-request speculation opt-out (X-Spec header); honored only
        # when the scheduler runs with spec_k > 0 — an opted-out slot rides
        # the same verify program with draft_len 0 (no recompile)
        self.spec = True if getattr(req, "spec", None) is None else bool(
            req.spec)
        # prefix-cache state (ISSUE-11): tokens satisfied from shared blocks
        # at admission, the prompt's full-block digest chain (for indexing
        # at prefill commit), and the streamed-token high-water mark
        self.prefix_hit = 0
        self.digests = None
        self.flushed = 0
        # per-request model delta (ISSUE-15): the adapter's bank row (0 =
        # base/identity) — a traced [S] step-program input, so heterogeneous
        # adapter mixes share one compiled program — and its registration
        # uid, which seeds the prefix-cache digest chain (KV isolation)
        self.adapter = 0
        self.adapter_seed = b""
        # multi-tenant QoS (ISSUE-17): resolved tenant name + priority tier
        # (lower = more urgent), and whether this sequence currently holds
        # its tenant's fair-share inflight count (pause releases it while
        # the blocks stay reserved)
        self.tenant = None
        self.priority = 0
        self.qos_held = False
        # phase attribution (ISSUE-18): scheduler-clock stamps — admission,
        # first/last generated token — plus paused-time accounting (total
        # seconds parked, the portion parked before the first token, and
        # the open pause interval's start). Pause time charges a distinct
        # `paused` phase: it is in neither TTFT's prefill nor TPOT's decode.
        self.t_admit = None
        self.t_first = None
        self.t_last = None
        self.t_pause = None
        self.paused_s = 0.0
        self.paused_pre_s = 0.0
        self.n_tok = 0      # tokens actually sampled (EOS freeze excluded)


class _DecodeLaunch:
    """One dispatched `decode_step` launch until it is read back: the
    slots it carries, the host arrays it was given (a launch dispatched
    ahead of it is built from them), its tokens still on the device, and
    the hook's record of it. `ahead`: it was dispatched before the launch
    before it had been read back."""

    __slots__ = ("picks", "lengths", "active", "maxlens", "temps", "tks",
                 "tables", "toks", "info", "t0", "ahead")

    def __init__(self, picks, lengths, active, maxlens, temps, tks, tables,
                 ahead):
        self.picks = picks
        self.lengths, self.active, self.maxlens = lengths, active, maxlens
        self.temps, self.tks, self.tables = temps, tks, tables
        self.ahead = ahead
        self.toks = self.info = None
        self.t0 = 0.0


class ContinuousGenerateBatchingPredictor(GenerateBatchingPredictor):
    """Token-level (continuous) scheduler for /generate over the paged pool.

    Knobs (see docs/DEPLOYMENT.md "Continuous batching"):

    max_slots            decode width S: concurrent in-flight sequences.
    prefill_chunk        static chunk width C — one slot's prefill quantum.
    prefill_token_budget max prompt tokens spent per tick across all slots
                         (default 2*C), oldest first; a slot's chunk is
                         never cut to what is left of it. Lower bounds
                         decode latency under long-prompt pressure; higher
                         finishes prompts sooner.
    decode_steps         tokens each decoding slot advances per tick (one
                         compiled scan). Higher amortizes dispatch; lower
                         tightens admit/retire granularity.
    max_seq_len          static per-sequence capacity (prompt + new tokens);
                         sets the block-table width of the two compiled
                         programs. Default: the whole pool for one sequence
                         (correct but widest table; size it to your real
                         longest request).
    max_new_tokens       server-wide output cap; `infer(max_new_tokens=n)`
                         requests fewer — the sequence retires at n and its
                         slot is reused immediately (the fixed-batch path
                         has no equivalent: every batch member decodes the
                         full cap).
    eos_token_id         optional early-exit token; on EOS the remainder is
                         frozen to EOS (sampler parity) and the slot retires.
    spec_k               speculative decoding width (ISSUE-10): when > 0 the
                         decode tick becomes one fixed-width `verify_step`
                         launch scoring up to spec_k host-drafted tokens per
                         slot — 1 + accepted tokens per launch, output
                         distribution unchanged. 0 (default) keeps the plain
                         decode_step tick.
    drafter              'ngram' (default; prompt-lookup, host-free) |
                         'self' (shallow-window reuse of the target model) |
                         any inference.speculative.Drafter instance.
    prefix_cache         content-addressed KV block sharing (ISSUE-11):
                         True builds a `PrefixCache` over this scheduler's
                         pool (pass an instance to share one across
                         predictors on the SAME pool). Admission consults
                         the index and a hit skips chunked prefill straight
                         to the first novel token — prefill cost ~O(new
                         tokens) on overlapping traffic, token-identical
                         output (greedy, sampled, and speculative paths).
                         Default False: the pool behaves exactly as before.
    admit_policy         'fifo' (default) | 'shortest_prompt_first': free
                         slots take the queued request with the shortest
                         prompt (ties to the most urgent deadline, then
                         arrival) — shorter prompts prefill in fewer chunks,
                         so slot turnover and aggregate goodput rise under
                         mixed-length pressure at the cost of bounded
                         long-prompt delay (they still admit whenever they
                         are the backlog minimum).
    warmup               ISSUE-13: True compiles every step program of this
                         configuration's compile-surface manifest
                         (analysis/compilesurface.py) on a background
                         "aot-warmup" thread before `ready()` reports True —
                         /readyz stays 503 until the first request can run
                         without a cold build. Once warmup covers the
                         manifest, the post-ready compile SENTINEL arms: any
                         later cold build increments
                         `paddle_serving_recompiles_total{component,program}`
                         and notifies the chaos-suite witness
                         (inference/warmup.py). Default False: ready
                         immediately, programs build lazily, sentinel off.
    compile_cache_dir    optional persistent XLA compile-cache directory
                         (warmup runs point the process at it unless
                         JAX_COMPILATION_CACHE_DIR already names one — the
                         environment wins, jit/compile_cache.py); a restarted
                         process reuses the serialized executables and pays
                         trace time only — the docs/DEPLOYMENT.md cold-start
                         runbook knob. Meaningful with warmup=True.
    hbm_budget           ISSUE-14: per-chip HBM budget in bytes. When set
                         (and no explicit kv_cache/num_blocks), the pool is
                         sized FROM the residency plan — analysis/hbm.py
                         ``plan_kv_pool`` takes what fits the budget after
                         params + headroom, clamped to what max_slots x
                         max_seq_len requests can actually reach — and the
                         plan publishes ``paddle_hbm_planned_bytes{
                         component=params|kv_pool|prefix_tier|temps|
                         adapter_bank}`` next
                         to ``paddle_hbm_budget_bytes``. ValueError when the
                         budget cannot fit even one sequence's blocks.
                         Default None: num_blocks is taken as given.
    adapters             ISSUE-15: an `inference.adapters.AdapterRegistry`
                         over THIS model — multi-LoRA serving. Every step
                         launch grows a traced [S] bank-index input;
                         `infer(adapter=name)` (HTTP `X-Adapter`) routes a
                         request through its adapter's low-rank delta while
                         base requests ride bank slot 0 (identity) of the
                         SAME program. Load/unload/mix changes never
                         recompile; admission refcount-pins the slot so an
                         unload can't race in-flight traffic. Default None:
                         base model only, step programs keep their exact
                         pre-adapter signature.
    qos                  ISSUE-17: an `inference.qos.TenantLedger` — multi-
                         tenant weighted fair-share admission, per-tenant
                         token-budget rate limits (429 + computed
                         Retry-After at the admission door) and priority
                         preemption: a strictly more urgent waiting request
                         PAUSES the least urgent running sequence (blocks
                         retained, slot state parked, tick width freed) and
                         the paused sequence resumes bit-exactly later
                         through the same continuation bookkeeping a
                         prefix-cache hit uses. Pause/resume and tenant mix
                         are host-side only: ZERO new compiled programs.
                         Share ONE ledger across a fleet's replicas for
                         global buckets. Default None: untenanted traffic,
                         admission exactly as before.
    slo                  ISSUE-18: an `observability.slo.SLOMonitor` —
                         retirement feeds it per-tenant TTFT/TPOT samples
                         and every terminal CAS feeds availability
                         (good = the outcome's HTTP status < 500), and it
                         exports `paddle_slo_error_budget_remaining{slo}` /
                         `paddle_slo_burn_rate{slo,window}` on this
                         scheduler's registry. With a flight recorder also
                         installed, a policy's not-alerting -> alerting
                         edge triggers an automatic ring dump (the breach
                         ships its own postmortem). Default None: no SLO
                         series (gauges exist iff a policy is installed).
    flight_recorder      ISSUE-18: per-tick postmortem ring. True builds a
                         default `observability.flightrecorder.
                         FlightRecorder`; an int sets its capacity; pass an
                         instance to share/configure. Each tick appends a
                         snapshot (slot map with tenant/adapter/phase,
                         batch widths, KV block accounting, paused/pending
                         depths, ledger fair-ratios) — dumped on demand
                         (`/debug/ticks`), on SLO alert, and by the chaos
                         conftest fixture on test failure. Overhead is
                         bench-gated <= 5% (slo_observability leg). Default
                         False: no capture, tick loop byte-identical.
    utilization          ISSUE-19: per-tick FLOPs attribution, shown. Every
                         scheduler keeps an `observability.utilization.
                         UtilizationLedger` of its ticks — wall split into
                         dispatch, read-back wait and host gap; issued /
                         useful / pad positions and live / table K,V rows
                         a launch — readable through `utilization.
                         ledgers()`, also after close(). True adds the
                         FLOPs probe (cost_flops on the lowered runner, one
                         trace per program key: issued FLOPs split into
                         useful / pad / spec_waste with EXACT integer
                         conservation, useful FLOPs billed per tenant —
                         paused time never bills, preempted sequences are
                         off-slot) and SHOWS the ledger: `self.util`,
                         `paddle_serving_flops_total{kind}`,
                         `paddle_tenant_flops_total{tenant}`,
                         `paddle_serving_host_gap_seconds`, (with a known
                         device peak) `paddle_serving_mfu`, JSON at
                         `/utilization`, per-tick fields on the flight
                         ring. Pass an instance to configure (injected
                         clock / peak_flops). Overhead is bench-gated <= 5%
                         (serving_utilization leg) with zero new compiled
                         programs. Default False: nothing exported,
                         launches carry no flops probe.
    """

    _component = "continuous"
    supports_sampler_knobs = True   # serving.py gates per-request headers
    supports_streaming = True       # tick-boundary flushes -> infer_stream

    @property
    def supports_adapters(self):
        """X-Adapter gate (serving.py): routing needs an actual registry —
        a continuous scheduler without one 400s the header like any
        whole-batch predictor would."""
        return getattr(self, "adapters", None) is not None

    @property
    def supports_tenants(self):
        """X-Tenant gate (serving.py): tenant routing needs a TenantLedger
        (qos= knob) — same strict 400 taxonomy as X-Adapter."""
        return getattr(self, "qos", None) is not None

    def __init__(self, model, max_slots=8, prefill_chunk=16,
                 prefill_token_budget=None, decode_steps=4, max_seq_len=None,
                 eos_token_id=None, max_defers=32, spec_k=0, drafter="ngram",
                 admit_policy="fifo", prefix_cache=False, warmup=False,
                 compile_cache_dir=None, hbm_budget=None, adapters=None,
                 qos=None, slo=None, flight_recorder=False,
                 utilization=False, **kwargs):
        self.max_slots = int(max_slots)
        self.prefill_chunk = int(prefill_chunk)
        self.prefill_token_budget = int(prefill_token_budget
                                        if prefill_token_budget is not None
                                        else 2 * self.prefill_chunk)
        if self.prefill_token_budget < self.prefill_chunk:
            raise ValueError("prefill_token_budget must cover at least one "
                             "chunk")
        self.decode_steps = int(decode_steps)
        self.eos_token_id = (None if eos_token_id is None
                             else int(eos_token_id))
        self.spec_k = int(spec_k)
        if self.spec_k < 0:
            raise ValueError("spec_k must be >= 0")
        self._drafter = (make_drafter(drafter, model) if self.spec_k > 0
                         else None)
        if admit_policy not in ("fifo", "shortest_prompt_first"):
            raise ValueError(f"unknown admit_policy {admit_policy!r} "
                             "(fifo | shortest_prompt_first)")
        self.admit_policy = admit_policy
        # reorder buffer for non-FIFO admission; deque: appends/pops are
        # atomic under the GIL (thread-lint atomic-type contract) — touched
        # by the batcher thread and by close()
        self._backlog: collections.deque = collections.deque()
        # speculation accounting (host ints; written under _slot_lock, read
        # by registry gauge scrapes from other threads)
        self._spec_drafted = 0
        self._spec_accepted = 0
        # per-tick RNG seed draw (atomic): sampling slots get fresh noise
        # each tick; greedy output is seed-independent (argmax)
        self._seed = itertools.count(1)
        # slot state exists BEFORE super().__init__ starts the loop thread
        # (prefix attrs too: the tick loop reads them; the real PrefixCache
        # is published below, after super() builds the kv pool — a tick
        # that races attachment just serves its admissions cold)
        self.prefix_cache = None
        self._prefix_hit_counter = None
        # AOT warmup state exists BEFORE super().__init__ too: the tick
        # loop's ready-gate preamble reads these from the batcher thread.
        # Events/deques only (thread-lint atomic-type contract) — the warm
        # thread writes, the batcher/readyz/test threads read.
        self.warmup = bool(warmup)
        self.compile_cache_dir = compile_cache_dir
        self._warm_done = threading.Event()
        self._warm_armed = threading.Event()
        self._warm_stats: collections.deque = collections.deque(maxlen=8)
        self._warm_errors: collections.deque = collections.deque(maxlen=8)
        self._warm_thread = None
        self._recompile_counter = None
        self._slots: list = [None] * self.max_slots
        # multi-LoRA registry (ISSUE-15): published before super().__init__
        # starts the tick thread — ticks read it, admission pins slots in it
        self.adapters = adapters
        self._lora_requests_counter = None
        # multi-tenant QoS ledger (ISSUE-17): published before the tick
        # thread starts; _qos_admit reads it. Paused (preempted) sequences
        # park in a deque (documented-atomic type): appended/removed by the
        # batcher thread, scraped by gauges and pending() from others.
        self.qos = qos
        self._paused: collections.deque = collections.deque()
        # ISSUE-18 SLO monitor + flight recorder: published before the tick
        # thread starts (the tick loop's retirement paths and _flight_tick
        # read them); the histograms/gauges bind after super() like every
        # other metric family — no request can be in flight until __init__
        # returns, so the late bind is unobservable
        self.slo = slo
        if flight_recorder is False or flight_recorder is None:
            self.flight = None
        elif flight_recorder is True:
            from ..observability.flightrecorder import FlightRecorder
            self.flight = FlightRecorder()
        elif isinstance(flight_recorder, int):
            from ..observability.flightrecorder import FlightRecorder
            self.flight = FlightRecorder(capacity=flight_recorder)
        else:
            self.flight = flight_recorder
        # Every scheduler keeps a tick ledger (time split, positions, K,V
        # rows: plain integers off the tick's own numbers), published before
        # the tick thread starts. `utilization=` decides what is SHOWN and
        # probed: the `util` property is the ledger when it is on and None
        # when off — the exported series, /utilization and the flight ring's
        # `util` block key on it — and the timing hook grows its wants_flops
        # marker only then, which is what gates the one-trace-per-program
        # FLOPs probe in generation.py.
        from ..observability.utilization import UtilizationLedger
        self._util_shown = utilization is not False and utilization is not None
        self._ledger = (utilization if self._util_shown
                        and utilization is not True else UtilizationLedger())
        self._last_launch = None        # tick-thread-only hook stash
        # the decode launch dispatched ahead of the tick that reads it back
        # (a _DecodeLaunch; tick thread only)
        self._ahead = None
        hook = self._gen_timing
        if self._util_shown:
            def hook(info, _h=self._gen_timing):
                _h(info)
            hook.wants_flops = True
        self._timing_hook = hook
        self._ttft_hist = None
        self._tpot_hist = None
        # gauges scrape from other threads; witness-wrapped under chaos
        self._slot_lock = make_lock(
            "scheduler.ContinuousGenerateBatchingPredictor._slot_lock")
        self.max_seq_len = None             # finalized below (needs kv_cache)
        self.table_width = None
        # ISSUE-14: hbm_budget= sizes the pool FROM the residency plan
        # (analysis/hbm.py plan_kv_pool) instead of taking num_blocks on
        # faith — the static lint and the runtime share one arithmetic.
        self.hbm_budget = None if hbm_budget is None else int(hbm_budget)
        self._hbm_plan = None
        if (self.hbm_budget is not None and kwargs.get("kv_cache") is None
                and "num_blocks" not in kwargs):
            from ..analysis.hbm import params_bytes_of, plan_kv_pool

            sizing = plan_kv_pool(
                self.hbm_budget, cache_spec=model._decode_cache_spec(),
                block_size=kwargs.get("block_size", 32),
                slots=self.max_slots, max_seq_len=max_seq_len,
                params_bytes=params_bytes_of(model),
                name=self._component, prefill_chunk=self.prefill_chunk,
                decode_steps=self.decode_steps, spec_k=self.spec_k,
                eos_token_id=self.eos_token_id,
                adapter_bank_bytes=(0 if adapters is None
                                    else adapters.bank_bytes()))
            kwargs["num_blocks"] = sizing["num_blocks"]
            self._hbm_plan = sizing["plan"]
        kwargs.setdefault("launch_rows",
                          max(self.prefill_chunk, self.spec_k + 1))
        super().__init__(model, max_batch_size=max_slots,
                         max_defers=max_defers, **kwargs)
        pool_tokens = self.kv_cache.num_blocks * self.kv_cache.block_size
        self.max_seq_len = int(max_seq_len) if max_seq_len else pool_tokens
        if self.max_seq_len > pool_tokens:
            raise ValueError(f"max_seq_len {self.max_seq_len} exceeds the "
                             f"pool ({pool_tokens} tokens)")
        self.table_width = self.kv_cache.blocks_for(self.max_seq_len)
        if any(c.window is not None for c in self.kv_cache.spec.layers) and (
                prefix_cache or qos is not None):
            # a window layer's ring belongs to a slot and holds only the
            # last rows: a shared prefix has none there, and a sequence
            # paused out of its slot loses them
            raise ValueError("a model whose layers keep a window cannot be "
                             "served with prefix_cache or qos preemption")
        (self._spec_counter, self._lora_requests_counter,
         self._ttft_hist, self._tpot_hist) = self._bind_scheduler_metrics()
        if prefix_cache:
            from .prefix_cache import PrefixCache
            pc = (prefix_cache if isinstance(prefix_cache, PrefixCache)
                  else PrefixCache(self.kv_cache, faults=self._faults))
            pc.bind_metrics(self.metrics.registry, component=self._component)
            self._prefix_hit_counter = self.metrics.registry.counter(
                "paddle_prefix_hit_tokens_total",
                "Prompt tokens served from shared prefix blocks instead of "
                "prefill compute", labels=("component",)).labels(
                    self._component)
            self.prefix_cache = pc      # published last: counter is ready
        # ISSUE-13 post-ready compile sentinel: counter exists before the
        # warm thread can arm it (the only reader of _recompile_counter is
        # the armed branch of _gen_timing, and arming happens on this thread)
        self._recompile_counter = self.metrics.registry.counter(
            "paddle_serving_recompiles_total",
            "Post-ready step-program cold builds by program — stays 0 when "
            "the AOT warmup covered the compile-surface manifest "
            "(analysis/compilesurface.py)", labels=("component", "program"))
        if self.warmup and not self.fallback_dense:
            self._warm_thread = threading.Thread(
                target=self._warm_start, name="aot-warmup", daemon=True)
            self._warm_thread.start()
        else:
            # nothing to compile ahead of time (or the dense fallback path
            # owns its own cache): ready immediately, sentinel stays off
            self._warm_done.set()

    # ------------------------------------------------------------ AOT warmup
    def _warm_start(self):
        """Body of the aot-warmup thread: compile the manifest, then gate.

        A warmup FAILURE never wedges readiness — the predictor serves cold
        exactly as if warmup were off, with the error recorded in
        warm_errors() and the sentinel left unarmed (a cold build after a
        failed warmup is expected, not a violation)."""
        try:
            stats = AOTWarmup(self, cache_dir=self.compile_cache_dir,
                              tracer=self.tracer).run()
            self._warm_carry()
            self._warm_stats.append(stats)
            if not stats["missing"] and not self._stop.is_set():
                self._warm_armed.set()
        except Exception as e:            # noqa: BLE001 — recorded, not fatal
            self._warm_errors.append(e)
        finally:
            self._warm_done.set()

    def _warm_carry(self):
        """Compile the two programs that hand a decode launch its tokens on
        the device (`_carry_tokens`, `_chunk_tokens`) at this scheduler's
        shapes: the first launch run ahead then compiles nothing."""
        if self.spec_k > 0:
            return
        S = self.max_slots
        idle = np.zeros(S, bool)
        _carry_tokens(jnp.zeros((S, self.decode_steps), jnp.int64), idle)
        _chunk_tokens(idle, jnp.zeros(S, jnp.int64), np.zeros(S, np.int64))

    def warm_stats(self):
        """Latest AOT warmup stats dict (programs/compiled/missing/
        fingerprints/seconds), or None before the first run finishes."""
        return self._warm_stats[-1] if self._warm_stats else None

    def warm_errors(self):
        return list(self._warm_errors)

    def ready(self) -> bool:
        """/readyz gate (ISSUE-13): False until the AOT warmup finished
        (instantly true with warmup=False) and while shutting down. The
        fleet router skips not-ready replicas (`ReplicaFleet._pick`), so a
        warming replica joins rotation only once its programs are built."""
        return self._warm_done.is_set() and not self._stop.is_set()

    # ------------------------------------------------------------- telemetry
    def _bind_scheduler_metrics(self):
        reg = self.metrics.registry
        slots = reg.gauge(
            "paddle_sched_slots",
            "Continuous-scheduler slots by phase; "
            "prefill + decode + free == slot count",
            labels=("component", "phase"))
        slots.labels(self._component, _PREFILL).set_function(
            lambda: self._phase_count(_PREFILL))
        slots.labels(self._component, _DECODE).set_function(
            lambda: self._phase_count(_DECODE))
        slots.labels(self._component, "free").set_function(
            lambda: self.max_slots - self._phase_count(None))
        reg.gauge(
            "paddle_sched_slot_count", "Configured continuous-scheduler "
            "slot width S", labels=("component",)).labels(
                self._component).set_function(lambda: self.max_slots)
        reg.gauge(
            "paddle_sched_prefill_token_budget",
            "Max prompt tokens spent per tick across slots (chunked "
            "prefill knob)", labels=("component",)).labels(
                self._component).set_function(
                    lambda: self.prefill_token_budget)
        reg.gauge(
            "paddle_sched_prefill_backlog_tokens",
            "Prompt tokens still to prefill across in-flight slots",
            labels=("component",)).labels(self._component).set_function(
                self._prefill_backlog)
        # speculative decoding accounting (ISSUE-10): drafted / accepted /
        # wasted token counters plus the derived acceptance-rate gauge —
        # THE dial that says whether spec_k is paying for its verify width.
        # Returned (not self-assigned) so the _spec_counter attribute write
        # happens in __init__, before any worker thread can observe it.
        # ISSUE-14 residency gauges: the plan the hbm_budget= knob sized the
        # pool from, component-by-component, next to the declared budget —
        # a scrape shows plan vs actual (paddle_kv_pool_per_chip_bytes is
        # the pool's own ground truth to reconcile against). Absent when the
        # knob is off: a gauge that would always read 0 is noise.
        if self._hbm_plan is not None:
            reg.gauge(
                "paddle_hbm_budget_bytes",
                "Declared per-chip HBM budget the serving plan was sized "
                "against (scheduler hbm_budget= knob)",
                labels=("component",)).labels(self._component).set(
                    self.hbm_budget)
            planned = reg.gauge(
                "paddle_hbm_planned_bytes",
                "Planned per-chip HBM residency by plan component "
                "(analysis/hbm.py DeploymentPlan)", labels=("component",))
            for part, nbytes in self._hbm_plan.components().items():
                planned.labels(part).set(nbytes)
        # ISSUE-15 multi-LoRA telemetry: bank occupancy by state (loaded =
        # resident, pinned = refcounted by in-flight slots, free = open
        # rows) plus per-adapter admission counts. Absent without a
        # registry — same no-dead-gauges policy as the hbm block above.
        # Returned (like spec_counter) so the attribute write lands in
        # __init__, before any worker thread can observe it.
        lora_counter = None
        if self.adapters is not None:
            lora = reg.gauge(
                "paddle_lora_adapters",
                "Adapter bank slots by state (loaded|pinned|free); slot 0 "
                "(base identity) is not counted",
                labels=("component", "state"))
            for state in ("loaded", "pinned", "free"):
                lora.labels(self._component, state).set_function(
                    lambda st=state: self.adapters.stats()[st])
            lora_counter = reg.counter(
                "paddle_lora_requests_total",
                "Admitted sequences by adapter name ('base' = no adapter)",
                labels=("component", "adapter"))
        # ISSUE-17 multi-tenant QoS telemetry: the ledger's tenant series
        # (requests/tokens/rate-limited/inflight — bound ONCE per registry,
        # fleet replicas sharing a ledger are no-ops) plus this scheduler's
        # own paused-width gauge and per-tenant backlog (scrape-time queue
        # scan: no incremental counters to drift across defer/requeue).
        if self.qos is not None:
            self.qos.bind_metrics(reg)
            reg.gauge(
                "paddle_sched_paused",
                "Preempted sequences parked off-slot (blocks retained; "
                "resumed through the prefix-hit continuation path)",
                labels=("component",)).labels(self._component).set_function(
                    lambda: float(len(self._paused)))
            backlog = reg.gauge(
                "paddle_tenant_backlog",
                "Queued (not yet slotted) requests by tenant on this "
                "scheduler (autoscaler pressure signal)",
                labels=("component", "tenant"))
            for name in self.qos.tenant_names():
                backlog.labels(self._component, name).set_function(
                    lambda n=name: float(self.tenant_backlog().get(n, 0)))
        spec_counter = reg.counter(
            "paddle_spec_tokens_total",
            "Speculative decoding tokens by kind: drafted (submitted to "
            "verify), accepted (kept), wasted (drafted - accepted)",
            labels=("component", "kind"))
        reg.gauge(
            "paddle_spec_acceptance_rate",
            "Cumulative speculative acceptance rate (accepted / drafted)",
            labels=("component",)).labels(self._component).set_function(
                self._acceptance_rate)
        # ISSUE-18 phase-attributed latency: TTFT (acceptance -> first
        # generated token) and TPOT (mean inter-token gap after the first,
        # with pause time excluded — preemption is a scheduling decision,
        # not model latency) per tenant. Untenanted traffic rides the
        # "default" label, so the families are live on every continuous
        # scheduler — retirement always observes them.
        from ..observability.metrics import DEFAULT_LATENCY_BUCKETS
        ttft_hist = reg.histogram(
            "paddle_serving_ttft_seconds",
            "Time to first generated token (acceptance -> first token) by "
            "tenant; door-rejected requests are never sampled",
            labels=("component", "tenant"), buckets=DEFAULT_LATENCY_BUCKETS)
        tpot_hist = reg.histogram(
            "paddle_serving_tpot_seconds",
            "Mean time per output token after the first (paused time "
            "excluded) by tenant",
            labels=("component", "tenant"),
            buckets=(0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05,
                     0.1, 0.25, 0.5, 1.0, 2.5))
        # SLO gauges exist IFF a monitor is installed (exposition-lint
        # contract); with a flight recorder too, an alert edge dumps the
        # ring — the breach window's slot state survives the incident.
        if self.slo is not None:
            self.slo.bind_metrics(reg)
            if self.flight is not None:
                self.slo.on_alert(
                    lambda p: self.flight.mark_alert(
                        p.name, state=p.state(),
                        burn_fast=round(p.burn_rate("fast"), 4),
                        burn_slow=round(p.burn_rate("slow"), 4)))
        # ISSUE-19 utilization series exist IFF `utilization=` is on (same
        # absent-iff-off exposition contract); the MFU gauge additionally
        # needs a known device peak — the ledger itself enforces that.
        if self._util_shown:
            self._ledger.bind_metrics(reg, component=self._component)
            self.metrics.attach_utilization(self._ledger)
        if self.flight is not None:
            occ = reg.gauge(
                "paddle_flightrec_ticks",
                "Flight-recorder ring state (occupancy = retained tick "
                "snapshots, capacity = ring bound, dropped = evicted)",
                labels=("component", "state"))
            occ.labels(self._component, "occupancy").set_function(
                lambda: float(self.flight.occupancy))
            occ.labels(self._component, "capacity").set_function(
                lambda: float(self.flight.capacity))
            occ.labels(self._component, "dropped").set_function(
                lambda: float(self.flight.dropped))
        return spec_counter, lora_counter, ttft_hist, tpot_hist

    def _acceptance_rate(self):
        with self._slot_lock:
            d, a = self._spec_drafted, self._spec_accepted
        return a / d if d else 0.0

    def _gen_timing(self, info):
        """Stash the launch's hook record for the tick function that made
        it: the launch-latency histogram is observed AFTER the read-back
        (`_launch_done`), because the hook fires when the launch has been
        dispatched, not when the device has finished. The base hook also
        counts batch*new_tokens as generated, but a tick's width includes
        masked idle slots — actual tokens are counted per sequence at
        retirement (_retire_ok) instead.

        Doubles as the post-ready compile sentinel's tap (ISSUE-13): once
        the AOT warmup armed it, any launch that had to cold-build its step
        program is a compile-surface violation — counted per program and
        reported to the chaos-suite witness (inference/warmup.py)."""
        self._last_launch = info    # tick fns read flops/dispatch_s/compiled
        if info["compiled"] and self._warm_armed.is_set():
            self._recompile_counter.labels(
                self._component, info["path"]).inc()
            _recompile_notify(self._component, info["path"])

    def _phase_count(self, phase):
        with self._slot_lock:
            if phase is None:       # live count
                return sum(1 for s in self._slots if s is not None)
            return sum(1 for s in self._slots
                       if s is not None and s.phase == phase)

    def _prefill_backlog(self):
        with self._slot_lock:
            return sum(s.plen - s.pos for s in self._slots
                       if s is not None and s.phase == _PREFILL)

    # ---------------------------------------------------------------- client
    def infer(self, ids, timeout=None, deadline=None, trace_id=None,
              max_new_tokens=None, temperature=None, top_k=None, spec=None,
              adapter=None, tenant=None):
        """One prompt in -> prompt + generated ids out.

        `max_new_tokens` (<= the server cap) asks for fewer tokens than the
        server-wide maximum; the sequence retires the moment it has them and
        its slot/blocks go to the next request — the aggregate-throughput
        win whole-request batching cannot give.

        `temperature` / `top_k` are PER-REQUEST sampler knobs (default
        greedy). They ride the step programs as traced per-slot arrays, so
        a greedy request and a temperature-0.8/top-k-40 request decode in
        the SAME tick of the SAME compiled program — mixed-sampler traffic
        never forks step programs (recompile-sentinel-pinned in tests).

        `spec` (tri-state) opts this request out of speculative decoding
        (`spec=False`) when the scheduler runs with spec_k > 0: the slot
        rides the same verify program with zero drafts. `spec=True` is a
        no-op beyond the default; it cannot force speculation on a
        scheduler configured without it.

        `adapter` (ISSUE-15) names a registered LoRA adapter; the request
        decodes through its low-rank delta in the SAME tick program as base
        and other-adapter batchmates. Unknown names (and any adapter on a
        registry-less scheduler) raise ValueError here, synchronously —
        HTTP maps it to 400, the X-Temperature taxonomy.

        `tenant` (ISSUE-17) bills the request to a TenantLedger tenant:
        weighted fair-share admission, the tenant's token-budget rate
        limit at the door (429 + computed Retry-After), and its priority
        tier for preemption. Unknown names (and any tenant on a
        ledger-less scheduler) raise ValueError — the X-Adapter taxonomy;
        None rides the ledger's built-in default tenant."""
        req = self._make_request([np.asarray(ids)], timeout, deadline,
                                 trace_id)
        if max_new_tokens is not None:
            req.max_new = max(1, min(int(max_new_tokens),
                                     self.max_new_tokens))
        if temperature is not None:
            req.temperature = float(temperature)
        if top_k is not None:
            req.top_k = int(top_k)
        if spec is not None:
            req.spec = bool(spec)
        self._route_adapter(req, adapter)
        self._route_tenant(req, tenant)
        return self._submit(req)

    def _route_adapter(self, req, adapter):
        """Validate-and-attach for infer/infer_stream's adapter= param.

        The name is checked NOW (a malformed request must fail before
        enqueue, 400-style) but resolved to a bank slot at ADMISSION —
        acquire() there takes the refcount pin for exactly the sequence's
        lifetime, and an unregister between submit and admit is then an
        admission failure, never a stale slot index."""
        if adapter is None:
            return
        if self.adapters is None:
            raise ValueError(
                "adapter routing needs an AdapterRegistry (scheduler "
                "adapters= knob); this scheduler serves the base model only")
        if not self.adapters.has(adapter):
            raise ValueError(f"unknown adapter {adapter!r}")
        req.adapter = adapter

    def _route_tenant(self, req, tenant):
        """Validate-and-attach for infer/infer_stream's tenant= param:
        unknown names fail NOW (400-style, before enqueue), None resolves
        to the ledger's default tenant, and a tenant on a ledger-less
        scheduler is a client misroute (same contract as _route_adapter)."""
        if tenant is None:
            if self.qos is not None:
                req.tenant = self.qos.resolve(None).name
            return
        if self.qos is None:
            raise ValueError(
                "tenant routing needs a TenantLedger (scheduler qos= "
                "knob); this scheduler serves untenanted traffic only")
        req.tenant = self.qos.resolve(tenant).name  # ValueError: unknown

    def infer_stream(self, ids, timeout=None, deadline=None, trace_id=None,
                     max_new_tokens=None, temperature=None, top_k=None,
                     spec=None, adapter=None, tenant=None):
        """Streaming twin of infer() (ISSUE-11): tokens arrive as the tick
        loop absorbs them instead of at retirement.

        Admission-time failures (ServerBusy / circuit open / malformed
        request) raise HERE, synchronously — an HTTP front end still maps
        them to proper 4xx/5xx statuses because no response bytes have
        flushed yet. The return value is an iterator yielding int64 arrays
        of newly generated tokens per tick-boundary flush; their
        concatenation is exactly infer()'s generated suffix (same sampler,
        same programs — streaming changes WHEN tokens are delivered, never
        WHICH). Terminal failures after acceptance (deadline mid-stream,
        shed, batch error) raise from the iterator; deadline semantics are
        identical to _await's client-side cancel."""
        req = self._make_request([np.asarray(ids)], timeout, deadline,
                                 trace_id)
        if max_new_tokens is not None:
            req.max_new = max(1, min(int(max_new_tokens),
                                     self.max_new_tokens))
        if temperature is not None:
            req.temperature = float(temperature)
        if top_k is not None:
            req.top_k = int(top_k)
        if spec is not None:
            req.spec = bool(spec)
        self._route_adapter(req, adapter)
        self._route_tenant(req, tenant)
        q: queue.Queue = queue.Queue()
        req.on_tokens = q.put       # published before enqueue (no races)
        self._start(req)            # raises Rejected/ValueError/503 here
        return self._stream_pump(req, q)

    def _stream_pump(self, req, q):
        """Generator half of infer_stream: drain the flush queue, mirroring
        _await's deadline-cancel / supervisor-heal loop between flushes."""
        try:
            while True:
                if req.deadline is None:
                    step = 0.1
                else:
                    rem = req.deadline.remaining()
                    if rem <= 0:
                        if req.cancel():
                            self.metrics.inc("timeouts")
                            self._observe(req)
                            if req.trace is not None:
                                req.trace.finish("timeout", cas="timeout",
                                                 where="client_stream")
                            raise DeadlineExceeded(
                                "inference request timed out mid-stream")
                        break   # lost the race: terminal outcome landed
                    step = min(0.1, rem)
                try:
                    yield np.asarray(q.get(timeout=step), np.int64)
                    continue
                except queue.Empty:
                    pass
                if req.event.is_set():
                    break
                try:
                    if self._sup.heal():
                        self.metrics.inc("batcher_restarts")
                except ServiceUnavailable as e:
                    self._fail(req, e)
                    raise
            # flushes that landed between the last drain and the terminal CAS
            while True:
                try:
                    yield np.asarray(q.get_nowait(), np.int64)
                except queue.Empty:
                    break
            if req.error is not None:
                raise req.error
        except GeneratorExit:
            # consumer walked away mid-stream (client disconnect): same
            # terminal path as a client-side timeout — the tick loop
            # reclaims the slot at the next boundary
            if req.cancel():
                self.metrics.inc("timeouts")
                self._observe(req)
                if req.trace is not None:
                    req.trace.finish("timeout", cas="timeout",
                                     where="stream_abandoned")
            raise
        finally:
            req.on_tokens = None

    def _admission_check(self, arrays, req=None):
        plen = len(arrays[0])
        total = plen + self.max_new_tokens
        if total > self.max_seq_len:
            raise ValueError(
                f"request needs {total} tokens but max_seq_len is "
                f"{self.max_seq_len}; no retry can succeed")
        self.model._decode_validate(plen, self.max_new_tokens)
        need = self.kv_cache.blocks_for(total)
        self.admission.admit(self._queue.qsize(), cache=self.kv_cache,
                             blocks_needed=need)
        if self.qos is not None and req is not None:
            # tenant token-budget rate limit (ISSUE-17): charged at the
            # door with the request's worst-case token bill; a shed raises
            # ServerBusy carrying the bucket's computed time-to-refill —
            # HTTP 429 with a Retry-After derived from the tenant's rate
            want = (req.max_new if req.max_new is not None
                    else self.max_new_tokens)
            self.qos.charge(getattr(req, "tenant", None), plen + want)

    def pending(self) -> int:
        """Queued + in-flight + paused sequences (drain condition)."""
        return (self._queue.qsize() + len(self._backlog)
                + len(self._paused) + self._phase_count(None))

    def tenant_backlog(self) -> dict:
        """Queued (not yet slotted) PENDING requests by tenant: a
        scrape-time scan of the arrival queue + reorder backlog, so there
        is no incremental counter to drift across defer/retry/requeue
        paths. Feeds the paddle_tenant_backlog gauge and the autoscaler's
        per-tenant pressure signal."""
        if self.qos is None:
            return {}
        counts: dict = {}
        for r in list(self._queue.queue) + list(self._backlog):
            if r.state != _PENDING:
                continue
            name = self._tenant_spec_of(r).name
            counts[name] = counts.get(name, 0) + 1
        return counts

    def _tenant_spec_of(self, req):
        """Request -> TenantSpec; anything unroutable rides the default
        tenant (routing already 400'd truly unknown names — this is the
        tick loop, which must never fail on a stray request field)."""
        try:
            return self.qos.resolve(getattr(req, "tenant", None))
        except ValueError:
            return self.qos.resolve(None)

    # ------------------------------------------------------------- tick loop
    def _loop(self):
        # ISSUE-13 ready gate: no tick runs (and so no step program can
        # cold-build under traffic) until the aot-warmup thread finished.
        # Wait with a poll so close() during warmup still exits promptly.
        while self.warmup and not self._warm_done.wait(0.05):
            if self._stop.is_set():
                return
        if self.fallback_dense:
            # signature-mismatch degradation: the paged step programs would
            # scatter garbage; serve through the base collect-and-run loop
            # (GenerateBatchingPredictor._run_batch -> _run_dense)
            return super()._loop()
        try:
            while not self._stop.is_set():
                try:
                    if self._faults is not None:
                        self._faults.check("batcher.tick")  # ThreadDeath
                    # A tick is one pass with at least one live slot. A
                    # pass that finds slots live follows a tick directly:
                    # its window opens where that one closed, admission
                    # inside. A pass that finds none lets _admit park on
                    # the queue, and that wait (and the admission that
                    # ended it) is in no tick and no span.
                    contiguous = self._phase_count(None) > 0
                    if not contiguous:
                        self._admit()
                        if self._phase_count(None) == 0:
                            self._ledger.poll_session()
                            continue
                    tick = RecordEvent("serve.tick")
                    tick.begin()
                    self._ledger.tick_begin(contiguous)
                    try:
                        if contiguous:
                            self._admit_span()
                        if RecordEvent.capturing():
                            tick.set_stats(**self._tick_stats())
                        self._busy = True
                        with RecordEvent("serve.retire"):
                            self._retire_unserviceable()
                        self._prefill_tick()
                        self._decode_tick()
                        self._util_tick()       # close BEFORE the flight
                        self._flight_tick()     # ring captures last_tick
                    finally:
                        self._busy = False
                        tick.end()
                except ThreadDeath:
                    # the dying thread strands no sequence: blocks go back to
                    # the pool, pending requests re-enter the queue, and the
                    # supervisor-healed thread re-runs them from scratch
                    self._abandon_slots()
                    raise
        finally:
            if self._stop.is_set():
                self._shutdown_slots()

    def _free_slot(self):
        with self._slot_lock:
            for i, s in enumerate(self._slots):
                if s is None:
                    return i
        return None

    def _admit(self):
        """Fill free slots from the queue (one tick's admissions).

        The reserve is atomic: a request either ends up fully reserved in a
        slot or the pool is untouched. On a dry pool the request defers or
        sheds (existing `_shed_or_defer` budget) and admission STOPS for
        this tick — blocks free as other slots retire, so later ticks
        retry; already-running slots never notice.

        With a TenantLedger (qos= knob) admission routes through
        `_qos_admit` instead: free slots go to the most under-served
        tenant's waiting work (paused sequences compete with new arrivals),
        then strictly more urgent waiters preempt the least urgent running
        sequences."""
        if self.qos is not None:
            return self._qos_admit()
        block = self._phase_count(None) == 0    # idle: park, don't spin
        while True:
            idx = self._free_slot()
            if idx is None:
                return
            try:
                req = self._next_request(block)
            except queue.Empty:
                return
            block = False
            if not self._usable(req):
                continue
            if not self._install_seq(idx, req):
                return

    def _install_seq(self, idx, req) -> bool:
        """Admit ONE usable request into free slot `idx`: pin its adapter,
        consult the prefix cache, atomically reserve its blocks, and place
        the sequence. Returns False only on a dry pool (CacheOutOfBlocks →
        `_shed_or_defer`; the caller stops admitting this tick); every
        other failure is THIS request's terminal and admission continues."""
        arr = req.arrays[0]
        plen = len(arr)
        max_new = (req.max_new if req.max_new is not None
                   else self.max_new_tokens)
        seq_n = next(self._rid)     # atomic draw (itertools.count)
        rid = ("cseq", seq_n)
        tr = req.trace
        traced = self.tracer.enabled
        ids64 = np.asarray(arr, np.int64)
        # ISSUE-15: pin the request's adapter slot FIRST — acquire
        # bumps the bank-row refcount for exactly the sequence's
        # lifetime (released in _evict_slot), so an unregister racing
        # this admission either loses (we hold the pin) or wins (the
        # name is gone and THIS request fails 400-style; the batch is
        # untouched). The uid seed keys the prefix lookup below: same
        # tokens under a different adapter can never share KV.
        aslot, aseed = 0, b""
        if self.adapters is not None:
            aname = getattr(req, "adapter", None)
            try:
                aslot, aseed = self.adapters.acquire(aname)
            except ThreadDeath:
                raise
            except Exception as e:
                self._fail(req, e)
                return True
            self._lora_requests_counter.labels(
                self._component,
                "base" if aname is None else aname).inc()
        hit, t_px = None, 0.0
        pc = self.prefix_cache
        if pc is not None:
            t_px = self.tracer.now_us() if traced else 0.0
            try:
                hit = pc.lookup(ids64, seed=aseed)  # kv.prefix_match
            except ThreadDeath:
                raise
            except Exception as e:
                # a broken index lookup is a cache MISS, never a failed
                # request — the cold path below is always correct
                if traced and tr is not None:
                    tr.child("prefix_lookup", t_px, self.tracer.now_us(),
                             error=repr(e))
                hit = None
        t_kv = self.tracer.now_us() if traced else 0.0
        try:
            self.kv_cache.reserve(
                rid, plen + max_new,
                shared=hit.pairs if hit is not None else None)
        except CacheOutOfBlocks as e:
            if traced and tr is not None:
                tr.child("kv_reserve", t_kv, self.tracer.now_us(),
                         error=repr(e))
            if self.adapters is not None:
                self.adapters.release(aslot)
            self._shed_or_defer(req, e)
            return False
        except Exception as e:
            # an eviction-path fault (kv.prefix_evict chaos) is THIS
            # request's admission failure, never a dead worker:
            # reserve's undo left the pool byte-identical, so fail the
            # one request and keep admitting (exactly-once terminal)
            if traced and tr is not None:
                tr.child("kv_reserve", t_kv, self.tracer.now_us(),
                         error=repr(e))
            if self.adapters is not None:
                self.adapters.release(aslot)
            self._fail(req, e)
            return True
        if traced and tr is not None:
            tr.child("kv_reserve", t_kv, self.tracer.now_us(),
                     blocks=self.kv_cache.blocks_for(plen + max_new))
        self._end_queue_wait([req])
        seq = _SlotSeq(req, rid, ids64, arr.dtype, max_new, seq_n)
        seq.adapter = aslot
        seq.adapter_seed = aseed
        if self.qos is not None:
            # ISSUE-17: bill the slot to its tenant — the inflight count is
            # held for exactly the RUNNING span (pause releases it, resume
            # re-takes it, every evict path drops it), and the expected
            # service cost advances the tenant's virtual-time clock ONCE,
            # here: _qos_pick admits the smallest clock first, which is what
            # makes steady-state throughput weight-proportional
            spec = self._tenant_spec_of(req)
            seq.tenant = spec.name
            seq.priority = spec.priority
            self.qos.acquire(spec.name, cost=plen + max_new)
            seq.qos_held = True
            self.qos.note_admitted(spec.name)
        seq.table = self.kv_cache.block_table(rid,
                                              pad_to=self.table_width)
        if hit is not None:
            # rows already resident after revalidation: reserve set the
            # committed length to the acquired shared blocks — chunked
            # prefill resumes at the first novel token (~O(new tokens))
            got = int(self.kv_cache.length(rid))
            seq.prefix_hit = got
            seq.pos = seq.length = got
            seq.digests = hit.digests
            if got:
                self.metrics.inc("prefix_hit_tokens", got)
                self._prefix_hit_counter.inc(got)
            if traced and tr is not None:
                tr.child("prefix_lookup", t_px, self.tracer.now_us(),
                         matched_blocks=len(hit.pairs),
                         hit_tokens=got)
        seq.t_admit = self._clock()     # queue phase ends here (ISSUE-18)
        with self._slot_lock:
            self._slots[idx] = seq
        self.metrics.inc("admitted_seqs")
        if tr is not None:
            tr.event("admitted", slot=idx, prompt_len=plen,
                     max_new=max_new)
        return True

    # ------------------------------------------------- multi-tenant QoS tick
    def _qos_admit(self):
        """Fair-share admission (qos= knob): free slots go to the waiting
        work — paused sequences AND queued arrivals, unified — of the most
        urgent tier's most under-served tenant; then strictly more urgent
        waiters preempt the least urgent running sequences. Host-side
        bookkeeping only: the step launches (and so the compile surface)
        are byte-identical to the untenanted scheduler's."""
        while True:     # drain arrivals into the reorder backlog
            try:
                self._backlog.append(self._queue.get_nowait())
            except queue.Empty:
                break
        if (not self._backlog and not self._paused
                and self._phase_count(None) == 0):
            try:        # fully idle: park briefly instead of spinning
                self._backlog.append(self._queue.get(timeout=0.05))
            except queue.Empty:
                return
        while True:
            idx = self._free_slot()
            if idx is None:
                break
            pick = self._qos_pick()
            if pick is None:
                break
            kind, item = pick
            if kind == "resume":
                self._resume_seq(idx, item)
            elif not self._install_seq(idx, item):
                return      # pool dry: stop admitting this tick
        self._preempt_for_priority()

    def _qos_pick(self):
        """Best waiting work item: ('resume', seq) | ('admit', req) | None.

        Order: priority tier first (lower = more urgent), then the
        tenant's fair-share deficit (inflight/weight — the MINIMUM is the
        most under-served, so contended slots converge to weight shares),
        then resumes before fresh admissions (a paused sequence holds
        blocks; finishing it frees memory), then arrival order."""
        while True:
            best = best_key = kind = None
            for s in self._paused:
                k = (s.priority, self.qos.fair_ratio(s.tenant), 0, s.order)
                if best_key is None or k < best_key:
                    best_key, best, kind = k, s, "resume"
            for pos, r in enumerate(self._backlog):
                spec = self._tenant_spec_of(r)
                k = (spec.priority, self.qos.fair_ratio(spec.name), 1, pos)
                if best_key is None or k < best_key:
                    best_key, best, kind = k, r, "admit"
            if best is None:
                return None
            if kind == "resume":
                try:
                    self._paused.remove(best)
                except ValueError:  # pragma: no cover - raced an evict
                    continue
                return ("resume", best)
            self._backlog.remove(best)
            if not self._usable(best):
                continue
            return ("admit", best)

    def _preempt_for_priority(self):
        """Priority preemption: while a waiting request (or paused
        sequence) is STRICTLY more urgent than the least urgent running
        sequence, pause that victim — blocks retained, slot state parked,
        tick width freed — and hand its slot to the waiter. Equal tiers
        never preempt each other (fair share handles those), so the loop
        terminates: each round strictly improves the worst running tier."""
        while self._backlog or self._paused:
            if self._free_slot() is not None:
                return      # width available; the admit loop already ran
            wprio = None
            for s in self._paused:
                wprio = (s.priority if wprio is None
                         else min(wprio, s.priority))
            for r in self._backlog:
                if r.state != _PENDING:
                    continue
                p = self._tenant_spec_of(r).priority
                wprio = p if wprio is None else min(wprio, p)
            if wprio is None:
                return
            with self._slot_lock:
                victim, vi = None, -1
                for i, s in enumerate(self._slots):
                    if s is None:
                        continue
                    if (victim is None or (s.priority, s.order)
                            > (victim.priority, victim.order)):
                        victim, vi = s, i
            if victim is None or victim.priority <= wprio:
                return
            if self._ahead is not None:
                # the victim's state on the host has to be whole before it
                # is parked: land the launch in flight, then look again
                self._drain()
                continue
            self._pause_slot(vi, victim)
            idx = self._free_slot()
            pick = self._qos_pick() if idx is not None else None
            if pick is None:
                return      # victim resumes via a later tick's admit loop
            kind, item = pick
            if kind == "resume":
                self._resume_seq(idx, item)
            elif not self._install_seq(idx, item):
                return      # pool dry (the paused victim keeps its blocks)

    def _pause_slot(self, i, s):
        """Preempt a running sequence: park it off-slot with its blocks
        RETAINED (the rid stays reserved — preemption frees tick width,
        not memory; adapter pin included, so an unload can't race a paused
        sequence either) and release its tenant's fair-share count. The
        parked pos/tok/length/table bookkeeping is exactly the state a
        prefix-hit admission produces, so resume is plain continuation —
        bit-identical tokens, zero new compiled programs."""
        t0 = self.tracer.now_us() if self.tracer.enabled else 0.0
        with self._slot_lock:
            if self._slots[i] is s:
                self._slots[i] = None
        if s.qos_held:
            s.qos_held = False
            self.qos.release(s.tenant)
        s.t_pause = self._clock()   # paused phase opens (ISSUE-18)
        self._paused.append(s)
        self.metrics.inc("preempted_seqs")
        tr = s.req.trace
        if tr is not None:
            tr.child("preempt", t0, self.tracer.now_us(), slot=i,
                     phase=s.phase, committed=int(s.length))

    def _resume_seq(self, idx, s):
        """Reinstall a paused sequence into a free slot: its blocks and
        pos/length bookkeeping never left, so the next tick continues it
        exactly where it stopped (mid-prefill resumes its chunk walk at
        pos — the prefix-hit continuation path; mid-decode feeds tok back
        to the decode launch)."""
        t0 = self.tracer.now_us() if self.tracer.enabled else 0.0
        if self.qos is not None and not s.qos_held:
            self.qos.acquire(s.tenant)
            s.qos_held = True
        self._close_pause(s)
        with self._slot_lock:
            self._slots[idx] = s
        self.metrics.inc("resumed_seqs")
        tr = s.req.trace
        if tr is not None:
            tr.child("resume", t0, self.tracer.now_us(), slot=idx,
                     phase=s.phase, committed=int(s.length))

    def _next_request(self, block):
        """One queue pop under the admit policy.

        FIFO pops the arrival queue directly. shortest_prompt_first drains
        arrivals into a reorder backlog and admits the backlog's shortest
        prompt, tie-broken by the most urgent deadline, then arrival order
        (deterministic). The reorder window is only ever the set of
        requests waiting while a slot is free — a long prompt is delayed,
        never starved: it admits the moment it is the backlog minimum."""
        if self.admit_policy == "fifo":
            return (self._queue.get(timeout=0.05) if block
                    else self._queue.get_nowait())
        while True:                 # drain arrivals into the backlog
            try:
                self._backlog.append(self._queue.get_nowait())
            except queue.Empty:
                break
        if not self._backlog:
            if not block:
                raise queue.Empty
            self._backlog.append(self._queue.get(timeout=0.05))

        def urgency(item):
            pos, r = item        # backlog preserves arrival order
            rem = (r.deadline.remaining() if r.deadline is not None
                   else float("inf"))
            return (len(r.arrays[0]), rem, pos)
        _, best = min(enumerate(self._backlog), key=urgency)
        self._backlog.remove(best)
        return best

    # ----------------------------------------------------------- retirement
    def _evict_slot(self, i, s):
        """Free the slot and return its blocks to the pool (all retirement
        paths funnel here — blocks can never outlive their sequence)."""
        with self._slot_lock:
            if self._slots[i] is s:
                self._slots[i] = None
        self._release_seq(s)

    def _evict_paused(self, s):
        """Paused-sequence twin of _evict_slot: unpark and release (the
        blocks a preempted sequence retained must not outlive it either)."""
        try:
            self._paused.remove(s)
        except ValueError:  # pragma: no cover - already unparked
            pass
        self._release_seq(s)

    def _release_seq(self, s):
        """Return a sequence's held resources: tenant fair-share count,
        adapter bank pin, KV blocks. Idempotent on every leg (double-evict
        from shutdown racing retirement releases exactly once)."""
        if self.qos is not None and s.qos_held:
            s.qos_held = False
            self.qos.release(s.tenant)
        if self.adapters is not None and s.adapter != 0:
            # drop the admission-time bank-slot pin; zeroing first makes a
            # double-evict (shutdown racing retirement) release exactly once
            aslot, s.adapter = s.adapter, 0
            self.adapters.release(aslot)
        try:
            self.kv_cache.mark_done(s.rid)
            self.kv_cache.release(s.rid)
        except KeyError:    # pragma: no cover - already evicted/released
            pass

    # ------------------------------------------------ phase attribution (18)
    def _close_pause(self, s):
        """Fold an open pause interval into the sequence's paused-time
        accounting (called on resume and on any terminal path that can
        reach a still-parked sequence)."""
        if s.t_pause is None:
            return
        dt = max(0.0, self._clock() - s.t_pause)
        s.t_pause = None
        s.paused_s += dt
        if s.t_first is None:
            s.paused_pre_s += dt

    def _attribute(self, s, observe=True):
        """Close out a sequence's phase accounting at its terminal: park
        the {queue,prefill,paused,decode}_share dict on the request (the
        terminal CAS tags the terminal span with it) and, when `observe`,
        emit the per-tenant TTFT/TPOT samples and feed the SLO monitor.
        Retry paths pass observe=False — a re-batched request must not
        sample TTFT twice."""
        self._close_pause(s)
        req = s.req
        walls = phase_walls(req.t0, s.t_admit, s.t_first, self._clock(),
                            s.paused_s, s.paused_pre_s)
        req.attribution = attribution_shares(*walls)
        if not observe:
            return
        tenant = s.tenant if s.tenant is not None else "default"
        if s.t_first is not None and req.t0 is not None:
            ttft = max(0.0, s.t_first - req.t0)
            self._ttft_hist.labels(self._component, tenant).observe(ttft)
            if self.slo is not None:
                self.slo.observe_ttft(ttft, tenant=tenant)
            if s.n_tok > 1:
                # decode wall with post-first-token pauses excluded: a
                # preempted sequence's park time is a scheduling decision,
                # never charged to TPOT
                gap = max(0.0, (s.t_last - s.t_first)
                          - (s.paused_s - s.paused_pre_s))
                tpot = gap / (s.n_tok - 1)
                self._tpot_hist.labels(self._component, tenant).observe(tpot)
                if self.slo is not None:
                    self.slo.observe_tpot(tpot, tenant=tenant)

    def _terminal_good(self, error):
        """Availability verdict of one terminal outcome: good iff the HTTP
        status the error maps to is non-5xx (mirrors the server's
        _fail_http taxonomy — a 400/429 is the client's problem, not an
        availability hit)."""
        if error is None:
            return True
        status = getattr(error, "status", None)     # Rejected carries one
        if status is None:
            if isinstance(error, TimeoutError):
                status = 504
            elif isinstance(error, CacheOutOfBlocks):
                status = 503
            elif isinstance(error, ValueError):
                status = 400
            else:
                status = 500
        return int(status) < 500

    def _finish_req(self, req, result) -> bool:
        won = super()._finish_req(req, result)
        if won and self.slo is not None:
            self.slo.observe_terminal(
                True, tenant=getattr(req, "tenant", None))
        return won

    def _fail(self, req, error) -> bool:
        won = super()._fail(req, error)
        if won and self.slo is not None:
            self.slo.observe_terminal(
                self._terminal_good(error),
                tenant=getattr(req, "tenant", None))
        return won

    @property
    def util(self):
        """The tick ledger where `utilization=` is on, else None: what
        /utilization, /metrics and the flight ring show."""
        return self._ledger if self._util_shown else None

    def _admit_span(self):
        with RecordEvent("serve.admit") as ev:
            if not RecordEvent.capturing():     # stats only for a capture
                return self._admit()
            before = self.metrics.get("admitted_seqs")
            self._admit()
            ev.set_stats(admitted=self.metrics.get("admitted_seqs") - before)

    def _tick_stats(self):
        """What the tick starts on, for the `serve.tick` span: live slots,
        how many of them prefill and decode, requests still waiting."""
        with self._slot_lock:
            phases = [s.phase for s in self._slots if s is not None]
        return dict(live=len(phases), prefill=phases.count(_PREFILL),
                    decode=phases.count(_DECODE),
                    pending=self._queue.qsize() + len(self._backlog))

    def _util_tick(self):
        """Close the ledger's tick window: tick wall minus the recorded
        launches (dispatch and read-back wait) becomes the host gap, and
        with `utilization=` on the per-kind flops land on the counters.
        Ledger failures never take the tick loop down (same contract as
        the flight ring)."""
        try:
            self._ledger.tick_end()
        except ThreadDeath:
            raise
        except Exception:       # pragma: no cover - telemetry must not bite
            pass

    def _read_back(self, phase, *arrays):
        """The launch's results on the host: the tick thread blocks here
        until the device has finished (`serve.<phase>.wait`). Returns the
        arrays and the seconds waited, on the ledger's clock."""
        clock = self._ledger.clock
        with RecordEvent(f"serve.{phase}.wait"):
            t0 = clock()
            out = [np.asarray(a._value if hasattr(a, "_value") else a)
                   for a in arrays]
            return out, clock() - t0

    def _counts_of(self, program, picks, t0, stats, positions, **kw):
        """What the model says the launch adds to the ledger (the contract:
        models/generation.py). A key under one of the ledger's own names is
        the model's fault against that contract: it is raised HERE, where
        it fails the launch's requests with the ValueError that names the
        key (None says so), and not inside `_util_launch`'s guard, where it
        would only be telemetry that is missing."""
        try:
            counts = self.model._launch_counts(
                program, stats, positions, self.kv_cache, self.table_width,
                **kw)
            self._ledger.check_counts(
                k for k in counts if k != "issued_positions")
        except ThreadDeath:
            raise
        except Exception as e:
            self._fail_picks(picks, e, program, t0)
            return None
        return counts

    def _take_launch(self):
        """The hook's record of the launch just dispatched, taken off the
        stash: a launch whose hook did not fire is never given another's."""
        info, self._last_launch = self._last_launch, None
        return info

    def _util_launch(self, program, info, wait_s, total_units, slot_units,
                     spec_units=0, live_rows=0, walked_rows=0, table_rows=0,
                     sampler=(False, False), counts=None, ahead=False,
                     ahead_dropped=0):
        """Account for a launch, read back and absorbed: observe
        `paddle_decode_launch_seconds` with the launch THROUGH its
        read-back, and hand the ledger its time split, positions and rows.
        `info` is the hook's record of the launch (`_take_launch`); a
        missing one or a path mismatch means the hook never fired for this
        program — skip rather than misattribute."""
        if (info or {}).get("path") != program:
            return
        info, launch_s = self._launch_done(wait_s, info)
        try:
            self._ledger.record_launch(
                program, info.get("flops"), launch_s, total_units,
                slot_units, spec_units, wait_s=wait_s, live_rows=live_rows,
                walked_rows=walked_rows, table_rows=table_rows,
                sampler=sampler, counts=counts, ahead=ahead,
                ahead_dropped=ahead_dropped)
        except ThreadDeath:
            raise
        except Exception:       # pragma: no cover - telemetry must not bite
            pass

    def _flight_tick(self):
        """One flight-recorder capture at the tick boundary (ISSUE-18): the
        slot map with per-slot tenant/adapter/phase/progress, batch widths,
        KV block accounting, paused/pending depths and the ledger's fair
        ratios. Capture failures are swallowed — the postmortem ring must
        never take the tick loop down."""
        rec = self.flight
        if rec is None:
            return
        try:
            with self._slot_lock:
                slots = [None if s is None else {
                    "slot": i, "tenant": s.tenant, "adapter": int(s.adapter),
                    "phase": s.phase, "plen": s.plen, "pos": int(s.pos),
                    "generated": len(s.generated), "priority": s.priority,
                } for i, s in enumerate(self._slots)]
            live = [d for d in slots if d is not None]
            kv = self.kv_cache
            snap = {
                "slots": slots,
                "width": {
                    "prefill": sum(1 for d in live
                                   if d["phase"] == _PREFILL),
                    "decode": sum(1 for d in live if d["phase"] == _DECODE),
                    "free": self.max_slots - len(live),
                },
                "kv": {"in_use": int(kv.blocks_in_use),
                       "free": int(kv.free_blocks),
                       "evictable": int(kv.evictable_blocks)},
                "paused": len(self._paused),
                "pending": self._queue.qsize() + len(self._backlog),
            }
            if self.qos is not None:
                snap["fair_ratios"] = self.qos.fair_snapshot()
            if self._util_shown and self._ledger.last_tick is not None:
                # ISSUE-19: the tick's own flops/gap decomposition rides
                # the ring — /debug/ticks shows WHY MFU dipped (which
                # slots were empty, which drafts died)
                snap["util"] = self._ledger.last_tick
            rec.record(snap)
        except ThreadDeath:
            raise
        except Exception:       # pragma: no cover - capture must not bite
            pass

    def _retire_ok(self, i, s):
        out = np.concatenate(
            [s.ids, np.asarray(s.generated[:s.max_new], np.int64)])
        # index the generated tail BEFORE the audit-only set_length below
        # rewrites the committed length: only rows actually written are
        # indexable (a decode tick's final launch may sample past max_new,
        # but the in-program write ceiling drops those rows — cap to it)
        self._register_prefix(s, out, min(s.length, s.plen + s.max_new),
                              digests=None)
        try:
            self.kv_cache.set_length(s.rid, s.plen + s.max_new)
        except (KeyError, ValueError):  # pragma: no cover - audit-only state
            pass
        self._attribute(s)      # ISSUE-18: shares + TTFT/TPOT samples
        self._finish_req(s.req, out.astype(s.out_dtype))
        if self.qos is not None and s.tenant is not None:
            # useful tokens by tenant (ISSUE-17): the fairness bench's
            # numerator is work DELIVERED at retirement, not work admitted
            self.qos.account(s.tenant, len(s.generated[:s.max_new]))
        self._evict_slot(i, s)
        self.metrics.inc("retired_seqs")
        self._tokens_total.labels(self._component).inc(len(s.generated))

    def _retire_unserviceable(self):
        """Per token-step deadline/cancel semantics: at every tick boundary a
        sequence whose client cancelled, or whose deadline expired mid-
        decode, is retired and its blocks freed — exactly one terminal
        outcome via the request CAS, batchmates untouched."""
        for i, s in enumerate(list(self._slots)):
            if s is None:
                continue
            req = s.req
            if req.state != _PENDING:
                self.metrics.inc("cancelled_skipped")
                if req.trace is not None:
                    req.trace.event("slot_reclaimed_after_cancel", slot=i)
                self._evict_slot(i, s)
                self.metrics.inc("retired_seqs")
                continue
            if req.deadline is not None and req.deadline.expired():
                self._attribute(s)      # where the deadline actually went
                if self._fail(req, DeadlineExceeded(
                        "deadline expired mid-decode (continuous tick)")):
                    self.metrics.inc("expired_in_flight")
                self._evict_slot(i, s)
                self.metrics.inc("retired_seqs")
        # paused (preempted) sequences age under the same contract: a
        # cancelled or expired one frees its retained blocks NOW instead of
        # waiting to be resumed (exactly-once terminal via the request CAS)
        for s in list(self._paused):
            req = s.req
            if req.state != _PENDING:
                self.metrics.inc("cancelled_skipped")
                if req.trace is not None:
                    req.trace.event("paused_reclaimed_after_cancel")
                self._evict_paused(s)
                self.metrics.inc("retired_seqs")
            elif req.deadline is not None and req.deadline.expired():
                self._attribute(s)      # paused_share carries the park time
                if self._fail(req, DeadlineExceeded(
                        "deadline expired while preempted (paused)")):
                    self.metrics.inc("expired_in_flight")
                self._evict_paused(s)
                self.metrics.inc("retired_seqs")

    def _absorb(self, i, s, toks) -> bool:
        """Fold one tick's sampled tokens into the sequence; True if it
        retired. EOS freezes the remainder (parity with the in-scan
        sampler's finished mask, which resets per launch)."""
        eos = self.eos_token_id
        absorbed = 0
        for t in toks:
            if len(s.generated) >= s.max_new:
                break
            t = int(t)
            s.generated.append(t)
            absorbed += 1
            if eos is not None and t == eos:
                s.generated.extend([eos] * (s.max_new - len(s.generated)))
                break
        if absorbed:
            # ISSUE-18: first/last token stamps (tick-boundary resolution —
            # TPOT is the mean inter-token gap, and a tick absorbs
            # decode_steps tokens at once, so per-token jitter averages out)
            now = self._clock()
            if s.t_first is None:
                s.t_first = now
            s.t_last = now
            s.n_tok += absorbed
        self._flush_stream(s)
        if len(s.generated) >= s.max_new:
            self._retire_ok(i, s)
            return True
        return False

    def _flush_stream(self, s):
        """Tick-boundary streaming (ISSUE-11): push newly absorbed tokens
        through the request's on_tokens channel so infer_stream() clients
        see them NOW, not at retirement. A broken consumer never takes the
        tick loop down — the buffered result is still delivered."""
        cb = s.req.on_tokens
        if cb is None:
            return
        upto = min(len(s.generated), s.max_new)
        if upto <= s.flushed:
            return
        chunk = s.generated[s.flushed:upto]
        s.flushed = upto
        try:
            cb(list(chunk))
        except Exception:       # pragma: no cover - consumer bug
            pass

    def _fail_picks(self, picks, error, span_name, t0):
        self.breaker.record_failure()
        self.metrics.inc("batch_failures")
        reqs = [s.req for _, s in picks]
        self._span_each(reqs, span_name, t0, self.tracer.now_us(),
                        error=repr(error))
        for i, s in picks:
            # shares only (observe=False): a retry re-enters the queue and
            # must not sample TTFT twice — a retried-then-served request
            # samples once, at its eventual retirement
            self._attribute(s, observe=False)
            self._evict_slot(i, s)
            self._fail_or_retry(s.req, error)

    def _adapter_tick_kwargs(self, picks, reqs):
        """Per-tick LoRA launch kwargs (ISSUE-15): the traced [S] bank-index
        vector — each live slot gathers its adapter's rows, idle slots ride
        identity row 0. The host-side assembly is recorded as the
        `adapter_gather` span with the tick's distinct-adapter count (the
        heterogeneity dial: 1 means merged-weights would have done)."""
        if self.adapters is None:
            return {}
        traced = self.tracer.enabled
        t_g = self.tracer.now_us() if traced else 0.0
        aidx = np.zeros(self.max_slots, np.int32)
        for i, s in picks:
            aidx[i] = s.adapter
        if traced:
            self._span_each(reqs, "adapter_gather", t_g,
                            self.tracer.now_us(),
                            distinct_adapters=len({int(a) for a in aidx}))
        return dict(adapters=self.adapters, adapter_slots=aidx)

    # -------------------------------------------------------------- prefill
    def _prefill_tick(self):
        with self._slot_lock:
            pre = [(i, s) for i, s in enumerate(self._slots)
                   if s is not None and s.phase == _PREFILL]
        if not pre:
            return
        pre.sort(key=lambda t: t[1].order)      # oldest prompt first
        budget = self.prefill_token_budget
        picks = []
        for i, s in pre:
            take = min(self.prefill_chunk, s.plen - s.pos)
            if take < 1:
                continue
            if take > budget:
                # a chunk is not cut to what the budget leaves (a prompt's
                # tail left part of one over): the slot waits a tick
                break
            picks.append((i, s, take))
            budget -= take
        if not picks:
            return
        self._drain()       # a chunk is no decode continuation
        S, C = self.max_slots, self.prefill_chunk
        with RecordEvent("serve.prefill.assemble"):
            chunk = np.zeros((S, C), np.int64)
            offs = np.zeros(S, np.int64)
            lens = np.zeros(S, np.int64)
            temps = np.zeros(S, np.float32)
            tks = np.zeros(S, np.int32)
            tables = np.zeros((S, self.table_width), np.int32)
            for i, s, take in picks:
                chunk[i, :take] = s.ids[s.pos:s.pos + take]
                offs[i] = s.pos
                lens[i] = take
                temps[i] = s.temperature
                tks[i] = s.top_k
                tables[i] = s.table
            reqs = [s.req for _, s, _ in picks]
            akw = self._adapter_tick_kwargs([(i, s) for i, s, _ in picks],
                                            reqs)
        traced = self.tracer.enabled
        t0 = self.tracer.now_us() if traced else 0.0
        try:
            if self._faults is not None:
                self._faults.check("predictor.generate")
            with RecordEvent("serve.prefill.dispatch") as ev:
                tk = self.model.prefill_chunk(
                    chunk, offs, lens, self.kv_cache, tables,
                    temperature=temps, top_k=tks,
                    eos_token_id=self.eos_token_id,
                    decode_kernel=self.decode_kernel, seed=next(self._seed),
                    timing_hook=self._timing_hook, **akw)
                if RecordEvent.capturing():
                    ev.set_stats(compiled=self._compiled_now("prefill_chunk"))
            info = self._take_launch()
        except ThreadDeath:
            raise
        except Exception as e:
            self._fail_picks([(i, s) for i, s, _ in picks], e,
                             "prefill_chunk", t0)
            return
        self.breaker.record_success()
        self.metrics.inc("prefill_ticks")
        self._ahead, failed = self._decode_behind_chunk(picks, tk)
        self._land_prefill(picks, tk, info, t0, reqs, temps, tks)
        if failed is not None:
            self._fail_dispatch(failed)

    def _land_prefill(self, picks, tk, info, t0, reqs, temps, tks):
        """Read a chunk launch back and absorb it: prompt positions taken,
        and a first token for each slot whose prompt it completes."""
        S, C = self.max_slots, self.prefill_chunk
        # the model's counts of the launch come back in the tokens' wait,
        # and the model says what they add to the ledger (the contract:
        # models/generation.py); a model that walks only the slots with a
        # chunk issues fewer positions than slots x chunk
        stats = info["stats"]
        (tk, *got), wait_s = self._read_back("prefill", tk, *stats.values())
        counts = self._counts_of(
            "prefill_chunk", [(i, s) for i, s, _ in picks], t0,
            dict(zip(stats, got)),
            np.concatenate([np.arange(s.pos, s.pos + take)
                            for _, s, take in picks]), holding=len(picks))
        if counts is None:
            return
        issued = counts.pop("issued_positions", S * C)
        useful = int(sum(t for _, _, t in picks))
        self._span_each(reqs, "prefill_chunk", t0, self.tracer.now_us(),
                        slots=len(picks), tokens=useful)
        with RecordEvent("serve.prefill.absorb", useful=useful,
                         issued=issued):
            for i, s, take in picks:
                s.pos += take
                s.length = s.pos
                try:
                    self.kv_cache.append_tokens(s.rid, take)
                except KeyError:    # pragma: no cover - raced an eviction
                    pass
                self._register_prefix(s, s.ids, s.pos)
                if s.pos >= s.plen:
                    s.phase = _DECODE
                    s.tok = int(tk[i])
                    self._absorb(i, s, [s.tok])
        # useful positions are exactly each pick's take; the rest of what
        # the program issued (idle slots, chunk tail) is pad
        self._util_launch("prefill_chunk", info, wait_s, issued,
                          [(s.tenant, take) for _, s, take in picks],
                          sampler=sampler_engages(temps, tks), counts=counts)

    def _register_prefix(self, s, tokens, committed, digests="prompt"):
        """Index this sequence's freshly COMMITTED full blocks (prefill
        chunks as they land, reusing the admission-time digest chain; the
        whole prompt+generation at retirement, rehashed since generated
        blocks have no precomputed digests). Registration is best-effort:
        an index failure must never take the sequence with it."""
        pc = self.prefix_cache
        if pc is None:
            return
        try:
            pc.register(s.rid, tokens,
                        digests=s.digests if digests == "prompt" else None,
                        length=int(committed), seed=s.adapter_seed)
        except ThreadDeath:
            raise
        except Exception:       # pragma: no cover - index bug, stay cold
            pass

    # --------------------------------------------------------------- decode
    def _decoding(self):
        with self._slot_lock:
            return [(i, s) for i, s in enumerate(self._slots)
                    if s is not None and s.phase == _DECODE]

    def _decode_tick(self):
        """Every decoding slot's next `decode_steps` tokens: one launch
        read back and absorbed. The launch may have been dispatched ahead
        (`_ahead`): behind this tick's chunk, or in the tick before; before
        reading it back the tick dispatches the next one wherever
        `_decode_ahead` can build it from the launch in flight alone. A
        decoding slot that the launch in flight does not carry (a resumed
        sequence) lands it first, and the tick assembles its launch from the
        host as before."""
        if self.spec_k > 0:
            return self._verify_tick()
        launch, self._ahead = self._ahead, None
        dec = self._decoding()
        if launch is not None:
            held = dict(launch.picks)
            if any(held.get(i) is not s for i, s in dec):
                self._land_decode(launch)
                launch, dec = None, self._decoding()
        if launch is None:
            if not dec:
                return
            launch, failed = self._assemble_decode(dec)
            if failed is not None:
                self._fail_dispatch(failed)
                return
        ahead, failed = self._decode_ahead(launch)
        self._land_decode(launch)
        if failed is not None:
            self._fail_dispatch(failed)
        elif ahead is not None:
            if any(self._slots[i] is s for i, s in ahead.picks):
                self._ahead = ahead
            else:           # all it carries finished: no later tick lands it
                self._land_decode(ahead)

    def _assemble_decode(self, dec, done=(), tk=None):
        """A launch for the decoding slots `dec`, from their state on the
        host, and for the slots `done` whose prompt the chunk launch `tk`
        completes: their first input is that chunk's token, on the device,
        and the launch goes out before the chunk is read back."""
        S = self.max_slots
        picks = list(dec) + list(done)
        with RecordEvent("serve.decode.assemble"):
            tok = np.zeros(S, np.int64)
            lengths = np.zeros(S, np.int64)
            maxlens = np.zeros(S, np.int64)
            active = np.zeros(S, bool)
            first = np.zeros(S, bool)
            temps = np.zeros(S, np.float32)
            tks = np.zeros(S, np.int32)
            tables = np.zeros((S, self.table_width), np.int32)
            for i, _ in done:
                first[i] = True
            for i, s in picks:
                tok[i] = s.tok
                lengths[i] = s.plen if first[i] else s.length
                maxlens[i] = s.plen + s.max_new  # write ceiling: reserved rows
                active[i] = True
                temps[i] = s.temperature
                tks[i] = s.top_k
                tables[i] = s.table
            if done:
                tok = _chunk_tokens(first, tk._value, tok)
            akw = self._adapter_tick_kwargs(picks, [s.req for _, s in picks])
        launch = _DecodeLaunch(picks, lengths, active, maxlens, temps, tks,
                               tables, ahead=tk is not None)
        return self._dispatch_decode(launch, tok, akw)

    def _decode_behind_chunk(self, picks, tk):
        """Dispatch the tick's decode launch right behind its chunk launch
        `tk`, before the chunk is read back: the decoding slots' inputs are
        on the host (the tick landed any launch in flight first), and a slot
        whose prompt the chunk completes starts from the chunk's token, on
        the device, unless its first token is all it asked for. The tick's
        `_decode_tick` lands it as a launch run ahead. Returns what
        `_dispatch_decode` does."""
        if self.spec_k > 0:
            return None, None
        dec = self._decoding()
        done = [(i, s) for i, s, take in picks
                if s.pos + take >= s.plen and s.max_new > 1]
        if not dec and not done:
            return None, None
        return self._assemble_decode(dec, done, tk)

    def _decode_ahead(self, launch):
        """Dispatch the launch after `launch` before `launch` is read back,
        where its inputs are known without `launch`'s tokens on the host:
        its input tokens are the last column of `launch`'s, still on the
        device; the lengths are `launch`'s plus `decode_steps`; the slots
        and tables are `launch`'s less the slots that leave before it runs
        (those the host knows finish in `launch` by count, and those
        cancelled or past their deadline). A slot that finishes in `launch`
        by EOS rides along and has its tokens dropped at the read-back.

        Runs no launch ahead while a slot prefills (the next tick's chunk
        would land this one before it, and its decoders would get two
        launches a chunk), nor under a tenant ledger while a sequence waits
        paused or a request waits: the next admission may resume one into
        a slot this launch does not carry, or pause one it does. Returns
        (launch, None), (None, failure) or (None, None)."""
        T = self.decode_steps
        with self._slot_lock:
            if any(s is not None and s.phase == _PREFILL
                   for s in self._slots):
                return None, None
        if self.qos is not None and (self._paused or self._backlog
                                     or not self._queue.empty()):
            return None, None
        keep = [(i, s) for i, s in launch.picks
                if self._slots[i] is s and s.req.state == _PENDING
                and (s.req.deadline is None or not s.req.deadline.expired())
                and len(s.generated) + T < s.max_new]
        if not keep:
            return None, None
        with RecordEvent("serve.decode.assemble"):
            active = np.zeros(self.max_slots, bool)
            active[[i for i, _ in keep]] = True
            nxt = _DecodeLaunch(
                keep, np.where(active, launch.lengths + T, 0), active,
                np.where(active, launch.maxlens, 0),
                np.where(active, launch.temps, 0).astype(np.float32),
                np.where(active, launch.tks, 0).astype(np.int32),
                np.where(active[:, None], launch.tables, 0).astype(np.int32),
                ahead=True)
            tok = _carry_tokens(launch.toks._value, active)
            akw = self._adapter_tick_kwargs(keep, [s.req for _, s in keep])
        return self._dispatch_decode(nxt, tok, akw)

    def _dispatch_decode(self, launch, tok, akw):
        """Hand `launch` to the device with input tokens `tok` (host or
        device): (launch, None), or (None, failure) where failure is what
        `_fail_picks` takes."""
        traced = self.tracer.enabled
        launch.t0 = t0 = self.tracer.now_us() if traced else 0.0
        try:
            if self._faults is not None:
                self._faults.check("predictor.generate")
            with RecordEvent("serve.decode.dispatch") as ev:
                launch.toks = self.model.decode_step(
                    tok, launch.lengths, launch.active, self.kv_cache,
                    launch.tables, steps=self.decode_steps,
                    max_lens=launch.maxlens, temperature=launch.temps,
                    top_k=launch.tks, eos_token_id=self.eos_token_id,
                    decode_kernel=self.decode_kernel, seed=next(self._seed),
                    timing_hook=self._timing_hook, **akw)
                if RecordEvent.capturing():
                    ev.set_stats(compiled=self._compiled_now("decode_step"))
            launch.info = self._take_launch()
        except ThreadDeath:
            raise
        except Exception as e:
            return None, (launch.picks, e, "decode_step", t0)
        self.breaker.record_success()
        self.metrics.inc("decode_ticks")
        return launch, None

    def _land_decode(self, launch):
        """Read `launch` back and absorb it into the slots that still hold
        the sequences it carried. A sequence that finished since it was
        dispatched (EOS, cancel, deadline: only a launch run ahead spans a
        tick boundary) has its tokens dropped; the ledger counts them as
        `ahead_dropped` slot-steps."""
        S, T = self.max_slots, self.decode_steps
        stats = launch.info["stats"]
        (toks, *got), wait_s = self._read_back("decode", launch.toks,
                                               *stats.values())
        live = [(i, s) for i, s in launch.picks if self._slots[i] is s]
        lengths, active = launch.lengths, launch.active
        counts = self._counts_of(
            "decode_step", live, launch.t0, dict(zip(stats, got)),
            (lengths[active][:, None] + np.arange(T)).reshape(-1), steps=T)
        if counts is None:
            return
        counts.pop("issued_positions", None)    # a tick carries every slot
        self._span_each([s.req for _, s in live], "decode_step", launch.t0,
                        self.tracer.now_us(), slots=len(launch.picks),
                        steps=T)
        live_rows, walked_rows, table_rows = self._kv_rows(lengths[active], T)
        units = []
        with RecordEvent("serve.decode.absorb") as ev:
            for i, s in live:
                s.length += T
                s.tok = int(toks[i, -1])
                n0 = s.n_tok
                self._absorb(i, s, toks[i])
                # useful = tokens the sequence actually ABSORBED this tick
                # (EOS-frozen / over-cap rows are pad, like idle slots)
                units.append((s.tenant, s.n_tok - n0))
            if RecordEvent.capturing():
                ev.set_stats(useful=sum(u for _, u in units), issued=S * T,
                             rows=live_rows)
        self._util_launch("decode_step", launch.info, wait_s, S * T, units,
                          live_rows=live_rows, walked_rows=walked_rows,
                          table_rows=table_rows,
                          sampler=sampler_engages(launch.temps, launch.tks),
                          counts=counts, ahead=launch.ahead,
                          ahead_dropped=T * (len(launch.picks) - len(live)))

    def _fail_dispatch(self, failed):
        """Fail a decode launch that could not be dispatched, once the
        launch before it has landed: as if the dispatch had come after that
        read-back, the slots that finished meanwhile keep their answers."""
        picks, error, span, t0 = failed
        self._fail_picks([(i, s) for i, s in picks if self._slots[i] is s],
                         error, span, t0)

    def _drain(self):
        """Land the decode launch run ahead, if one is in flight: a tick
        that is not a pure decode continuation does so first."""
        launch, self._ahead = self._ahead, None
        if launch is not None:
            self._land_decode(launch)

    def _kv_rows(self, lengths, steps, one_call=False):
        """(live_rows, walked_rows, table_rows) of one decode or verify
        launch. live: the context lengths of the active slots summed over
        the launch's token steps — a slot with `length` rows in the pool
        attends over length + t + 1 at step t. walked: the rows the paged
        kernel's loop visits for them, the same lengths rounded up to its
        block of pages (`paged_walk_blocks`, which also gives the kernel its
        trip count): a decode tick is one call a token step with one new
        row each, a verify launch (`one_call`) one call whose `steps` query
        rows all walk the slot's length + steps. table: the rows the block
        tables handed to the launch span, slots x table width x block size
        x steps."""
        from ..ops.pallas import decode_attention as da

        live = int(steps * np.sum(lengths)
                   + len(lengths) * steps * (steps + 1) // 2)
        span = self.table_width * self.kv_cache.block_size
        block = (da.paged_pages_per_step(self.table_width,
                                         self.kv_cache.block_size)
                 * self.kv_cache.block_size)
        lengths = np.asarray(lengths, np.int64)
        calls = ([(lengths, steps)] * steps if one_call
                 else [(lengths + t, 1) for t in range(steps)])
        walked = block * sum(int(da.paged_walk_blocks(ln, new, block).sum())
                             for ln, new in calls)
        return live, walked, self.max_slots * span * steps

    def _compiled_now(self, program):
        """1 if the launch the hook just stashed had to build `program`."""
        info = self._last_launch
        return int(bool(info and info.get("path") == program
                        and info["compiled"]))

    def _verify_tick(self):
        """Speculative decode tick (spec_k > 0): draft on the host, verify
        in ONE fixed-width `verify_step` launch across all decoding slots.

        Per slot the drafted width is min(drafter proposal, SPARE width) —
        spare = tokens still owed minus the launch's guaranteed one, so a
        slot about to retire rides along with zero drafts instead of
        forking a narrower program. Rollback on rejection is length
        bookkeeping only (verify_step's contract); the KV ceiling stays
        the reserved plen + max_new exactly like the decode tick."""
        with self._slot_lock:
            dec = [(i, s) for i, s in enumerate(self._slots)
                   if s is not None and s.phase == _DECODE]
        if not dec:
            return
        S, K = self.max_slots, self.spec_k
        with RecordEvent("serve.decode.assemble"):
            chunk = np.zeros((S, K + 1), np.int64)
            offs = np.zeros(S, np.int64)
            dlens = np.zeros(S, np.int64)
            maxlens = np.zeros(S, np.int64)
            active = np.zeros(S, bool)
            temps = np.zeros(S, np.float32)
            tks = np.zeros(S, np.int32)
            tables = np.zeros((S, self.table_width), np.int32)
            for i, s in dec:
                # shared-prefix safety (ISSUE-11): a verify launch writes
                # its whole window at [length, length+1+K) and rejection
                # "rollback" is length bookkeeping only — length never
                # drops below plen, and a prefix hit covers at most plen-1
                # tokens, so a verify tick can never write into (or roll
                # back into) a shared block
                assert s.length >= s.plen > s.prefix_hit, \
                    (f"verify tick would touch shared prefix rows: "
                     f"length={s.length} plen={s.plen} hit={s.prefix_hit}")
                chunk[i, 0] = s.tok
                offs[i] = s.length
                maxlens[i] = s.plen + s.max_new
                active[i] = True
                temps[i] = s.temperature
                tks[i] = s.top_k
                tables[i] = s.table
                spare = s.max_new - len(s.generated) - 1
                if s.spec and spare > 0:
                    hist = np.concatenate(
                        [s.ids, np.asarray(s.generated, np.int64)])
                    prop = np.asarray(self._drafter.draft(hist, K),
                                      np.int64).reshape(-1)[:K]
                    n = min(len(prop), spare)
                    if n > 0:
                        chunk[i, 1:1 + n] = prop[:n]
                        dlens[i] = n
            reqs = [s.req for _, s in dec]
            akw = self._adapter_tick_kwargs(dec, reqs)
        traced = self.tracer.enabled
        t0 = self.tracer.now_us() if traced else 0.0
        try:
            if self._faults is not None:
                self._faults.check("predictor.generate")
            with RecordEvent("serve.decode.dispatch") as ev:
                acc, nxt = self.model.verify_step(
                    chunk, offs, dlens, active, self.kv_cache, tables,
                    max_lens=maxlens, temperature=temps, top_k=tks,
                    decode_kernel=self.decode_kernel, seed=next(self._seed),
                    timing_hook=self._timing_hook, **akw)
                if RecordEvent.capturing():
                    ev.set_stats(compiled=self._compiled_now("verify_step"))
            info = self._take_launch()
        except ThreadDeath:
            raise
        except Exception as e:
            self._fail_picks(dec, e, "verify_step", t0)
            return
        self.breaker.record_success()
        self.metrics.inc("verify_ticks")
        (acc, nxt), wait_s = self._read_back("decode", acc, nxt)
        drafted = int(sum(dlens[i] for i, _ in dec))
        accepted = int(sum(acc[i] for i, _ in dec))
        self._span_each(reqs, "verify_step", t0, self.tracer.now_us(),
                        slots=len(dec), drafted=drafted, accepted=accepted)
        self._spec_counter.labels(self._component, "drafted").inc(drafted)
        self._spec_counter.labels(self._component, "accepted").inc(accepted)
        self._spec_counter.labels(self._component,
                                  "wasted").inc(drafted - accepted)
        with self._slot_lock:
            self._spec_drafted += drafted
            self._spec_accepted += accepted
        live_rows, walked_rows, table_rows = self._kv_rows(
            offs[active], K + 1, one_call=True)
        units = []
        with RecordEvent("serve.decode.absorb") as ev:
            for i, s in dec:
                a = int(acc[i])
                s.length += 1 + a   # committed rows: accepted prefix + emitted
                s.tok = int(nxt[i])
                n0 = s.n_tok
                self._absorb(i, s, [int(t) for t in chunk[i, 1:1 + a]]
                             + [s.tok])
                # useful = absorbed (accepted prefix + the emitted token,
                # minus any over-cap shortfall); rejected drafts are
                # spec_waste; the rest of the S*(K+1) window is pad
                units.append((s.tenant, s.n_tok - n0))
            if RecordEvent.capturing():
                ev.set_stats(useful=sum(u for _, u in units),
                             issued=S * (K + 1), rows=live_rows)
        self._util_launch("verify_step", info, wait_s, S * (K + 1), units,
                          spec_units=drafted - accepted,
                          live_rows=live_rows, walked_rows=walked_rows,
                          table_rows=table_rows,
                          sampler=sampler_engages(temps, tks))

    # ------------------------------------------------------------- lifecycle
    def _abandon_slots(self):
        """ThreadDeath path: free every slot's blocks; still-pending
        requests re-enter the queue and re-run from scratch after the
        supervisor heals the thread (their chunked-prefill progress is
        lost with the thread — correctness over cleverness). A launch run
        ahead is left to the device unread: nothing it carries survives."""
        self._ahead = None
        for i, s in enumerate(list(self._slots)):
            if s is None:
                continue
            self._evict_slot(i, s)
            if s.req.state == _PENDING:
                if s.req.trace is not None:
                    s.req.trace.event("requeued_after_thread_death")
                self._enqueue(s.req)
        for s in list(self._paused):
            # paused sequences lose their progress with the thread too:
            # blocks back to the pool, still-pending requests re-enter the
            # queue and re-run from scratch (correctness over cleverness)
            self._evict_paused(s)
            if s.req.state == _PENDING:
                if s.req.trace is not None:
                    s.req.trace.event("requeued_after_thread_death")
                self._enqueue(s.req)

    def _shutdown_slots(self):
        """stop() path: nobody hangs on a closed scheduler. A launch run
        ahead lands first: what it finished is answered."""
        try:
            self._drain()
        except ThreadDeath:
            raise
        except Exception:       # pragma: no cover - the device failed it
            pass
        for i, s in enumerate(list(self._slots)):
            if s is None:
                continue
            self._fail(s.req, ServiceUnavailable("predictor closed",
                                                 retry_after=None))
            self._evict_slot(i, s)
        for s in list(self._paused):
            self._fail(s.req, ServiceUnavailable("predictor closed",
                                                 retry_after=None))
            self._evict_paused(s)
        self._drain_backlog()

    def _drain_backlog(self):
        """Backlog twin of close()'s queue drain: requests parked in the
        admit-policy reorder buffer get a terminal outcome too."""
        while True:
            try:
                r = self._backlog.popleft()
            except IndexError:
                break
            self._fail(r, ServiceUnavailable("predictor closed",
                                             retry_after=None))

    def close(self):
        super().close()
        self._drain_backlog()
        self._ledger.close()    # readable through utilization.ledgers()
