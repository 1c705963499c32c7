"""Serving: dynamic batching + an HTTP endpoint over the Predictor.

Reference role: the AnalysisPredictor deployment stack (paddle/fluid/
inference/, ~90K C++) + Paddle Serving's request batching. TPU-native shape:
one resident compiled program per batch bucket; a collector thread coalesces
concurrent requests into a single device launch (decode throughput on TPU is
bound by the batch), then splits results.
The HTTP front end is a stdlib ThreadingHTTPServer speaking npz, so a client
needs nothing but numpy.

Fault tolerance (inference/resilience.py): every request carries ONE deadline
from HTTP header → queue → decode launch and reaches exactly ONE terminal
outcome (result | timeout | shed) through a compare-and-swap on the request
state — a client timing out while the batcher is mid-launch can never race
into both a TimeoutError and a delivered result. Overload is rejected at the
door (429/503 + Retry-After) instead of exploding mid-batch; a dead batcher
thread is restarted by the clients waiting on it; repeated predictor failures
trip a circuit breaker; a KV-pool/model signature mismatch degrades to the
dense generate path instead of crashing. inference/faults.py injects
deterministic faults at the seams for the chaos tests.
"""
from __future__ import annotations

import collections
import io
import itertools
import math
import queue
import threading
import time

import numpy as np

from ..analysis.lockwitness import make_lock
from ..observability.metrics import MetricsRegistry, render_prometheus
from ..observability.trace import RequestTrace, Tracer, new_trace_id
from .faults import ThreadDeath
from .kv_cache import CacheOutOfBlocks
from .resilience import (
    AdmissionController,
    CircuitBreaker,
    Deadline,
    DeadlineExceeded,
    Rejected,
    ServerBusy,
    ServiceUnavailable,
    ServingMetrics,
    Supervisor,
)

__all__ = ["BatchingPredictor", "GenerateBatchingPredictor",
           "ContinuousGenerateBatchingPredictor", "InferenceServer",
           "ReplicaFleet", "retry_after_header", "RETRY_AFTER_CAP"]

# Retry-After ceiling (seconds): a rate-limited tenant with a deep token
# debt should re-probe within a minute, not sleep out the whole debt — the
# server's picture of its own load is stale long before that.
RETRY_AFTER_CAP = 60.0

# /debug/profile duration ceiling (ms). A device trace grows with capture
# length and the handler thread sleeps through the whole window — 10s is
# plenty to catch a steady-state tick pattern and short enough that a fat-
# fingered ms=3600000 can't pin a handler (and a trace directory) for an
# hour. Larger requests are a client bug: 400, not a silent clamp.
PROFILE_MS_CAP = 10_000


def retry_after_header(retry_after, cap=RETRY_AFTER_CAP) -> str:
    """Retry-After header value from a shed's computed hint: ceil to whole
    seconds (the header is integral), floor 1 (clients treat 0 as "retry
    immediately" — that is how retry storms start), cap at `cap`. A hint-
    less shed (None) gets the 1s floor — a 429/503 without Retry-After
    makes clients invent their own backoff."""
    if retry_after is None:
        return "1"
    return str(int(min(max(1, math.ceil(float(retry_after))),
                       math.ceil(cap))))


def __getattr__(name):
    # lazy re-export (PEP 562): scheduler.py subclasses this module's
    # GenerateBatchingPredictor, so a top-of-module import would be circular
    if name == "ContinuousGenerateBatchingPredictor":
        from .scheduler import ContinuousGenerateBatchingPredictor

        return ContinuousGenerateBatchingPredictor
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")

_PENDING, _DONE, _CANCELLED = "pending", "done", "cancelled"


class _Request:
    """One in-flight request with compare-and-swap terminal semantics.

    Exactly one of finish()/fail()/cancel() wins; the losers observe False
    and must not deliver their outcome. This is what makes "timed out in the
    queue", "computed but the client already gave up", and "failed mid-batch"
    mutually exclusive instead of racy."""

    __slots__ = ("arrays", "event", "result", "error", "deadline", "retries",
                 "defers", "t0", "trace", "enq_us", "max_new", "temperature",
                 "top_k", "spec", "adapter", "tenant", "on_tokens",
                 "attribution", "_lock", "_state")

    def __init__(self, arrays, deadline=None, trace=None):
        self.arrays = arrays
        self.deadline = deadline
        self.event = threading.Event()
        self.result = None
        self.error = None
        self.retries = 0        # failed-batch re-runs consumed
        self.defers = 0         # pool-full next-batch deferrals consumed
        self.t0 = None
        self.trace = trace      # observability.trace.RequestTrace | None
        self.enq_us = None      # queue-entry stamp (tracer µs) of this pass
        self.max_new = None     # per-request token budget (continuous sched.)
        self.temperature = None  # per-request sampling (continuous sched.)
        self.top_k = None
        self.spec = None        # tri-state speculative opt-out (continuous)
        self.adapter = None     # LoRA adapter name (ISSUE-15, continuous)
        self.tenant = None      # QoS tenant name (ISSUE-17, continuous)
        # streaming delivery channel (ISSUE-11): set by infer_stream before
        # enqueue, called by the scheduler's tick loop with each newly
        # absorbed token chunk; None = buffered (non-streaming) request
        self.on_tokens = None
        # ISSUE-18 deadline attribution: the continuous scheduler computes
        # {queue,prefill,paused,decode}_share at retirement and parks the
        # dict here so the terminal CAS (whichever leg wins) tags the
        # terminal span with where the request's wall time actually went
        self.attribution = None
        self._lock = make_lock("serving._Request._lock")
        self._state = _PENDING

    @property
    def state(self):
        return self._state

    def finish(self, result) -> bool:
        with self._lock:
            if self._state != _PENDING:
                return False
            self.result = result
            self._state = _DONE
            self.event.set()
            return True

    def fail(self, error) -> bool:
        with self._lock:
            if self._state != _PENDING:
                return False
            self.error = error
            self._state = _DONE
            self.event.set()
            return True

    def cancel(self) -> bool:
        with self._lock:
            if self._state != _PENDING:
                return False
            self._state = _CANCELLED
            self.event.set()
            return True


class BatchingPredictor:
    """Coalesce concurrent single requests into batched Predictor.run calls.

    Requests are padded to the next bucket size (powers of two up to
    `max_batch_size`) so the number of compiled programs stays bounded —
    dynamic shapes would recompile per batch size otherwise.

    Resilience knobs: `admission` sheds load at submit time (ServerBusy →
    429), `breaker` fails fast after repeated predictor faults
    (ServiceUnavailable → 503), `max_retries` re-runs requests from a failed
    batch before surfacing the error, and a Supervisor restarts the batcher
    thread if it dies (clients waiting in `_await` drive the restart, so a
    dead batcher with a full queue heals without a watchdog thread)."""

    # per-request sampler headers (X-Temperature/X-Top-K/X-Spec) only make
    # sense on the continuous scheduler, whose step programs take traced
    # per-slot sampler inputs; the whole-batch predictors run one sampler
    # config per compiled program, so the HTTP layer 400s the headers there
    supports_sampler_knobs = False

    # SSE token streaming (ISSUE-11) needs tick-boundary flushes, which only
    # the continuous scheduler produces; the HTTP layer 400s Accept:
    # text/event-stream against whole-batch predictors instead of buffering
    # silently (a "stream" that arrives all at once is a lie)
    supports_streaming = False

    # multi-LoRA routing (ISSUE-15) lives in the continuous scheduler's
    # banked step programs; X-Adapter against a whole-batch predictor is a
    # client misroute -> 400, same taxonomy as the sampler headers
    supports_adapters = False

    # multi-tenant QoS (ISSUE-17) lives in the continuous scheduler's
    # tenant ledger; X-Tenant against a whole-batch predictor is the same
    # client misroute -> 400
    supports_tenants = False

    _component = "batcher"      # prometheus `component` label value

    def __init__(self, predictor, max_batch_size=8, max_delay_ms=2.0,
                 faults=None, admission=None, breaker=None, max_retries=1,
                 max_restarts=5, tracer=None, registry=None, component=None):
        self.predictor = predictor
        # instance override of the prometheus `component` label: replicas in
        # a ReplicaFleet share one registry, so each needs a distinct name
        # ("r0", "r1", ...) or their series would merge
        if component is not None:
            self._component = str(component)
        self.max_batch_size = int(max_batch_size)
        self.max_delay = max_delay_ms / 1000.0
        self.max_retries = int(max_retries)
        self._faults = faults
        self._clock = faults.monotonic if faults is not None else time.monotonic
        # observability: request-scoped spans (trace.py) + typed registry
        # (metrics.py). Pass Tracer(enabled=False) to serve untraced — the
        # bench's observability_overhead leg measures exactly that delta.
        self.tracer = tracer if tracer is not None else Tracer()
        self.metrics = ServingMetrics(registry=registry,
                                      component=self._component)
        # ISSUE-18: span loss is invisible until it bites a postmortem —
        # surface the tracer ring's eviction count on the scrape (function-
        # backed: the tracer already maintains the number; no double books)
        self.metrics.registry.counter(
            "paddle_trace_dropped_spans_total",
            "Spans evicted from the tracer ring buffer (raise Tracer "
            "capacity= if this grows during an incident window)",
            labels=("component",)).labels(self._component).set_function(
                lambda: float(self.tracer.dropped))
        self.admission = admission if admission is not None \
            else AdmissionController()
        self.breaker = breaker if breaker is not None else CircuitBreaker(
            failure_threshold=5, reset_after=1.0, clock=self._clock)
        self._queue: queue.Queue = queue.Queue()
        self._stop = threading.Event()
        self._draining = threading.Event()
        self._busy = False
        # deque: appends from the batcher thread are atomic (thread-lint
        # documented-atomic type; a plain list.append is too under the GIL,
        # but the contract is explicit this way)
        self.batch_sizes: collections.deque = collections.deque()
        # component-qualified names: a ReplicaFleet runs N of these, and an
        # unqualified thread dump / permanent-503 message can't say WHICH
        # replica died
        self._sup = Supervisor(self._make_thread,
                               name=f"{type(self).__name__}[{self._component}]",
                               max_restarts=max_restarts)
        self._sup.start()

    def _make_thread(self):
        return threading.Thread(target=self._thread_main, daemon=True,
                                name=f"batching-predictor[{self._component}]")

    def _thread_main(self):
        try:
            self._loop()
        except ThreadDeath:
            pass    # worker dies (supervisor will heal) without excepthook noise

    # ---------------------------------------------------------------- client
    def infer(self, *arrays, timeout=None, deadline=None, trace_id=None):
        """One logical sample in (arrays WITHOUT the batch dim), one out.

        `timeout` seconds become a Deadline that rides with the request
        through the queue and into the batch (`deadline` passes one in
        directly); expiry anywhere raises DeadlineExceeded (a TimeoutError)
        here, exactly once, with the queue slot reclaimed. `trace_id` joins
        the request to an existing trace (HTTP `X-Trace-Id` propagation);
        omitted, a fresh trace is minted."""
        req = self._make_request([np.asarray(a) for a in arrays],
                                 timeout, deadline, trace_id)
        return self._submit(req)

    def _make_request(self, arrays, timeout, deadline, trace_id=None):
        if deadline is None and timeout is not None:
            deadline = Deadline.after(float(timeout), self._clock)
        return _Request(arrays, deadline,
                        trace=RequestTrace(self.tracer, trace_id))

    def _admission_check(self, arrays, req=None):
        self.admission.admit(self._queue.qsize())

    def _enqueue(self, req):
        """Queue entry point (first pass AND defer/retry/death re-passes):
        stamps the queue-wait span start before handing to the batcher."""
        req.enq_us = req.trace.now_us() if req.trace is not None else None
        self._queue.put(req)

    def _submit(self, req):
        self._start(req)
        return self._await(req)

    def _start(self, req):
        """Synchronous admission half of _submit: shed/breaker/validation
        outcomes raise HERE — so the streaming path (infer_stream) can
        surface 4xx/5xx statuses before any response bytes flush — then
        the accepted request enters the queue."""
        tr = req.trace
        t_adm = tr.now_us()
        try:
            if self._stop.is_set() or self._draining.is_set():
                raise ServiceUnavailable("predictor is shutting down",
                                         retry_after=None)
            if self._sup.heal():
                self.metrics.inc("batcher_restarts")
            if not self.breaker.allow():
                raise ServiceUnavailable(
                    "circuit open after repeated predictor failures",
                    retry_after=self.breaker.retry_after())
            self._admission_check(req.arrays, req)
        except Rejected as e:
            self.metrics.inc("rejected_busy" if isinstance(e, ServerBusy)
                             else "rejected_unavailable")
            # ISSUE-18 availability SLO: a door rejection is terminal too —
            # 429 is the client's backpressure (good), 503 is ours (bad)
            slo = getattr(self, "slo", None)
            if slo is not None:
                slo.observe_terminal(e.status < 500,
                                     tenant=getattr(req, "tenant", None))
            tr.child("admission", t_adm, tr.now_us(), error=repr(e))
            # door rejection (ISSUE-18): 100% of the request's life was
            # queue-side — attribute it as such; rejected requests never
            # enter the TTFT histogram (a zero-valued sample would drag
            # p50 toward the shed path instead of measuring served ones)
            tr.finish("rejected", status=e.status, error=repr(e),
                      queue_share=1.0, prefill_share=0.0,
                      paused_share=0.0, decode_share=0.0)
            raise
        except ValueError as e:  # malformed/oversized: no retry can fix it
            self.metrics.inc("rejected_invalid")
            tr.child("admission", t_adm, tr.now_us(), error=repr(e))
            tr.finish("rejected", status=400, error=repr(e))
            raise
        tr.child("admission", t_adm, tr.now_us())
        self.metrics.inc("accepted")
        req.t0 = self._clock()
        self._enqueue(req)

    def _await(self, req):
        """Wait for the terminal outcome, healing a dead batcher meanwhile."""
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
                                             where="client_wait")
                        raise DeadlineExceeded("inference request timed out")
                    break   # lost the race: a terminal outcome just landed
                step = min(0.1, rem)
            if req.event.wait(step):
                break
            try:
                if self._sup.heal():
                    self.metrics.inc("batcher_restarts")
            except ServiceUnavailable as e:
                self._fail(req, e)
                raise
        if req.error is not None:
            raise req.error
        return req.result

    # --------------------------------------------------------- terminal CAS
    def _observe(self, req):
        if req.t0 is not None:
            self.metrics.observe_latency(self._clock() - req.t0)

    def _finish_req(self, req, result) -> bool:
        if req.finish(result):
            self.metrics.inc("completed")
            self._observe(req)
            if req.trace is not None:
                req.trace.finish("result", cas="result",
                                 **(req.attribution or {}))
            return True
        # computed a result nobody will read (client cancelled mid-batch)
        self.metrics.inc("wasted_results")
        if req.trace is not None:
            req.trace.event("wasted_result")
        return False

    def _fail(self, req, error) -> bool:
        if not req.fail(error):
            return False
        if isinstance(error, DeadlineExceeded):
            self.metrics.inc("timeouts")
            terminal = "timeout"
        else:
            self.metrics.inc("failed")
            terminal = "error"
            if isinstance(error, ServerBusy):
                self.metrics.inc("shed_busy")
                terminal = "shed"
            elif isinstance(error, ServiceUnavailable):
                self.metrics.inc("shed_unavailable")
                terminal = "shed"
        self._observe(req)
        if req.trace is not None:
            req.trace.finish(terminal, cas=terminal, error=repr(error),
                             **(req.attribution or {}))
        return True

    def _fail_or_retry(self, req, error):
        """Failure isolation: give the request another batch before failing
        it, unless the error is terminal by construction (shed/deadline) or
        the request can no longer make its deadline."""
        retryable = not isinstance(error, (Rejected, DeadlineExceeded))
        if (retryable and req.retries < self.max_retries
                and not self._stop.is_set()
                and not (req.deadline is not None
                         and req.deadline.expired())):
            req.retries += 1
            self.metrics.inc("retries")
            if req.trace is not None:
                req.trace.event("retry", attempt=req.retries,
                                error=repr(error))
            self._enqueue(req)
        else:
            self._fail(req, error)

    def _usable(self, req) -> bool:
        """Collection-time filter: cancelled requests are skipped (their
        client already took the timeout), expired ones are failed here —
        either way they never cost a batch slot or a predictor call."""
        state = req.state
        if state != _PENDING:    # cancelled, or already terminal (requeued
            if state == _CANCELLED:  # by a dying thread after finishing)
                self.metrics.inc("cancelled_skipped")
            return False
        if req.deadline is not None and req.deadline.expired():
            if req.trace is not None and req.enq_us is not None:
                req.trace.child("queue_wait", req.enq_us,
                                req.trace.now_us(), expired=True)
                req.enq_us = None
            if self._fail(req, DeadlineExceeded("deadline expired in queue")):
                self.metrics.inc("expired_in_queue")
            return False
        return True

    # ---------------------------------------------------------------- worker
    def _bucket(self, n):
        b = 1
        while b < n:
            b *= 2
        return min(b, self.max_batch_size)

    def _loop(self):
        while not self._stop.is_set():
            if self._faults is not None:
                self._faults.check("batcher.tick")  # ThreadDeath escapes
            try:
                first = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            self._busy = True
            try:
                t_as = self.tracer.now_us() if self.tracer.enabled else 0.0
                batch = self._collect(first)
                if self.tracer.enabled and batch:
                    t_as1 = self.tracer.now_us()
                    for r in batch:     # batch-level span, in each member's
                        if r.trace is not None:  # trace (shared batch tags)
                            r.trace.child("batch_assembly", t_as, t_as1,
                                          batch_size=len(batch))
                try:
                    self._run_batch(batch)
                except ThreadDeath:
                    for r in batch:     # the dying thread strands no work
                        if r.state == _PENDING:
                            self._enqueue(r)
                    raise
            finally:
                self._busy = False

    def _collect(self, first):
        """Collect up to max_batch_size requests within the max_delay window —
        waking EARLY once the bucket fills (a full batch arriving instantly
        used to still pay the whole window; VERDICT r5 weak #5)."""
        batch = [first] if self._usable(first) else []
        # the injectable clock (faults.monotonic under chaos): skew-driven
        # tests steer the collection window too (thread-lint raw-clock rule)
        deadline = self._clock() + self.max_delay
        while len(batch) < self.max_batch_size:
            remaining = deadline - self._clock()
            if remaining <= 0:
                break
            try:
                r = self._queue.get(timeout=remaining)
            except queue.Empty:
                break
            if self._usable(r):
                batch.append(r)
        return batch

    def _end_queue_wait(self, batch):
        """Close each collected request's queue-wait span (re-opened by
        _enqueue on defer/retry re-passes)."""
        if not self.tracer.enabled:
            return
        now = self.tracer.now_us()
        for r in batch:
            if r.trace is not None and r.enq_us is not None:
                r.trace.child("queue_wait", r.enq_us, now)
                r.enq_us = None

    def _span_each(self, batch, name, start_us, end_us, **tags):
        """Record one batch-level interval under every member's trace."""
        if not self.tracer.enabled:
            return
        for r in batch:
            if r.trace is not None:
                r.trace.child(name, start_us, end_us, **tags)

    def _run_batch(self, batch):
        if self._faults is not None:
            self._faults.check("batcher.batch")  # ThreadDeath escapes
        batch = [r for r in batch if self._usable(r)]
        if not batch:
            return
        self._end_queue_wait(batch)
        t_launch0 = self.tracer.now_us() if self.tracer.enabled else 0.0
        try:
            n = len(batch)
            bucket = self._bucket(n)
            self.batch_sizes.append(n)
            stacked = []
            for i in range(len(batch[0].arrays)):
                arr = np.stack([r.arrays[i] for r in batch])
                if bucket > n:  # pad to the bucket to bound compilations
                    pad = np.repeat(arr[:1], bucket - n, axis=0)
                    arr = np.concatenate([arr, pad], axis=0)
                stacked.append(arr)
            if self._faults is not None:
                self._faults.check("predictor.run")
            t_dec = self.tracer.now_us() if self.tracer.enabled else 0.0
            outs = self.predictor.run(stacked)
            self.breaker.record_success()
            self._span_each(batch, "decode_launch", t_launch0, t_dec,
                            batch_size=n, bucket=bucket)
            self._span_each(batch, "decode", t_dec, self.tracer.now_us(),
                            batch_size=n)
            for j, r in enumerate(batch):
                self._finish_req(r, [o[j] for o in outs])
        except Exception as e:
            self.breaker.record_failure()
            self.metrics.inc("batch_failures")
            self._span_each(batch, "decode", t_launch0, self.tracer.now_us(),
                            error=repr(e))
            for r in batch:
                self._fail_or_retry(r, e)

    # ------------------------------------------------------------- lifecycle
    def pending(self) -> int:
        """Queued + in-flight work (drain condition for InferenceServer)."""
        return self._queue.qsize() + (1 if self._busy else 0)

    def drain(self):
        """Refuse new requests; queued/in-flight ones keep running."""
        self._draining.set()

    def close(self):
        self._stop.set()
        t = self._sup.thread
        if t is not None:
            t.join(timeout=2)
        while True:     # nobody hangs on a closed predictor
            try:
                r = self._queue.get_nowait()
            except queue.Empty:
                break
            self._fail(r, ServiceUnavailable("predictor closed",
                                             retry_after=None))


class GenerateBatchingPredictor(BatchingPredictor):
    """Dynamic batching for autoregressive generation over a SHARED paged KV
    cache (paddle_tpu/inference/kv_cache.py).

    Mixed-length prompts batch together: each request reserves only
    ceil((len + max_new) / block_size) pages from the shared pool — memory
    scales with the tokens actually cached, not batch * server-max-length.
    Prompts are right-padded to the batch max for the compiled program;
    per-request lengths mask the padding in the paged decode-attention kernel
    and the out-of-bounds-scatter trick drops padding rows from the pool, so
    batching never changes tokens (parity pinned in tests).

    Backpressure: requests that cannot fit the pool RIGHT NOW are deferred to
    a later batch at most `max_defers` times (blocks free as earlier batches
    retire), then shed with ServerBusy (HTTP 429 + Retry-After) — a
    CacheOutOfBlocks never escapes to a whole batch. A request larger than
    the entire pool is rejected at submit time (ValueError: no retry can
    fix it). If the pool's shape signature does not match the model, the
    predictor degrades to the dense generate() path per request instead of
    launching a paged program that would scatter garbage."""

    _component = "generator"

    def __init__(self, model, max_batch_size=8, max_delay_ms=2.0,
                 max_new_tokens=32, kv_cache=None, decode_kernel="pallas",
                 block_size=32, num_blocks=64, faults=None, admission=None,
                 breaker=None, max_retries=1, max_defers=8, max_restarts=5,
                 tracer=None, registry=None, component=None, launch_rows=1):
        from .kv_cache import PagedKVCache

        spec = model._decode_cache_spec()
        if kv_cache is None:
            # `launch_rows`: the most rows one launch writes for a slot,
            # which sizes the ring of a layer that keeps only a window
            kv_cache = PagedKVCache.for_model(
                model, block_size=block_size, num_blocks=num_blocks,
                faults=faults, slots=max_batch_size, launch_rows=launch_rows)
        self.model = model
        self.kv_cache = kv_cache
        self.max_new_tokens = int(max_new_tokens)
        self.max_defers = int(max_defers)
        self.decode_kernel = decode_kernel
        # paged decode launches against a mismatched pool would scatter into
        # wrong shapes; degrade to per-request dense generation instead
        self.fallback_dense = kv_cache.spec != spec
        # itertools.count: request-id draws are atomic (next() is a single
        # C-level op), so the batcher thread and any future helper threads
        # can draw ids without a lock (thread-lint unguarded-write fix)
        self._rid = itertools.count(1)
        super().__init__(predictor=None, max_batch_size=max_batch_size,
                         max_delay_ms=max_delay_ms, faults=faults,
                         admission=admission, breaker=breaker,
                         max_retries=max_retries, max_restarts=max_restarts,
                         tracer=tracer, registry=registry,
                         component=component)
        # pool state scrapes through the shared registry (live/free/evictable
        # gauges + eviction counter), decode launches feed the histogram below
        kv_cache.bind_metrics(self.metrics.registry, pool=self._component)
        self._decode_hist = self.metrics.registry.histogram(
            "paddle_decode_launch_seconds",
            "Host wall of one decode launch by path, from its dispatch "
            "through the read-back of its tokens (the device's work "
            "included)", labels=("component", "path"))
        self._tokens_total = self.metrics.registry.counter(
            "paddle_generated_tokens_total", "Tokens generated (batch * new)",
            labels=("component",))
        self._last_launch = None        # the hook's stash (_gen_timing)

    def _gen_timing(self, info):
        """models/generation.py timing hook -> registry series. The hook
        fires when the launch is DISPATCHED; the worker observes the
        launch's wall once it has read the tokens back (`_launch_done`)."""
        self._last_launch = info        # worker-thread-only stash
        self._tokens_total.labels(self._component).inc(
            info["batch"] * info["new_tokens"])

    def _launch_done(self, wait_s, info=None):
        """Observe a launch now that its result is on the host: dispatch
        plus the `wait_s` of the read-back. `info` is the launch's hook
        record, by default the one the hook last stashed. Returns (hook
        record, launch seconds), or (None, 0.0) if no hook fired."""
        if info is None:
            info, self._last_launch = self._last_launch, None
        if info is None:
            return None, 0.0
        launch_s = info["dispatch_s"] + wait_s
        self._decode_hist.labels(self._component, info["path"]).observe(
            launch_s)
        return info, launch_s

    def infer(self, ids, timeout=None, deadline=None, trace_id=None):
        """One prompt (1-D int ids) in -> full generated sequence out."""
        req = self._make_request([np.asarray(ids)], timeout, deadline,
                                 trace_id)
        return self._submit(req)

    def _admission_check(self, arrays, req=None):
        need = self.kv_cache.blocks_for(len(arrays[0]) + self.max_new_tokens)
        self.admission.admit(self._queue.qsize(), cache=self.kv_cache,
                             blocks_needed=need)

    # ---------------------------------------------------------------- worker
    def _shed_or_defer(self, req, error):
        """Pool-full isolation: THIS request alone waits for blocks or sheds;
        the rest of its batch proceeds."""
        if req.deadline is not None and req.deadline.expired():
            self._fail(req, DeadlineExceeded("deadline expired waiting for "
                                             "KV blocks"))
        elif req.defers >= self.max_defers:
            self._fail(req, ServerBusy(
                f"KV pool exhausted after {req.defers} deferrals: {error}",
                retry_after=self.admission.retry_after))
        else:
            req.defers += 1
            self.metrics.inc("deferred")
            if req.trace is not None:
                req.trace.event("deferred", attempt=req.defers,
                                error=repr(error))
            self._enqueue(req)

    def _run_batch(self, batch):
        if self._faults is not None:
            self._faults.check("batcher.batch")  # ThreadDeath escapes
        batch = [r for r in batch if self._usable(r)]
        if not batch:
            return
        if self.fallback_dense:
            return self._run_dense(batch)
        self._end_queue_wait(batch)
        traced = self.tracer.enabled
        t_launch0 = self.tracer.now_us() if traced else 0.0
        cache = self.kv_cache
        admitted: list[tuple] = []
        try:
            for r in batch:
                plen = len(r.arrays[0])
                rid = ("req", next(self._rid))
                t_kv = self.tracer.now_us() if traced else 0.0
                try:
                    cache.reserve(rid, plen + self.max_new_tokens)
                except CacheOutOfBlocks as e:
                    if traced and r.trace is not None:
                        r.trace.child("kv_reserve", t_kv,
                                      self.tracer.now_us(), error=repr(e))
                    self._shed_or_defer(r, e)
                    continue
                if traced and r.trace is not None:
                    r.trace.child(
                        "kv_reserve", t_kv, self.tracer.now_us(),
                        blocks=cache.blocks_for(plen + self.max_new_tokens))
                admitted.append((rid, r))
            if not admitted:
                return
            n = len(admitted)
            self.batch_sizes.append(n)
            plens = np.asarray([len(r.arrays[0]) for _, r in admitted],
                               np.int64)
            P = int(plens.max())
            prompts = np.zeros((n, P), admitted[0][1].arrays[0].dtype)
            for i, (_, r) in enumerate(admitted):
                prompts[i, :plens[i]] = r.arrays[0]
            nb = max(cache.blocks_for(int(p) + self.max_new_tokens)
                     for p in plens)
            tbl = np.stack([cache.block_table(rid, pad_to=nb)
                            for rid, _ in admitted])
            if self._faults is not None:
                self._faults.check("predictor.generate")
            dls = [r.deadline for _, r in admitted]
            batch_dl = (max(dls, key=lambda d: d.remaining())
                        if all(d is not None for d in dls) else None)
            t_dec = self.tracer.now_us() if traced else 0.0
            toks = self.model.generate_paged(
                prompts, plens, cache, tbl,
                max_new_tokens=self.max_new_tokens,
                decode_kernel=self.decode_kernel, deadline=batch_dl,
                timing_hook=self._gen_timing)
            t_wait = time.perf_counter()
            toks = np.asarray(toks._value if hasattr(toks, "_value") else toks)
            self._launch_done(time.perf_counter() - t_wait)
            self.breaker.record_success()
            adm = [r for _, r in admitted]
            self._span_each(adm, "decode_launch", t_launch0, t_dec,
                            batch_size=n)
            self._span_each(adm, "decode", t_dec, self.tracer.now_us(),
                            batch_size=n, path="paged",
                            kernel=self.decode_kernel)
            for i, (rid, r) in enumerate(admitted):
                cache.set_length(rid, int(plens[i]) + self.max_new_tokens)
                self._finish_req(r, np.concatenate(
                    [r.arrays[0], toks[i].astype(r.arrays[0].dtype)]))
        except Exception as e:
            self.breaker.record_failure()
            self.metrics.inc("batch_failures")
            self._span_each([r for _, r in admitted], "decode", t_launch0,
                            self.tracer.now_us(), error=repr(e))
            for _, r in admitted:
                self._fail_or_retry(r, e)
        finally:
            # all-paths release guard: blocks reserved above can never leak,
            # whatever the batch body did
            for rid, _ in admitted:
                try:
                    cache.mark_done(rid)
                    cache.release(rid)
                except KeyError:    # pragma: no cover - evicted already
                    pass

    def _run_dense(self, batch):
        """Graceful degradation: per-request dense generate() (correct but
        unshared-memory) when the paged pool cannot serve this model."""
        self.metrics.inc("dense_fallback_batches")
        self.batch_sizes.append(len(batch))
        self._end_queue_wait(batch)
        dtype = (None if str(self.kv_cache.dtype) == "float32"
                 else str(self.kv_cache.dtype))
        for r in batch:
            t_dec = self.tracer.now_us() if self.tracer.enabled else 0.0
            try:
                if self._faults is not None:
                    self._faults.check("predictor.generate")
                out = self.model.generate(
                    r.arrays[0][None], max_new_tokens=self.max_new_tokens,
                    dtype=dtype, decode_kernel=self.decode_kernel,
                    deadline=r.deadline, timing_hook=self._gen_timing)
                self.breaker.record_success()
                t_wait = time.perf_counter()
                out = np.asarray(out._value if hasattr(out, "_value")
                                 else out)[0]
                self._launch_done(time.perf_counter() - t_wait)
                self._span_each([r], "decode", t_dec, self.tracer.now_us(),
                                path="dense_fallback")
                self._finish_req(r, out.astype(r.arrays[0].dtype))
            except Exception as e:
                self.breaker.record_failure()
                self.metrics.inc("batch_failures")
                self._span_each([r], "decode", t_dec, self.tracer.now_us(),
                                error=repr(e))
                self._fail_or_retry(r, e)


class InferenceServer:
    """HTTP npz endpoint: POST /predict with an .npz body of inputs
    (x0, x1, ...) -> .npz response of outputs (out0, ...); POST /generate
    (npz {ids} -> npz {out0}) when a generator is wired in.

    Operational surface (docs/DEPLOYMENT.md "Operations & failure modes"):
    GET /health (liveness), GET /readyz (readiness: 503 while draining),
    GET /metrics (legacy JSON counters; `?format=prom` or an Accept header
    naming text/plain serves the Prometheus text exposition of the full
    observability registry), GET /utilization (UtilizationLedger JSON:
    flops by kind, tenant chargeback, serving MFU; 404 without a ledger),
    GET /debug/profile?ms=N (on-demand jax.profiler capture, single-flight:
    409 while one is running, 400 on malformed/oversized N).
    Overload answers 429/503 with Retry-After;
    deadline expiry answers 504; stop() drains in-flight work before tearing
    the batchers down. EVERY response (success and every error path) carries
    `X-Trace-Id` — minted here, or propagated from the client's own
    `X-Trace-Id` request header — so a 504 in a client log joins the
    server-side trace (`tracer.trace(id)`) without guesswork."""

    def __init__(self, predictor, host="127.0.0.1", port=0, batching=True,
                 max_batch_size=8, max_delay_ms=2.0, generator=None,
                 default_timeout=30.0, faults=None, tracer=None,
                 profile_dir=None):
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        self.predictor = predictor
        # ISSUE-19 on-demand device profiling: GET /debug/profile?ms=N
        # captures a duration-capped jax.profiler trace under profile_dir
        # (a fresh temp dir per server when unset). Single-flight by
        # construction: one non-blocking lock, concurrent captures 409.
        self.profile_dir = profile_dir
        self._profile_lock = threading.Lock()
        self._profile_seq = itertools.count(1)
        self.batcher = (BatchingPredictor(predictor, max_batch_size,
                                          max_delay_ms, faults=faults,
                                          tracer=tracer)
                        if batching and predictor is not None else None)
        # optional token-generation endpoint: a GenerateBatchingPredictor
        # (paged KV serving path) answering POST /generate
        self.generator = generator
        self.default_timeout = float(default_timeout)
        self._ready = threading.Event()
        self._draining = threading.Event()
        # server-level registry: HTTP surface + lifecycle state; /metrics
        # merges it with the batcher/generator registries into ONE exposition
        self.registry = MetricsRegistry()
        self.registry.gauge(
            "paddle_server_draining",
            "1 while draining (readyz answers 503)").set_function(
                lambda: 1 if self._draining.is_set() else 0)
        self._http_responses = self.registry.counter(
            "paddle_http_responses_total", "HTTP responses by path and status",
            labels=("path", "status"))
        outer = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # quiet
                pass

            def _trace_id(self):
                """One trace id per HTTP request: the client's X-Trace-Id if
                it sent one (cross-service propagation), else minted here."""
                tid = getattr(self, "_tid", None)
                if tid is None:
                    tid = self.headers.get("X-Trace-Id") or new_trace_id()
                    self._tid = tid
                return tid

            def _metric_path(self):
                p = self.path.split("?", 1)[0]
                return p if p in ("/health", "/readyz", "/metrics",
                                  "/predict", "/generate", "/slo",
                                  "/debug/ticks", "/utilization",
                                  "/debug/profile") else "other"

            def _reply(self, status, body, headers=()):
                # count BEFORE writing: a client that saw the response must
                # never scrape a /metrics page that hasn't counted it yet
                outer._http_responses.labels(self._metric_path(),
                                             str(status)).inc()
                self.send_response(status)
                for k, v in headers:
                    self.send_header(k, v)
                self.send_header("X-Trace-Id", self._trace_id())
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def _fail_http(self, e):
                """Exception -> status: the client must be able to tell
                "back off and retry" (429/503 + Retry-After) from "your
                request is broken" (400) from "you ran out of time" (504).
                Every load-shed status carries Retry-After (a Rejected with
                no hint still gets the 1s floor — a 429/503 without
                Retry-After makes clients invent their own backoff)."""
                headers = []
                if isinstance(e, Rejected):
                    status = e.status
                    # computed hint (e.g. a tenant bucket's time-to-refill)
                    # capped and floored by retry_after_header — never the
                    # old flat 1s floor when the shed knows better
                    headers.append(("Retry-After",
                                    retry_after_header(e.retry_after)))
                elif isinstance(e, TimeoutError):
                    status = 504
                elif isinstance(e, CacheOutOfBlocks):
                    status = 503
                    headers.append(("Retry-After", retry_after_header(None)))
                elif isinstance(e, ValueError):
                    status = 400
                else:
                    status = 500
                self._reply(status, repr(e).encode(), headers)

            def _timeout(self):
                ms = self.headers.get("X-Timeout-Ms")
                if ms is None:
                    return outer.default_timeout
                try:
                    return min(outer.default_timeout, float(ms) / 1000.0)
                except ValueError:
                    return outer.default_timeout

            def _sampler_knobs(self):
                """Per-request sampler knobs over HTTP (ROADMAP item 1):
                X-Temperature / X-Top-K / X-Spec / X-Max-New-Tokens ride
                the continuous scheduler's traced infer(temperature=,
                top_k=, spec=, max_new_tokens=) path — no recompile, no
                server restart. A malformed value is a
                client bug: ValueError -> 400 via _fail_http, never a
                silently-applied default (unlike X-Timeout-Ms, where
                clamping is the safe interpretation)."""
                kw = {}
                t = self.headers.get("X-Temperature")
                if t is not None:
                    try:
                        tv = float(t)
                    except ValueError:
                        raise ValueError(
                            f"malformed X-Temperature {t!r}") from None
                    if not math.isfinite(tv) or tv < 0:
                        raise ValueError(
                            f"X-Temperature out of range: {t!r} "
                            "(need a finite value >= 0)")
                    kw["temperature"] = tv
                k = self.headers.get("X-Top-K")
                if k is not None:
                    try:
                        kv = int(k)
                    except ValueError:
                        raise ValueError(
                            f"malformed X-Top-K {k!r}") from None
                    if kv < 0:
                        raise ValueError(
                            f"X-Top-K out of range: {k!r} (need >= 0)")
                    kw["top_k"] = kv
                s = self.headers.get("X-Spec")
                if s is not None:
                    sv = s.strip().lower()
                    if sv not in ("on", "off"):
                        raise ValueError(
                            f"malformed X-Spec {s!r} (on|off)")
                    kw["spec"] = sv == "on"
                n = self.headers.get("X-Max-New-Tokens")
                if n is not None:
                    # the per-request output budget infer(max_new_tokens=)
                    # already takes (clamped to the server cap there)
                    try:
                        nv = int(n)
                    except ValueError:
                        raise ValueError(
                            f"malformed X-Max-New-Tokens {n!r}") from None
                    if nv < 1:
                        raise ValueError(
                            f"X-Max-New-Tokens out of range: {n!r} "
                            "(need >= 1)")
                    kw["max_new_tokens"] = nv
                if kw and not getattr(outer.generator,
                                      "supports_sampler_knobs", False):
                    raise ValueError(
                        "per-request sampler headers need the continuous "
                        "scheduler (ContinuousGenerateBatchingPredictor); "
                        "this server's generator batches whole requests "
                        "with a fixed sampler config")
                # X-Adapter (ISSUE-15): LoRA routing by registry name.
                # Same strictness as the sampler knobs — an empty name or
                # an adapter-less generator is a client bug (400), and an
                # UNKNOWN name 400s from the scheduler's synchronous
                # validation (never a silent base-model fallback)
                a = self.headers.get("X-Adapter")
                if a is not None:
                    av = a.strip()
                    if not av:
                        raise ValueError("malformed X-Adapter (empty name)")
                    if not getattr(outer.generator,
                                   "supports_adapters", False):
                        raise ValueError(
                            "X-Adapter needs the continuous scheduler with "
                            "an AdapterRegistry (adapters= knob); this "
                            "server's generator serves the base model only")
                    kw["adapter"] = av
                # X-Tenant (ISSUE-17): QoS billing by ledger tenant name.
                # Same strict taxonomy again — empty name or a ledger-less
                # generator is a client bug (400), an UNKNOWN name 400s
                # from the scheduler's synchronous _route_tenant (never a
                # silent ride on the default tenant)
                tn = self.headers.get("X-Tenant")
                if tn is not None:
                    tv = tn.strip()
                    if not tv:
                        raise ValueError("malformed X-Tenant (empty name)")
                    if not getattr(outer.generator,
                                   "supports_tenants", False):
                        raise ValueError(
                            "X-Tenant needs the continuous scheduler with "
                            "a TenantLedger (qos= knob); this server's "
                            "generator serves untenanted traffic only")
                    kw["tenant"] = tv
                return kw

            def do_GET(self):
                path, _, query = self.path.partition("?")
                if path == "/health":
                    self._reply(200, b"ok")
                elif path == "/readyz":
                    # fleet-aware: a ReplicaFleet generator exposes ready()
                    # (any dispatchable replica) — a fleet with every
                    # replica dead/draining flips /readyz to 503 even
                    # though the HTTP loop itself is up
                    workers_ready = all(
                        w.ready() for w in (outer.batcher, outer.generator)
                        if w is not None and hasattr(w, "ready"))
                    if (outer._ready.is_set()
                            and not outer._draining.is_set()
                            and workers_ready):
                        self._reply(200, b"ready")
                    else:
                        body = (b"draining" if outer._draining.is_set()
                                else b"no ready replicas"
                                if outer._ready.is_set() else b"not started")
                        self._reply(503, body, [("Retry-After", "1")])
                elif path == "/metrics":
                    accept = self.headers.get("Accept", "")
                    if ("format=prom" in query or "text/plain" in accept
                            or "openmetrics" in accept):
                        try:
                            body = outer.render_prometheus().encode()
                        except ValueError as e:   # conflicting registries
                            self._fail_http(e)
                            return
                        self._reply(200, body, [
                            ("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")])
                        return
                    import json

                    snap = {"draining": outer._draining.is_set()}
                    if outer.batcher is not None:
                        snap["batcher"] = outer.batcher.metrics.snapshot()
                    if outer.generator is not None:
                        snap["generator"] = outer.generator.metrics.snapshot()
                        if hasattr(outer.generator, "replica_states"):
                            snap["replicas"] = \
                                outer.generator.replica_states()
                    # ISSUE-18: span loss + postmortem-ring occupancy in
                    # the JSON snapshot — the numbers an operator checks
                    # FIRST when a trace or dump comes back thinner than
                    # the incident it should cover
                    tracers = {}
                    for wname, w in (("batcher", outer.batcher),
                                     ("generator", outer.generator)):
                        t = getattr(w, "tracer", None)
                        if t is not None:
                            tracers[wname] = {
                                "dropped": t.dropped,
                                "recorded_spans": len(t.spans()),
                            }
                    if tracers:
                        snap["tracer"] = tracers
                    fl = getattr(outer.generator, "flight", None)
                    if fl is not None:
                        snap["flight_recorder"] = {
                            "occupancy": fl.occupancy,
                            "capacity": fl.capacity,
                            "dropped": fl.dropped,
                        }
                    # ISSUE-19: compact utilization block (mfu, flops by
                    # kind, host-gap tail) next to the tracer/flight blocks
                    util = getattr(outer.generator, "util", None)
                    if util is not None:
                        snap["utilization"] = util.metrics_block()
                    self._reply(200, json.dumps(snap).encode(),
                                [("Content-Type", "application/json")])
                elif path == "/slo":
                    # ISSUE-18: burn-rate/budget JSON for the SLO monitor
                    # (404 when none installed — same absent-iff-off
                    # contract as the paddle_slo_* gauges)
                    import json

                    mon = self._find_slo()
                    if mon is None:
                        self._reply(404, b"no SLO policy installed")
                    else:
                        self._reply(200, json.dumps(mon.snapshot()).encode(),
                                    [("Content-Type", "application/json")])
                elif path == "/debug/ticks":
                    # ISSUE-18: flight-recorder dump on demand; ?last=N
                    # bounds the artifact to the newest N ticks
                    import json

                    last = None
                    if "last=" in query:
                        try:
                            last = int(query.split("last=", 1)[1]
                                       .split("&", 1)[0])
                        except ValueError:
                            self._reply(400, b"malformed last= (need int)")
                            return
                    dumps = self._find_flight_dumps(last)
                    if not dumps:
                        self._reply(404, b"no flight recorder installed")
                    else:
                        self._reply(200, json.dumps(dumps).encode(),
                                    [("Content-Type", "application/json")])
                elif path == "/utilization":
                    # ISSUE-19: full UtilizationLedger snapshot — flops by
                    # kind, tenant chargeback, MFU, host-gap tail, last
                    # tick. 404 when no ledger installed (absent-iff-off,
                    # same contract as /slo and /debug/ticks).
                    import json

                    snaps = self._find_utilization()
                    if not snaps:
                        self._reply(404, b"no utilization ledger installed")
                    else:
                        self._reply(200, json.dumps(snaps).encode(),
                                    [("Content-Type", "application/json")])
                elif path == "/debug/profile":
                    self._do_profile(query)
                else:
                    self._reply(404, b"")

            def _find_slo(self):
                """The generator's SLOMonitor — fleet-aware: replicas
                usually share one monitor; the first one found wins."""
                mon = getattr(outer.generator, "slo", None)
                if mon is None and hasattr(outer.generator, "_snapshot"):
                    for rep in outer.generator._snapshot():
                        mon = getattr(rep.predictor, "slo", None)
                        if mon is not None:
                            break
                return mon

            def _find_flight_dumps(self, last):
                """Flight-recorder dumps keyed by recorder name — one entry
                for a plain scheduler, one per replica for a fleet."""
                fl = getattr(outer.generator, "flight", None)
                if fl is not None:
                    return {fl.name: fl.dump(last=last)}
                dumps = {}
                if hasattr(outer.generator, "_snapshot"):
                    for rep in outer.generator._snapshot():
                        f = getattr(rep.predictor, "flight", None)
                        if f is not None:
                            dumps[f.name] = f.dump(last=last)
                return dumps

            def _find_utilization(self):
                """Utilization snapshots keyed by component — one entry for
                a plain scheduler, one per replica for a fleet (same shape
                as _find_flight_dumps)."""
                u = getattr(outer.generator, "util", None)
                if u is not None:
                    name = getattr(outer.generator, "_component", "generator")
                    return {name: u.snapshot()}
                snaps = {}
                if hasattr(outer.generator, "_snapshot"):
                    for rep in outer.generator._snapshot():
                        u = getattr(rep.predictor, "util", None)
                        if u is not None:
                            snaps[rep.name] = u.snapshot()
                return snaps

            def _do_profile(self, query):
                """ISSUE-19: GET /debug/profile?ms=N — capture N ms of
                jax.profiler device trace (the tick thread's serve.*
                spans are in it, on the device ops' clock), write the
                serving tracer's request spans beside it (their own
                perf_counter timebase), answer JSON naming the artifacts.
                Taxonomy: malformed/absent/oversized ms= is a client bug
                (400); a concurrent capture answers 409 (the
                profiler is a process-global singleton — two start_trace
                calls corrupt each other); a profiler failure answers 503
                (retryable: the runtime may just be busy)."""
                import json

                ms = None
                for part in query.split("&"):
                    if part.startswith("ms="):
                        try:
                            ms = int(part[3:])
                        except ValueError:
                            self._reply(400, b"malformed ms= (need int)")
                            return
                if ms is None:
                    self._reply(400, b"missing ms= duration")
                    return
                if ms <= 0 or ms > PROFILE_MS_CAP:
                    self._reply(
                        400,
                        f"ms= out of range: {ms} (need 1..{PROFILE_MS_CAP})"
                        .encode())
                    return
                if not outer._profile_lock.acquire(blocking=False):
                    self._reply(409, b"profile capture already in flight",
                                [("Retry-After", "1")])
                    return
                try:
                    out = outer._capture_profile(ms)
                except Exception as e:
                    self._reply(503, repr(e).encode(),
                                [("Retry-After", "1")])
                    return
                finally:
                    outer._profile_lock.release()
                self._reply(200, json.dumps(out).encode(),
                            [("Content-Type", "application/json")])

            def _wants_stream(self):
                """SSE opt-in: `X-Stream: sse`, or Accept: text/event-stream
                with no X-Stream override. A malformed X-Stream is a client
                bug -> 400 (same contract as the sampler headers)."""
                xs = self.headers.get("X-Stream")
                if xs is not None:
                    sv = xs.strip().lower()
                    if sv not in ("sse", "off"):
                        raise ValueError(
                            f"malformed X-Stream {xs!r} (sse|off)")
                    return sv == "sse"
                return "text/event-stream" in (
                    self.headers.get("Accept") or "")

            def _generate_sse(self, ids):
                """Chunked/SSE streaming for /generate (ISSUE-11): tokens
                flush at tick boundaries, EVERY event carries the trace id
                (SSE `id:` field AND the JSON payload), and deadline
                semantics are unchanged — a mid-stream expiry arrives as an
                `error` event naming status 504. Admission errors raise
                before any bytes flush (infer_stream is eagerly admitted),
                so 429/503/400 still travel as real HTTP statuses. The
                response is close-delimited (HTTP/1.0): no Content-Length,
                the `done`/`error` event is the terminator."""
                import json

                gen = outer.generator
                if not getattr(gen, "supports_streaming", False):
                    raise ValueError(
                        "streaming needs the continuous scheduler "
                        "(ContinuousGenerateBatchingPredictor); this "
                        "server's generator buffers whole responses")
                it = gen.infer_stream(ids, timeout=self._timeout(),
                                      trace_id=self._trace_id(),
                                      **self._sampler_knobs())
                tid = self._trace_id()
                # counted before any bytes flush, same contract as _reply
                outer._http_responses.labels(self._metric_path(),
                                             "200").inc()
                self.send_response(200)
                self.send_header("Content-Type", "text/event-stream")
                self.send_header("Cache-Control", "no-cache")
                self.send_header("X-Trace-Id", tid)
                self.end_headers()

                def emit(event, payload):
                    payload["trace_id"] = tid
                    self.wfile.write(
                        (f"id: {tid}\nevent: {event}\n"
                         f"data: {json.dumps(payload)}\n\n").encode())
                    self.wfile.flush()

                sent = 0
                try:
                    for chunk in it:
                        toks = [int(t) for t in
                                np.asarray(chunk).reshape(-1)]
                        sent += len(toks)
                        emit("tokens", {"tokens": toks})
                    emit("done", {"generated": sent,
                                  "prompt_len": int(len(ids))})
                except Exception as e:
                    # headers are gone — the failure travels in-band, with
                    # the same status taxonomy _fail_http would have used
                    if isinstance(e, Rejected):
                        status = e.status
                    elif isinstance(e, TimeoutError):
                        status = 504
                    elif isinstance(e, CacheOutOfBlocks):
                        status = 503
                    elif isinstance(e, ValueError):
                        status = 400
                    else:
                        status = 500
                    try:
                        emit("error", {"status": status, "error": repr(e)})
                    except OSError:     # client went away mid-stream
                        pass
                finally:
                    # a consumer-side failure (broken pipe) must cancel the
                    # in-flight sequence NOW, not at GC time — close() fires
                    # the pump's GeneratorExit cancel path deterministically
                    it.close()

            def do_POST(self):
                if outer._draining.is_set():
                    self._reply(503, b"draining", [("Retry-After", "1")])
                    return
                if self.path == "/generate" and outer.generator is not None:
                    try:
                        n = int(self.headers.get("Content-Length", 0))
                        data = np.load(io.BytesIO(self.rfile.read(n)))
                        ids = data[data.files[0]]
                        if self._wants_stream():
                            self._generate_sse(ids)
                            return
                        out = outer.generator.infer(ids,
                                                    timeout=self._timeout(),
                                                    trace_id=self._trace_id(),
                                                    **self._sampler_knobs())
                        buf = io.BytesIO()
                        np.savez(buf, out0=out)
                        body = buf.getvalue()
                        self._reply(200, body,
                                    [("Content-Type", "application/npz")])
                    except Exception as e:
                        self._fail_http(e)
                    return
                if self.path != "/predict":
                    self._reply(404, b"")
                    return
                try:
                    n = int(self.headers.get("Content-Length", 0))
                    data = np.load(io.BytesIO(self.rfile.read(n)))

                    def _num_key(k):
                        digits = "".join(c for c in k if c.isdigit())
                        return (int(digits) if digits else 0, k)

                    arrays = [data[k] for k in sorted(data.files,
                                                      key=_num_key)]
                    if outer.batcher is not None:
                        outs = outer.batcher.infer(*arrays,
                                                   timeout=self._timeout(),
                                                   trace_id=self._trace_id())
                    else:
                        outs = [o[0] for o in outer.predictor.run(
                            [a[None] for a in arrays])]
                    buf = io.BytesIO()
                    np.savez(buf, **{f"out{i}": o
                                     for i, o in enumerate(outs)})
                    body = buf.getvalue()
                    self._reply(200, body,
                                [("Content-Type", "application/npz")])
                except Exception as e:
                    self._fail_http(e)

        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True, name="inference-server")

    def _capture_profile(self, ms):
        """One duration-capped jax.profiler capture (ISSUE-19).

        Runs under self._profile_lock (the handler holds it): starts the
        device trace into a fresh numbered directory under profile_dir,
        sleeps out the window on the handler thread, stops the trace, then
        writes a chrome view of the host tracer's request spans (on
        perf_counter µs, not the capture's clock) next to the raw trace,
        which holds the tick thread's serve.* spans itself. The chrome view
        is best-effort — a tracer-less server still returns the raw trace
        directory."""
        import os
        import tempfile

        import jax

        base = self.profile_dir
        if base is None:
            base = self.profile_dir = tempfile.mkdtemp(
                prefix="paddle_profile_")
        run_dir = os.path.join(base, f"capture_{next(self._profile_seq):04d}")
        os.makedirs(run_dir, exist_ok=True)
        jax.profiler.start_trace(run_dir)
        try:
            time.sleep(ms / 1000.0)
        finally:
            jax.profiler.stop_trace()
        joined = None
        tracer = None
        for w in (self.generator, self.batcher):
            tracer = getattr(w, "tracer", None)
            if tracer is not None:
                break
        if tracer is not None:
            from ..observability.trace import export_joined_chrome

            joined = os.path.join(run_dir, "joined_host_trace.json")
            try:
                export_joined_chrome(joined, tracer=tracer)
            except Exception:
                joined = None   # raw device trace still stands on its own
        return {"ms": int(ms), "trace_dir": run_dir, "joined_chrome": joined}

    def render_prometheus(self) -> str:
        """One merged Prometheus text exposition over the server, batcher and
        generator registries (render_prometheus dedupes shared registries and
        raises on conflicting/duplicate series rather than emitting an
        invalid scrape)."""
        regs = [self.registry]
        if self.batcher is not None:
            regs.append(self.batcher.metrics.registry)
        if self.generator is not None:
            regs.append(self.generator.metrics.registry)
        return render_prometheus(*regs)

    def start(self):
        self._thread.start()
        self._ready.set()
        return self

    def stop(self, drain_timeout=5.0):
        """Graceful drain: flip /readyz to 503 and refuse new POSTs, let
        queued + in-flight requests finish (up to drain_timeout), then tear
        down the HTTP loop and the batcher threads."""
        self._draining.set()
        self._ready.clear()
        workers = [w for w in (self.batcher, self.generator)
                   if w is not None]
        for w in workers:
            w.drain()
        deadline = time.monotonic() + float(drain_timeout)
        while (time.monotonic() < deadline
               and any(w.pending() for w in workers)):
            time.sleep(0.01)
        if self._thread.is_alive():
            self._httpd.shutdown()
        self._httpd.server_close()
        for w in workers:
            w.close()
        if self._thread.is_alive():
            self._thread.join(timeout=2)


# ---------------------------------------------------------------------------
# Replica fleet: data-parallel serving over N continuous schedulers
# ---------------------------------------------------------------------------
class _Replica:
    """One fleet member: a continuous scheduler plus its routing state.

    `state` is the FLEET's routing view ("ready" | "draining" | "dead"), not
    the predictor's own lifecycle — a draining replica still finishes its
    queued work, the router just stops feeding it."""

    __slots__ = ("name", "predictor", "state")

    def __init__(self, name, predictor):
        self.name = name
        self.predictor = predictor
        self.state = "ready"


class ReplicaFleet:
    """Least-loaded router over N data-parallel scheduler replicas.

    The mesh-serving split of labor (ISSUE-12): tensor parallelism lives
    INSIDE each replica's step programs (the tp axis shards weights and the
    paged KV pool head-wise; GSPMD + the shard_map'd split-KV kernel insert
    the collectives), while data parallelism lives HERE, entirely on the
    host — N independent ``ContinuousGenerateBatchingPredictor`` replicas
    over one shared model, so every replica reuses the same compiled step
    programs (replica admit/retire/kill never recompiles; pinned by the
    bench recompile audit) while holding its own KV pool and slot state.

    Routing contract:

    * Admission happens ONCE at the fleet door (aggregate pending depth);
      per-replica admission still applies at dispatch and a busy replica
      fails over to the next-least-loaded sibling.
    * A replica whose circuit breaker is OPEN is skipped by reading
      ``breaker.state`` — never ``allow()``, which would consume the
      half-open probe the replica's own admission path needs to close it.
    * A ``ServiceUnavailable(permanent=True)`` (supervisor restart budget
      spent — the worker is dead for good) marks the replica dead and
      re-dispatches to a sibling. Clients parked in a dead replica's
      ``_await``/``_stream_pump`` surface the same permanent 503 through
      their heal loop, so the dead replica's queued requests re-enter this
      router and land on survivors; the terminal-outcome CAS on the
      original request already fired (``_fail``), so re-dispatch is a NEW
      request — exactly-once terminals per request object hold throughout.
    * Draining is routing-only until ``retire_replica``: ``drain_replica``
      just stops new dispatches (queued work finishes), ``undrain_replica``
      reverses it, ``retire_replica`` drains, waits, and closes.

    Observability: ``paddle_fleet_replicas{state=...}`` gauge (scrape-time
    membership counts), ``paddle_fleet_dispatch_total{replica,outcome}``
    counter, and a ``fleet_dispatch`` child span per dispatch attempt on a
    trace shared (same trace id) with the replica-side request spans.
    Fleet-level ``ServingMetrics`` (component="fleet") keeps the same
    conservation contract as every other component:
    accepted == completed + failed + timeouts."""

    supports_sampler_knobs = True   # replicas are continuous schedulers
    supports_streaming = True

    @property
    def supports_adapters(self):
        """Fleet dispatch is adapter-oblivious (ISSUE-15): every replica
        shares the ONE AdapterRegistry (build() passes adapters= to all),
        so X-Adapter routing works iff the replicas carry it — any replica
        answers for the fleet."""
        return any(getattr(rep.predictor, "supports_adapters", False)
                   for rep in self._snapshot())

    @property
    def supports_tenants(self):
        """X-Tenant twin of supports_adapters (ISSUE-17): build() passes
        one shared TenantLedger to every replica (qos= knob), so tenant
        routing works iff the replicas carry it."""
        return any(getattr(rep.predictor, "supports_tenants", False)
                   for rep in self._snapshot())

    def __init__(self, replicas, *, admission=None, registry=None,
                 tracer=None, clock=time.monotonic):
        self._lock = make_lock("serving.ReplicaFleet._lock")
        self._replicas = list(replicas)
        self._next_id = len(self._replicas)
        self._clock = clock
        self.tracer = tracer if tracer is not None else Tracer()
        self.registry = registry if registry is not None else MetricsRegistry()
        self.metrics = ServingMetrics(registry=self.registry,
                                      component="fleet")
        self.admission = admission if admission is not None \
            else AdmissionController()
        self._draining = threading.Event()
        # build()-made fleets can mint new replicas (admit) on demand
        self._model = None
        self._replica_kwargs = {}
        g = self.registry.gauge(
            "paddle_fleet_replicas",
            "Replica-fleet membership by routing state",
            labels=("state",))
        for st in ("ready", "draining", "dead"):
            g.labels(st).set_function(
                lambda s=st: float(self._count_state(s)))
        self._dispatch_total = self.registry.counter(
            "paddle_fleet_dispatch_total",
            "Fleet dispatch attempts by replica and outcome",
            labels=("replica", "outcome"))

    @classmethod
    def build(cls, model, n_replicas=2, *, registry=None, tracer=None,
              admission=None, replica_kwargs=None, **kwargs):
        """Construct a fleet of ``n_replicas`` continuous schedulers over ONE
        shared model (shared step-program caches -> zero recompiles across
        the fleet) and one shared metrics registry/tracer, each replica
        labelled ``r0``, ``r1``, ... via the ``component`` override.
        ``replica_kwargs`` (a list of dicts) overlays per-replica settings
        on the common ``**kwargs`` (e.g. a per-replica FaultInjector for the
        chaos suite)."""
        from .scheduler import ContinuousGenerateBatchingPredictor

        registry = registry if registry is not None else MetricsRegistry()
        tracer = tracer if tracer is not None else Tracer()
        per = list(replica_kwargs) if replica_kwargs else []
        replicas = []
        for i in range(int(n_replicas)):
            kw = dict(kwargs)
            if i < len(per) and per[i]:
                kw.update(per[i])
            name = f"r{i}"
            replicas.append(_Replica(name, ContinuousGenerateBatchingPredictor(
                model, registry=registry, tracer=tracer, component=name,
                **kw)))
        fleet = cls(replicas, admission=admission, registry=registry,
                    tracer=tracer)
        fleet._model = model
        fleet._replica_kwargs = dict(kwargs)
        return fleet

    # ------------------------------------------------------------ membership
    def _snapshot(self):
        with self._lock:
            return list(self._replicas)

    def _by_name(self, name) -> _Replica:
        for rep in self._snapshot():
            if rep.name == name:
                return rep
        raise KeyError(f"no replica named {name!r}")

    def _refresh(self, rep) -> str:
        """Routing state with supervisor-death folded in (non-healing)."""
        if rep.state != "dead" and rep.predictor._sup.dead():
            rep.state = "dead"
        return rep.state

    def _count_state(self, state) -> int:
        return sum(1 for rep in self._snapshot()
                   if self._refresh(rep) == state)

    def replica_states(self) -> dict:
        """{name: "ready" | "draining" | "dead"} — the /readyz payload."""
        return {rep.name: self._refresh(rep) for rep in self._snapshot()}

    def add_replica(self, name=None, **overrides):
        """Admit a new replica (build()-made fleets only). Reuses the shared
        model, registry and tracer; the new replica's step programs come
        straight from the shared model caches — no recompile."""
        from .scheduler import ContinuousGenerateBatchingPredictor

        if self._model is None:
            raise RuntimeError("add_replica needs a ReplicaFleet.build() "
                               "fleet (it owns the shared model handle)")
        with self._lock:
            name = name if name is not None else f"r{self._next_id}"
            self._next_id += 1
        kw = dict(self._replica_kwargs)
        kw.update(overrides)
        pred = ContinuousGenerateBatchingPredictor(
            self._model, registry=self.registry, tracer=self.tracer,
            component=name, **kw)
        with self._lock:
            self._replicas.append(_Replica(name, pred))
        return name

    def drain_replica(self, name):
        """Stop routing NEW requests to `name`; its queued work finishes."""
        rep = self._by_name(name)
        if rep.state == "ready":
            rep.state = "draining"

    def undrain_replica(self, name):
        rep = self._by_name(name)
        if rep.state == "draining":
            rep.state = "ready"

    def retire_replica(self, name, drain_timeout=5.0):
        """Drain-then-close: routing stops immediately, queued + in-flight
        requests get up to `drain_timeout` to finish, then the replica's
        threads come down and it reads as dead in the state gauge."""
        rep = self._by_name(name)
        rep.state = "draining"
        rep.predictor.drain()
        deadline = time.monotonic() + float(drain_timeout)
        while time.monotonic() < deadline and rep.predictor.pending():
            time.sleep(0.01)
        rep.predictor.close()
        rep.state = "dead"

    # --------------------------------------------------------------- routing
    def _pick(self, exclude=()):
        """Least-loaded ready replica, skipping draining/dead members, open
        circuit breakers (state read only — allow() would eat the half-open
        probe), replicas still AOT-warming their step programs (ISSUE-13:
        the predictor's own ready() gate), and already-tried names."""
        best, best_load = None, None
        for rep in self._snapshot():
            if rep.name in exclude or self._refresh(rep) != "ready":
                continue
            if rep.predictor.breaker.state == "open":
                continue
            pred_ready = getattr(rep.predictor, "ready", None)
            if pred_ready is not None and not pred_ready():
                continue
            load = rep.predictor.pending()
            if best is None or load < best_load:
                best, best_load = rep, load
        return best

    def _dispatched(self, rep, outcome, tr, t_start):
        self._dispatch_total.labels(rep.name, outcome).inc()
        tr.child("fleet_dispatch", t_start, tr.now_us(),
                 replica=rep.name, outcome=outcome)

    def _admit(self, tr):
        t_adm = tr.now_us()
        try:
            if self._draining.is_set():
                raise ServiceUnavailable("fleet is shutting down",
                                         retry_after=None)
            self.admission.admit(self.pending())
            if self._pick() is None:
                raise ServiceUnavailable("no ready replicas",
                                         retry_after=0.5)
        except Rejected as e:
            self.metrics.inc("rejected_busy" if isinstance(e, ServerBusy)
                             else "rejected_unavailable")
            # ISSUE-18 availability SLO: a door rejection is terminal too —
            # 429 is the client's backpressure (good), 503 is ours (bad)
            slo = getattr(self, "slo", None)
            if slo is not None:
                slo.observe_terminal(e.status < 500,
                                     tenant=getattr(req, "tenant", None))
            tr.child("admission", t_adm, tr.now_us(), error=repr(e))
            # door rejection (ISSUE-18): 100% of the request's life was
            # queue-side — attribute it as such; rejected requests never
            # enter the TTFT histogram (a zero-valued sample would drag
            # p50 toward the shed path instead of measuring served ones)
            tr.finish("rejected", status=e.status, error=repr(e),
                      queue_share=1.0, prefill_share=0.0,
                      paused_share=0.0, decode_share=0.0)
            raise
        tr.child("admission", t_adm, tr.now_us())
        self.metrics.inc("accepted")

    def _terminal(self, outcome, t0, tr, **tags):
        self.metrics.inc(outcome)
        if outcome in ("completed", "timeouts"):
            self.metrics.observe_latency(self._clock() - t0)
        tr.finish({"completed": "result", "timeouts": "timeout",
                   "failed": "error"}[outcome], **tags)

    def _dispatch(self, call, deadline, tr, t0):
        """Shared failover loop: try least-loaded replicas until one accepts.

        `call(rep)` runs the replica-side request to ITS outcome — for
        infer() that is the full round trip, for infer_stream() just the
        synchronous admission half — so every exception type below has one
        meaning: busy/unavailable = failover, permanent = replica death +
        failover, timeout/value-error = the request's own terminal."""
        tried = set()
        last_busy = None
        while True:
            if deadline is not None and deadline.expired():
                self._terminal("timeouts", t0, tr, where="fleet_dispatch")
                raise DeadlineExceeded("request timed out during fleet "
                                       "dispatch")
            rep = self._pick(exclude=tried)
            if rep is None:
                err = last_busy if last_busy is not None else \
                    ServiceUnavailable("no ready replicas", retry_after=0.5)
                self._terminal("failed", t0, tr, error=repr(err))
                raise err
            t_d = tr.now_us()
            try:
                out = call(rep)
            except DeadlineExceeded:
                self._dispatched(rep, "timeout", tr, t_d)
                self._terminal("timeouts", t0, tr, replica=rep.name)
                raise
            except ServiceUnavailable as e:
                if e.permanent or rep.predictor._sup.dead():
                    # replica-kill healing: mark dead, re-dispatch the work
                    rep.state = "dead"
                    self._dispatched(rep, "dead", tr, t_d)
                    continue
                self._dispatched(rep, "unavailable", tr, t_d)
                tried.add(rep.name)
                last_busy = e
            except ServerBusy as e:
                self._dispatched(rep, "busy", tr, t_d)
                tried.add(rep.name)
                last_busy = e
            except ValueError as e:
                # malformed/oversized: no sibling can serve it either
                self._dispatched(rep, "invalid", tr, t_d)
                self._terminal("failed", t0, tr, error=repr(e))
                raise
            except Exception as e:
                self._dispatched(rep, "error", tr, t_d)
                self._terminal("failed", t0, tr, error=repr(e))
                raise
            else:
                self._dispatched(rep, "ok", tr, t_d)
                return rep, out

    # ---------------------------------------------------------------- client
    def infer(self, ids, timeout=None, deadline=None, trace_id=None, **kw):
        """Fleet twin of the continuous scheduler's infer(): ONE deadline is
        minted up front and rides through every failover attempt — a request
        that hops replicas does not get its clock reset."""
        if deadline is None and timeout is not None:
            deadline = Deadline.after(float(timeout), self._clock)
        tr = RequestTrace(self.tracer, trace_id)
        t0 = self._clock()
        self._admit(tr)
        rep, out = self._dispatch(
            lambda rep: rep.predictor.infer(ids, deadline=deadline,
                                            trace_id=tr.trace_id, **kw),
            deadline, tr, t0)
        self._terminal("completed", t0, tr, replica=rep.name)
        return out

    def infer_stream(self, ids, timeout=None, deadline=None, trace_id=None,
                     **kw):
        """Streaming dispatch. Failover happens ONLY at admission time (the
        replica-side infer_stream raises busy/unavailable synchronously,
        before any tokens flow); once a replica accepts, the stream is
        pinned to it and mid-stream death raises from the iterator exactly
        like a single-replica deployment."""
        if deadline is None and timeout is not None:
            deadline = Deadline.after(float(timeout), self._clock)
        tr = RequestTrace(self.tracer, trace_id)
        t0 = self._clock()
        self._admit(tr)
        rep, gen = self._dispatch(
            lambda rep: rep.predictor.infer_stream(
                ids, deadline=deadline, trace_id=tr.trace_id, **kw),
            deadline, tr, t0)
        return self._stream_relay(rep, gen, tr, t0)

    def _stream_relay(self, rep, gen, tr, t0):
        """Relay the replica's token iterator, landing the fleet-level
        terminal (conservation: this request was already `accepted`)."""
        try:
            yield from gen
        except DeadlineExceeded:
            self._terminal("timeouts", t0, tr, replica=rep.name)
            raise
        except GeneratorExit:
            # consumer walked away: replica side already counted its
            # timeout-terminal through _stream_pump's cancel path
            self._terminal("timeouts", t0, tr, replica=rep.name,
                           where="stream_abandoned")
            raise
        except Exception as e:
            self._terminal("failed", t0, tr, replica=rep.name,
                           error=repr(e))
            raise
        else:
            self._terminal("completed", t0, tr, replica=rep.name)

    # ------------------------------------------------------------- lifecycle
    def ready(self) -> bool:
        """At least one replica can take a dispatch right now (/readyz)."""
        return not self._draining.is_set() and self._pick() is not None

    def pending(self) -> int:
        """Aggregate queued + in-flight across live replicas."""
        return sum(rep.predictor.pending() for rep in self._snapshot()
                   if self._refresh(rep) != "dead")

    def drain(self):
        self._draining.set()
        for rep in self._snapshot():
            if rep.state == "ready":
                rep.state = "draining"
            rep.predictor.drain()

    def close(self):
        self._draining.set()
        for rep in self._snapshot():
            rep.predictor.close()
            rep.state = "dead"
