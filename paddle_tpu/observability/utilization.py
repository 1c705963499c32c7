"""Fleet utilization ledger (ISSUE-19): per-tick FLOPs attribution.

The continuous scheduler launches FIXED-WIDTH programs — prefill_chunk
[S, C], decode_step [S]xT, verify_step [S, K+1] — so every launch issues a
CONSTANT amount of compute regardless of how much of it serves live
tokens. Padding (idle slots, masked chunk tail, EOS-frozen rows), rejected
speculation and host gaps between launches are all invisible to the
existing token counters: a fleet can read "healthy tok/s" while most of
its FLOPs heat pad rows. This module makes the waste a first-class,
CONSERVED quantity:

    issued == useful + pad_waste + spec_waste        (exactly, per tick)
    sum(per-tenant billed) == useful                 (exactly)

Exactness is by construction, not by epsilon: all attribution happens in
INTEGER flops units. A launch's issued FLOPs (``observability/xla.py
cost_flops`` on the lowered step program, computed once per program cache
key) are split token-proportionally with floor division —
``useful_i = issued * units_i // total_units`` — and pad_waste absorbs
the rounding remainder, so the invariants above hold bit-exactly and the
conservation property sweep (tests/test_utilization.py) can assert ``==``
after every tick under mixed greedy/sampled/spec/preempted traffic.
Tenant bills are the SAME per-slot integers grouped by tenant, so the
chargeback sum closes on useful by construction too; preempted (paused)
sequences are off-slot and contribute no units, so paused time can never
bill a tenant.

Tick wall-time splits three ways. A launch is ``dispatch_s`` (the call
that hands the program to the runtime, which returns before the device has
finished; from the generation timing hook) plus ``wait_s`` (the tick thread
blocked in the read-back of the launch's tokens; timed by the scheduler):
``launch_s = dispatch_s + wait_s``. HOST GAP is everything else of the tick
— admission, numpy assembly, absorb, stream flushes. The window of a tick
opens where the previous one closed (admission included) and time parked
on an empty queue is in no tick, so ``wait_s / wall_s`` is the share of the
tick thread's time in which the device, not the host, was the one working.

Beside the FLOPs, every launch counts POSITIONS with the same conservation
(``issued_positions == useful + pad + spec``: S x C, S x T, S x (K+1)
issued; prompt tokens taken / tokens absorbed useful) and, for decode and
verify, K,V ROWS: ``live_rows``, the context lengths of the active slots
summed over the launch's token steps; ``walked_rows``, the rows the paged
kernel's loop visits for them (each length rounded up to the kernel's block
of pages: ``live / walked`` is the kernel's efficiency); and ``table_rows``,
the rows the block tables handed to the launch span (slots x table width x
block size x token steps: the scheduler's geometry, which the kernel's grid
walked before it followed the lengths: ``walked / table`` is what that
saved). Beside ``launches``, two counts say how often the step programs'
sampler engaged: ``sampler_drawn``, the launches in which some slot sampled
at a temperature (the random draw ran), and ``sampler_sorted``, those in
which such a slot also asked for top-k (the sort over the vocabulary ran);
a launch of greedy slots counts in neither. Two more say how often the
scheduler kept a decode launch ahead of its read-back: ``ahead``, the
launches dispatched before the launch before them had been read back, and
``ahead_dropped``, the slot-steps such launches computed for a sequence
that had finished meanwhile (EOS, cancel, deadline), dropped on the host;
``ahead / launches`` is the share of launches that overlapped the host's
tick. These are plain integers and
need no FLOPs probe, so every continuous scheduler keeps them;
``utilization=True`` adds the probe and the exported series.

Every total is kept twice: for the process, and for the ticks that ran
wholly inside the current (or, with none running, the last) ``jax.profiler``
session (``snapshot()["profiled"]``), so a capture's tick accounting needs
no clock to align. The ledger cannot ask the profiler which session it is
in, only whether one runs: it asks at every tick's begin and end, at every
launch and on every pass of a parked loop, and a session found running
where the last answer was "none" starts the account afresh. A stop and a
restart that both fall between two such questions read as one session.
``ledgers()`` lists the live ledgers and the last few whose scheduler was
closed.

Exported series (absent-iff-off, like every optional subsystem):

* ``paddle_serving_flops_total{component,kind}`` — kind in
  useful | pad | spec_waste; the sum over kinds is issued.
* ``paddle_tenant_flops_total{component,tenant}`` — chargeback counters.
* ``paddle_serving_host_gap_seconds{component}`` — per-tick histogram.
* ``paddle_serving_moe_expert_load_skew{component}`` — the busiest held
  expert's assignments over the mean's; registered when a model first
  reports ``moe_expert_tokens`` among its counts.
* ``paddle_serving_mfu{component}`` — rolling-window useful FLOP/s over
  ``device_peak_flops`` — registered only when the peak is KNOWN (real
  accelerator or an injected ``peak_flops=``); on CPU the gauge is absent,
  never a made-up number (same contract as training MFU).
"""
from __future__ import annotations

import collections
import threading
import time
import weakref

from jax.profiler import TraceAnnotation

from .xla import device_peak_flops

__all__ = ["UtilizationLedger", "attribute_launch", "ledgers",
           "HOST_GAP_BUCKETS"]

_LIVE: "weakref.WeakSet[UtilizationLedger]" = weakref.WeakSet()
# ledgers whose scheduler was closed: readable after the server is gone. A
# ledger refers to no scheduler, model or pool, so keeping it keeps only it.
_CLOSED: collections.deque = collections.deque(maxlen=4)


def ledgers():
    """Every live ledger and the last few closed ones, oldest first."""
    closed = list(_CLOSED)
    return closed + [led for led in _LIVE if led not in closed]


# per-tick host gaps are sub-millisecond on a healthy scheduler and spike
# to tens of ms when the host falls behind — finer-than-latency buckets
HOST_GAP_BUCKETS = (0.0001, 0.00025, 0.0005, 0.001, 0.0025, 0.005, 0.01,
                    0.025, 0.05, 0.1, 0.25, 0.5, 1.0)


def attribute_launch(flops, total_units, slot_units, spec_units=0):
    """Integer decomposition of one launch's issued FLOPs.

    ``slot_units``: iterable of ``(tenant_or_None, useful_units)`` — one
    entry per live slot. ``spec_units``: rejected draft positions across
    the launch. Returns ``(issued, useful, pad, spec, bills)`` where
    ``bills`` maps tenant name -> integer flops and every invariant holds
    exactly: ``issued == useful + pad + spec``, ``sum(bills) == useful``.

    Floor division can only UNDER-attribute each slot, so pad (the
    remainder) is always >= 0 as long as the caller's units fit the
    launch: ``sum(useful_units) + spec_units <= total_units``.
    """
    issued = max(0, int(round(flops or 0.0)))
    total = int(total_units)
    useful = 0
    bills: dict = {}
    spec = 0
    if issued > 0 and total > 0:
        for tenant, units in slot_units:
            units = int(units)
            if units <= 0:
                continue
            share = issued * units // total
            if share <= 0:
                continue
            useful += share
            key = "default" if tenant is None else str(tenant)
            bills[key] = bills.get(key, 0) + share
        spec = issued * int(spec_units) // total
    pad = issued - useful - spec
    return issued, useful, pad, spec, bills


_FLOPS = ("issued", "useful", "pad", "spec_waste")
_POSITIONS = ("issued_positions", "useful_positions", "pad_positions",
              "spec_positions")
_PROGRAM_KEYS = (_FLOPS + ("launches", "sampler_drawn", "sampler_sorted",
                            "ahead", "ahead_dropped")
                 + _POSITIONS
                 + ("live_rows", "walked_rows", "table_rows", "dispatch_s",
                    "wait_s"))
_TICK_KEYS = _FLOPS + ("launch_s", "dispatch_s", "wait_s")
# Beside these a program's account holds whatever a model counts of its own
# launches (record_launch's `counts`, from the model's `_launch_counts`),
# under the model's key names: summed like the ledger's own, a list place by
# place. A model's key may not be one of the ledger's.


def _add_count(acc, key, value):
    """acc[key] += value, a number or a list added place by place."""
    if isinstance(value, (list, tuple)):
        have = acc.get(key) or [0] * len(value)
        acc[key] = [a + int(b) for a, b in zip(have, value)]
    else:
        acc[key] = acc.get(key, 0) + int(value)


def _new_account():
    """Totals over ticks: one for the process, one for profiled ticks."""
    acc = dict.fromkeys(_TICK_KEYS + ("wall_s", "host_gap_s"), 0)
    acc.update(ticks=0, launches=0, programs={})
    return acc


def _fold(acc, tick):
    """Add one closed tick to an account."""
    for key in _TICK_KEYS + ("wall_s", "host_gap_s"):
        acc[key] += tick[key]
    acc["ticks"] += 1
    for name, p in tick["programs"].items():
        acc["launches"] += p["launches"]
        tot = acc["programs"].setdefault(name, dict.fromkeys(_PROGRAM_KEYS, 0))
        for key, value in p.items():
            if key in _PROGRAM_KEYS:
                tot[key] += value
            else:                   # a model's own count
                _add_count(tot, key, value)


def _account_view(acc):
    """JSON form of an account, FLOPs under the names /utilization has."""
    return {
        "flops": {"issued": acc["issued"], "useful": acc["useful"],
                  "pad_waste": acc["pad"], "spec_waste": acc["spec_waste"]},
        "ticks": acc["ticks"], "launches": acc["launches"],
        "wall_s": round(acc["wall_s"], 6),
        "launch_wall_s": round(acc["launch_s"], 6),
        "dispatch_s": round(acc["dispatch_s"], 6),
        "wait_s": round(acc["wait_s"], 6),
        "host_gap_s": round(acc["host_gap_s"], 6),
        "programs": {name: dict(p) for name, p in acc["programs"].items()},
    }


class UtilizationLedger:
    """Per-tick FLOPs/positions/rows/wall decomposition for one continuous
    scheduler.

    The tick thread drives ``tick_begin`` / ``record_launch`` /
    ``tick_end``; gauges and the ``/utilization`` endpoint read
    ``snapshot()`` / ``last_tick`` from other threads (totals are guarded
    by a lock; in-tick accumulators are tick-thread-only).

    ``peak_flops``: MFU denominator (FLOP/s). Default resolves
    ``device_peak_flops`` of the first jax device — None on CPU, which
    leaves the MFU gauge unregistered (absent-iff-off). ``clock`` is
    injectable for deterministic tests; the scheduler times its read-backs
    on it too.
    """

    def __init__(self, *, peak_flops=None, device=None,
                 clock=time.monotonic, mfu_window_s=10.0,
                 gap_samples=1024):
        if peak_flops is None:
            if device is None:
                import jax

                device = jax.devices()[0]
            peak_flops = device_peak_flops(device)
        self.peak_flops = peak_flops
        self.clock = clock
        self.mfu_window_s = float(mfu_window_s)
        self._lock = threading.Lock()
        # lifetime totals (integers exact), and the same over the ticks of
        # the current or last profiler session (poll_session starts it anew)
        self._process = _new_account()
        self._profiled = _new_account()
        self._in_session = False
        self.by_tenant: dict = {}
        self._gaps = collections.deque(maxlen=int(gap_samples))
        # MFU window: (t_end, tick_wall_s, useful_flops) per tick
        self._window: collections.deque = collections.deque()
        self.last_tick = None
        # in-tick state — tick thread only
        self._t0 = None
        self._t_end = None      # where the last tick closed
        self._tick = None
        # metric children, bound by bind_metrics (None = no registry)
        self._flops_counter = None
        self._tenant_counter = None
        self._gap_hist = None
        self._skew_gauge = None     # (registry, component) until it is bound
        _LIVE.add(self)

    def close(self):
        """The scheduler is gone: stay readable through ``ledgers()``."""
        if self not in _CLOSED:
            _CLOSED.append(self)

    # ------------------------------------------------------------- metrics
    def bind_metrics(self, registry, component="continuous"):
        """Register the utilization series on ``registry``. The MFU gauge
        binds only when ``peak_flops`` is known — a denominator-less MFU
        would be a made-up number, so on CPU the series is simply absent."""
        self._component = component
        self._flops_counter = registry.counter(
            "paddle_serving_flops_total",
            "Issued step-program FLOPs decomposed by kind; conservation: "
            "useful + pad + spec_waste == issued (exact, integer units)",
            labels=("component", "kind"))
        self._tenant_counter = registry.counter(
            "paddle_tenant_flops_total",
            "Useful FLOPs billed per tenant (chargeback); the sum over "
            "tenants equals the useful kind exactly — paused sequences "
            "are off-slot and never billed",
            labels=("component", "tenant"))
        self._gap_hist = registry.histogram(
            "paddle_serving_host_gap_seconds",
            "Per-tick host time outside step-program launches (tick wall "
            "minus dispatch and read-back wait) — the dispatch-efficiency "
            "dial",
            labels=("component",), buckets=HOST_GAP_BUCKETS).labels(
                component)
        self._skew_gauge = (registry, component)
        if self.peak_flops:
            registry.gauge(
                "paddle_serving_mfu",
                "Serving model FLOPs utilization: rolling-window USEFUL "
                "FLOP/s over device_peak_flops (pad and rejected "
                "speculation excluded — the honest utilization number)",
                labels=("component",)).labels(component).set_function(
                    self.mfu)
        return self

    # ------------------------------------------------------------ tick API
    def poll_session(self):
        """Ask whether a profiler session runs (tick thread). One found
        running where the last answer was no is a NEW session: its ticks
        get a fresh ``profiled`` account, and the open tick, which began
        before it, is not one of them."""
        on = TraceAnnotation.is_enabled()
        if on and not self._in_session:
            with self._lock:
                self._profiled = _new_account()
        self._in_session = on
        if self._tick is not None and not on:
            self._tick["profiled"] = False
        return on

    def tick_begin(self, contiguous=False):
        """Open a tick's window: where the last tick closed if this pass of
        the loop follows it directly (``contiguous``: admission and the
        loop's own bookkeeping are then inside), else now (after a park on
        an empty queue, which is in no tick)."""
        now = self.clock()
        self._t0 = (self._t_end if contiguous and self._t_end is not None
                    else now)
        self._t_end = None
        profiled = self.poll_session()
        self._tick = dict.fromkeys(_TICK_KEYS, 0)
        self._tick.update(tenants={}, programs={}, profiled=profiled)

    @staticmethod
    def check_counts(counts):
        """Raise ValueError if a model's count is under one of the ledger's
        own key names. The scheduler asks this of every `_launch_counts`
        answer in the tick, where the error fails the launch's requests:
        inside its guard around the ledger it would only be missing
        telemetry."""
        taken = sorted(k for k in counts if k in _PROGRAM_KEYS)
        if taken:
            raise ValueError(f"a model's count may not be the ledger's own "
                             f"key: {taken}")

    def record_launch(self, program, flops, launch_s, total_units,
                      slot_units, spec_units=0, *, wait_s=0.0, live_rows=0,
                      walked_rows=0, table_rows=0, sampler=(False, False),
                      counts=None, ahead=False, ahead_dropped=0):
        """Attribute one launch inside the current tick. ``launch_s`` is
        the launch THROUGH its read-back, ``wait_s`` the read-back's part
        of it. ``total_units`` are the positions the program issued,
        ``slot_units`` ``[(tenant_or_None, useful_units), ...]`` per live
        slot — the scheduler's ground truth of which positions carried live
        tokens — and ``spec_units`` rejected draft positions. ``sampler``
        is ``(drawn, sorted)``, which of the sampler's branches the launch
        ran (``models.generation.sampler_engages``). ``ahead``: the launch
        was dispatched before the one before it had been read back;
        ``ahead_dropped``: slot-steps it computed for sequences that had
        finished by then, whose tokens were dropped. ``counts`` are
        the model's own of this launch, under its own key names: every one
        is summed on the program's account; one that is the ledger's own
        raises ValueError."""
        if self._tick is None:      # launch outside a tick (warmup): skip
            return
        counts = counts or {}
        self.check_counts(counts)   # before anything of the launch is added
        self.poll_session()
        slot_units = list(slot_units)
        issued, useful, pad, spec, bills = attribute_launch(
            flops, total_units, slot_units, spec_units)
        launch_s = float(launch_s or 0.0)
        wait_s = min(float(wait_s or 0.0), launch_s)
        useful_pos = sum(max(0, int(u)) for _, u in slot_units)
        t = self._tick
        p = t["programs"].setdefault(program,
                                     dict.fromkeys(_PROGRAM_KEYS, 0))
        for acc in (t, p):
            acc["issued"] += issued
            acc["useful"] += useful
            acc["pad"] += pad
            acc["spec_waste"] += spec
            acc["dispatch_s"] += launch_s - wait_s
            acc["wait_s"] += wait_s
        t["launch_s"] += launch_s
        for tenant, share in bills.items():
            t["tenants"][tenant] = t["tenants"].get(tenant, 0) + share
        p["launches"] += 1
        p["sampler_drawn"] += int(bool(sampler[0]))
        p["sampler_sorted"] += int(bool(sampler[1]))
        p["ahead"] += int(bool(ahead))
        p["ahead_dropped"] += int(ahead_dropped)
        p["issued_positions"] += int(total_units)
        p["useful_positions"] += useful_pos
        p["spec_positions"] += int(spec_units)
        p["pad_positions"] += int(total_units) - useful_pos - int(spec_units)
        p["live_rows"] += int(live_rows)
        p["walked_rows"] += int(walked_rows)
        p["table_rows"] += int(table_rows)
        for key, value in counts.items():
            _add_count(p, key, value)
        if self._skew_gauge and "moe_expert_tokens" in counts:
            # absent until an expert layer reports: a model without one has
            # no such series
            registry, component = self._skew_gauge
            self._skew_gauge = None
            registry.gauge(
                "paddle_serving_moe_expert_load_skew",
                "Assignments of the busiest held expert over the mean's, "
                "over the process (1.0: even)",
                labels=("component",)).labels(component).set_function(
                    lambda: self.expert_load_skew() or 0.0)

    def expert_load_skew(self):
        """The busiest held expert's assignments over the mean's, over the
        process so far (1.0: even; None: no expert layer reported)."""
        with self._lock:
            per = [p["moe_expert_tokens"] for p in
                   self._process["programs"].values()
                   if p.get("moe_expert_tokens")]
        if not per:
            return None
        load = [sum(col) for col in zip(*per)]
        return max(load) * len(load) / sum(load) if sum(load) else None

    def tick_end(self):
        if self._tick is None:
            return None
        self.poll_session()
        t, self._tick = self._tick, None
        now = self._t_end = self.clock()
        wall = max(0.0, now - (self._t0 if self._t0 is not None else now))
        self._t0 = None
        gap = max(0.0, wall - t["launch_s"])
        t["wall_s"] = wall
        t["host_gap_s"] = gap
        with self._lock:
            _fold(self._process, t)
            if t["profiled"]:
                _fold(self._profiled, t)
            for tenant, share in t["tenants"].items():
                self.by_tenant[tenant] = (self.by_tenant.get(tenant, 0)
                                          + share)
            self._gaps.append(gap)
            self._window.append((now, wall, t["useful"]))
            self._prune_window(now)
            self.last_tick = t
        if self._flops_counter is not None:
            c = self._flops_counter
            c.labels(self._component, "useful").inc(t["useful"])
            c.labels(self._component, "pad").inc(t["pad"])
            c.labels(self._component, "spec_waste").inc(t["spec_waste"])
            for tenant, share in t["tenants"].items():
                self._tenant_counter.labels(
                    self._component, tenant).inc(share)
            self._gap_hist.observe(gap)
        return t

    def _prune_window(self, now):
        horizon = now - self.mfu_window_s
        w = self._window
        while w and w[0][0] < horizon:
            w.popleft()

    # ------------------------------------------------------------- reading
    def mfu(self):
        """Rolling-window useful FLOP/s over peak (0.0 with no peak or no
        ticks in the window). Elapsed time spans from the oldest retained
        tick's BEGIN to now, so a single tick reads its own wall."""
        if not self.peak_flops:
            return 0.0
        now = self.clock()
        with self._lock:
            self._prune_window(now)
            if not self._window:
                return 0.0
            t_end0, wall0, _ = self._window[0]
            elapsed = max(1e-9, now - (t_end0 - wall0))
            useful = sum(u for _, _, u in self._window)
        return useful / (elapsed * self.peak_flops)

    @staticmethod
    def _pct(sorted_vals, q):
        if not sorted_vals:
            return None
        i = min(len(sorted_vals) - 1, int(round(q * (len(sorted_vals) - 1))))
        return sorted_vals[i]

    def snapshot(self) -> dict:
        """Full JSON state for ``/utilization``: lifetime totals (integers,
        conservation checkable by the reader) of FLOPs, time and, per
        program, positions and K,V rows; the same totals over the ticks
        that ran wholly inside a profiler session (``profiled``); per-tenant
        bills, host-gap percentiles and the last tick's decomposition."""
        with self._lock:
            gaps = sorted(self._gaps)
            out = _account_view(self._process)
            out["profiled"] = _account_view(self._profiled)
            out["tenants"] = dict(self.by_tenant)
            out["last_tick"] = self.last_tick
        issued = out["flops"]["issued"]
        if issued:
            out["useful_ratio"] = round(out["flops"]["useful"] / issued, 6)
        for q, name in ((0.50, "host_gap_p50_s"), (0.99, "host_gap_p99_s")):
            v = self._pct(gaps, q)
            if v is not None:
                out[name] = round(v, 6)
        out["peak_flops"] = self.peak_flops
        out["mfu"] = round(self.mfu(), 6) if self.peak_flops else None
        return out

    def metrics_block(self) -> dict:
        """Compact block for the JSON /metrics snapshot (mirrors the PR 18
        tracer/flight blocks): mfu, flops by kind, host-gap tail."""
        snap = self.snapshot()
        return {
            "mfu": snap["mfu"],
            "flops": snap["flops"],
            "host_gap_p50_s": snap.get("host_gap_p50_s"),
            "host_gap_p99_s": snap.get("host_gap_p99_s"),
        }
