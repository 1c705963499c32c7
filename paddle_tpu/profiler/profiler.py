"""Profiler core: scheduler-driven host tracing + XLA device trace capture.

Reference: python/paddle/profiler/profiler.py:358 (Profiler with
ProfilerState scheduler, RecordEvent instrumentation, chrome-trace export
:227). TPU-native split: host-side events (python ranges, dataloader, step
markers) are recorded here with zero native deps; DEVICE-side timing comes
from jax.profiler trace capture (XLA's profiler emits TensorBoard/perfetto
data), toggled by the same scheduler. Statistics aggregate the host events.
"""
from __future__ import annotations

import json
import os
import threading
import time
from enum import Enum

from jax.profiler import TraceAnnotation


class ProfilerState(Enum):
    # reference profiler.py:89
    CLOSED = 0
    READY = 1
    RECORD = 2
    RECORD_AND_RETURN = 3  # record, and emit the collected trace at this step


class ProfilerTarget(Enum):
    # reference profiler.py:110 (CPU/GPU/XPU/CUSTOM_DEVICE) — TPU is the
    # custom device of this build
    CPU = 0
    TPU = 1
    GPU = 2


class TracerEventType(Enum):
    # subset of reference's paddle.base.core.TracerEventType used by statistics
    Operator = 0
    Dataloader = 1
    ProfileStep = 2
    Forward = 3
    Backward = 4
    Optimization = 5
    PythonUserDefined = 6
    Communication = 7


def make_scheduler(*, closed: int, ready: int, record: int, repeat: int = 0,
                   skip_first: int = 0):
    """Reference profiler.py:129. Returns fn(step)->ProfilerState cycling
    [closed, ready, record) with the last record step RECORD_AND_RETURN."""
    if closed < 0 or ready < 0 or record <= 0:
        raise ValueError("closed/ready must be >=0 and record >= 1")
    span = closed + ready + record

    def scheduler(step: int) -> ProfilerState:
        if step < skip_first:
            return ProfilerState.CLOSED
        s = step - skip_first
        if repeat and s >= repeat * span:
            return ProfilerState.CLOSED
        pos = s % span
        if pos < closed:
            return ProfilerState.CLOSED
        if pos < closed + ready:
            return ProfilerState.READY
        if pos == span - 1:
            return ProfilerState.RECORD_AND_RETURN
        return ProfilerState.RECORD

    return scheduler


def _default_state_scheduler(step: int) -> ProfilerState:
    return ProfilerState.RECORD


class _HostEvent:
    __slots__ = ("name", "start_us", "end_us", "tid", "event_type")

    def __init__(self, name, start_us, end_us, tid, event_type):
        self.name = name
        self.start_us = start_us
        self.end_us = end_us
        self.tid = tid
        self.event_type = event_type

    @property
    def duration_us(self):
        return self.end_us - self.start_us


class _Collector:
    """Thread-safe host event buffer, active only while the profiler records."""

    def __init__(self):
        self.lock = threading.Lock()
        self.events: list[_HostEvent] = []
        self.recording = False

    def add(self, ev):
        with self.lock:
            if self.recording:
                self.events.append(ev)

    def drain(self):
        with self.lock:
            out, self.events = self.events, []
        return out


_collector = _Collector()
_now_us = lambda: time.perf_counter_ns() / 1e3  # noqa: E731


class RecordEvent:
    """Reference utils.py:47 — context manager/decorator marking a host range.

    The range is a ``jax.profiler.TraceAnnotation``: inside any
    ``jax.profiler`` capture it lands on ``/host:CPU`` of the same xplane as
    the device's ``XLA Ops``, on the capture's one clock, with ``stats`` as
    the event's stats; with no capture running it is a no-op. While a
    Profiler of this module records, the range also lands in its host
    buffer, on ``time.perf_counter``. Usable standalone::

        with profiler.RecordEvent("data_copy", rows=n) as ev:
            ...
            ev.set_stats(copied=m)      # what is only known at the end
    """

    def __init__(self, name: str,
                 event_type: TracerEventType = TracerEventType.PythonUserDefined,
                 **stats):
        self.name = name
        self.event_type = event_type
        self.stats = stats
        self._start = None
        self._annotation = None

    def begin(self):
        self._annotation = TraceAnnotation(self.name, **self.stats)
        self._annotation.__enter__()
        self._start = _now_us()

    @staticmethod
    def capturing():
        """True inside a ``jax.profiler`` capture: the only time a range's
        stats are kept, so work done only for them can be skipped."""
        return TraceAnnotation.is_enabled()

    def set_stats(self, **stats):
        """Add stats to the open range's annotation."""
        if self._annotation is not None:
            self._annotation.set_metadata(**stats)

    def end(self):
        if self._start is None:
            return
        end_us = _now_us()
        self._annotation.__exit__(None, None, None)
        self._annotation = None
        if _collector.recording:
            _collector.add(_HostEvent(self.name, self._start, end_us,
                                      threading.get_ident(),
                                      self.event_type))
        self._start = None

    def __enter__(self):
        self.begin()
        return self

    def __exit__(self, *exc):
        self.end()
        return False

    def __call__(self, fn):
        import functools

        @functools.wraps(fn)
        def wrapped(*args, **kwargs):
            with RecordEvent(self.name, self.event_type, **self.stats):
                return fn(*args, **kwargs)

        return wrapped


def export_chrome_tracing(dir_name: str, worker_name: str | None = None):
    """Reference profiler.py:227 — returns an on_trace_ready callback writing
    chrome://tracing JSON into `dir_name`."""

    def handler(prof):
        os.makedirs(dir_name, exist_ok=True)
        name = worker_name or f"host_{os.getpid()}"
        fname = os.path.join(dir_name, f"{name}_time_{int(time.time()*1000)}.paddle_trace.json")
        prof._export_chrome(fname)
        prof.last_export_path = fname

    return handler


class Profiler:
    """Reference profiler.py:358.

    Usage::

        p = profiler.Profiler(scheduler=(2, 5),
                              on_trace_ready=profiler.export_chrome_tracing("./log"))
        p.start()
        for it, batch in enumerate(loader):
            train_step(batch)
            p.step()
        p.stop()

    `scheduler` may be None (always RECORD), a (start, end) tuple, or an
    fn(step)->ProfilerState from make_scheduler. When `capture_device_trace`
    is set, XLA's profiler (jax.profiler) records device activity over the
    same RECORD windows; the resulting TensorBoard/perfetto dump lands in
    `device_trace_dir`.
    """

    def __init__(self, *, targets=None, scheduler=None, on_trace_ready=None,
                 record_shapes=False, profile_memory=False, timer_only=False,
                 capture_device_trace=False, device_trace_dir=None):
        if scheduler is None:
            self._scheduler = _default_state_scheduler
        elif isinstance(scheduler, (tuple, list)):
            start, end = scheduler
            self._scheduler = make_scheduler(
                closed=max(start - 1, 0), ready=1 if start > 0 else 0,
                record=end - start, repeat=1)
        else:
            self._scheduler = scheduler
        self.targets = targets or [ProfilerTarget.CPU, ProfilerTarget.TPU]
        self.on_trace_ready = on_trace_ready
        self.timer_only = timer_only
        self.capture_device_trace = capture_device_trace and not timer_only
        self.device_trace_dir = device_trace_dir or "./profiler_device_trace"
        self.step_num = 0
        self.current_state = ProfilerState.CLOSED
        self._snapshots: list[list[_HostEvent]] = []
        self._step_start_us = None
        self._device_tracing = False
        self.last_export_path = None
        from .timer import benchmark

        self._benchmark = benchmark()

    # ------------------------------------------------------------ lifecycle
    def start(self):
        self._benchmark.begin()
        self.current_state = self._scheduler(self.step_num)
        self._apply_state(self.current_state)
        self._step_start_us = _now_us()
        return self

    def step(self, num_samples=None):
        """Advance one train-step boundary."""
        if self._step_start_us is not None and not self.timer_only:
            _collector.add(_HostEvent(f"ProfileStep#{self.step_num}",
                                      self._step_start_us, _now_us(),
                                      threading.get_ident(),
                                      TracerEventType.ProfileStep))
        self._benchmark.step(num_samples)
        self.step_num += 1
        next_state = self._scheduler(self.step_num)
        if (self.current_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
                and (self.current_state is ProfilerState.RECORD_AND_RETURN
                     or next_state in (ProfilerState.CLOSED, ProfilerState.READY))):
            self._emit_trace()
        self.current_state = next_state
        self._apply_state(next_state)
        self._step_start_us = _now_us()

    def stop(self):
        self._benchmark.end()
        if self.current_state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN):
            self._emit_trace()
        self._stop_device_trace()
        self.current_state = ProfilerState.CLOSED
        _collector.recording = False

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.stop()
        return False

    # ------------------------------------------------------------ internals
    def _apply_state(self, state):
        rec = state in (ProfilerState.RECORD, ProfilerState.RECORD_AND_RETURN)
        _collector.recording = rec and not self.timer_only
        if rec:
            self._start_device_trace()
        else:
            self._stop_device_trace()

    def _start_device_trace(self):
        if not self.capture_device_trace or self._device_tracing:
            return
        try:
            import jax

            jax.profiler.start_trace(self.device_trace_dir)
            self._device_tracing = True
        except Exception:
            self.capture_device_trace = False  # unsupported backend: degrade

    def _stop_device_trace(self):
        if not self._device_tracing:
            return
        try:
            import jax

            jax.profiler.stop_trace()
        finally:
            self._device_tracing = False

    def _emit_trace(self):
        events = _collector.drain()
        if events:
            self._snapshots.append(events)
        if self.on_trace_ready is not None:
            self.on_trace_ready(self)

    # ------------------------------------------------------------ results
    @property
    def events(self):
        out = []
        for snap in self._snapshots:
            out.extend(snap)
        return out

    def chrome_events(self):
        """Complete-event ("X") dicts of the collected host events, sorted by
        start time. Timestamps are ``time.perf_counter`` microseconds — the
        timebase of paddle_tpu.observability.trace, so these merge with
        serving spans via observability.export_joined_chrome. (The device's
        ops are not on it: RecordEvent puts the same ranges into a
        ``jax.profiler`` capture for that.)"""
        trace = []
        for ev in self.events:
            trace.append({
                "name": ev.name, "ph": "X", "cat": ev.event_type.name,
                "ts": ev.start_us, "dur": ev.duration_us,
                "pid": os.getpid(), "tid": ev.tid,
            })
        trace.sort(key=lambda e: e["ts"])
        return trace

    def _export_chrome(self, path):
        with open(path, "w") as f:
            json.dump({"traceEvents": self.chrome_events(),
                       "displayTimeUnit": "ms"}, f)
        return path

    def export(self, path, format="json"):
        if format != "json":
            raise ValueError("only chrome-trace json export is supported")
        return self._export_chrome(path)

    def summary(self, sorted_by=None, op_detail=True, thread_sep=False,
                time_unit="ms"):
        """Aggregated per-name table of host events (reference
        profiler_statistic.py role, host scope)."""
        div = {"s": 1e6, "ms": 1e3, "us": 1.0}[time_unit]
        agg: dict[str, list[float]] = {}
        for ev in self.events:
            agg.setdefault(ev.name, []).append(ev.duration_us / div)
        rows = []
        for name, ds in sorted(agg.items(), key=lambda kv: -sum(kv[1])):
            rows.append((name, len(ds), sum(ds), sum(ds) / len(ds), max(ds), min(ds)))
        header = (f"{'Name':40s} {'Calls':>6s} {'Total('+time_unit+')':>12s} "
                  f"{'Avg':>10s} {'Max':>10s} {'Min':>10s}")
        lines = [header, "-" * len(header)]
        for name, n, tot, avg, mx, mn in rows:
            lines.append(f"{name[:40]:40s} {n:6d} {tot:12.3f} {avg:10.3f} "
                         f"{mx:10.3f} {mn:10.3f}")
        table = "\n".join(lines)
        print(table)
        return rows
