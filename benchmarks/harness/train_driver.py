"""Traffic of kind `train`: a job that steps a compiled train step.

Set-up builds ONE Trainer, drives its first steps through the window's own
call and feed (recording what the comparison needs), warms up, and hands the
same object to the window. The window counts every step that finished and
divides by its whole wall time, closed by `block_until_ready` on the last
loss and one updated parameter. After the window: the peak is read, the
program's state freed, and the plain reference follows the first steps."""
import gc
import queue
import threading
import time

import jax
import numpy as np

from . import compare, program, reference, trace, traffic
from .job import Outcome, log

IN_FLIGHT = 2       # steps dispatched ahead of the device


class Feed:
    """Batches made ahead on one thread: a host queue of device tensors."""

    def __init__(self, trainer, batches, depth):
        self._q = queue.Queue(maxsize=depth)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._fill, args=(trainer, batches), name="bench-feed",
            daemon=True)
        self._thread.start()

    def _fill(self, trainer, batches):
        for ids, labels in batches:
            item = (ids, labels, trainer.to_device(ids, labels))
            while not self._stop.is_set():
                try:
                    self._q.put(item, timeout=0.1)
                    break
                except queue.Full:
                    pass
            if self._stop.is_set():
                return

    def get(self):
        return self._q.get(timeout=120)

    def close(self):
        self._stop.set()
        self._thread.join(timeout=30)
        if self._thread.is_alive():
            raise RuntimeError("the feed thread did not stop")


def first_steps(trainer, feed, job, steps):
    """The first `steps` steps through the window's own call and feed: each
    loss, the first gradient's norms (from AdamW's state after step 1) and
    the norm of the parameters' change after the last, read before the next
    step donates them away."""
    fed, losses, grad_norms = [], [], None
    for i in range(steps):
        ids, labels, (x, y) = feed.get()
        fed.append((ids, labels))
        losses.append(trainer.step(x, y))
        if i == 0:
            grad_norms = trainer.first_gradient_norms()
    delta = trainer.delta_norms(
        program.start_leaves(job.family, job.cfg, job.seed))
    return fed, {"losses": [float(v) for v in losses],
                 "grad_norms": np.asarray(grad_norms),
                 "delta_norms": np.asarray(delta)}


def _closed(trainer, loss):
    """Close a stretch of steps: the last loss and one updated parameter."""
    jax.block_until_ready((loss, trainer.parameters_now()[0]))


def _steps_for(trainer, feed, seconds):
    """Step for `seconds`; returns (steps, wall seconds), closed."""
    pending, steps = [], 0
    start = time.perf_counter()
    while time.perf_counter() - start < seconds:
        _, _, (x, y) = feed.get()
        pending.append(trainer.step(x, y))
        steps += 1
        if len(pending) > IN_FLIGHT:
            jax.block_until_ready(pending.pop(0))
    _closed(trainer, pending[-1])
    return steps, time.perf_counter() - start


def reference_numbers(job, fed, *, quant=False, drop_half=False):
    ref = reference.TrainReference(job.family, job.cfg,
                                   job.mix["optimizer"], job.seed,
                                   quant=quant, drop_half=drop_half)
    losses, grad_norms = [], None
    for i, (ids, labels) in enumerate(fed):
        loss, norms = ref.step(ids, labels)
        losses.append(loss)
        if i == 0:
            grad_norms = norms
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": ref.delta_norms()}


def run(job, make_trainer=program.Trainer):
    mix = job.mix
    trainer = make_trainer(job.family, job.cfg, mix["optimizer"], job.seed)
    log("model built, weights made from the seed")
    feed = Feed(trainer, traffic.train_batches(mix, job.cfg, job.seed),
                mix.get("queue_depth", 4))
    try:
        fed, seen = first_steps(trainer, feed, job, mix["check_steps"])
        log(f"first steps: losses {seen['losses']}")
        # step 4 onwards warms the allocator and the feed; nothing compiles
        _steps_for(trainer, feed, mix.get("warmup_seconds", 1.0))
        compiles = trainer.compiles()
        records = {"batch": mix["batch"], "seq": mix["seq"],
                   "first_steps": (fed, seen)}
        trace_dir = None
        setup_s = time.perf_counter() - job.t0
        start = time.perf_counter()
        steps = 0
        if job.trace:
            trace_dir = job.trace_dir()
            jax.profiler.start_trace(trace_dir)
            with jax.profiler.TraceAnnotation(trace.WINDOW_ANNOTATION):
                traced, _ = _steps_for(trainer, feed, mix["trace_seconds"])
            jax.profiler.stop_trace()
            records["traced_steps"] = traced
            steps += traced
        rest = job.seconds - (time.perf_counter() - start)
        if rest > 0:
            more, _ = _steps_for(trainer, feed, rest)
            steps += more
        wall = time.perf_counter() - start
        if trainer.compiles() != compiles:
            raise RuntimeError("the train step compiled inside the window")
    finally:
        feed.close()
    tokens = steps * mix["batch"] * mix["seq"]
    log(f"window: {steps} steps, {tokens} tokens in {wall:.3f}s")
    peak = program.memory_peak_bytes()
    del trainer, feed
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    numbers = compare.train_numbers(seen, reference_numbers(job, fed))
    log(f"reference followed {len(fed)} steps in "
        f"{time.perf_counter() - t_ref:.1f}s")
    return Outcome(attempted=steps, failed=0,
                   metrics={"train_tokens_per_s": tokens / wall,
                            "setup_s": setup_s},
                   numbers=numbers, memory_peak_bytes=peak, records=records,
                   trace_dir=trace_dir)
