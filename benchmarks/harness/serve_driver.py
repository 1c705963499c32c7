"""Traffic of kind `open_loop`: requests to a server, in process, one client
thread per request in flight, tokens timed as they reach the client.

Open loop: every request is sent at its due time whatever the server does,
and its latency counts from the DUE time, so a stall is charged to the
requests behind it. How late the generator itself ran is printed. After the
window closes the driver waits for every request that was due (a late answer
is late, not wrong), reads the peak, frees the server, and runs the plain
reference over a sample of what was served."""
import dataclasses
import gc
import json
import threading
import time

import jax
import numpy as np

from . import program, reference, trace, traffic
from .job import Outcome, log

DRAIN_SECONDS = 60.0        # wait this long past the close for answers due


@dataclasses.dataclass
class Answer:
    """One request as its client saw it."""
    req: traffic.Request
    sent: float = None
    flushes: list = dataclasses.field(default_factory=list)  # (time, tokens)
    tokens: list = dataclasses.field(default_factory=list)
    error: Exception = None
    done: bool = False          # the stream ended, well or badly

    @property
    def ok(self):
        return self.done and self.error is None and bool(self.flushes)


def ask(server, answer, clock, timeout):
    """Send one request through the timed entry and stamp every flush of
    tokens as it reaches the client."""
    answer.sent = clock()
    try:
        for chunk in server.stream(answer.req.prompt, answer.req.max_new,
                                   timeout):
            now = clock()
            if len(chunk):
                answer.flushes.append((now, len(chunk)))
                answer.tokens.extend(int(t) for t in chunk)
    except Exception as e:      # noqa: BLE001 — a failed request is data
        answer.error = e
    answer.done = True


def _join(threads, clock, deadline):
    for t in threads:
        t.join(timeout=max(0.0, deadline - clock()))


def offer_open_loop(server, requests, seconds, spans=()):
    """Send each request at its due time, one client thread per request in
    flight; returns every request's Answer once all are in or given up on.
    `spans` are (start_s, end_s, enter, leave): callbacks without arguments,
    fired on the sending thread's schedule (the traced part)."""
    lead = max(0.0, -min((r.due_s for r in requests), default=0.0))
    t_begin = time.perf_counter() + lead        # the window opens at 0
    clock = lambda: time.perf_counter() - t_begin     # noqa: E731
    answers, threads, late = [], [], []
    events = sorted([(s, 0, enter) for s, _, enter, _ in spans]
                    + [(e, 1, leave) for _, e, _, leave in spans]
                    + [(r.due_s, 2, r) for r in requests],
                    key=lambda ev: (ev[0], ev[1]))
    for due, kind, what in events:
        wait = due - clock()
        if wait > 0:
            time.sleep(wait)
        if kind != 2:
            what()
            continue
        answers.append(Answer(what))
        threads.append(threading.Thread(
            target=ask, name=f"bench-client-{what.index}", daemon=True,
            args=(server, answers[-1], clock, seconds + DRAIN_SECONDS)))
        late.append(clock() - due)
        threads[-1].start()
    rest = seconds - clock()
    if rest > 0:
        time.sleep(rest)
    closed = clock()
    _join(threads, clock, seconds + DRAIN_SECONDS)
    log(f"generator ran late by mean {1e3 * np.mean(late):.3f} ms, "
        f"max {1e3 * np.max(late):.3f} ms over {len(late)} requests; "
        f"window closed at {closed:.3f}s, last answer at {clock():.3f}s")
    return answers, t_begin


def request_metrics(answers, seconds):
    """Every end-to-end statistic a serving run offers; BENCHMARK.json names
    the ones a cell reports. The rate is over every token that reached a
    client inside [0, seconds]; the latencies are over ALL requests that were
    due in the window (a lead-in request, due before 0, only adds its tokens
    to the rate). Time to the first token counts from the DUE time, and a
    failed request counts as the window's length. Time per output token is
    (last token - first token) / (tokens - 1) at the client: `tpot_p50_ms`
    and `tpot_p95_ms` take each request's own, `tpot_mean_ms` all decode time
    over all decode tokens. The inter-token latency `itl_p50_ms`, `itl_p95_ms`
    is over every gap between two arrivals of tokens at one client (an
    arrival carries the tokens of one tick). Returns (statistics, requests
    due, how many of them failed)."""
    ttft, tpot, gaps, in_window, due, failed = [], [], [], 0, 0, 0
    decode_s, decode_tokens = 0.0, 0
    for a in answers:
        in_window += sum(n for t, n in a.flushes if 0 <= t <= seconds)
        if a.req.due_s < 0:
            continue
        due += 1
        if not a.ok:
            failed += 1
            ttft.append(seconds)        # a failure counts as the window
            continue
        first, last = a.flushes[0][0], a.flushes[-1][0]
        ttft.append(first - a.req.due_s)
        gaps.extend(t1 - t0 for (t0, _), (t1, _) in zip(a.flushes,
                                                         a.flushes[1:]))
        if len(a.tokens) > 1:
            tpot.append((last - first) / (len(a.tokens) - 1))
            decode_s += last - first
            decode_tokens += len(a.tokens) - 1
    out = {"serve_output_tokens_per_s": in_window / seconds}
    for name, values in (("ttft", ttft), ("tpot", tpot), ("itl", gaps)):
        for q in (50, 95):
            if values:
                out[f"{name}_p{q}_ms"] = 1e3 * traffic.percentile(values, q)
    if decode_tokens:
        out["tpot_mean_ms"] = 1e3 * decode_s / decode_tokens
    if gaps:    # where the percentiles sit among the gaps' modes
        counts = np.bincount((np.asarray(gaps) / 0.1).astype(int))
        log(f"{len(gaps)} gaps between arrivals, by 100 ms: "
            + " ".join(f"{i / 10:.1f}s:{c}" for i, c in enumerate(counts)
                       if c))
    return out, due, failed


def pool_occupancy(answers, geometry, seconds, step=0.25):
    """Slots and pages of the pool in use over the window, by the request
    records: a request holds a slot, and pages for its prompt and the tokens
    it has got so far, from the moment it is sent to its last flush (so an
    upper estimate: a request waits a little before it is admitted).
    Returns {"slots_mean", "slots_peak", "pages_mean", "pages_peak"}."""
    block = geometry["block_size"]
    slots, pages = [], []
    for t in np.arange(0.0, seconds, step):
        live = [a for a in answers if a.flushes and a.sent <= t
                and not (a.done and a.flushes[-1][0] < t)]
        slots.append(len(live))
        pages.append(sum(
            -(-(len(a.req.prompt)
                + sum(n for at, n in a.flushes if at <= t)) // block)
            for a in live))
    return {"slots_mean": float(np.mean(slots)), "slots_peak": max(slots),
            "pages_mean": float(np.mean(pages)), "pages_peak": max(pages)}


def traced_work(family, cfg, answers, a, b):
    """Useful work inside [a, b] by the benchmark's own request records. An
    output token after the first is one decode pass at the moment its flush
    arrived. A prompt's forward ran somewhere in [sent, first token]: the
    estimate spreads it evenly over that span; `*_low` counts a prompt only
    where the whole span lies inside [a, b], `*_high` wherever it overlaps,
    so the truth lies between the two whatever the scheduler did when.
    Counts operations and the K,V rows the attention must read, as the
    family's `counts.py` gives them for a prompt and for an output token."""
    counts = family.counts
    out = dict(model_flops=0.0, attention_flops=0.0, kv_rows=0.0,
               prompt_tokens=0.0, output_tokens=0)
    low = dict(model_flops=0.0, kv_rows=0.0)
    high = dict(low)
    for c in answers:
        if c.error is not None or not c.flushes:
            continue
        plen = len(c.req.prompt)
        first = c.flushes[0][0]
        span = max(first - c.sent, 1e-9)
        share = max(0.0, min(b, first) - max(a, c.sent)) / span
        if share > 0:
            work = counts.prompt_work(cfg, plen)
            for key, value in work.items():
                out[key] += share * value
            out["prompt_tokens"] += share * plen
            inside = a <= c.sent and first <= b
            for side, counted in ((low, inside), (high, True)):
                if counted:
                    for key in side:
                        side[key] += work[key]
        j = 0
        for t, n in c.flushes:
            for k in range(j, j + n):
                if k >= 1 and a <= t <= b:          # token 0 is the prompt's
                    work = counts.token_work(cfg, plen + k)
                    for key, value in work.items():
                        out[key] += value
                    out["output_tokens"] += 1
                    for side in (low, high):
                        for key in side:
                            side[key] += work[key]
            j += n
    for key in low:
        out[f"{key}_low"], out[f"{key}_high"] = low[key], high[key]
    return out


def sample_for_check(answers, seed, count):
    """(prompt, served tokens) of finished requests to compare, drawn from
    the seed, the longest among them."""
    done = [a for a in answers if a.ok]
    if not done:
        return []
    longest = max(done, key=lambda a: len(a.req.prompt) + len(a.tokens))
    rest = [a for a in done if a is not longest]
    pick = traffic.rng_for(seed, 2).permutation(len(rest))[:max(0, count - 1)]
    return [(a.req.prompt, list(a.tokens))
            for a in [longest] + [rest[i] for i in pick]]


def wrong_token_counts(answers):
    """Finished requests whose answer has another length than asked."""
    return sum(1 for a in answers if a.ok and len(a.tokens) != a.req.max_new)


def served_logit_gaps(family, cfg, seed, answers, width, *, most_new=None,
                      hbm_bytes=None, control=False):
    """Run the reference once over each answer's prompt and served tokens,
    `width` positions a row (the mix's `geometry.max_seq_len`).
    `served_logit_gap`: the widest gap by which a served token's reference
    logit lies below the reference's best at its position; with `control`,
    `control_logit_gap`: the same for the token the fp8 control puts first.
    `hbm_bytes` (the chip's memory) decides whether the whole float32 image
    is made or the reference runs a layer at a time: `reference.ServeCheck`."""
    rows = []
    for prompt, tokens in answers:
        plen, n = len(prompt), len(tokens)
        ids = np.zeros((1, width), np.int64)
        ids[0, :plen] = prompt
        ids[0, plen:plen + n] = tokens[:width - plen]
        rows.append((ids, slice(plen - 1, min(plen - 1 + n, width - 1))))
    rows = [(ids, keep) for ids, keep in rows if keep.stop > keep.start]
    compared = sum(keep.stop - keep.start for _, keep in rows)
    log(f"comparing {compared} served tokens of {len(rows)} requests")
    if not compared:
        return {}
    check = reference.ServeCheck(family, cfg, seed, width, most_new=most_new,
                                 hbm_bytes=hbm_bytes, control=control)
    gaps = check.gaps(rows)
    return {f"{name}_logit_gap": float(max(np.max(g[f"{name}_gap"])
                                           for g in gaps))
            for name in (("served", "control") if control else ("served",))}


def warm(server, cfg, mix, seed):
    """The mix's `warm` requests ({"requests", "prompt", "output"}) through
    the timed entry before any traffic: every program has run once."""
    reqs = traffic.requests_due(dict(mix, **mix["warm"]), cfg, seed,
                                np.zeros(mix["warm"]["requests"]))
    answers, _ = offer_open_loop(server, reqs, 0.0)   # all at once, and wait
    bad = [a for a in answers if not a.ok]
    if bad or not answers:
        raise RuntimeError(f"a warm-up request failed: "
                           f"{bad[0].error if bad else 'none sent'!r}")


def run(job, make_server=program.Server):
    """An `open_loop` mix: offers `rate_per_s` and hands back every
    statistic of `request_metrics`, of which the cell reports those that
    BENCHMARK.json names for it."""
    mix, cfg = job.mix, job.cfg
    server = make_server(job.family, cfg, mix["geometry"], job.seed)
    log("model built, weights made from the seed, server started")
    records, trace_dir = {}, None
    try:
        stats = server.wait_ready(mix.get("ready_timeout_s", 1100))
        log(f"server ready: warm-up {stats['seconds']:.1f}s, "
            f"{stats['compiled']}/{stats['programs']} programs")
        warm(server, cfg, mix, job.seed)
        log("warm requests answered")
        requests = traffic.open_loop_requests(mix, cfg, job.seed, job.seconds)
        spans = ()
        if job.trace:
            trace_dir = job.trace_dir()
            mark = {}

            def enter():
                jax.profiler.start_trace(trace_dir)
                # made only now: an annotation made before the profiler
                # started records nothing
                mark["ann"] = jax.profiler.TraceAnnotation(
                    trace.WINDOW_ANNOTATION)
                mark["ann"].__enter__()
                mark["a"] = time.perf_counter()

            def leave():
                mark["b"] = time.perf_counter()
                mark["ann"].__exit__(None, None, None)
                jax.profiler.stop_trace()

            t_a = mix.get("trace_start_s", 2.0)
            spans = ((t_a, t_a + mix["trace_seconds"], enter, leave),)
        answers, t_begin = offer_open_loop(server, requests, job.seconds,
                                           spans)
        metrics, due, failed = request_metrics(answers, job.seconds)
        log("offered statistics " + json.dumps(metrics))
        log("pool in use over the window " + json.dumps(dict(
            pool_occupancy(answers, mix["geometry"], job.seconds),
            slots=mix["geometry"]["max_slots"],
            pages=mix["geometry"]["num_blocks"])))
        metrics["setup_s"] = t_begin - job.t0   # the lead-in is set-up too
        if job.trace:
            a, b = mark["a"] - t_begin, mark["b"] - t_begin
            records.update(traced_work(job.family, cfg, answers, a, b))
            log("useful work in the traced window " + json.dumps(records))
        if server.compiles_after_ready():
            raise RuntimeError("a step program compiled after the warm-up")
        checked = sample_for_check(answers, job.seed,
                                   mix.get("check_requests", 12))
        records["answers"] = checked
    finally:
        server.close()
    peak = program.memory_peak_bytes()
    del server
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    numbers = served_logit_gaps(
        job.family, cfg, job.seed, checked, mix["geometry"]["max_seq_len"],
        most_new=mix["geometry"].get("max_new_tokens"),
        hbm_bytes=(job.peaks or {}).get("hbm_bytes"))
    numbers["wrong_token_counts"] = wrong_token_counts(answers)
    log(f"reference ran in {time.perf_counter() - t_ref:.1f}s")
    return Outcome(attempted=due, failed=failed, metrics=metrics,
                   numbers=numbers, memory_peak_bytes=peak, records=records,
                   trace_dir=trace_dir)
