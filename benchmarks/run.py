#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Loads the cell's configuration, its family (`benchmarks/families/<the
configuration's model_type>/`) and traffic mix by name, refuses anything but
the chips the cell asks for (exit 2, no result), sets up (weights from the
seed, compile or cache load, warm-up, the first steps), measures for
`--seconds`, compares what the timed path produced with the plain reference,
and prints ONE last line on standard output, built and checked by
`harness/lastline.py`. Everything else goes to standard error."""
import time

T0 = time.perf_counter()    # process start, as near as Python can tell

import argparse
import json
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def load_job(workload, seed, seconds, trace, root=ROOT):
    from benchmarks.harness import compare, traffic
    from benchmarks.harness.job import Job

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json: "
                         f"{sorted(cells)}")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(root, config["file"])) as f:
        cfg = json.load(f)
    here = os.path.join(root, "benchmarks")
    mix = traffic.load_mix(os.path.join(here, "traffic",
                                        f"{cell['traffic']}.json"))
    limits = compare.load_limits(os.path.join(here, "limits",
                                              f"{workload}.json"))
    return Job(root=root, bench=bench, workload=cell, cfg=cfg, mix=mix,
               limits=limits, seed=seed, seconds=seconds, trace=bool(trace),
               t0=T0)


def require_chips(job):
    """The device as JAX reports it; exit 2 with no result unless it is a
    TPU with at least the chips the cell asks for."""
    import jax

    from benchmarks.harness.job import log
    from benchmarks.harness.peaks import peaks_for

    devices = jax.devices()
    dev = devices[0]
    log(f"device platform={dev.platform} kind={dev.device_kind!r} "
        f"count={len(devices)} jax={jax.__version__}")
    if dev.platform != "tpu" or len(devices) < job.chips:
        log(f"refusing to run: need {job.chips} TPU chip(s), found "
            f"{len(devices)} of platform {dev.platform!r}")
        raise SystemExit(2)
    job.peaks = peaks_for(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devices)}


def finish(job, outcome, device):
    """From a driver's outcome to the validated last line (a dict)."""
    from benchmarks.harness import compare, lastline, trace
    from benchmarks.harness.job import View, layer_reader, log

    device = dict(device, memory_peak_bytes=outcome.memory_peak_bytes)
    breakdown = None
    if job.trace:
        t_read = time.perf_counter()
        reduced = trace.reduce(trace.read_xplane(outcome.trace_dir))
        shutil.rmtree(outcome.trace_dir, ignore_errors=True)
        log(f"trace read and reduced in {time.perf_counter() - t_read:.1f}s")
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        view = View(cfg=job.cfg, mix=job.mix, peaks=job.peaks,
                    chips=job.chips, family=job.family,
                    records=outcome.records,
                    window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                    events=reduced["events"])
        metrics = {name: layer_reader(job.root, name)(view) for name in
                   lastline.expected_metrics(job.bench, job.workload["name"],
                                             trace=True)}
        breakdown = {
            "device_ops": trace.top_ops(reduced["events"][0]),
            "idle_gaps": trace.idle_gaps(reduced["events"][0],
                                         reduced["window"])}
    else:       # a driver may offer more statistics than the cell reports
        metrics = {name: outcome.metrics.get(name) for name in
                   lastline.expected_metrics(job.bench, job.workload["name"],
                                             trace=False)}
    correct, compared = compare.judge(outcome.numbers, job.limits)
    return lastline.build(
        job.bench, job.workload["name"], job.trace, correct=correct,
        attempted=outcome.attempted, failed=outcome.failed, metrics=metrics,
        device=device, compared=compared, breakdown=breakdown)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import paddle_tpu  # noqa: F401 — the system under test; absent, no run
    from benchmarks.harness import job as job_module, lastline
    from benchmarks.harness.drivers import DRIVERS
    from benchmarks.harness.job import log
    from paddle_tpu.jit.compile_cache import enable_compile_cache

    job_module.START = T0
    job = load_job(args.workload, args.seed, args.seconds, args.trace)
    device = require_chips(job)
    log(f"compile cache: "
        f"{enable_compile_cache(os.path.join(ROOT, '.jax_cache'))}")
    outcome = DRIVERS[job.mix["kind"]](job)
    try:
        line = finish(job, outcome, device)
    except lastline.MalformedLine as e:
        log(f"the last line would be malformed, so none is printed: {e}")
        return 3
    print(lastline.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
