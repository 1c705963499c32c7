#!/usr/bin/env python3
"""One traced run of a serve cell that KEEPS what `benchmarks/run.py` reads
once and throws away: where PERF.md's tables of the serving host come from.

    python tools/keep_serve_trace.py --seed <n> --out <dir>
        [--workload gpt2-large-serve-chat] [--seconds 30]

Drives the cell as `run.py --trace 1` does (its job, its driver, its last
line, printed last on standard output; a TPU is required as there) and
writes under `--out`:

  trace_summary.txt        what the xplane holds (`harness/trace.py`)
  trace_gaps.txt           `tools/trace_gaps.py`'s tables: the device's idle
                           gaps by host span, the spans inside the window
  mosaic_names.json        the Mosaic calls of the window by kernel: how
                           many different HLO instructions, how many calls
  ledgers.json             `snapshot()` of every tick ledger (whole, and
                           `profiled`: the ticks inside the session)
  launch_histogram.txt     `paddle_decode_launch_seconds` as /metrics
                           rendered it just before the server closed
  program_counters.json    the three `program_counter` readers beside
                           `device_idle.serve` of the same window, and the
                           decode launches' walked rows as a share of the
                           tables' and the live rows as a share of those

The trace itself is as large as the run is long (hundreds of MB): it is
read here and removed by `run.finish`, as in every traced run."""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "tools"))

from benchmarks import run as R  # noqa: E402  (stamps the process start)

COUNTER_READERS = ("host_gap_share.serve", "pad_positions_share.serve",
                   "kv_live_rows_share.serve")


def mosaic_names(events):
    """{kernel: [different instructions, calls]} of the Mosaic calls among
    the op events, a kernel being the instruction's name less its number."""
    from benchmarks.harness.trace import MOSAIC_TARGET

    names = {}
    for name, _, _, stats in events:
        if stats.get("custom_call_target") == MOSAIC_TARGET:
            names[name] = names.get(name, 0) + 1
    out = {}
    for name, calls in names.items():
        row = out.setdefault(name.rstrip("0123456789").rstrip("."), [0, 0])
        row[0] += 1
        row[1] += calls
    return out


def keep(job, outcome, out_dir):
    """Everything read off the kept trace and the program's counters."""
    import trace_gaps

    from benchmarks.harness import trace
    from benchmarks.harness.job import View, layer_reader
    from paddle_tpu.observability import utilization

    def write(name, text):
        with open(os.path.join(out_dir, name), "w") as f:
            f.write(text)

    with open(os.path.join(out_dir, "trace_summary.txt"), "w") as f:
        trace.summarize(outcome.trace_dir, out=f)
    with open(os.path.join(out_dir, "trace_gaps.txt"), "w") as f:
        trace_gaps.report(*trace_gaps.read(outcome.trace_dir), out=f)
    snaps = [led.snapshot() for led in utilization.ledgers()]
    write("ledgers.json", json.dumps(snaps, indent=1))
    reduced = trace.reduce(trace.read_xplane(outcome.trace_dir))
    write("mosaic_names.json", json.dumps(mosaic_names(reduced["events"][0])))
    view = View(cfg=job.cfg, mix=job.mix, peaks=job.peaks, chips=job.chips,
                records=outcome.records, window_s=reduced["window_s"],
                busy_s=reduced["busy_s"], events=reduced["events"])
    values = {name: layer_reader(ROOT, name)(view)
              for name in COUNTER_READERS + ("device_idle.serve",)}
    values.update(window_s=reduced["window_s"], busy_s=reduced["busy_s"])
    # how far the paged kernel's walk follows the lengths (PR 27): the rows
    # its loop visited against the tables' and against the live ones
    rows = [p for s in snaps for name, p in s["profiled"]["programs"].items()
            if name == "decode_step" and p.get("walked_rows")]
    if len(rows) == 1:
        values["walked_of_table_rows_pct"] = (
            100.0 * rows[0]["walked_rows"] / rows[0]["table_rows"])
        values["live_of_walked_rows_pct"] = (
            100.0 * rows[0]["live_rows"] / rows[0]["walked_rows"])
    write("program_counters.json", json.dumps(values))
    return values


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--workload", default="gpt2-large-serve-chat")
    ap.add_argument("--seconds", type=float, default=30.0)
    args = ap.parse_args(argv)
    os.makedirs(args.out, exist_ok=True)

    import paddle_tpu  # noqa: F401
    from benchmarks.harness import job as job_module, lastline, program
    from benchmarks.harness.drivers import DRIVERS
    from benchmarks.harness.job import log
    from paddle_tpu.jit.compile_cache import enable_compile_cache
    from paddle_tpu.observability.metrics import render_prometheus

    job_module.START = R.T0
    job = R.load_job(args.workload, args.seed, args.seconds, 1)
    device = R.require_chips(job)
    log(f"compile cache: "
        f"{enable_compile_cache(os.path.join(ROOT, '.jax_cache'))}")

    # the registry goes with the server: render the histogram as it closes
    close = program.Server.close

    def close_and_render(server):
        text = render_prometheus(server.pred.metrics.registry)
        with open(os.path.join(args.out, "launch_histogram.txt"), "w") as f:
            f.write("\n".join(ln for ln in text.splitlines()
                              if "paddle_decode_launch_seconds" in ln))
        close(server)

    program.Server.close = close_and_render
    try:
        outcome = DRIVERS[job.mix["kind"]](job)
    finally:
        program.Server.close = close
    log("kept " + json.dumps(keep(job, outcome, args.out)))
    print(lastline.dumps(R.finish(job, outcome, device)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
