#!/usr/bin/env python3
"""Find the knee of an open-loop cell once, on the chip: the highest of a few
fixed rates at which the backlog does not grow. One process, one server, one
short open loop per rate.

    python3 benchmarks/sweep.py --workload <name> --rates 1,2,3,4,6 --seconds 30

For each rate it prints the requests in flight at the window's close against
its middle, the share of requests still unanswered at the close, the tails
and the tokens per second. The knee goes into the mix's file as `knee_per_s`,
and `rate_per_s` is 0.8 of it. Not a benchmark run: it prints no result line."""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import paddle_tpu  # noqa: F401
    import benchmarks.run as run
    from benchmarks.harness import program, serve_driver, traffic
    from paddle_tpu.jit.compile_cache import enable_compile_cache

    job = run.load_job(args.workload, args.seed, args.seconds, 0)
    run.require_chips(job)
    enable_compile_cache(os.path.join(ROOT, ".jax_cache"))
    server = program.Server(job.family, job.cfg, job.mix["geometry"],
                            job.seed)
    try:
        server.wait_ready(job.mix.get("ready_timeout_s", 1100))
        serve_driver.warm(server, job.cfg, job.mix, job.seed)
        for rate in (float(r) for r in args.rates.split(",")):
            mix = dict(job.mix, rate_per_s=rate)
            reqs = traffic.open_loop_requests(mix, job.cfg, args.seed,
                                              args.seconds)
            t0 = time.perf_counter()
            answers, _ = serve_driver.offer_open_loop(server, reqs,
                                                      args.seconds)
            drained = time.perf_counter() - t0
            metrics, due, failed = serve_driver.request_metrics(
                answers, args.seconds)

            def in_flight(at):
                return sum(1 for a in answers if a.req.due_s <= at and not (
                    a.ok and a.flushes[-1][0] <= at))

            def ttft_median(lo, hi):
                xs = [a.flushes[0][0] - a.req.due_s for a in answers
                      if a.ok and lo <= a.req.due_s < hi]
                return traffic.percentile(xs, 50) if xs else None
            half = args.seconds / 2
            print(json.dumps({
                "rate_per_s": rate, "requests": due, "failed": failed,
                "in_flight_at_half": in_flight(half),
                "in_flight_at_three_quarters": in_flight(1.5 * half),
                "in_flight_at_close": in_flight(args.seconds),
                "ttft_median_first_half_s": ttft_median(0, half),
                "ttft_median_second_half_s": ttft_median(half, args.seconds),
                "drained_s": drained, **metrics}), flush=True)
    finally:
        server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
