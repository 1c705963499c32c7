#!/usr/bin/env python3
"""What the serving host was doing while the device sat idle.

    python tools/trace_gaps.py <trace dir or .xplane.pb> [--min-ms 1]

Reads a `jax.profiler` capture of a serving run. Every stretch of more than
`--min-ms` with no op on the device's `XLA Ops` line, inside the host
annotation `benchmark_traced_window` (the whole capture where there is
none), is split among the tick thread's spans (`serve.*`, `generate.*`:
`profiler.RecordEvent` ranges, on `/host:CPU` of the same xplane and so on
the device ops' clock): each instant of a gap goes to the INNERMOST span
open at it, so the rows add up to the idle time. Prints a table by span
name and the gaps' count and lengths, then the spans that lie inside the
window by name: how many, how long."""
import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import trace as T  # noqa: E402

SPAN_PREFIXES = ("serve.", "generate.")
NO_SPAN = "(no span)"


def idle_gaps(ops, window, min_ns):
    """[(start, end)] of the stretches of `window` longer than `min_ns`
    that no interval of `ops` [(start, end)] covers."""
    lo, hi = window
    gaps, end = [], lo
    for a, b in sorted(ops):
        if a > end:
            gaps.append((end, min(a, hi)))
        end = max(end, b)
        if end >= hi:
            break
    if hi > end:
        gaps.append((end, hi))
    return [(a, b) for a, b in gaps if b - a > min_ns]


def innermost(gap, spans):
    """{name: ns} of one gap: each instant to the innermost of `spans`
    [(name, start, end)] open at it (spans of one thread nest)."""
    lo, hi = gap
    inside = [(n, max(a, lo), min(b, hi)) for n, a, b in spans
              if a < hi and b > lo]
    cuts = sorted({lo, hi} | {t for _, a, b in inside for t in (a, b)})
    out = {}
    for a, b in zip(cuts, cuts[1:]):
        open_ = [(sb - sa, n) for n, sa, sb in inside if sa <= a and b <= sb]
        name = min(open_)[1] if open_ else NO_SPAN
        out[name] = out.get(name, 0.0) + (b - a)
    return out


def by_span(gaps, spans):
    """{name: [ns over all gaps, gaps it appears in]}."""
    total = {}
    for gap in gaps:
        for name, ns in innermost(gap, spans).items():
            row = total.setdefault(name, [0.0, 0])
            row[0] += ns
            row[1] += 1
    return total


def spans_in(window, spans):
    """{name: [count, ns]} of the spans that lie wholly inside the window."""
    agg = {}
    for name, a, b in spans:
        if a >= window[0] and b <= window[1]:
            row = agg.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += b - a
    return agg


def read(path):
    """(device op intervals, window, tick-thread spans) of a capture."""
    import jax

    data = jax.profiler.ProfileData.from_file(T.find_xplane(path))
    ops, spans, window = [], [], None
    for plane in data.planes:
        device = plane.name.startswith(T.DEVICE_PLANE_PREFIX)
        if device and plane.name != T.DEVICE_PLANE_PREFIX + "0":
            continue
        for line in plane.lines:
            if device and line.name == T.OP_LINE:
                ops = [(e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
            elif plane.name == "/host:CPU":
                for e in line.events:
                    if e.name == T.WINDOW_ANNOTATION:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns))
    if not ops:
        raise LookupError(f"no {T.OP_LINE!r} line on "
                          f"{T.DEVICE_PLANE_PREFIX}0 in {path}")
    if window is None:
        window = (min(a for a, _ in ops), max(b for _, b in ops))
    return ops, window, spans


def report(ops, window, spans, min_ms=1.0, out=sys.stdout):
    gaps = idle_gaps(ops, window, min_ms * 1e6)
    idle = sum(b - a for a, b in gaps)
    lengths = sorted((b - a) / 1e6 for a, b in gaps)
    print(f"window {(window[1] - window[0]) / 1e9:.3f}s, {len(gaps)} idle "
          f"gaps over {min_ms} ms: {idle / 1e9:.4f}s in all"
          + (f", median {lengths[len(lengths) // 2]:.2f} ms, longest "
             f"{lengths[-1]:.2f} ms" if gaps else ""), file=out)
    rows = sorted(by_span(gaps, spans).items(), key=lambda kv: -kv[1][0])
    print(f"{'span (innermost open)':32s} {'idle s':>9s} {'share':>7s} "
          f"{'ms a gap':>9s} {'gaps':>5s}", file=out)
    for name, (ns, n) in rows:
        print(f"{name:32s} {ns / 1e9:9.4f} {100 * ns / idle:6.1f}% "
              f"{ns / 1e6 / len(gaps):9.3f} {n:5d}", file=out)
    print(f"\n{'span inside the window':32s} {'count':>5s} {'total s':>9s} "
          f"{'mean ms':>10s}", file=out)
    for name, (n, ns) in sorted(spans_in(window, spans).items(),
                                key=lambda kv: -kv[1][1]):
        print(f"{name:32s} {n:5d} {ns / 1e9:9.4f} {ns / 1e6 / n:10.3f}",
              file=out)
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace")
    ap.add_argument("--min-ms", type=float, default=1.0)
    args = ap.parse_args(argv)
    report(*read(args.trace), min_ms=args.min_ms)
    return 0


if __name__ == "__main__":
    sys.exit(main())
