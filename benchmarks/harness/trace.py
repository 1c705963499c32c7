"""From a profiler trace to numbers. The trace is read once into a plain
form — planes > lines > events [name, start_ns, duration_ns, stats] — so that
the same reduction runs on the chip's `.xplane.pb` and on the small recorded
trace the tests keep (`benchmarks/testdata/`, the same form as JSON).

  window     the span of the host annotation that the driver put round the
             traced part of the run, on the trace's one clock.
  busy       the UNION of the op intervals on one device's op line, clipped
             to the window — never their sum: ops of one step overlap.
  kernel     summed durations of the Mosaic custom calls on the op line. On
             this stack (jax 0.9.0, libtpu 0.0.34) an op event is named by
             its HLO instruction text and carries no stat with the Pallas
             kernel's own name (`_fwd_kernel`, `_paged_kernel`): a Mosaic
             call is told by `custom_call_target="tpu_custom_call"`, and the
             step programs of both kinds of cell hold no other Mosaic call
             than their attention kernels (chip_smoke.py gates the counts).

`python benchmarks/harness/trace.py <dir or file>` prints what a trace holds:
look at one by hand before trusting a number reduced from it."""
import glob
import gzip
import json
import os
import re
import sys

WINDOW_ANNOTATION = "benchmark_traced_window"
DEVICE_PLANE_PREFIX = "/device:TPU:"
OP_LINE = "XLA Ops"
MOSAIC_TARGET = "tpu_custom_call"
_TARGET = re.compile(r'custom_call_target="([^"]+)"')


def find_xplane(path):
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(
        path, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return found[-1]


def short_event(name, start_ns, duration_ns):
    """An op event is named by its whole HLO instruction, `%fusion.12 = bf16[
    ...] fusion(...), kind=...`: keep the instruction's name and, for a custom
    call, its target."""
    target = _TARGET.search(name)
    head = name.split(" = ", 1)[0].lstrip("%")
    return [head, float(start_ns), float(duration_ns),
            {"custom_call_target": target.group(1)} if target else {}]


def read_xplane(path):
    """Device planes' op lines and the host lines that hold the window's
    annotation; nothing else of the trace is needed."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane.lines:
            if device and line.name == OP_LINE:
                events = [short_event(e.name, e.start_ns, e.duration_ns)
                          for e in line.events]
            elif not device:
                events = [[e.name, float(e.start_ns), float(e.duration_ns), {}]
                          for e in line.events if e.name == WINDOW_ANNOTATION]
            else:
                continue
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def read_json(path):
    """A recorded trace in the plain form, gzipped or not."""
    with (gzip.open if path.endswith(".gz") else open)(path, "rt") as f:
        return json.load(f)


def find_window(trace, annotation=WINDOW_ANNOTATION):
    """(start_ns, end_ns) of the host annotation; the longest, should the
    name occur more than once."""
    best = None
    for plane in trace["planes"]:
        if plane["name"].startswith(DEVICE_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur, _ in line["events"]:
                if name == annotation and (best is None or dur > best[1] - best[0]):
                    best = (start, start + dur)
    if best is None:
        raise LookupError(f"no host event named {annotation!r} in the trace")
    return best


def device_planes(trace):
    return sorted((p for p in trace["planes"]
                   if p["name"].startswith(DEVICE_PLANE_PREFIX)
                   and p["name"][len(DEVICE_PLANE_PREFIX):].isdigit()),
                  key=lambda p: int(p["name"][len(DEVICE_PLANE_PREFIX):]))


def op_events(plane, window=None):
    """Events of the plane's op line, clipped to the window."""
    for line in plane["lines"]:
        if line["name"] == OP_LINE:
            break
    else:
        raise LookupError(f"plane {plane['name']} has no line {OP_LINE!r}: "
                          f"{[l['name'] for l in plane['lines']]}")
    if window is None:
        return list(line["events"])
    lo, hi = window
    out = []
    for name, start, dur, stats in line["events"]:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append([name, a, b - a, stats])
    return out


def union_seconds(events):
    """Length of the union of the events' intervals."""
    total, end = 0.0, None
    for _, start, dur, _ in sorted(events, key=lambda e: e[1]):
        stop = start + dur
        if end is None or start > end:
            total, end = total + dur, stop
        elif stop > end:
            total, end = total + (stop - end), stop
    return total / 1e9


def mosaic_seconds(events):
    """(seconds, calls) of the Mosaic custom calls among the events."""
    seconds, calls = 0.0, 0
    for _, _, dur, stats in events:
        if stats.get("custom_call_target") == MOSAIC_TARGET:
            seconds, calls = seconds + dur / 1e9, calls + 1
    return seconds, calls


def mosaic_calls(events, expected):
    """(seconds, calls) of the Mosaic custom calls among the events, which
    have to be one of the `expected` numbers of different instructions: the
    attention kernels of the step programs and nothing else. The trace tells a Mosaic call by its
    call target alone, so a kernel of another kind in a step program would
    be summed into the attention's time unseen; with it the count of
    instructions differs, and the reader fails instead."""
    names = {name for name, _, _, stats in events
             if stats.get("custom_call_target") == MOSAIC_TARGET}
    if names and len(names) not in expected:
        raise ValueError(
            f"the traced window holds {len(names)} different Mosaic calls "
            f"where the attention kernels make {expected}: another kernel "
            f"runs in the step programs, and this reader would count its "
            f"time as attention's ({sorted(names)[:8]} ...)")
    return mosaic_seconds(events)


def self_seconds(events):
    """(name, stats, seconds) of each event with the time of the events
    nested inside it taken out: a `while` op spans the ops of its body on the
    same line, and counting both would show the loop's time twice."""
    out, open_ = [], []                 # open_: indices into out, innermost last
    for name, start, dur, stats in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and start >= out[open_[-1]][3]:
            open_.pop()
        if open_:
            out[open_[-1]][2] -= dur
        out.append([name, stats, dur, start + dur])
        open_.append(len(out) - 1)
    return [(name, stats, max(0.0, dur) / 1e9) for name, stats, dur, _ in out]


def top_ops(events, n=10):
    """The n groups of ops that took most device time, by their own time
    (nested ops taken out of their parent). A group is an instruction name
    without its number; Mosaic calls are marked."""
    by_name = {}
    for name, stats, seconds in self_seconds(events):
        key = name.rstrip("0123456789").rstrip(".")
        if stats.get("custom_call_target") == MOSAIC_TARGET:
            key += "(mosaic)"
        by_name[key] = by_name.get(key, 0.0) + seconds
    return [[k, v] for k, v in
            sorted(by_name.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events, window, n=10):
    """The longest stretches of the window with no op on the device, named
    by where in the window they fall."""
    lo, hi = window
    gaps, end = [], lo
    for _, start, dur, _ in sorted(events, key=lambda e: e[1]):
        if start > end:
            gaps.append((end, start))
        end = max(end, start + dur)
    if hi > end:
        gaps.append((end, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[f"idle_at_{(a - lo) / 1e9:.3f}s", (b - a) / 1e9]
            for a, b in gaps[:n]]


def reduce(trace, annotation=WINDOW_ANNOTATION):
    """The numbers every traced run needs: the window, busy time averaged
    over the device planes, and the clipped op events of each plane."""
    window = find_window(trace, annotation)
    planes = device_planes(trace)
    if not planes:
        raise LookupError("the trace holds no device plane: "
                          f"{[p['name'] for p in trace['planes']]}")
    per_plane = [op_events(p, window) for p in planes]
    busy = [union_seconds(ev) for ev in per_plane]
    return {"window": window, "window_s": (window[1] - window[0]) / 1e9,
            "busy_s": sum(busy) / len(busy), "events": per_plane}


def summarize(path, out=sys.stdout):
    """What the trace holds, for a reader: planes, lines, counts, the
    commonest events of each line with one example's stats."""
    import jax

    data = jax.profiler.ProfileData.from_file(find_xplane(path))
    for plane in data.planes:
        print(f"PLANE {plane.name!r}", file=out)
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            span = (min(e.start_ns for e in events),
                    max(e.start_ns + e.duration_ns for e in events))
            print(f"  LINE {line.name!r}: {len(events)} events, "
                  f"{span[0]:.0f}..{span[1]:.0f} ns", file=out)
            groups = {}
            for e in events:
                g = groups.setdefault(e.name, [0, 0.0, e])
                g[0] += 1
                g[1] += e.duration_ns
            for name, (count, dur, example) in sorted(
                    groups.items(), key=lambda kv: -kv[1][1])[:25]:
                stats = {k: (v if not isinstance(v, str) else v[:160])
                         for k, v in example.stats}
                print(f"    {dur / 1e6:10.3f} ms x{count:<6} {name[:80]} "
                      f"{stats}", file=out)


if __name__ == "__main__":
    summarize(sys.argv[1])
