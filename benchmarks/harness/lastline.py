"""The one place that builds a run's last line, for plain and traced runs,
and checks it against the contract before anything is printed.

    {"correct": bool, "attempted": int, "failed": int,
     "metrics": {name: {"value": number, "unit": str}, ...},
     "device": {"platform", "kind", "count", "memory_peak_bytes"
                [, "busy_s", "window_s"]},
     ["breakdown": {"device_ops": [[name, s]...], "idle_gaps": [[name, s]...]},]
     "compared": {name: {"value": number, "limit": number}, ...}}

`compared` comes last: each number the comparison with the reference read,
beside its limit."""
import json
import math


class MalformedLine(ValueError):
    """The line would not meet the contract; nothing may be printed."""


def _number(x):
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def expected_metrics(bench, workload, trace):
    """Names and units of the metrics this cell's line must carry: with
    --trace 0 its end-to-end metrics, with --trace 1 its per-layer ones. A
    metric with a `workloads` key belongs to those cells only."""
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" not in m or workload in m["workloads"]:
            out[m["name"]] = m["unit"]
    return out


def validate(line, bench, workload, trace):
    """Raise MalformedLine with the reason, or return the line."""
    def need(cond, why):
        if not cond:
            raise MalformedLine(why)

    need(isinstance(line, dict), "the line is not an object")
    for key in ("correct", "attempted", "failed", "metrics", "device"):
        need(key in line, f"key {key!r} is missing")
    need(isinstance(line["correct"], bool), "correct is not true or false")
    for key in ("attempted", "failed"):
        need(isinstance(line[key], int) and not isinstance(line[key], bool)
             and line[key] >= 0, f"{key} is not a count")
    need(line["failed"] <= line["attempted"], "failed exceeds attempted")
    metrics = line["metrics"]
    need(isinstance(metrics, dict), "metrics is not an object")
    expected = expected_metrics(bench, workload, trace)
    # every metric lists its cells under `workloads`: where a cell is
    # listed the reader finds something to read, so the metric is there
    need(expected, "BENCHMARK.json gives this cell no metric for this mode")
    for name in expected:
        need(name in metrics, f"metric {name!r} is missing")
    for name, m in metrics.items():
        need(name in expected, f"metric {name!r} is not one of this cell's")
        need(isinstance(m, dict) and "value" in m and "unit" in m,
             f"metric {name!r} lacks value or unit")
        need(_number(m["value"]), f"metric {name!r} is not a finite number")
        need(m["unit"] == expected[name],
             f"metric {name!r} has unit {m['unit']!r}, not {expected[name]!r}")
        if m["unit"] == "%" and ("roofline" in name or "mfu" in name):
            need(0 < m["value"] <= 100,
                 f"{name} reads {m['value']}%: a share of a peak lies in "
                 f"(0, 100]")
    device = line["device"]
    need(isinstance(device, dict), "device is not an object")
    for key in ("platform", "kind", "count", "memory_peak_bytes"):
        need(key in device, f"device.{key} is missing")
    need(isinstance(device["platform"], str) and device["platform"],
         "device.platform is empty")
    need(isinstance(device["kind"], str) and device["kind"],
         "device.kind is empty")
    need(isinstance(device["count"], int) and device["count"] >= 1,
         "device.count is not a count")
    need(isinstance(device["memory_peak_bytes"], int)
         and device["memory_peak_bytes"] > 0,
         "device.memory_peak_bytes is not a positive count of bytes")
    if trace:
        for key in ("busy_s", "window_s"):
            need(key in device, f"traced run without device.{key}")
            need(_number(device[key]), f"device.{key} is not a number")
        need(device["busy_s"] > 0, "device.busy_s is 0: no operation ran on "
             "the device inside the traced window")
        need(device["busy_s"] <= device["window_s"],
             f"device.busy_s {device['busy_s']} exceeds window_s "
             f"{device['window_s']}")
    if "breakdown" in line:
        need(trace, "breakdown in an untraced run")
        bd = line["breakdown"]
        need(isinstance(bd, dict) and set(bd) <= {"device_ops", "idle_gaps"},
             "breakdown holds other keys than device_ops and idle_gaps")
        for rows in bd.values():
            need(isinstance(rows, list) and len(rows) <= 10,
                 "a breakdown list has more than 10 entries")
            for row in rows:
                need(isinstance(row, list) and len(row) == 2
                     and isinstance(row[0], str) and _number(row[1]),
                     "a breakdown entry is not [name, seconds]")
    need(list(line)[-1] == "compared", "compared does not come last")
    need(isinstance(line["compared"], dict), "compared is not an object")
    for name, c in line["compared"].items():
        need(isinstance(c, dict) and {"value", "limit"} <= set(c),
             f"compared.{name} lacks value or limit")
    return line


def build(bench, workload, trace, *, correct, attempted, failed, metrics,
          device, compared, breakdown=None):
    """Assemble the line in the contract's order and validate it. `metrics`
    maps name -> value; units come from BENCHMARK.json. A value of None is a
    reader that found nothing: the metric is left out, and validation then
    refuses the line if this cell is one of the metric's own."""
    units = expected_metrics(bench, workload, trace)
    unknown = set(metrics) - set(units)
    if unknown:
        raise MalformedLine(f"metrics {sorted(unknown)} are not this cell's")
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {name: {"value": value, "unit": units[name]}
                    for name, value in metrics.items() if value is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared
    return validate(line, bench, workload, trace)


def dumps(line):
    return json.dumps(line, allow_nan=False)
