"""Share of the rows the expert layer's grouped products issued that carried
a token's assignment: 100 * moe_rows_useful / moe_rows_issued over every
launch of the ticks that ran inside the profiler session. The walk issues
whole tiles (sized by the launch's rows: 128 rows a tile in a launch of two
chunks of 1,024, 16 in a decode step), each tile one held expert's, and none
of an expert nobody chose; useful are the (token, held expert) assignments.
Returns nothing where the program counts no expert rows."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    issued = useful = elsewhere = 0
    for name, p in sorted(acc["programs"].items()):
        if not p.get("moe_rows_issued"):
            continue
        issued += p["moe_rows_issued"]
        useful += p["moe_rows_useful"]
        elsewhere += p.get("moe_assignments_elsewhere", 0)
        load = p.get("moe_expert_tokens") or [0]
        log(f"{name}: {p['moe_rows_useful']} of {p['moe_rows_issued']} expert "
            f"rows useful, {p.get('moe_assignments_elsewhere', 0)} "
            f"assignments to experts held elsewhere, busiest held expert "
            f"{max(load) * len(load) / max(sum(load), 1):.2f}x the mean")
    if not issued:
        return None
    return counters.share(useful, issued)
