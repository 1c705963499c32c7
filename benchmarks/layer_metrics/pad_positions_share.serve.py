"""Share of the positions the step programs issued that carried no live
token: 100 * (1 - useful / issued) over every launch of the ticks that ran
inside the profiler session. A prefill chunk issues slots x chunk positions
and a decode tick slots x token steps, whatever is live; useful are the
prompt tokens taken and the output tokens absorbed."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    issued = useful = 0
    for name, p in sorted(acc["programs"].items()):
        issued += p["issued_positions"]
        useful += p["useful_positions"]
        pad = counters.share(p["issued_positions"] - p["useful_positions"],
                             p["issued_positions"])
        log(f"{name}: {p['launches']} launches, {p['useful_positions']} of "
            f"{p['issued_positions']} positions useful, pad {pad:.2f}%")
    return counters.share(issued - useful, issued)
