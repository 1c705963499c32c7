"""Share of the tick thread's time in which it was NOT blocked on the
device: 100 * (1 - wait / wall) over the ticks that ran inside the profiler
session. `wall` is the tick loop's time with at least one live slot
(admission included, time parked on an empty queue left out); `wait` is the
read-back of each launch's tokens. Dispatch (the call that enqueues the
program) counts as the host's, and is logged beside the share."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    gap = counters.share(acc["wall_s"] - acc["wait_s"], acc["wall_s"])
    log(f"tick thread over {acc['ticks']} profiled ticks, {acc['launches']} "
        f"launches: wall {acc['wall_s']:.3f}s, blocked on the device "
        f"{acc['wait_s']:.3f}s, dispatch {acc['dispatch_s']:.3f}s = "
        f"{counters.share(acc['dispatch_s'], acc['wall_s']):.2f}% of wall")
    return gap
