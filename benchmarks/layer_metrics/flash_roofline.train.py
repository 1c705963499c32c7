"""The flash attention kernels (forward, dq, dkv and the chunked pair)
against their roofline: the least time the chip could take for the
operations and bytes the algorithm needs at the cell's shape, over the time
the device spent in those kernels in the traced window. The train step's
only Mosaic calls are these kernels, three a layer; the trace names them by
their call target, not by the kernel, so the reader fails where it finds
another number of them (see harness/trace.py). A reader of the GPT-2
cells: the kernel's cost is `families/gpt2/counts.py:flash_train_cost`."""
from benchmarks.harness import trace
from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.job import log


def read(view):
    steps = view.records.get("traced_steps")
    if not steps:
        return None
    seconds, calls = trace.mosaic_calls(view.events[0],
                                        (3 * view.cfg["n_layer"],))
    if not calls:
        return None
    work, nbytes = view.family.counts.flash_train_cost(
        view.cfg, view.records["batch"], view.records["seq"])
    least, bound = roofline_seconds(work, nbytes, view.peaks)
    least *= steps * view.cfg["n_layer"]
    log(f"flash kernels: {calls} calls, {seconds:.4f}s on the "
        f"device, least {least:.4f}s ({bound}-bound)")
    return 100.0 * least / seconds
