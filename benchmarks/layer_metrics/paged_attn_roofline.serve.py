"""The paged attention kernel against its roofline. The operations and the
K,V bytes are what the ALGORITHM needs, worked out from the benchmark's own
request records, so the count does not depend on what implements the kernel:
every output token reads its valid context rows once, every prompt's rows are
read at least once, and each query-key pair costs 4 * head_dim * heads
operations a layer. Over the time the device spent in `_paged_kernel`, the
step programs' only Mosaic call: the reader fails where it finds another
number of them (see harness/trace.py)."""
from benchmarks.harness import trace
from benchmarks.harness.peaks import roofline_seconds
from benchmarks.harness.job import log


def read(view):
    rows = view.records.get("kv_rows")
    if not rows:
        return None
    # one call a layer in each of the two step programs, of which a short
    # window may hold only one
    layers = view.cfg["n_layer"]
    seconds, calls = trace.mosaic_calls(view.events[0], (layers, 2 * layers))
    if not calls:
        return None
    nbytes = rows * view.family.counts.kv_bytes_per_row(view.cfg)
    least, bound = roofline_seconds(view.records["attention_flops"], nbytes,
                                    view.peaks)
    log(f"paged kernel: {calls} calls, {seconds:.4f}s on the "
        f"device, least {least:.4f}s ({bound}-bound)")
    return 100.0 * least / seconds
