"""Share of the K,V rows that the block tables hand to a decode launch
which hold live context: 100 * live_rows / table_rows over the decode
launches of the ticks that ran inside the profiler session. `live_rows`
sums the context lengths of the active slots over the launch's token steps;
`table_rows` is slots x table width x block size x token steps — the
scheduler's geometry, which the paged kernel's grid walks today."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    decode = acc["programs"].get("decode_step")
    if not decode:
        raise LookupError(f"no decode launch among the profiled ticks: "
                          f"{sorted(acc['programs'])}")
    log(f"decode_step: {decode['launches']} launches, {decode['live_rows']} "
        f"live of {decode['table_rows']} table rows")
    return counters.share(decode["live_rows"], decode["table_rows"])
