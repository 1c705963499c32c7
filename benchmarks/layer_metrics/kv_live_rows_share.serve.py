"""Share of the K,V rows that the block tables hand to a decode launch
which hold live context: 100 * live_rows / table_rows over the decode
launches of the ticks that ran inside the profiler session. `live_rows`
sums the context lengths of the active slots over the launch's token steps;
`table_rows` is slots x table width x block size x token steps: the
scheduler's geometry, what a kernel that ignored the lengths would walk (and
what the paged kernel's grid did walk until PR 27). Since PR 27 the kernel
walks each active slot's length rounded up to its block of pages, which the
ledger counts as `walked_rows`: logged beside the share, `live / walked`
being the kernel's own efficiency and `walked / table` what following the
lengths saves."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    decode = acc["programs"].get("decode_step")
    if not decode:
        raise LookupError(f"no decode launch among the profiled ticks: "
                          f"{sorted(acc['programs'])}")
    walked = decode.get("walked_rows")
    log(f"decode_step: {decode['launches']} launches, {decode['live_rows']} "
        f"live of {decode['table_rows']} table rows, {walked} walked"
        + (f" (live / walked {counters.share(decode['live_rows'], walked):.2f}"
           f"%, walked / table "
           f"{counters.share(walked, decode['table_rows']):.2f}%)"
           if walked else ""))
    return counters.share(decode["live_rows"], decode["table_rows"])
