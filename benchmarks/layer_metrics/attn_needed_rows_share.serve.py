"""Share of the (query, cache row) pairs the attention went over that its
queries needed: 100 * attn_rows_needed / attn_rows_read over every launch of
the ticks that ran inside the profiler session. Needed: min(context,
index_topk) rows a real query on a full layer, min(context, window) on a
window layer. Read: what each query the programs carry is attended over: in
a chunk launch the whole table span of its slot (the selection is a mask)
and the whole ring, every position of the two lanes; in a decode step the
gathered rows and the ring, every slot. Returns nothing where the program
counts no rows."""
from benchmarks.harness import counters
from benchmarks.harness.job import log


def read(view):
    acc = counters.profiled()
    needed = read_rows = 0
    for name, p in sorted(acc["programs"].items()):
        if not p.get("attn_rows_read"):
            continue
        needed += p["attn_rows_needed"]
        read_rows += p["attn_rows_read"]
        log(f"{name}: {p['attn_rows_needed']} rows needed of "
            f"{p['attn_rows_read']} read, indexer scored "
            f"{p.get('indexer_rows_scored', 0)} query-key pairs")
    if not read_rows:
        return None
    return counters.share(needed, read_rows)
