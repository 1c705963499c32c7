"""The program's own tick counters, as the per-layer readers of source
`program_counter` see them. Beside `program.py` the one module that touches
the program: it reads `paddle_tpu.observability.utilization.ledgers()`, the
tick ledgers of the continuous schedulers, which stay readable after their
server was closed and freed.

A traced run's numbers are those of the ticks that ran wholly inside the
profiler session (`snapshot()["profiled"]`): the program clips them itself,
so no clock has to be aligned with the trace's. The session opens a little
before the window's annotation and closes a little after it, so the
profiled ticks may reach a tick past either end of the window."""


def profiled():
    """The `profiled` account of the one ledger that has profiled ticks;
    raises, naming what it found, where none or several hold such ticks."""
    from paddle_tpu.observability import utilization

    accounts = [led.snapshot()["profiled"] for led in utilization.ledgers()]
    hit = [a for a in accounts if a["ticks"]]
    if len(hit) != 1:
        raise LookupError(
            f"{len(hit)} of the program's {len(accounts)} tick ledgers hold "
            f"ticks that ran inside a profiler session, where one server "
            f"under one traced window makes exactly one (ticks of each: "
            f"{[a['ticks'] for a in accounts]})")
    return hit[0]


def share(part, whole):
    """100 * part / whole; the whole must be there."""
    if not whole:
        raise ZeroDivisionError("the profiled ticks counted none of the "
                                "quantity this share is of")
    return 100.0 * part / whole
