"""Device: share of the traced window in which the device idled while
no ``index.query`` span of the program was open on the host: the
caller's own work between calls (drawing the next table, keeping
answers), the benchmark's loop, and the runtime outside the program
(`bench.spans`)."""

from bench import spans


def read(record, trace, ctx):
    sp = spans.read(trace, ctx)
    if sp is None or not sp.named("index.query"):
        return None
    return sp.idle_pct_outside("index.query")
