"""Store query: share of the traced window in which the device idled
while the host was in the program's ``store.put`` span, the upload of
a table's sources and targets in ``DenseStore.query``
(`bench.spans`)."""

from bench import spans


def read(record, trace, ctx):
    sp = spans.read(trace, ctx)
    if sp is None or not sp.named("store.put"):
        return None
    return sp.idle_pct_in("store.put")
