"""Service: the 99th percentile of query latency (due to answered) in
the traced run. It is not an end-to-end metric: at 64,000 q/s it is
set by pauses of Python's garbage collector over the service's
unbounded ticket list and swung from 169 to 524 ms between runs of one
seed (PERF.md), wider than any bound can hold."""


def read(record, trace, ctx):
    return record["metrics"].get("query_p99_ms")
