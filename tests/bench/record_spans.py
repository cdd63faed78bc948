"""Record the profiler trace that ``test_bench_spans.py`` reads.

    python tests/bench/record_spans.py [out_dir]   # on a machine with a TPU

Under the benchmark's own tracer it plants three batches of 64 roots of
a 32 x 32 road grid through the engine (``repro.engine.run_build``),
then answers five 64 x 64 distance tables through ``CHLIndex.query``,
and writes the trace to ``<out_dir>/spans.xplane.pb`` (default
``tests/bench/data``). ``spans.json`` holds the counts the program's
spans must show there (supersteps, ``run_build`` calls, query calls),
the six span metrics as ``bench/metrics`` read the trace when it was
recorded, and what one span costs on this host (``span_cost_us``:
``jax.profiler.TraceAnnotation`` and ``StepTraceAnnotation``, each
entered and left, with the profiler off and on, less the bare loop).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time
import types

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]

METRICS = ("build_init_ms", "programs_per_superstep", "sink_device_ms",
           "put_idle_share.bulk", "fetch_idle_share.bulk",
           "caller_idle_share.bulk")


def span_cost_us(n: int) -> dict:
    """Microseconds one span adds, entered and left ``n`` times."""
    import jax

    def per_call(make) -> float:
        t0 = time.perf_counter()
        for i in range(n):
            with make(i):
                pass
        return (time.perf_counter() - t0) / n

    def bare() -> float:
        t0 = time.perf_counter()
        for i in range(n):
            pass
        return (time.perf_counter() - t0) / n

    kinds = {"TraceAnnotation": lambda i: jax.profiler.TraceAnnotation(
                 "bench.cost"),
             "StepTraceAnnotation": lambda i:
                 jax.profiler.StepTraceAnnotation("bench.cost", step_num=i)}
    return {name: 1e6 * (per_call(make) - bare())
            for name, make in kinds.items()}


def main() -> int:
    import jax
    import numpy as np

    from bench import harness, spans
    from bench.data import graphs, samplers
    from bench.tracing import Trace, Tracer
    from repro.engine import run_build
    from repro.graphs.graph import from_edges
    from repro.graphs.ranking import degree_ranking
    from repro.index import BuildPlan, build

    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU", file=sys.stderr)
        return 2
    e = graphs.grid_road(32, 32, seed=0)
    g = from_edges(e.n, e.src, e.dst, e.w)
    rank = degree_ranking(g)
    roots = samplers.systematic_batches(rank, 64, 3,
                                        samplers.rng_of(2, "roots"))
    idx = build(g, rank, BuildPlan(algo="plant", batch=64))
    rng = samplers.rng_of(2, "pairs")
    pool = np.arange(e.n, dtype=np.int32)
    tables = [samplers.block_pairs(pool, 64, rng) for _ in range(5)]
    run_build(g, rank, algo="plant", batch=64, roots_order=roots.ravel())
    idx.query(*tables[0])

    dest = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    os.makedirs(dest, exist_ok=True)
    tracer = Tracer(os.path.join(dest, "profile"))
    with tracer.window(60.0):
        res = run_build(g, rank, algo="plant", batch=64,
                        roots_order=roots.ravel())
        for u, v in tables:
            idx.query(u, v)
    trace = tracer.load()
    ctx = types.SimpleNamespace(tracer=tracer)
    readings = {}
    for name in METRICS:
        mod = harness.load_module(os.path.join(REPO, "bench", "metrics",
                                               name + ".py"), "metric")
        readings[name] = mod.read({}, trace, ctx)
    sp = spans.read(trace, ctx)
    src = spans.trace_path(tracer)
    shutil.copy(src, os.path.join(dest, "spans.xplane.pb"))
    shutil.rmtree(os.path.join(dest, "profile"))

    cost = {"off": span_cost_us(200_000)}
    with tempfile.TemporaryDirectory() as tmp:
        jax.profiler.start_trace(tmp)
        try:
            cost["on"] = span_cost_us(20_000)
        finally:
            jax.profiler.stop_trace()
    facts = {"supersteps": len(res.records),
             "trees": sum(r.trees for r in res.records),
             "build_calls": 1, "query_calls": len(tables),
             "launches": len(sp.launches),
             "idle_pct": Trace.from_file(os.path.join(
                 dest, "spans.xplane.pb")).idle_pct(),
             "readings": readings, "span_cost_us": cost,
             "device_kind": jax.devices()[0].device_kind}
    with open(os.path.join(dest, "spans.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts), os.path.getsize(
        os.path.join(dest, "spans.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
