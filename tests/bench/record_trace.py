"""Record the small profiler trace that ``test_bench_trace.py`` reads.

    python tests/bench/record_trace.py [out_dir]   # on a machine with a TPU

Under the benchmark's own tracer it plants two batches of 64 roots of
a 32 x 32 road grid through the engine (``repro.engine.run_build``),
then answers five 64 x 64 distance tables through ``CHLIndex.query``
and a few dozen queries through the QLSN service, and writes the
trace to ``<out_dir>/small.xplane.pb`` (default ``tests/bench/data``)
with the counts the test checks against (``small.json``). Sizes are
kept small so the file stays a few hundred KB.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [REPO, os.path.join(REPO, "src")]


def main() -> int:
    import jax
    import numpy as np

    from bench.data import graphs, samplers
    from bench.tracing import Tracer
    from repro.engine import run_build
    from repro.graphs.graph import from_edges
    from repro.graphs.ranking import degree_ranking
    from repro.index import BuildPlan, build

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 2
    e = graphs.grid_road(32, 32, seed=0)
    g = from_edges(e.n, e.src, e.dst, e.w)
    rank = degree_ranking(g)
    roots = samplers.systematic_batches(rank, 64, 2,
                                        samplers.rng_of(1, "roots"))
    idx = build(g, rank, BuildPlan(algo="plant", batch=64))
    svc = idx.serve(mode="qlsn")
    svc.warmup(buckets=True)
    rng = samplers.rng_of(1, "pairs")
    pool = np.arange(e.n, dtype=np.int32)
    tables = [samplers.block_pairs(pool, 64, rng) for _ in range(5)]
    run_build(g, rank, algo="plant", batch=64, roots_order=roots.ravel())
    for u, v in tables[:1]:
        idx.query(u, v)

    dest = sys.argv[1] if len(sys.argv) > 1 else os.path.join(HERE, "data")
    tracer = Tracer(os.path.join(dest, "profile"))
    with tracer.window(60.0):
        with tracer.span("bench.build"):
            res = run_build(g, rank, algo="plant", batch=64,
                            roots_order=roots.ravel())
        with tracer.span("bench.bulk"):
            for u, v in tables:
                idx.query(u, v)
        with tracer.span("bench.open"):
            for i in range(40):
                svc.try_submit(int(tables[0][0][i]), int(tables[0][1][i]))
                time.sleep(0.0005)
                svc.pump()
            while svc.queue_depth:
                svc.pump()
    path = sorted(glob.glob(os.path.join(dest, "profile", "plugins",
                                         "profile", "*", "*.xplane.pb")))[-1]
    shutil.copy(path, os.path.join(dest, "small.xplane.pb"))
    shutil.rmtree(os.path.join(dest, "profile"))
    facts = {"supersteps": len(res.records),
             "trees": sum(r.trees for r in res.records),
             "sweeps": sum(r.sweeps for r in res.records),
             "bulk_calls": len(tables), "service_launches":
             svc.stats_.batches, "device_kind": jax.devices()[0].device_kind}
    with open(os.path.join(dest, "small.json"), "w") as f:
        json.dump(facts, f, indent=1)
    print(json.dumps(facts), os.path.getsize(
        os.path.join(dest, "small.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
