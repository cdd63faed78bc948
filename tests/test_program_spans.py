"""The program's own profiler spans: ``jax.profiler`` annotations at the
boundaries of the engine (``engine.init``, ``engine.superstep``), the
label sink (``sink.insert``) and the store query (``index.query``,
``store.put``, ``store.fetch``). The benchmark's reduction
(``bench/spans.py``) reads them by these names and this nesting; with
no profiler running they change no result."""

import glob
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.engine import run_build
from repro.graphs import grid_road
from repro.graphs.ranking import degree_ranking
from repro.index import BuildPlan, build

SPANS = ("engine.init", "engine.superstep", "sink.insert", "index.query",
         "store.put", "store.fetch")


def _graph():
    g = grid_road(5, 5, seed=3)
    return g, degree_ranking(g)


def _profiled(directory, fn):
    """``fn()`` under the profiler; its result and the program's spans
    in the trace, ``(name, start, end, stats)`` by start. The profiler
    stops whatever ``fn`` does, so no later test finds it running."""
    jax.profiler.start_trace(str(directory))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(directory), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    spans = [(e.name, e.start_ns, e.end_ns, dict(e.stats))
             for plane in ProfileData.from_file(path).planes
             for line in plane.lines for e in line.events
             if e.name in SPANS]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def _named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.mark.parametrize("shards", [None, 2])
def test_build_spans_one_superstep_per_record_with_its_insert(tmp_path,
                                                              shards):
    g, rank = _graph()
    calls = lambda: [run_build(g, rank, algo="plant", batch=8,  # noqa: E731
                               streaming_shards=shards)
                     for _ in range(2)]
    results, spans = _profiled(tmp_path, calls)
    inits = _named(spans, "engine.init")
    steps = _named(spans, "engine.superstep")
    inserts = _named(spans, "sink.insert")
    assert len(inits) == len(results)            # one per run_build call
    assert len(steps) == sum(len(r.records) for r in results)
    assert len(inserts) == len(steps)
    # each superstep holds its own insert, and steps follow their init
    for step, insert in zip(steps, inserts):
        assert _inside(insert, step)
    per_call = len(results[0].records)
    for k, init in enumerate(inits):
        mine = steps[k * per_call:(k + 1) * per_call]
        assert all(init[2] <= s[1] for s in mine)
        # the step number is the superstep's place in the schedule
        assert [s[3]["step_num"] for s in mine] == [8 * j for j in
                                                    range(per_call)]


def test_query_spans_put_and_fetch_inside_each_call(tmp_path):
    g, rank = _graph()
    idx = build(g, rank, BuildPlan(algo="plant", batch=8))
    rng = np.random.default_rng(5)
    tables = [(rng.integers(0, g.n, 16), rng.integers(0, g.n, 16))
              for _ in range(3)]
    idx.query(*tables[0])                         # compiled outside
    _, spans = _profiled(tmp_path, lambda: [idx.query(u, v)
                                            for u, v in tables])
    calls = _named(spans, "index.query")
    puts, fetches = _named(spans, "store.put"), _named(spans, "store.fetch")
    assert len(calls) == len(puts) == len(fetches) == len(tables)
    assert not _named(spans, "engine.init")
    for call, put, fetch in zip(calls, puts, fetches):
        assert _inside(put, call) and _inside(fetch, call)
        assert put[2] <= fetch[1]                 # upload, then fetch


def test_spans_change_no_result(tmp_path):
    g, rank = _graph()
    u = np.arange(g.n, dtype=np.int32)
    v = u[::-1].copy()

    def work():
        res = run_build(g, rank, algo="plant", batch=8)
        idx = build(g, rank, BuildPlan(algo="plant", batch=8))
        t = res.sink.table()
        return (np.asarray(t.hubs), np.asarray(t.dist), np.asarray(t.count),
                [r.labels for r in res.records], idx.query_with_hub(u, v))

    plain = work()
    traced, spans = _profiled(tmp_path, work)
    assert spans
    for a, b in zip(plain[:3], traced[:3]):
        np.testing.assert_array_equal(a, b)
    assert plain[3] == traced[3]
    for a, b in zip(plain[4], traced[4]):
        np.testing.assert_array_equal(a, b)
