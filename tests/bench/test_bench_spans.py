"""The reduction from the program's spans to device launches and idle
time (``bench/spans.py``), on synthetic traces and on traces recorded
on a TPU v5 lite: ``spans.xplane.pb`` (``record_spans.py``: three
PLaNT supersteps of a 32 x 32 grid and five 64 x 64 distance tables,
with the program's spans) and ``small.xplane.pb`` (``record_trace.py``,
a program without them)."""

from __future__ import annotations

import bisect
import json
import os
import shutil
import types

import pytest

from bench import harness, spans
from bench.spans import Dispatch, Launch, Span, Spans, pair_dispatches
from bench.tracing import Trace, Tracer

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
METRICS = ("build_init_ms", "programs_per_superstep", "sink_device_ms",
           "put_idle_share.bulk", "fetch_idle_share.bulk",
           "caller_idle_share.bulk")


def _metric(name):
    return harness.load_module(os.path.join(
        os.path.dirname(harness.__file__), "metrics", name + ".py"),
        "metric")


# ----------------------------------------------------- synthetic

def _dispatches(n):
    return [Dispatch(10.0 * i, 10.0 * i + 5) for i in range(n)]


def test_dispatches_pair_by_anchor_then_by_order():
    ds = _dispatches(6)
    # dispatch 2 enqueued its launch itself (run 12); the others were
    # enqueued later by the runtime
    pair_dispatches(ds, {2: 12}, [10, 11, 12, 13, 14, 15])
    assert [d.run_id for d in ds] == [10, 11, 12, 13, 14, 15]


def test_dispatches_at_the_trace_edges_pair_from_the_anchor():
    # the first dispatch's enqueue fell before the trace began, the
    # last one's after it ended
    ds = _dispatches(5)
    pair_dispatches(ds, {2: 7}, [6, 7, 8])
    assert [d.run_id for d in ds] == [None, 6, 7, 8, None]


def test_dispatches_between_anchors_whose_counts_differ_stay_unpaired():
    ds = _dispatches(5)
    pair_dispatches(ds, {0: 1, 4: 5}, [1, 2, 3, 5])   # run 4 is missing
    assert [d.run_id for d in ds] == [1, None, None, None, 5]


def _synthetic():
    """Window 0..100. Host: a superstep 0..40 holding an insert 20..30,
    then a query 50..90 holding a put 50..55 and a fetch 70..90.
    Device: ops 5..18, 22..28 and 60..75; five launches."""
    ops = [[("a", 5, 18), ("b", 22, 28), ("c", 60, 75)]]
    mods = [[("jit_step(1)", 5, 18), ("jit_ins(2)", 22, 28),
             ("jit_q(3)", 60, 75), ("jit_late(4)", 95, 110),
             ("jit_x(5)", 1, 2)]]
    trace = Trace((0, 100), mods, ops, [])
    sp = [Span("index.query", 50, 90), Span("engine.superstep", 0, 40),
          Span("sink.insert", 20, 30), Span("store.put", 50, 55),
          Span("store.fetch", 70, 90)]
    launches = [Launch(name[:-3], lo, hi, int(name[-2]))
                for name, lo, hi in mods[0]]
    ds = [Dispatch(1, 2, 1), Dispatch(21, 22, 2), Dispatch(56, 57, 3),
          Dispatch(85, 86, 4), Dispatch(45, 46, 5)]
    return Spans(trace, sp, launches, ds)


def test_spans_nest_and_launches_go_to_the_innermost():
    sp = _synthetic()
    names = [s.name for s in sp.spans]
    assert names == ["engine.superstep", "sink.insert", "index.query",
                     "store.put", "store.fetch"]
    assert [s.parent for s in sp.spans] == [None, 0, None, 2, 2]
    owner = {launch.name: (None if launch.span is None
                           else sp.spans[launch.span].name)
             for launch in sp.launches}
    assert owner == {"jit_step": "engine.superstep",
                     "jit_ins": "sink.insert", "jit_q": "index.query",
                     "jit_late": "store.fetch", "jit_x": None}
    # device time clipped to the window, by span: every second counted
    assert sp.launch_s() == {"engine.superstep": pytest.approx(13e-9),
                             "sink.insert": pytest.approx(6e-9),
                             "index.query": pytest.approx(15e-9),
                             "store.fetch": pytest.approx(5e-9),
                             None: pytest.approx(1e-9)}
    assert sum(sp.launch_s().values()) == pytest.approx(
        sum(sp.trace.module_names().values()))


def test_idle_goes_to_the_span_open_on_the_host():
    sp = _synthetic()
    idle = sp.idle_ns()
    # idle: 0..5, 18..20 and 30..40 in the superstep, 20..22 and
    # 28..30 in its insert, 40..50 none, 50..55 put, 55..60 query,
    # 75..90 fetch, 90..100 none
    by_name = {}
    for i, ns in idle.items():
        key = None if i is None else sp.spans[i].name
        by_name[key] = by_name.get(key, 0) + ns
    assert by_name == {"engine.superstep": 17, "sink.insert": 4,
                       "store.put": 5, "index.query": 5,
                       "store.fetch": 15, None: 20}
    assert sum(idle.values()) == pytest.approx(100 - 34)
    assert sp.trace.idle_pct() == pytest.approx(66.0)
    assert sp.idle_pct_in("store.fetch") == pytest.approx(15.0)
    assert sp.idle_pct_in("engine.superstep") == pytest.approx(21.0)
    assert sp.idle_pct_outside("index.query") == pytest.approx(41.0)


def test_whole_spans_leave_out_a_span_whose_launch_left_the_window():
    sp = _synthetic()
    steps = sp.whole_spans("engine.superstep")
    assert [[launch.name for launch in ls] for ls in steps.values()] == [
        ["jit_step", "jit_ins"]]
    assert list(sp.whole_spans("sink.insert").values()) == [
        [sp.launches[1]]]
    # the query's fetch dispatched a launch that ends past the window
    assert sp.whole_spans("index.query") == {}


def test_metrics_say_nothing_without_spans():
    trace = Trace((0, 100), [[("jit_a(1)", 1, 2)]], [[("a", 1, 2)]], [])
    for ctx in (types.SimpleNamespace(tracer=None),
                types.SimpleNamespace(tracer=Tracer(None))):
        for name in METRICS:
            assert _metric(name).read({}, trace, ctx) is None


# ------------------------------------------------------ recorded

def _tracer(tmp_path, name):
    """A tracer whose directory holds the recorded trace where the
    profiler would have written it."""
    where = tmp_path / "plugins" / "profile" / "2026_01_01_00_00_00"
    where.mkdir(parents=True)
    shutil.copy(os.path.join(DATA, name), str(where / name))
    return Tracer(str(tmp_path))


def _read(tmp_path, name="spans.xplane.pb"):
    tracer = _tracer(tmp_path, name)
    trace = tracer.load()
    return trace, spans.Spans.from_file(spans.trace_path(tracer), trace)


def _facts():
    with open(os.path.join(DATA, "spans.json")) as f:
        return json.load(f)


def _flow_run_ids(path):
    """Each ``PJRT_LoadedExecutable_Execute`` dispatch's run id, read
    along the runtime's flow events instead of by order: the dispatch
    holds a ``tpu::System::Execute`` (flow out ``_p``) whose
    ``...=>IssueSequencedEvent`` (flow in ``_c``), on whichever thread,
    holds the ``DoEnqueueProgram`` with the run id."""
    from jax.profiler import ProfileData
    execute, by_flow = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            issued = []
            for e in line.events:
                if e.name == "tpu::System::Execute":
                    execute[e.start_ns] = dict(e.stats)["_p"]
                elif e.name == "tpu::System::Execute=>IssueSequencedEvent":
                    issued.append((e.start_ns, e.end_ns,
                                   dict(e.stats)["_c"]))
                elif e.name == spans.ENQUEUE:
                    for lo, hi, flow in issued:
                        if lo <= e.start_ns <= hi:
                            by_flow[flow] = dict(e.stats)["run_id"]
    return sorted(execute), execute, by_flow


@pytest.mark.parametrize("name", ["spans.xplane.pb", "small.xplane.pb"])
def test_recorded_launches_pair_with_their_dispatch_exactly(tmp_path,
                                                            name):
    trace, sp = _read(tmp_path, name)
    starts, execute, by_flow = _flow_run_ids(os.path.join(DATA, name))
    assert len(sp.dispatches) == len(sp.launches) > 0
    for d in sp.dispatches:
        k = bisect.bisect_left(starts, d.start)
        assert starts[k] <= d.end
        assert d.run_id == by_flow[execute[starts[k]]]
    # every launch is paired, to one dispatch, so to at most one span
    run_ids = [d.run_id for d in sp.dispatches]
    assert sorted(run_ids) == sorted(launch.run_id
                                     for launch in sp.launches)
    assert len(set(run_ids)) == len(run_ids)


def test_recorded_device_seconds_all_counted_once(tmp_path):
    trace, sp = _read(tmp_path)
    by_span = sp.launch_s()
    assert set(by_span) <= set(spans.SPANS) | {None}
    assert by_span["engine.superstep"] > 0 and by_span["sink.insert"] > 0
    assert by_span["index.query"] > 0
    assert sum(by_span.values()) == pytest.approx(
        sum(trace.module_names().values()), rel=1e-12)
    # the tree batch goes to the superstep, the insert's programs to it
    owner = {sp.spans[launch.span].name for launch in sp.launches
             if launch.name == "jit_plant_batch"}
    assert owner == {"engine.superstep"}
    owner = {sp.spans[launch.span].name for launch in sp.launches
             if launch.name == "jit_query_pairs"}
    assert owner == {"index.query"}


def test_recorded_idle_split_sums_to_the_idle_share(tmp_path):
    trace, sp = _read(tmp_path)
    put = sp.idle_pct_in("store.put")
    fetch = sp.idle_pct_in("store.fetch")
    query = sp.idle_pct_in("index.query") - put - fetch
    caller = sp.idle_pct_outside("index.query")
    assert min(put, fetch, query, caller) >= 0
    assert put + fetch + query + caller == pytest.approx(
        trace.idle_pct(), abs=0.01)
    assert trace.idle_pct() == pytest.approx(_facts()["idle_pct"])


def test_recorded_spans_count_the_work_done(tmp_path):
    trace, sp = _read(tmp_path)
    facts = _facts()
    assert len(sp.named("engine.init")) == facts["build_calls"]
    assert len(sp.named("engine.superstep")) == facts["supersteps"]
    assert len(sp.named("sink.insert")) == facts["supersteps"]
    for name in ("index.query", "store.put", "store.fetch"):
        assert len(sp.named(name)) == facts["query_calls"]
    assert len(sp.launches) == facts["launches"]
    # each whole superstep's launches are the dispatches made in it
    whole = sp.whole_spans("engine.superstep")
    assert len(whole) == facts["supersteps"]
    for i, launches in whole.items():
        s = sp.spans[i]
        made = [d for d in sp.dispatches if s.start <= d.start <= s.end]
        assert len(launches) == len(made) > 1


def test_recorded_trace_feeds_the_span_metrics(tmp_path):
    tracer = _tracer(tmp_path, "spans.xplane.pb")
    trace = tracer.load()
    ctx = types.SimpleNamespace(tracer=tracer)
    facts = _facts()
    read = {name: _metric(name).read({}, trace, ctx) for name in METRICS}
    assert read == pytest.approx(facts["readings"], rel=1e-12)
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["programs_per_superstep"] == int(
        read["programs_per_superstep"])
    idle = trace.idle_pct()
    shares = sum(read[n] for n in METRICS[3:])
    assert shares <= idle + 1e-9
    # a trace of a program without spans reads nothing
    tracer = _tracer(tmp_path / "old", "small.xplane.pb")
    ctx = types.SimpleNamespace(tracer=tracer)
    old = tracer.load()
    assert all(_metric(n).read({}, old, ctx) is None for n in METRICS)
