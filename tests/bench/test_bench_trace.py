"""The reduction from a profiler trace to per-layer metrics, on a
synthetic trace and on a small trace recorded on a TPU v5 lite
(``record_trace.py``: two PLaNT supersteps of a 32 x 32 grid, five
64 x 64 distance tables, a few dozen served queries)."""

from __future__ import annotations

import json
import os
import types

import pytest

from bench import harness
from bench.peaks import peaks
from bench.tracing import Trace, module_name, union_ns

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_module_names():
    assert union_ns([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert union_ns([]) == []
    assert module_name("jit_plant_batch(1234)") == "jit_plant_batch"
    assert module_name("jit__lambda") == "jit__lambda"


def _synthetic():
    # window 0..100 ns; device busy 10..30 and 50..60 (ops overlap)
    mods = [[("jit_a(1)", 10, 30), ("jit_b(2)", 50, 60),
             ("jit_a(1)", 120, 130)]]
    ops = [[("fusion.1", 10, 25), ("fusion.2", 20, 30),
            ("copy", 50, 60), ("late", 120, 130)]]
    host = [("bench.query", 30, 50), ("wait", 62, 99)]
    return Trace((0, 100), mods, ops, host)


def test_synthetic_busy_idle_modules_and_gaps():
    tr = _synthetic()
    assert tr.window_s() == pytest.approx(100e-9)
    assert tr.busy_s() == pytest.approx(30e-9)
    assert tr.idle_pct() == pytest.approx(70.0)
    assert tr.module_names() == {"jit_a": pytest.approx(20e-9),
                                 "jit_b": pytest.approx(10e-9)}
    assert tr.module_launches(["jit_a"]) == 1        # one is outside
    assert tr.module_launches(["jit_*"]) == 2
    assert tr.top_ops(2) == [["fusion.1", pytest.approx(15e-9)],
                             ["fusion.2", pytest.approx(10e-9)]]
    gaps = tr.idle_gaps()
    assert [g[0] for g in gaps] == ["wait", "bench.query", "host idle"]
    assert [round(g[1] * 1e9) for g in gaps] == [40, 20, 10]
    # only launches wholly inside the window are timed
    assert tr.launch_s(["jit_a", "jit_b"]) == [pytest.approx(20e-9),
                                               pytest.approx(10e-9)]
    # the steady part starts at the first launch named
    st = tr.steady(["jit_b"])
    assert st.window == (50, 100)
    assert st.idle_pct() == pytest.approx(80.0)
    assert tr.steady(["jit_none"]).window == tr.window


def _recorded():
    path = os.path.join(DATA, "small.xplane.pb")
    with open(os.path.join(DATA, "small.json")) as f:
        facts = json.load(f)
    return Trace.from_file(path), facts


def test_recorded_trace_reduces_to_layers():
    tr, facts = _recorded()
    assert tr.has_device()
    assert 0 < tr.busy_s() <= tr.window_s()
    assert 0 <= tr.idle_pct() < 100
    mods = tr.module_names()
    assert mods["jit_plant_batch"] > 0 and mods["jit_query_pairs"] > 0
    assert tr.module_launches(["jit_plant_batch"]) == facts["supersteps"]
    assert tr.module_launches(["jit_query_pairs"]) == facts["bulk_calls"]
    assert tr.module_launches(["jit__lambda*"]) == facts["service_launches"]
    bd = tr.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    secs = [s for _, s in bd["device_ops"]]
    assert secs == sorted(secs, reverse=True)
    # the bench's own host spans are on the trace's clock
    names = {name for name, _, _ in tr.host}
    assert {"bench.build", "bench.bulk", "bench.open"} <= names


def test_recorded_trace_feeds_the_metric_readers():
    from bench.data import graphs, reference

    tr, facts = _recorded()
    ctx = types.SimpleNamespace(peaks=peaks(facts["device_kind"]))
    arcs = reference.arcs(graphs.grid_road(32, 32, seed=0))
    record = {"batch": facts["trees"] // facts["supersteps"], "n": arcs.n,
              "arcs": len(arcs.tail),
              "traced_calls": facts["bulk_calls"],
              "traced_bytes": facts["bulk_calls"] * 4096 * 100}
    read = {}
    for name in ("plant_device_ms", "plant_batch_roofline",
                 "insert_device_ms", "device_idle_share.build",
                 "query_device_ms.bulk", "query_pairs_roofline",
                 "query_device_ms.open"):
        mod = harness.load_module(os.path.join(
            harness.__file__.rsplit(os.sep, 1)[0], "metrics",
            name + ".py"), "metric")
        read[name] = mod.read(record, tr, ctx)
    assert all(v is not None and v > 0 for v in read.values()), read
    assert read["plant_batch_roofline"] <= 100
    assert read["query_pairs_roofline"] <= 100
    assert read["device_idle_share.build"] < 100
    # a reader with nothing to read says nothing, never 0
    mod = harness.load_module(os.path.join(
        harness.__file__.rsplit(os.sep, 1)[0], "metrics",
        "plant_batch_roofline.py"), "metric")
    assert mod.read({}, tr, ctx) is None
    assert mod.read(record, tr, types.SimpleNamespace(peaks=None)) is None
