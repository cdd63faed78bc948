"""The harness: the contract of BENCHMARK.json, finding cells by name,
refusing a machine without a chip, and whole runs on the CPU at a
tiny size, sound and with the timed path broken underneath."""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

import jax.numpy as jnp
import pytest

import tiny
from bench import harness

REPO = tiny.REPO
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _bench():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_benchmark_json_keeps_the_contract():
    b = _bench()
    assert list(b) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert 1 <= b["run_seconds"] <= 51
    for p in b["paths"]:
        assert re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p)
        assert os.path.isdir(os.path.join(REPO, p))
    assert not any(w.startswith("/") or ".." in w for w in b["command"])
    names = set()
    for c in b["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and all(NAME.match(k)
                                             for k in c["reduced"])
        assert c["file"].startswith("bench/configs/")
        with open(os.path.join(REPO, c["file"])) as f:
            cfg = json.load(f)
        assert all(k in cfg for k in c["reduced"])
        names.add(c["name"])
    used, pairs = set(), set()
    for w in b["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and w["config"] in names
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        used.add(w["config"])
        assert os.path.isfile(os.path.join(
            REPO, "bench", "traffic", w["traffic"] + ".json"))
    assert used == names
    cells = {w["name"] for w in b["workloads"]}
    metrics = [m["name"] for m in b["end_to_end"] + b["per_layer"]]
    assert len(metrics) == len(set(metrics))
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in b["end_to_end"])
    e2e = {m["name"]: m for m in b["end_to_end"]}
    for m in b["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = set()
    for m in b["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        for cell in m["workloads"]:
            assert cell in cells
            assert cell in e2e[m["moves"]].get("workloads", [cell])
        assert os.path.isfile(os.path.join(REPO, "bench", "metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
        layers.add(m["layer"])
    for cell in cells:
        e, layer = harness.cell_metrics(b, cell)
        assert "setup_s" in {m["name"] for m in e} and len(e) >= 2
        assert layer
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.\-]{1,16}$", m["unit"])
    assert len(json.dumps(b)) < 64 * 1024


def test_new_config_traffic_and_metric_are_found_by_name(tmp_path):
    """A later change adds a cell by adding files and an entry, never
    by editing an existing file: the harness finds all three."""
    root = tiny.layout(str(tmp_path))
    before = harness.listing(root)
    with open(os.path.join(root, "bench", "configs",
                           "road-dimacs-ny40k.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "road-small"
    cfg["graph"]["rows"] = cfg["graph"]["cols"] = 6
    with open(os.path.join(root, "bench", "configs", "road-small.json"),
              "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(root, "bench", "traffic",
                           "bulk-narrow.json"), "w") as f:
        json.dump({"driver": "bulk", "side": 4, "check_tables": 2,
                   "trace_seconds": 0.2}, f)
    with open(os.path.join(root, "bench", "metrics",
                           "calls_per_window.bulk.py"), "w") as f:
        f.write("def read(record, trace, ctx):\n"
                "    return record.get('calls')\n")
    b = harness.load_benchmark(root)
    b["configs"].append({"name": "road-small", "source": "x",
                         "file": "bench/configs/road-small.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "road-bulk", "config": "road-small",
                           "traffic": "bulk-narrow", "chips": 1,
                           "why": "test"})
    next(m for m in b["end_to_end"]
         if m["name"] == "bulk_queries_per_s")["workloads"].append("road-bulk")
    b["per_layer"].append({"name": "calls_per_window.bulk", "unit": "calls",
                           "better": "higher", "source": "host_clock",
                           "layer": "load generator",
                           "moves": "bulk_queries_per_s",
                           "workloads": ["road-bulk"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)

    after = harness.listing(root)
    assert set(after["configs"]) - set(before["configs"]) == {"road-small"}
    assert set(after["traffic"]) - set(before["traffic"]) == {"bulk-narrow"}
    assert (set(after["metrics"]) - set(before["metrics"])
            == {"calls_per_window.bulk"})
    out = tiny.run(root, "road-bulk", seconds=0.3)
    assert out["correct"] and set(out["metrics"]) == {"bulk_queries_per_s",
                                                      "setup_s"}
    out = tiny.run(root, "road-bulk", seconds=0.5, trace=True)
    assert out["correct"] and out["metrics"]["calls_per_window.bulk"][
        "value"] > 0


def test_names_outside_the_alphabet_are_refused(tmp_path):
    root = tiny.layout(str(tmp_path))
    with pytest.raises(ValueError):
        harness.check_name("road plant", "workload")
    for bad in ("a/b", "a,b", "", "-x", "x" * 65, "café"):
        with pytest.raises(ValueError):
            harness.check_name(bad, "config")
    open(os.path.join(root, "bench", "traffic", "two words.json"),
         "w").write("{}")
    with pytest.raises(ValueError):
        harness.listing(root)
    b = harness.load_benchmark(root)
    b["workloads"][0]["traffic"] = "two words"
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    with pytest.raises(ValueError):
        harness.resolve(root, b["workloads"][0]["name"])


def test_compile_timer_counts_lowerings_compiles_and_cache_hits():
    t = harness.CompileTimer()
    t(t.LOWER, 0.25)
    t(t.COMPILE, 2.0)
    t("/jax/other", 9.0)
    t.event(t.HIT)
    t.event("/jax/other")
    assert (t.count, t.compiles, t.hits, t.seconds) == (1, 1, 1, 2.0)


@pytest.mark.parametrize("runtime", [None, {"max_inflight_computations": 192}])
def test_runtime_options_of_the_configuration_reach_jax(runtime):
    class Config:
        def __init__(self):
            self.updates = []

        def update(self, name, value):
            self.updates.append((name, value))

    class Jax:
        __version__ = "0.0"
        config = Config()

    config = {} if runtime is None else {"runtime": runtime}
    harness.apply_runtime(Jax, config)
    if runtime is None:
        assert Jax.config.updates == []
    else:
        [(name, value)] = Jax.config.updates
        assert name == "jax_pjrt_client_create_options"
        assert value["max_inflight_computations"] == 192
        assert value["ml_framework_name"] == "JAX"


def test_run_without_a_chip_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(REPO, "bench", "run.py"),
         "--workload", "road-plant", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert "no accelerator" in p.stderr
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


def test_run_with_only_the_benchmark_files_exits_nonzero(tmp_path):
    """A checkout of BENCHMARK.json and the benchmark's paths alone
    holds no program to measure."""
    import shutil
    root = tmp_path / "bare"
    for p in _bench()["paths"]:
        shutil.copytree(os.path.join(REPO, p), str(root / p))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), str(root))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "road-plant",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, cwd=str(root), timeout=300)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]


# ------------------------------------------------------ whole runs

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.layout(str(tmp_path_factory.mktemp("bench")),
                       rate_qps=2000.0)


CELLS = ["road-plant", "kron-plant", "road-qlsn-open", "kron-query-bulk"]


@pytest.mark.parametrize("trace", [False, True])
@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(root, cell, trace):
    out = tiny.run(root, cell, seed=2**35 + 11, seconds=0.6, trace=trace)
    assert list(out)[-1] == "checks"
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    e2e, layer = harness.cell_metrics(harness.load_benchmark(root), cell)
    if trace:
        assert set(out["metrics"]) <= {m["name"] for m in layer}
        assert {"busy_s", "window_s"} <= set(out["device"])
        assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(out["metrics"]) == {m["name"] for m in e2e}
        assert all(m["value"] > 0 for m in out["metrics"].values())
    assert all(c["value"] <= c["limit"] for c in out["checks"].values())


def _plant_fault(monkeypatch, kind):
    import repro.core.plant as plant
    from repro.engine.sink import DenseSink

    if kind == "state_unchanged":
        monkeypatch.setattr(DenseSink, "insert", lambda self, *a, **k: None)
        return
    orig = plant.plant_batch

    def broken(ell_src, ell_w, rank, roots, valid, *a, **k):
        if kind == "half_batch":
            valid = valid & (jnp.arange(valid.shape[0]) % 2 == 0)
        tb = orig(ell_src, ell_w, rank, roots, valid, *a, **k)
        if kind == "answer_altered":
            tb = tb._replace(dist=tb.dist.at[jnp.arange(roots.shape[0]),
                                             roots].add(1.0))
        return tb
    monkeypatch.setattr(plant, "plant_batch", broken)


def _query_fault(monkeypatch, kind):
    """Break the answers where they are produced: the service's answer
    function, and the store's query behind ``CHLIndex.query``."""
    import numpy as np

    from repro.index.store.dense import DenseStore
    from repro.serve import backends

    def alter(d):
        d = jnp.asarray(d)
        if kind == "answer_altered":
            return d.at[0].add(1.0)
        # every other answer left out
        return jnp.where(jnp.arange(d.shape[0]) % 2 == 0, d, jnp.inf)

    make = backends.make_answer_fn

    def broken_make(*a, **k):
        fn = make(*a, **k)
        return lambda u, v: alter(fn(u, v))
    monkeypatch.setattr(backends, "make_answer_fn", broken_make)
    query = DenseStore.query

    def broken_query(self, u, v):
        d, h = query(self, u, v)
        return np.asarray(alter(d)), h
    monkeypatch.setattr(DenseStore, "query", broken_query)


@pytest.mark.parametrize("cell,kind", [
    ("road-plant", "state_unchanged"), ("road-plant", "half_batch"),
    ("road-plant", "answer_altered"), ("kron-plant", "state_unchanged"),
    ("kron-plant", "half_batch"), ("kron-plant", "answer_altered"),
    ("road-qlsn-open", "answer_altered"), ("road-qlsn-open", "half_batch"),
    ("kron-query-bulk", "answer_altered"),
    ("kron-query-bulk", "half_batch")])
def test_broken_timed_path_is_not_correct(root, monkeypatch, cell, kind):
    if "plant" in cell:
        _plant_fault(monkeypatch, kind)
    else:
        _query_fault(monkeypatch, kind)
    out = tiny.run(root, cell, seed=2**34 + 5, seconds=0.6)
    assert not out["correct"]
    assert any(c["value"] > c["limit"] for c in out["checks"].values())
