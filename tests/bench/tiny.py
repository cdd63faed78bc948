"""A copy of the benchmark's layout at a size the CPU runs in seconds:
the same cells, drivers and metrics, with the configurations shrunk
(by default a road network of 10 x 10 intersections, a scale-6
Kronecker graph, batches of 8). The copy also holds the cells that
wait outside BENCHMARK.json (PERF.md, Open questions): the Graph500
build, and the open-loop serving cell slowed to what the CPU serves,
so that their drivers and metrics stay exercised."""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


#: the open-loop serving cell, its end-to-end and per-layer metrics,
#: as BENCHMARK.json held them before the cell was taken out to wait
#: for its measurement on the road network
OPEN_CELL = {"name": "road-qlsn-open", "config": "road-dimacs-ny40k",
             "traffic": "qlsn-open-uniform", "chips": 1,
             "why": "open-loop Poisson uniform pairs through QueryService "
                    "and the store query; no sweep"}
OPEN_E2E = {"name": "query_p50_ms", "unit": "ms", "better": "lower",
            "bound": 0.11, "source": "host_clock",
            "workloads": ["road-qlsn-open"]}
OPEN_LAYER = [
    {"name": name, "unit": unit, "better": better, "source": source,
     "layer": layer, "moves": "query_p50_ms", "workloads": ["road-qlsn-open"]}
    for name, unit, better, source, layer in [
        ("query_p99_ms.open", "ms", "lower", "host_clock", "service"),
        ("gen_late_p99_ms.open", "ms", "lower", "host_clock",
         "load generator"),
        ("batch_fill.open", "%", "higher", "program_counter", "service"),
        ("queue_wait_p99_ms.open", "ms", "lower", "program_span",
         "service"),
        ("query_device_ms.open", "ms", "lower", "device_trace",
         "store query"),
        ("device_idle_share.open", "%", "lower", "device_trace", "device")]]


#: the Graph500 build cell, which waits outside BENCHMARK.json for the
#: program change that ends its stalls (PERF.md, Open questions); it
#: shares road-plant's metrics, whose readers it keeps exercised
KRON_PLANT_CELL = {"name": "kron-plant", "config": "kron-g500-s12",
                   "traffic": "plant-stratified", "chips": 1,
                   "why": "PLaNT build of the scale-12 Kronecker graph, "
                          "three whole builds: a few wide padded sweeps"}


def layout(tmp: str, *, road_side: int = 10, kron_scale: int = 6,
           rate_qps: float = 200.0) -> str:
    """``tmp``/checkout: the benchmark's files with tiny configurations;
    ``src`` links to the program. Returns the layout's root."""
    tiny_graphs = {
        "road-dimacs-ny40k": {"rows": road_side, "cols": road_side},
        "kron-g500-s12": {"scale": kron_scale},
    }
    root = os.path.join(tmp, "checkout")
    os.makedirs(root)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if not any(w["name"] == "kron-plant" for w in bench["workloads"]):
        bench["workloads"].append(KRON_PLANT_CELL)
        for m in bench["end_to_end"] + bench["per_layer"]:
            if "road-plant" in m.get("workloads", []):
                m["workloads"].append("kron-plant")
    bench["workloads"].append(OPEN_CELL)
    bench["end_to_end"].append(OPEN_E2E)
    bench["per_layer"] += OPEN_LAYER
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    shutil.copytree(os.path.join(REPO, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    for name, graph in tiny_graphs.items():
        path = os.path.join(root, "bench", "configs", name + ".json")
        with open(path) as f:
            cfg = json.load(f)
        cfg["graph"].update(graph)
        cfg["plan"]["batch"] = 8
        cfg["plant_superstep_s"] = 0.01
        with open(path, "w") as f:
            json.dump(cfg, f)
    path = os.path.join(root, "bench", "traffic", "qlsn-open-uniform.json")
    with open(path) as f:
        traffic = json.load(f)
    traffic.update(rate_qps=rate_qps)
    with open(path, "w") as f:
        json.dump(traffic, f)
    return root


def run(root: str, cell: str, seed: int = 7, seconds: float = 1.0,
        trace: bool = False) -> dict:
    """One run of ``cell`` on the CPU, past the harness's look for a
    chip, as ``bench/run.py`` would make it on the chip."""
    from bench.harness import run_cell
    return run_cell(root, cell, seed, seconds, trace, require_chip=False,
                    compile_cache=False, log=lambda msg: None)
