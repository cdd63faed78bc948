"""Run one cell of the benchmark once and print its result line.

The harness is driven by data. ``BENCHMARK.json`` names each cell's
configuration and traffic; everything that belongs to one of them
sits in a file of its own, found by name:

- a configuration: the ``file`` its ``configs`` entry names
  (``bench/configs/<name>.json``); its ``runtime``, where it has one,
  holds the deployment's PJRT client options;
- a traffic mix: ``bench/traffic/<name>.json``, whose ``driver`` names
  a module ``bench/drivers/<driver>.py``;
- a per-layer metric: ``bench/metrics/<name>.py``, whose ``read(record,
  trace, ctx)`` returns the metric, or ``None`` when it finds nothing
  to read (never 0 in place of a missing share).

A driver module has four functions. ``setup(ctx)`` makes the cell's
state and warms up every shape the window uses. ``window(state, ctx)``
measures for ``ctx.seconds`` and returns the run record: a dict with
``metrics`` (the end-to-end readings), ``attempted`` and ``failed``,
and whatever its per-layer metrics read.
``release(state, record)`` runs once the window has closed and the
memory peak has been read: the driver lets go of the program state the
comparison does not need. ``check(state, record, ctx)`` then compares
the window's results with the plain reference and returns a list of
:class:`Check`.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import math
import os
import re
import sys
import time
from typing import Any, Dict, List, Optional

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell needs."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit: the run
    is correct only while ``value <= limit``."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Context:
    """What a driver sees of its cell and run."""
    root: str
    cell: dict
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    deployment: Any = None     # bench.deploy.Deployment
    tracer: Any = None         # bench.tracing.Tracer
    peaks: Any = None          # bench.peaks.Peaks, or None off the chip
    log: Any = None


def check_name(name: str, what: str) -> str:
    if not isinstance(name, str) or not NAME.match(name):
        raise ValueError(f"{what} name {name!r} is not 1-64 of "
                         "[A-Za-z0-9_.-] starting with a letter, digit "
                         "or _")
    return name


def load_benchmark(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, tag: str):
    """Import a driver or metric file by its path (its name may hold
    dots, so it is not imported as a package member)."""
    mod_name = "bench_" + tag + "_" + re.sub(r"\W", "_", os.path.basename(
        path)[:-3])
    spec = importlib.util.spec_from_file_location(mod_name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[mod_name] = mod
    spec.loader.exec_module(mod)
    return mod


def listing(root: str) -> Dict[str, List[str]]:
    """Every configuration, traffic mix, driver and per-layer metric
    the layout under ``root`` holds, by name (from the files alone)."""
    def names(sub: str, ext: str) -> List[str]:
        d = os.path.join(root, "bench", sub)
        if not os.path.isdir(d):
            return []
        out = []
        for f in sorted(os.listdir(d)):
            if f.endswith(ext) and not f.startswith("__"):
                out.append(check_name(f[:-len(ext)], sub))
        return out
    return {"configs": names("configs", ".json"),
            "traffic": names("traffic", ".json"),
            "drivers": names("drivers", ".py"),
            "metrics": names("metrics", ".py")}


def cell_metrics(bench: dict, cell: str) -> tuple:
    """The cell's end-to-end and per-layer metric entries."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    moved = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (cell in m["workloads"] if "workloads" in m
                 else m["moves"] in moved)]
    return e2e, layer


def resolve(root: str, cell: str) -> Context:
    """Find everything the cell names, refusing bad names."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if cell not in cells:
        raise KeyError(f"no workload {cell!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[cell]
    for key, what in (("name", "workload"), ("config", "config"),
                      ("traffic", "traffic")):
        check_name(w[key], what)
    configs = {c["name"]: c for c in bench["configs"]}
    centry = configs[w["config"]]
    config = _load_json(os.path.join(root, centry["file"]))
    config.setdefault("name", w["config"])
    traffic = _load_json(os.path.join(root, "bench", "traffic",
                                      w["traffic"] + ".json"))
    check_name(traffic["driver"], "driver")
    return Context(root=root, cell=w, config=config, traffic=traffic,
                   seed=0, seconds=0.0, trace=False)


def device_info(jax) -> dict:
    devs = jax.devices()
    peak = 0
    for d in devs:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def apply_runtime(jax, config: dict) -> None:
    """Hand the deployment's PJRT client options (the configuration's
    ``runtime``) to JAX, which reads them once, when its backend
    starts: so before the first ``jax.devices()``."""
    options = config.get("runtime")
    if options:
        jax.config.update("jax_pjrt_client_create_options", {
            "ml_framework_name": "JAX",
            "ml_framework_version": jax.__version__, **options})


def finite(x) -> Optional[float]:
    if x is None:
        return None
    x = float(x)
    return x if math.isfinite(x) else None


class CompileTimer:
    """Counts the programs JAX lowers (a new shape or function, whether
    or not the persistent cache then holds its binary), those the cache
    held, and the seconds spent compiling or loading them, from JAX's
    monitoring events."""
    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    COMPILE = "/jax/core/compile/backend_compile_duration"
    HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.count = 0
        self.compiles = 0
        self.hits = 0
        self.seconds = 0.0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.LOWER:
            self.count += 1
        elif event == self.COMPILE:
            self.compiles += 1
            self.seconds += duration

    def event(self, event: str, **_) -> None:
        if event == self.HIT:
            self.hits += 1


class GcClock:
    """Seconds and count of Python's cyclic garbage collections, from
    ``gc.callbacks``: a pause here is host time no device op covers."""

    def __init__(self):
        self.count = 0
        self.seconds = 0.0
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.count += 1
            self.seconds += time.perf_counter() - self._t


def run_cell(root: str, cell: str, seed: int, seconds: float,
             trace: bool, *, t_start: Optional[float] = None,
             require_chip: bool = True, compile_cache: bool = True,
             log=None) -> dict:
    """One run of ``cell``; returns the result object (not printed)."""
    t_start = time.perf_counter() if t_start is None else t_start
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    ctx = resolve(root, cell)
    ctx.seed, ctx.seconds, ctx.trace, ctx.log = (int(seed),
                                                 float(seconds),
                                                 bool(trace), log)
    bench = load_benchmark(root)
    e2e, layer = cell_metrics(bench, cell)

    import jax

    from bench import deploy, tracing
    from bench.peaks import peaks

    apply_runtime(jax, ctx.config)
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"JAX found no accelerator (platform "
                         f"{devs[0].platform!r})")
        if len(devs) < int(ctx.cell["chips"]):
            raise NoChip(f"the cell needs {ctx.cell['chips']} chips, "
                         f"JAX found {len(devs)}")
        ctx.peaks = peaks(devs[0].device_kind)
    if compile_cache:
        from repro.compat import enable_compile_cache
        log(f"compile cache: {enable_compile_cache()}")
        # cache every program, not only those that took a second to
        # compile: a warm run then compiles nothing in its set-up
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    driver = load_module(os.path.join(root, "bench", "drivers",
                                      ctx.traffic["driver"] + ".py"),
                         "driver")
    readers = {m["name"]: load_module(
        os.path.join(root, "bench", "metrics", m["name"] + ".py"),
        "metric") for m in layer} if trace else {}

    timer = CompileTimer()
    jax.monitoring.register_event_duration_secs_listener(timer)
    jax.monitoring.register_event_listener(timer.event)
    try:
        ctx.tracer = tracing.Tracer(
            os.path.join(root, "bench", ".cache", "trace", cell)
            if trace else None)
        ctx.deployment = deploy.make(ctx)
        state = driver.setup(ctx)
        # set-up's garbage is collected in set-up, not in the window
        gc.collect()
        setup_s = time.perf_counter() - t_start
        compiles_setup = timer.count
        log(f"setup: {setup_s:.3f} s; {timer.count} programs lowered, "
            f"{timer.compiles} compiled or loaded ({timer.seconds:.3f} s), "
            f"{timer.hits} of them from the cache")
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        try:
            record = driver.window(state, ctx)
        finally:
            gc.callbacks.remove(gc_clock)
        compiles_window = timer.count - compiles_setup
    finally:
        jax.monitoring.unregister_event_duration_listener(timer)
        jax.monitoring.unregister_event_listener(timer.event)
    log(f"window: {compiles_window} programs lowered; {gc_clock.count} "
        f"garbage collections, {gc_clock.seconds:.6f} s")
    device = device_info(jax)
    driver.release(state, record)
    gc.collect()

    metrics: Dict[str, dict] = {}
    breakdown = None
    if trace:
        tr = ctx.tracer.load()
        if require_chip and not tr.has_device():
            raise RuntimeError("the trace holds no device operation")
        mods = sorted(tr.module_names().items(), key=lambda kv: -kv[1])
        log("trace modules: " + ", ".join(f"{k} {v:.6f} s"
                                          for k, v in mods[:20]))
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s()
        breakdown = tr.breakdown()
        for m in layer:
            value = finite(readers[m["name"]].read(record, tr, ctx))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        for m in e2e:
            if m["name"] == "setup_s":
                value = setup_s
            else:
                value = finite(record["metrics"].get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = driver.check(state, record, ctx)
    # nothing may be lowered or compiled inside the measured window
    checks.append(Check("window_compiles", compiles_window, 0))
    correct = all(c.ok for c in checks) and record["failed"] == 0
    out = {"correct": correct, "attempted": int(record["attempted"]),
           "failed": int(record["failed"]), "metrics": metrics,
           "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                     for c in checks}
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    import argparse
    ap = argparse.ArgumentParser(
        description="Run one benchmark cell once; the last line of "
        "standard output is its JSON result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        out = run_cell(root, args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0
