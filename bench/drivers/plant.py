"""Build window: PLaNT trees over a systematic sample of root batches.

A full build cannot fit a window, but PLaNT trees are independent: a
root's labels depend on its own tree alone. So the window drives the
program's engine (``repro.engine.run_build``, ``algo="plant"``) once
over ``k`` whole batches of a full build's schedule, one from each of
``k`` strata of its batch sequence (`bench.data.samplers`), and its
rate of completed trees is the full build's rate.

``k`` is ``--seconds`` over the configuration's ``plant_superstep_s``
(a superstep's time measured on a v5e), at most the build's full
batches. Where the whole build is shorter than the window, the window
makes the same call again, the nearest whole number of times: a graph
small enough to build in seconds is rebuilt, as a user rebuilding it
would. Either way the work is fixed, the same for every seed and for
every version of the program, so a faster program shows as a shorter
window at a higher rate. ``--seed`` orders the batches and draws the
roots whose labels are checked; every call's labels are compared.

With ``--trace 1`` the first ``trace_seconds`` of the same window run
under the profiler.

Traffic keys: ``driver`` ("plant"), ``check_roots`` (roots whose
labels are compared with the reference), ``trace_seconds``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import List

import numpy as np

from bench.data import reference, samplers
from bench.harness import Check


@dataclasses.dataclass
class State:
    batches: np.ndarray          # int32 [k, batch]
    calls: int                   # run_build calls over them in a window


def _run(ctx, roots: np.ndarray):
    from repro.engine import run_build
    dep, plan = ctx.deployment, ctx.config["plan"]
    return run_build(dep.graph, dep.rank, algo=plan["algo"],
                     batch=plan["batch"], roots_order=roots.reshape(-1))


def setup(ctx) -> State:
    import jax.numpy as jnp

    from repro.engine.records import STAT_SLOTS, fetch_stat_rows

    dep, batch = ctx.deployment, ctx.config["plan"]["batch"]
    full = dep.graph.n // batch
    want = max(1, round(ctx.seconds / ctx.config["plant_superstep_s"]))
    k = int(min(full, want))
    calls = max(1, int(want / k + 0.5))
    batches = samplers.systematic_batches(
        dep.rank, batch, k, samplers.rng_of(ctx.seed, "roots"))
    t0 = time.perf_counter()
    _run(ctx, batches[:1])               # lowers every program
    # the engine fetches its k deferred stats rows with one stack
    # whose program depends on k: lower that one too
    fetch_stat_rows([jnp.zeros(len(STAT_SLOTS), jnp.int32)] * k)
    ctx.log(f"plant: warm-up superstep {time.perf_counter() - t0:.3f} s; "
            f"window of {calls} x {k} batches of {batch} of the "
            f"build's {full}")
    return State(batches=batches, calls=calls)


def window(state: State, ctx) -> dict:
    results, call_s = [], []
    with ctx.tracer.window(float(ctx.traffic["trace_seconds"])):
        t0 = time.perf_counter()
        for _ in range(state.calls):
            with ctx.tracer.span("bench.run_build"):
                results.append(_run(ctx, state.batches))
            call_s.append(time.perf_counter() - t0 - sum(call_s))
        window_s = time.perf_counter() - t0
    records = [r for res in results for r in res.records]
    trees = sum(r.trees for r in records)
    ctx.log("plant: calls " + ", ".join(f"{s:.3f}" for s in call_s)
            + " s")
    return {
        "metrics": {"build_roots_per_s": trees / window_s},
        "attempted": trees, "failed": 0,
        "window_s": window_s, "supersteps": len(records),
        "sweeps": sum(r.sweeps for r in records),
        "labels": sum(r.labels for r in records),
        "batch": state.batches.shape[1],
        "n": ctx.deployment.graph.n, "arcs": len(ctx.deployment.arcs.tail),
        "sinks": [res.sink for res in results],
    }


def release(state: State, record: dict) -> None:
    """Bring each call's label table to the host; the device copies
    go."""
    record["tables"] = []
    for sink in record.pop("sinks"):
        t = sink.table()
        record["tables"].append((np.asarray(t.hubs), np.asarray(t.dist)))


def check_sample(state: State, ctx) -> np.ndarray:
    """The window's roots whose labels are compared: its highest-rank
    root (the one with the most labels), and the rest drawn from the
    seed."""
    roots = state.batches.reshape(-1)
    rank = ctx.deployment.rank
    top = roots[np.argmax(rank[roots])]
    rest = np.setdiff1d(roots, [top])
    rng = samplers.rng_of(ctx.seed, "check")
    count = min(len(rest), int(ctx.traffic["check_roots"]) - 1)
    return np.concatenate([[top], rng.choice(rest, count, replace=False)])


def program_labels(table: tuple, roots: np.ndarray) -> List[dict]:
    """``{v: d}`` of each root's labels, as one call's label table
    ``(hubs, dist)`` holds them."""
    out = [dict() for _ in roots]
    where = {int(r): i for i, r in enumerate(roots)}
    hubs, dist = table
    vs, slots = np.nonzero(np.isin(hubs, roots))
    for v, s in zip(vs.tolist(), slots.tolist()):
        out[where[int(hubs[v, s])]][v] = float(dist[v, s])
    return out


def reference_labels(ctx, roots: np.ndarray, dtype=None) -> List[dict]:
    """The reference's labels of each root; with ``dtype``, their
    distances stored in that narrower type (the control)."""
    dep = ctx.deployment
    rows = reference.distances(dep.arcs, roots)
    out = [reference.canonical_labels(dep.arcs, dep.rank, int(r), row)
           for r, row in zip(roots, rows)]
    if dtype is not None:
        out = [dict(zip(lab, reference.round_to(list(lab.values()),
                                                 dtype).tolist()))
               for lab in out]
    return out


def compare(want: List[dict], got: List[dict]) -> List[Check]:
    bad = sum(reference.label_mismatches(w, g.items())
              for w, g in zip(want, got))
    return [Check("wrong_labels", bad, 0)]


def check(state: State, record: dict, ctx) -> List[Check]:
    roots = check_sample(state, ctx)
    want = reference_labels(ctx, roots)
    ctx.log(f"plant check: {len(roots)} roots, "
            f"{sum(len(w) for w in want)} reference labels, "
            f"{len(record['tables'])} calls")
    checks = [compare(want, program_labels(t, roots))[0]
              for t in record["tables"]]
    return [Check("wrong_labels", sum(c.value for c in checks), 0)]
