"""Dynamic-repair benchmark: incremental ``CHLIndex.apply`` vs a
from-scratch rebuild, swept over mutation batch sizes and both
benchmark graph families.

Each cell draws a seeded mutation batch (mixed insert/delete/reweight,
weighted toward reweights for small batches — the road-network common
case), then times (a) ``repair``: one ``CHLIndex.apply`` on a fresh
view of the pre-mutation index, and (b) ``rebuild``: one full
``build`` on the mutated graph. Both paths are warmed on identical
shapes first, so the comparison is steady-state kernel work, not
compile time. Repair and rebuild produce bit-identical labels (pinned
by ``tests/test_dynamic.py``), so the speedup column compares equal
outputs.

The headline ``road_small_batch_speedup`` is the repair-vs-rebuild
speedup on the road family at the smallest mutation batch — the
acceptance gate (must exceed 1.0; CI asserts it in quick mode). Road
networks are the motivating dynamic workload: a mutated edge there
has a *local* invalidation cone, so most trees survive. Scale-free
graphs are reported too but not gated — a random edge sits on
hub-routed shortest paths for most roots, so the affected fraction
approaches 1.0 and repair honestly converges to rebuild cost (the
``min_speedup_small_batch`` field records that worst case). A
sharded-store repair row per graph pins the streaming-sink path's
cost next to the dense one.

Besides the CSV rows for ``benchmarks.run``, this module regenerates
``BENCH_dynamic.json`` at the repo root — CI smokes it in interpret
mode (``REPRO_PALLAS_BACKEND=interpret``).
"""

import json
import pathlib
import sys
import time
from typing import List

import jax
import numpy as np

from benchmarks.common import Row, bench_graphs, row
from repro.compat import resolve_interpret
from repro.dynamic import random_mutations
from repro.index import BuildPlan, CHLIndex, build

BENCH_JSON = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_dynamic.json"

BATCH = 16                       # construction root-batch width


def _mutation_counts(m: int) -> dict:
    """Mixed batch shape: reweight-heavy for small m (road closures /
    weight updates), inserts+deletes joining as m grows."""
    ins = m // 4
    dele = m // 4
    return {"inserts": ins, "deletes": dele,
            "reweights": m - ins - dele}


def _fresh_view(idx: CHLIndex) -> CHLIndex:
    """A pre-mutation view sharing the (immutable) label arrays —
    ``apply`` swaps the store object, never writes the arrays, so a
    per-timing view is O(1)."""
    return CHLIndex(store=idx.store, plan=idx.plan, report=idx.report,
                    rank=idx.rank)


def _time_repair(idx, batch, g) -> tuple:
    view = _fresh_view(idx)
    t0 = time.perf_counter()
    rep = view.apply(batch, graph=g)
    return time.perf_counter() - t0, rep


def run(quick: bool = False) -> List[Row]:
    interp = resolve_interpret()
    mode = "interpret" if interp else "compiled"
    sizes = (1, 8) if quick else (1, 8, 32)
    repeats = 2 if quick else 3

    out: List[Row] = []
    min_speedup_small = float("inf")
    road_speedup_small = float("inf")
    for gname, g, rank in bench_graphs("small"):
        plan = BuildPlan(algo="plant", batch=BATCH)
        idx = build(g, rank, plan)
        # warm the rebuild path (plant shapes are identical for any
        # root schedule at this batch width)
        _, rebuild_s = _min_time(lambda: build(
            _mutated(g, 99, sizes[0]), rank, plan), repeats)
        for m in sizes:
            counts = _mutation_counts(m)
            batch = random_mutations(g, np.random.default_rng(m),
                                     **counts)
            _time_repair(idx, batch, g)        # warm frontier shapes
            repair_s, rep = min(
                (_time_repair(idx, batch, g) for _ in range(repeats)),
                key=lambda t: t[0])
            _, rebuild_s = _min_time(
                lambda: build(batch.apply(g), rank, plan), repeats)
            speedup = rebuild_s / repair_s
            if m == sizes[0]:
                min_speedup_small = min(min_speedup_small, speedup)
                if gname.startswith("road"):
                    road_speedup_small = min(road_speedup_small,
                                             speedup)
            r = row(f"dynamic/{gname}/m{m}", repair_s,
                    f"speedup={speedup:.2f}x vs rebuild "
                    f"affected={rep.affected}/{g.n} "
                    f"invalidated={rep.invalidated} "
                    f"repaired={rep.repaired}")
            r.update({
                "graph": gname, "n": g.n, "mutations": m,
                "store": "dense",
                "repair_s": repair_s, "rebuild_s": rebuild_s,
                "speedup": speedup,
                "affected": rep.affected,
                "affected_frac": rep.affected / g.n,
                "invalidated": rep.invalidated,
                "repaired": rep.repaired,
                "total_labels": rep.total_labels,
            })
            out.append(r)

        # the streaming-sink path: same smallest batch, sharded store
        plan_sh = BuildPlan(algo="plant", batch=BATCH,
                            store="sharded", shards=2)
        idx_sh = build(g, rank, plan_sh)
        batch = random_mutations(g, np.random.default_rng(sizes[0]),
                                 **_mutation_counts(sizes[0]))
        _time_repair(idx_sh, batch, g)
        repair_s, rep = _time_repair(idx_sh, batch, g)
        r = row(f"dynamic/{gname}/m{sizes[0]}_sharded", repair_s,
                f"streaming shard repair affected={rep.affected} "
                f"repaired={rep.repaired}")
        r.update({"graph": gname, "n": g.n, "mutations": sizes[0],
                  "store": "sharded", "repair_s": repair_s,
                  "affected": rep.affected,
                  "repaired": rep.repaired,
                  "total_labels": rep.total_labels})
        out.append(r)

    BENCH_JSON.write_text(json.dumps({
        "generated_by": "benchmarks/dynamic_bench.py",
        "jax": jax.__version__,
        "pallas_backend": mode,
        "quick": quick,
        "road_small_batch_speedup": road_speedup_small,
        "min_speedup_small_batch": min_speedup_small,
        "rows": out,
    }, indent=2) + "\n")
    if road_speedup_small <= 1.0:
        print(f"WARNING: repair did not beat rebuild for the smallest "
              f"road mutation batch (speedup "
              f"{road_speedup_small:.2f}x)", file=sys.stderr)
    return out


def _mutated(g, seed: int, m: int):
    return random_mutations(g, np.random.default_rng(seed),
                            **_mutation_counts(m)).apply(g)


def _min_time(fn, repeats: int) -> tuple:
    fn()                                      # warm
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return out, best


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for r in run(quick="--quick" in sys.argv):
        d = str(r.get("derived", "")).replace(",", ";")
        print(f"{r['name']},{r['us_per_call']},{d}")
    print(f"wrote {BENCH_JSON}")
