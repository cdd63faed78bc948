"""Smoke run of the CHL build → save/load → serve path on a TPU.

    python chip_smoke.py             # one chip: PLaNT build, QLSN serving
    python chip_smoke.py --chips 4   # four chips: Hybrid build, QFDL serving
    python chip_smoke.py --side 224  # another grid side

One chip (the default) drives the main path through the entry points a
user calls: a road graph at the shape of the 9th DIMACS Implementation
Challenge road networks (``grid_road``: low degree, integer travel-time
weights), ranked by sampled betweenness as ``repro.launch.chl`` does,
``build(g, rank, BuildPlan(algo="plant"))``, ``idx.save`` /
``CHLIndex.load``, then ``idx.serve(mode="qlsn")`` answering random
pairs through ``submit``/``flush`` plus every target of a few sources
through ``idx.query``. Every answer is compared with host Dijkstra
(``repro.sssp.oracle.dijkstra``); weights are integers, so equality is
exact.

``--chips 4`` runs only the paper's distributed path and what it is
compared with: ``algo="hybrid"`` on a four-device node mesh into a
four-shard store, QFDL serving on that mesh (shard k on chip k), and
host Dijkstra on the same pairs.

The script exits non-zero, without printing a result line, when JAX
finds no TPU, when ``REPRO_PALLAS_BACKEND`` or ``REPRO_ELL_RELAX``
tries to pick the kernel form, when any answer differs, or when any
phase raises. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

#: One-chip graph side. The deployment this mirrors is NY scale
#: (512 x 512 = 262,144 vertices). PLaNT builds every vertex's full
#: shortest-path tree with dense Bellman-Ford sweeps over all n
#: vertices, and a tree needs more sweeps the wider the graph, so the
#: build grows faster than n^2. The side is the largest whose cold run
#: on a v5e is predicted to stay well inside ten minutes (PERF.md
#: section 5).
ONE_CHIP_SIDE = 200
ONE_CHIP_BATCH = 256
#: Four-chip graph side. The Hybrid's dense DGLL superstep answers its
#: cleaning queries with `labels.cover_best_rank`, which materializes a
#: [q*T, n, cap] cube: compiled for a described v5e:2x2, the widest
#: superstep needs 9.8 GB of temporaries at 64 x 64 and overflows a
#: chip's 16 GiB at 72 x 72, and at 200 x 200 the TPU compiler refuses
#: it (an operand of more than 2^31 words), as it did on four v5e
#: chips (PERF.md section 5).
FOUR_CHIP_SIDE = 64
FOUR_CHIP_BATCH = 64
PAIR_SOURCES = 64          # Dijkstra sources behind the random pairs
PAIRS = 4096
FULL_ROWS = 4              # sources whose every target is checked
KERNEL_ENV_VARS = ("REPRO_PALLAS_BACKEND", "REPRO_ELL_RELAX")


def say(msg: str) -> None:
    print(msg, flush=True)


def _pairs_and_truth(g, rng, sources: int, pairs: int):
    """Random pairs whose sources come from ``sources`` sampled
    vertices (one host Dijkstra each), targets uniform; returns
    ``(u, v, truth)`` in shuffled order."""
    from repro.sssp.oracle import dijkstra
    src = rng.choice(g.n, size=min(sources, g.n), replace=False)
    per = -(-pairs // len(src))
    u = np.repeat(src, per)[:pairs]
    v = rng.integers(0, g.n, size=len(u))
    rows = {int(s): dijkstra(g, int(s)) for s in src}
    truth = np.array([rows[int(a)][b] for a, b in zip(u, v)])
    order = rng.permutation(len(u))
    return u[order], v[order], truth[order].astype(np.float32)


def _check(name: str, got: np.ndarray, want: np.ndarray) -> int:
    """Exact comparison; prints and returns the mismatch count."""
    got = np.asarray(got, dtype=np.float32)
    bad = int(np.sum(~(got == want)))
    say(f"check: {name}: {len(want)} answers, {bad} mismatches")
    return bad


class CompileTimer:
    """Sums the XLA backend-compile seconds JAX's monitoring events
    report while registered."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def run_one_chip(side: int = ONE_CHIP_SIDE, batch: int = ONE_CHIP_BATCH,
                 *, pair_sources: int = PAIR_SOURCES, pairs: int = PAIRS,
                 full_rows: int = FULL_ROWS, seed: int = 0,
                 workdir: str | None = None) -> int:
    """Graph → rank → PLaNT build → save/load → QLSN serving → exact
    check against Dijkstra. Returns the total mismatch count."""
    import jax

    from repro.graphs import grid_road
    from repro.graphs.ranking import betweenness_ranking
    from repro.index import BuildPlan, CHLIndex, build
    from repro.kernels.ell_relax import sweep_form
    from repro.sssp.oracle import dijkstra
    from repro.sssp.relax import ell_layout

    rng = np.random.default_rng(seed)

    t0 = time.perf_counter()
    g = grid_road(side, side, seed=seed)
    rank = betweenness_ranking(g, samples=16, seed=seed)
    say(f"graph: grid_road {side}x{side} n={g.n} m={g.m // 2} "
        f"max_deg={g.max_deg_in}; betweenness rank (16 samples) "
        f"{time.perf_counter() - t0:.1f}s")
    if side != 512:
        say("graph: cut from 512x512 (n=262144) so that the PLaNT "
            "build (wall time below) keeps the cold run near ten "
            "minutes")

    plan = BuildPlan(algo="plant", batch=batch)
    say(f"build: {sweep_form(g.n, layout=ell_layout(g.ell_src, g.ell_w))}")
    timer = CompileTimer()
    jax.monitoring.register_event_duration_secs_listener(timer)
    t0 = time.perf_counter()
    try:
        idx = build(g, rank, plan)
    finally:
        jax.monitoring.unregister_event_duration_listener(timer)
    build_s = time.perf_counter() - t0
    mr = idx.memory_report()
    say(f"build: algo=plant batch={batch} wall_s={build_s:.2f} "
        f"compile_s={timer.seconds:.2f} ({timer.count} compiles) "
        f"supersteps={len(idx.report.supersteps)}")
    say(f"labels: cap={idx.report.cap} total={idx.total_labels} "
        f"als={idx.als:.2f} label_bytes={mr['label_bytes']}")
    for note in idx.report.notes:
        say(f"build note: {note}")

    with tempfile.TemporaryDirectory(dir=workdir) as tmp:
        t0 = time.perf_counter()
        path = idx.save(os.path.join(tmp, "index"))
        loaded = CHLIndex.load(path, rank=rank)
        say(f"save/load: store={loaded.store.kind} "
            f"labels={loaded.total_labels} "
            f"{time.perf_counter() - t0:.2f}s")
    if loaded.total_labels != idx.total_labels:
        raise RuntimeError("loaded index lost labels")

    svc = loaded.serve(mode="qlsn")
    u, v, want = _pairs_and_truth(g, rng, pair_sources, pairs)
    svc.submit(u, v)
    got = svc.flush()
    say(f"serve: qlsn batch_size={svc.batch_size} queries={len(got)}")
    bad = _check("served pairs", got, want)

    for s in rng.choice(g.n, size=full_rows, replace=False):
        want = dijkstra(g, int(s)).astype(np.float32)
        got = loaded.query(np.full(g.n, s), np.arange(g.n))
        bad += _check(f"idx.query row of source {int(s)}", got, want)
    return bad


def run_four_chips(side: int = FOUR_CHIP_SIDE,
                   batch: int = FOUR_CHIP_BATCH, *,
                   pair_sources: int = PAIR_SOURCES, pairs: int = PAIRS,
                   seed: int = 0) -> int:
    """Hybrid build on a four-device node mesh into four hub shards,
    QFDL serving with shard k on device k, exact check against
    Dijkstra. Returns the mismatch count."""
    from repro.core.dgll import make_node_mesh
    from repro.graphs import grid_road
    from repro.graphs.ranking import betweenness_ranking
    from repro.index import BuildPlan, build

    mesh = make_node_mesh(4)
    if int(mesh.devices.size) != 4:
        raise RuntimeError(f"--chips 4 needs four devices, found "
                           f"{int(mesh.devices.size)}")
    rng = np.random.default_rng(seed)
    g = grid_road(side, side, seed=seed)
    rank = betweenness_ranking(g, samples=16, seed=seed)
    say(f"graph: grid_road {side}x{side} n={g.n} m={g.m // 2}")
    t0 = time.perf_counter()
    idx = build(g, rank, BuildPlan(algo="hybrid", batch=batch,
                                   store="sharded", shards=4), mesh=mesh)
    modes = sorted({r.mode for r in idx.report.supersteps})
    say(f"build: algo=hybrid batch={batch} q={idx.report.q} wall_s="
        f"{time.perf_counter() - t0:.2f} supersteps="
        f"{len(idx.report.supersteps)} modes={modes}")
    say(f"labels: total={idx.total_labels} als={idx.als:.2f} "
        f"shard_label_bytes={idx.store.shard_label_bytes()}")

    svc = idx.serve(mode="qfdl", mesh=mesh)
    part = idx.store.as_partitioned(mesh)     # the arrays QFDL serves
    per_dev: dict = {}
    for arr in part:
        for sh in arr.addressable_shards:
            per_dev[str(sh.device)] = (per_dev.get(str(sh.device), 0)
                                       + sh.data.nbytes)
    say(f"placement: bytes per device {per_dev}")
    if len(per_dev) != 4 or len(set(per_dev.values())) != 1:
        raise RuntimeError(f"QFDL shards not one per device: {per_dev}")

    u, v, want = _pairs_and_truth(g, rng, pair_sources, pairs)
    svc.submit(u, v)
    got = svc.flush()
    say(f"serve: qfdl queries={len(got)}")
    return _check("qfdl pairs", got, want)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--side", type=int, default=None,
                    help="grid_road side (default %d on one chip, %d "
                    "on four)" % (ONE_CHIP_SIDE, FOUR_CHIP_SIDE))
    args = ap.parse_args(argv)

    forced = {k: os.environ[k] for k in KERNEL_ENV_VARS
              if os.environ.get(k, "auto").strip().lower() != "auto"}
    if forced:
        print(f"chip_smoke: refusing to run with {forced}: the sweep "
              "form is fixed by code", file=sys.stderr)
        return 2

    import jax

    from repro.compat import enable_compile_cache

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r})",
              file=sys.stderr)
        return 2
    say(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())}")
    say(f"compile cache: {enable_compile_cache()}")

    run = run_four_chips if args.chips == 4 else run_one_chip
    bad = run() if args.side is None else run(args.side)
    for d in jax.devices():
        stats = d.memory_stats() or {}
        say(f"memory: {d} peak_bytes_in_use="
            f"{stats.get('peak_bytes_in_use')}")
    if bad:
        print(f"chip_smoke: {bad} answers differ from Dijkstra",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
