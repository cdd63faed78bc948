"""A configuration made real: the graph, as the yardstick generates it
and as the program takes it, its hierarchy, and the built index of
the cells that serve queries.

The hierarchy is the user's input to CHL and part of the deployment:
the yardstick computes it with its own copy of the program's ranking
code (`bench.data.ranking`), from the configuration; its time counts
as set-up.
"""

from __future__ import annotations

import dataclasses
import hashlib
import os
import shutil
import time
from typing import Any, List

import numpy as np

from bench.data import graphs, ranking, reference


@dataclasses.dataclass
class Deployment:
    edges: graphs.Edges
    graph: Any               # the program's repro.graphs.Graph
    rank: np.ndarray
    arcs: reference.Arcs     # the reference's view of the same graph
    pool: np.ndarray         # vertices with at least one edge


def make(ctx) -> Deployment:
    from repro.graphs.graph import from_edges

    cfg = ctx.config
    t0 = time.perf_counter()
    e = graphs.make(cfg["graph"])
    g = from_edges(e.n, e.src, e.dst, e.w, directed=False)
    arcs = reference.arcs(e)
    rank = ranking.make(cfg["hierarchy"], arcs)
    pool = np.nonzero(graphs.degrees(e) > 0)[0].astype(np.int32)
    dep = Deployment(edges=e, graph=g, rank=rank, pool=pool, arcs=arcs)
    ctx.log(f"deployment {cfg['name']}: n={e.n} edges={len(e.src)} "
            f"ell_width={g.max_deg_in} non_isolated={len(pool)} "
            f"({time.perf_counter() - t0:.3f} s with the hierarchy)")
    return dep


def src_tree_hash(root: str, extra: List[str]) -> str:
    """sha256 over every file of the program's ``src/`` tree and the
    given files: a key that changes with any program code."""
    h = hashlib.sha256()
    paths = []
    src = os.path.join(root, "src")
    for dirpath, dirnames, files in os.walk(src):
        dirnames[:] = sorted(d for d in dirnames if d != "__pycache__")
        for f in sorted(files):
            if not f.endswith((".pyc", ".pyo")):
                paths.append(os.path.join(dirpath, f))
    paths += [os.path.join(root, p) for p in extra]
    for p in paths:
        h.update(os.path.relpath(p, root).encode() + b"\0")
        with open(p, "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


def index_path(ctx) -> str:
    """Where this checkout keeps the configuration's built index: the
    key changes with the configuration, the yardstick's graph code and
    every file of the program's ``src/``."""
    from bench.harness import load_benchmark

    cfile = next(c["file"] for c in load_benchmark(ctx.root)["configs"]
                 if c["name"] == ctx.cell["config"])
    key = src_tree_hash(ctx.root, [cfile, "bench/data/graphs.py",
                                   "bench/data/ranking.py",
                                   "bench/deploy.py"])
    return os.path.join(ctx.root, "bench", ".cache", "index",
                        f"{ctx.config['name']}-{key}")


def index(ctx, **load):
    """The configuration's built index, loaded with ``CHLIndex.load``
    (``load`` passes its options, such as another residency). A run
    that finds none under :func:`index_path` builds it and saves it
    there (``CHLIndex.save``) for the runs that follow."""
    from repro.index import BuildPlan, CHLIndex, build

    cfg, dep = ctx.config, ctx.deployment
    path = index_path(ctx)
    if not os.path.isfile(os.path.join(path, "manifest.json")):
        t0 = time.perf_counter()
        plan = cfg["plan"]
        built = build(dep.graph, dep.rank,
                      BuildPlan(algo=plan["algo"], batch=plan["batch"]))
        ctx.log(f"index: built in {time.perf_counter() - t0:.3f} s, "
                f"{built.total_labels} labels, cap {built.report.cap}")
        # one index per configuration: drop those of older program code
        base = os.path.dirname(path)
        if os.path.isdir(base):
            for old in os.listdir(base):
                if old.startswith(cfg["name"] + "-"):
                    shutil.rmtree(os.path.join(base, old),
                                  ignore_errors=True)
        built.save(path)
        del built
    t0 = time.perf_counter()
    idx = CHLIndex.load(path, rank=dep.rank, **load)
    ctx.log(f"index: loaded {os.path.basename(path)} {load or ''}"
            f"({time.perf_counter() - t0:.3f} s)")
    return idx
