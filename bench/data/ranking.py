"""The benchmark's own copies of the two hierarchies of the PLaNT paper
(§7.1.1): by degree for scale-free graphs, by sampled betweenness for
road networks.

The hierarchy is the user's input to CHL and part of the deployment:
label counts, the trees' sizes and the work of every cell follow from
it. These copies of ``repro.graphs.ranking`` keep a later change to
the program's ranking code from moving the yardstick. They work on the
reference's view of the graph (`reference.Arcs`, both directions of
each edge once at its lightest weight, sorted by tail and then head,
the program's own adjacency order), so they give the program's ranks
to the last tie. ``rank[v]`` is an ``int32`` in ``[0, n)``; larger is
more important, ties broken by vertex id.
"""

from __future__ import annotations

import heapq

import numpy as np

from bench.data.reference import Arcs


def _order_to_rank(order_desc: np.ndarray, n: int) -> np.ndarray:
    """``order_desc[0]`` is the most important vertex: rank ``n - 1``."""
    rank = np.empty(n, dtype=np.int32)
    rank[order_desc] = np.arange(n - 1, -1, -1, dtype=np.int32)
    return rank


def degree_ranking(a: Arcs) -> np.ndarray:
    """By degree, highest first, ties by vertex id."""
    deg = np.bincount(a.tail, minlength=a.n).astype(np.int64)
    order = np.lexsort((np.arange(a.n), -deg))
    return _order_to_rank(order.astype(np.int64), a.n)


def _dijkstra_tree(indptr: np.ndarray, head: list, w: list,
                   root: int) -> tuple:
    """Distances and one shortest-path tree's parents, settling and
    relaxing in the program's order (binary heap, strict improvement)."""
    n = len(indptr) - 1
    dist = np.full(n, np.inf)
    parent = np.full(n, -1, dtype=np.int64)
    dist[root] = 0.0
    pq = [(0.0, root)]
    while pq:
        d, v = heapq.heappop(pq)
        if d > dist[v]:
            continue
        for i in range(indptr[v], indptr[v + 1]):
            u = head[i]
            nd = d + w[i]
            if nd < dist[u]:
                dist[u] = nd
                parent[u] = v
                heapq.heappush(pq, (nd, u))
    return dist, parent


def betweenness_ranking(a: Arcs, samples: int = 16,
                        seed: int = 0) -> np.ndarray:
    """Sampled shortest-path-tree betweenness: over ``samples`` trees
    from random roots, the number of tree descendants of each vertex,
    highest first, ties by vertex id."""
    n = a.n
    indptr = np.zeros(n + 1, dtype=np.int64)
    np.cumsum(np.bincount(a.tail, minlength=n), out=indptr[1:])
    indptr = indptr.tolist()
    head, w = a.head.tolist(), a.w.tolist()
    rng = np.random.default_rng(seed)
    score = np.zeros(n, dtype=np.float64)
    for r in rng.choice(n, size=min(samples, n), replace=False):
        dist, parent = _dijkstra_tree(indptr, head, w, int(r))
        reach = np.isfinite(dist)
        acc = np.where(reach, 1.0, 0.0)
        # subtree sizes bottom-up: farthest first
        for v in np.argsort(dist)[::-1].tolist():
            p = parent[v]
            if p >= 0 and reach[v]:
                acc[p] += acc[v]
        score += np.where(reach, acc, 0.0)
    order = np.lexsort((np.arange(n), -score))
    return _order_to_rank(order.astype(np.int64), n)


def make(spec: dict, a: Arcs) -> np.ndarray:
    """The hierarchy a configuration's ``hierarchy`` block describes."""
    if spec["kind"] == "betweenness":
        return betweenness_ranking(a, samples=spec["samples"],
                                   seed=spec["seed"])
    if spec["kind"] == "degree":
        return degree_ranking(a)
    raise ValueError(f"unknown hierarchy {spec['kind']!r}")
