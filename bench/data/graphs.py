"""The benchmark's own graph generators.

These are the yardstick's copies: a later change to the program's
generators cannot move the graphs the benchmark measures on. Each
generator returns an :class:`Edges` list of undirected edges with
integer weights held as float32. Both the system under test (through
its own graph constructor) and the plain reference (`reference.py`)
are built from that one list.

- :func:`road_network` is a road network at the shape of the 9th
  DIMACS challenge's USA-road-t graphs: intersections on a jittered
  lattice, a grid of arterials and highways kept whole, local streets
  a random spanning forest plus random extra segments up to a given
  number of arcs per vertex (NY: 733,846 / 264,346 = 2.776), and
  travel-time weights (segment length over its class's speed).
- :func:`grid_road` is a copy of ``repro.graphs.grid_road``: a lattice
  with a sprinkling of diagonal shortcuts and integer weights in
  ``[1, sqrt(n))``, the weighting of the PLaNT paper §7.1.1. The
  benchmark's cells do not use it; the recorded trace fixture
  (``tests/bench/record_trace.py``) does.
- :func:`kronecker` is the Graph500 specification's Kronecker
  generator (A, B, C = 0.57, 0.19, 0.19, the spec's vertex and edge
  permutations), with self-loops and duplicate edges removed and
  isolated vertices kept, weighted as above.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np


class Edges(NamedTuple):
    n: int
    src: np.ndarray      # int32 [m]
    dst: np.ndarray      # int32 [m]
    w: np.ndarray        # float32 [m], integral values


def _weights(rng: np.random.Generator, m: int, n: int) -> np.ndarray:
    hi = max(2, int(np.sqrt(n)))
    return rng.integers(1, hi, size=m).astype(np.float32)


def grid_road(rows: int, cols: int, seed: int = 0,
              diag_frac: float = 0.1) -> Edges:
    """``rows x cols`` lattice plus ``diag_frac * n`` random diagonals.

    Draws from the generator in the same order as
    ``repro.graphs.grid_road``, so one seed gives the same graph."""
    rng = np.random.default_rng(seed)
    n = rows * cols
    vid = np.arange(n).reshape(rows, cols)
    src = [vid[:, :-1].ravel(), vid[:-1, :].ravel()]
    dst = [vid[:, 1:].ravel(), vid[1:, :].ravel()]
    n_diag = int(diag_frac * n)
    if n_diag and rows > 1 and cols > 1:
        r = rng.integers(0, rows - 1, n_diag)
        c = rng.integers(0, cols - 1, n_diag)
        src.append(vid[r, c])
        dst.append(vid[r + 1, c + 1])
    src = np.concatenate(src).astype(np.int32)
    dst = np.concatenate(dst).astype(np.int32)
    return Edges(n, src, dst, _weights(rng, len(src), n))


def road_network(rows: int, cols: int, arcs_per_node: float,
                 seed: int = 0, block_m: float = 100.0,
                 jitter: float = 0.3, arterial_every: int = 10,
                 highway_every: int = 50,
                 kmh: tuple = (30.0, 60.0, 100.0)) -> Edges:
    """A connected road network on ``rows x cols`` intersections.

    Intersection ``(r, c)`` sits ``block_m`` metres apart on a lattice,
    each coordinate jittered by up to ``jitter`` blocks. A candidate
    segment joins lattice neighbours; it lies on a highway when its row
    (or column) is a multiple of ``highway_every``, on an arterial when
    a multiple of ``arterial_every``, else on a local street. Every
    arterial and highway segment is kept. Local segments first join
    every intersection to that grid (a spanning forest, in a random
    order: scipy's minimum spanning tree over distinct random keys),
    then random further local segments are added until the graph has
    ``round(arcs_per_node * n / 2)`` edges. A segment's weight is its
    travel time in tenths of a second, its length over the speed of
    its class (``kmh``: local, arterial, highway), at least 1."""
    from scipy.sparse import coo_matrix
    from scipy.sparse.csgraph import minimum_spanning_tree

    rng = np.random.default_rng(seed)
    n = rows * cols
    vid = np.arange(n).reshape(rows, cols)
    y = (np.arange(rows)[:, None]
         + rng.uniform(-jitter, jitter, (rows, cols))) * block_m
    x = (np.arange(cols)[None, :]
         + rng.uniform(-jitter, jitter, (rows, cols))) * block_m
    src = np.concatenate([vid[:, :-1].ravel(), vid[:-1, :].ravel()])
    dst = np.concatenate([vid[:, 1:].ravel(), vid[1:, :].ravel()])
    line = np.concatenate([np.repeat(np.arange(rows), cols - 1),
                           np.tile(np.arange(cols), rows - 1)])
    cls = np.where(line % highway_every == 0, 2,
                   np.where(line % arterial_every == 0, 1, 0))
    keep = cls > 0
    # Kruskal with the kept grid first, then local segments in a
    # random order: keys below 1 for the grid, a permutation above it
    local = np.nonzero(~keep)[0]
    key = np.empty(len(src))
    key[keep] = (1.0 + np.arange(keep.sum())) / (2.0 + keep.sum())
    key[local] = 1.0 + rng.permutation(len(local))
    tree = minimum_spanning_tree(coo_matrix(
        (key, (src, dst)), shape=(n, n)).tocsr()).tocoo()
    picked = np.zeros(len(src), dtype=bool)
    pos = {(a, b): i for i, (a, b) in enumerate(zip(src.tolist(),
                                                    dst.tolist()))}
    for a, b in zip(tree.row.tolist(), tree.col.tolist()):
        picked[pos[(a, b)] if (a, b) in pos else pos[(b, a)]] = True
    keep |= picked
    spare = local[~keep[local]]
    extra = int(round(arcs_per_node * n / 2)) - int(keep.sum())
    if extra < 0 or extra > len(spare):
        raise ValueError(f"{arcs_per_node} arcs per vertex is out of "
                         f"reach of a {rows} x {cols} lattice")
    keep[spare[rng.permutation(len(spare))[:extra]]] = True
    src, dst, cls = src[keep], dst[keep], cls[keep]
    length = np.hypot(x.ravel()[src] - x.ravel()[dst],
                      y.ravel()[src] - y.ravel()[dst])
    speed = np.asarray(kmh, dtype=np.float64)[cls] / 3.6
    w = np.maximum(1.0, np.round(10.0 * length / speed))
    return Edges(n, src.astype(np.int32), dst.astype(np.int32),
                 w.astype(np.float32))


#: Graph500 initiator probabilities (D = 1 - A - B - C = 0.05)
KRON_A, KRON_B, KRON_C = 0.57, 0.19, 0.19


def kronecker_pairs(scale: int, edgefactor: int,
                    rng: np.random.Generator) -> np.ndarray:
    """The specification's edge tuples, ``int64 [2, edgefactor * 2^scale]``,
    before any cleaning (the reference code's ``kronecker_generator``)."""
    n = 1 << scale
    m = edgefactor * n
    ab = KRON_A + KRON_B
    c_norm = KRON_C / (1.0 - ab)
    a_norm = KRON_A / ab
    ij = np.zeros((2, m), dtype=np.int64)
    for ib in range(scale):
        ii_bit = rng.random(m) > ab
        jj_bit = rng.random(m) > np.where(ii_bit, c_norm, a_norm)
        ij[0] += ii_bit.astype(np.int64) << ib
        ij[1] += jj_bit.astype(np.int64) << ib
    perm = rng.permutation(n)            # vertex relabelling
    ij = perm[ij]
    return ij[:, rng.permutation(m)]     # edge order


def kronecker(scale: int, edgefactor: int = 16, seed: int = 0) -> Edges:
    """Undirected Graph500 Kronecker graph on ``2^scale`` vertices:
    self-loops dropped, each unordered pair kept once, isolated
    vertices kept, integer weights in ``[1, sqrt(n))``."""
    rng = np.random.default_rng(seed)
    n = 1 << scale
    ij = kronecker_pairs(scale, edgefactor, rng)
    lo = np.minimum(ij[0], ij[1])
    hi = np.maximum(ij[0], ij[1])
    keep = lo != hi
    key = np.unique(lo[keep] * n + hi[keep])
    src = (key // n).astype(np.int32)
    dst = (key % n).astype(np.int32)
    return Edges(n, src, dst, _weights(rng, len(key), n))


def make(spec: dict) -> Edges:
    """The graph a configuration's ``graph`` block describes."""
    kind = spec["generator"]
    if kind == "grid_road":
        return grid_road(spec["rows"], spec["cols"],
                         seed=spec["graph_seed"],
                         diag_frac=spec["diag_frac"])
    if kind == "road_network":
        return road_network(spec["rows"], spec["cols"],
                            spec["arcs_per_node"], seed=spec["graph_seed"],
                            block_m=spec["block_m"], jitter=spec["jitter"],
                            arterial_every=spec["arterial_every"],
                            highway_every=spec["highway_every"],
                            kmh=tuple(spec["kmh"]))
    if kind == "kronecker":
        return kronecker(spec["scale"], spec["edgefactor"],
                         seed=spec["graph_seed"])
    raise ValueError(f"unknown graph generator {kind!r}")


def degrees(e: Edges) -> np.ndarray:
    """Undirected degree of each vertex (duplicates counted once)."""
    lo = np.minimum(e.src, e.dst).astype(np.int64)
    hi = np.maximum(e.src, e.dst).astype(np.int64)
    key = np.unique(lo * e.n + hi)
    return (np.bincount(key // e.n, minlength=e.n)
            + np.bincount(key % e.n, minlength=e.n))
