"""The plain reference: distances and canonical labels from first
principles, and the comparisons that decide ``correct``.

Nothing here imports the program. Distances come from SciPy's
Dijkstra over the yardstick's own edge list (duplicate edges keep
their lightest weight). A canonical hub label ``(r, d(r, v))`` sits at
``v`` exactly when ``r`` has the highest rank on every shortest
``r``-``v`` path, so when the largest rank over the union of those
paths, endpoints included, is ``rank[r]``.
"""

from __future__ import annotations

from typing import Dict, Iterable, NamedTuple, Tuple

import numpy as np
import scipy.sparse as sp
from scipy.sparse.csgraph import dijkstra

from bench.data.graphs import Edges


class Arcs(NamedTuple):
    """Both directions of every edge once, at its lightest weight."""
    n: int
    tail: np.ndarray     # int64 [a]
    head: np.ndarray     # int64 [a]
    w: np.ndarray        # float64 [a]
    csr: sp.csr_matrix


def arcs(e: Edges) -> Arcs:
    tail = np.concatenate([e.src, e.dst]).astype(np.int64)
    head = np.concatenate([e.dst, e.src]).astype(np.int64)
    w = np.concatenate([e.w, e.w]).astype(np.float64)
    keep = tail != head
    tail, head, w = tail[keep], head[keep], w[keep]
    key = tail * e.n + head
    order = np.lexsort((w, key))
    key, tail, head, w = key[order], tail[order], head[order], w[order]
    first = np.ones(len(key), dtype=bool)
    first[1:] = key[1:] != key[:-1]
    tail, head, w = tail[first], head[first], w[first]
    csr = sp.csr_matrix((w, (tail, head)), shape=(e.n, e.n))
    return Arcs(e.n, tail, head, w, csr)


def distances(a: Arcs, sources: np.ndarray, block: int = 128
              ) -> np.ndarray:
    """``float64 [len(sources), n]`` shortest distances, ``inf`` where
    unreachable, computed in blocks of sources to bound memory."""
    sources = np.asarray(sources, dtype=np.int64)
    out = np.empty((len(sources), a.n), dtype=np.float64)
    for i in range(0, len(sources), block):
        out[i:i + block] = dijkstra(a.csr, directed=True,
                                    indices=sources[i:i + block])
    return out


def canonical_labels(a: Arcs, rank: np.ndarray, root: int,
                     dist: np.ndarray) -> Dict[int, float]:
    """``{v: d(root, v)}`` for every vertex at which ``(root, ·)`` is a
    canonical label, given the root's distance row."""
    rank = np.asarray(rank, dtype=np.int64)
    reach = np.isfinite(dist)
    mrank = np.where(reach, rank, -1)
    du, dv = dist[a.tail], dist[a.head]
    tight = np.isfinite(du) & (du + a.w == dv)
    t, h = a.tail[tight], a.head[tight]
    # weights are >= 1, so a tail is strictly closer than its head:
    # visiting tight arcs by the head's distance settles every tail
    # before any arc leaves it
    order = np.argsort(dv[tight], kind="stable")
    mr = mrank.tolist()
    for ti, hi in zip(t[order].tolist(), h[order].tolist()):
        if mr[ti] > mr[hi]:
            mr[hi] = mr[ti]
    mrank = np.asarray(mr)
    vs = np.nonzero(reach & (mrank == rank[root]))[0]
    return {int(v): float(dist[v]) for v in vs}


def label_mismatches(want: Dict[int, float],
                     got: Iterable[Tuple[int, float]]) -> int:
    """Labels of one hub missing, extra, or at a different distance."""
    got = dict(got)
    bad = sum(1 for v, d in want.items() if got.get(v) != d)
    return bad + sum(1 for v in got if v not in want)


def answer_mismatches(want: np.ndarray, got: np.ndarray) -> int:
    """Answers that differ from the reference; ``inf == inf`` (both
    say unreachable) is a match, ``nan`` never is."""
    want = np.asarray(want, dtype=np.float64)
    got = np.asarray(got, dtype=np.float64)
    return int(np.sum(~(got == want)))


def round_to(x: np.ndarray, dtype: str) -> np.ndarray:
    """``x`` stored in a narrower float type and read back as float64
    (the control's lower precision); ``inf`` stays ``inf``."""
    import ml_dtypes
    narrow = {"bfloat16": ml_dtypes.bfloat16,
              "float8_e4m3fn": ml_dtypes.float8_e4m3fn,
              "float16": np.float16}[dtype]
    x = np.asarray(x, dtype=np.float64)
    out = x.astype(np.float32).astype(narrow).astype(np.float64)
    return np.where(np.isinf(x), x, out)


def pair_distances(a: Arcs, u: np.ndarray, v: np.ndarray) -> np.ndarray:
    """``float64 [len(u)]`` reference distance of each pair ``(u, v)``,
    one Dijkstra per distinct source."""
    u = np.asarray(u, dtype=np.int64)
    v = np.asarray(v, dtype=np.int64)
    sources, row = np.unique(u, return_inverse=True)
    out = np.empty(len(u), dtype=np.float64)
    block = 128
    for i in range(0, len(sources), block):
        rows = distances(a, sources[i:i + block], block)
        sel = (row >= i) & (row < i + block)
        out[sel] = rows[row[sel] - i, v[sel]]
    return out
