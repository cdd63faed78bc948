"""Fixed-capacity padded hub-label tables (DESIGN.md §2 A5).

JAX requires static shapes, so the paper's dynamic per-vertex label
vectors become a padded table:

    hubs : int32 [n, L]   (-1 = empty slot)
    dist : f32   [n, L]   (+inf = empty slot)
    count: int32 [n]

All batched operations below are pure-jnp references; the Pallas
``label_query`` kernel accelerates the intersection probes on TPU
(``repro.kernels.label_query``).
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array


class LabelOverflowError(RuntimeError):
    """A fixed-capacity label table ran out of slots.

    Carries the offending ``cap`` so callers (``repro.index.build``)
    can retry with a geometrically grown capacity instead of burning
    the whole run.
    """

    def __init__(self, cap: int, what: str = "label table"):
        super().__init__(f"{what} overflow (cap={cap}); raise `cap`")
        self.cap = cap
        self.what = what


def default_cap(n: int) -> int:
    """Default per-vertex label capacity for an n-vertex graph.

    CHL label counts concentrate around O(√n·polylog) on the paper's
    graph families; ``4√n + 32`` leaves generous headroom while keeping
    the padded table O(n^1.5). Capacity can never usefully exceed n.
    """
    return min(max(16, 4 * int(np.sqrt(n)) + 32), max(1, n))


class LabelTable(NamedTuple):
    hubs: Array    # i32 [n, L]
    dist: Array    # f32 [n, L]
    count: Array   # i32 [n]

    @property
    def n(self) -> int:
        return self.hubs.shape[0]

    @property
    def cap(self) -> int:
        return self.hubs.shape[1]


def empty(n: int, cap: int) -> LabelTable:
    return LabelTable(
        hubs=jnp.full((n, cap), -1, dtype=jnp.int32),
        dist=jnp.full((n, cap), jnp.inf, dtype=jnp.float32),
        count=jnp.zeros((n,), dtype=jnp.int32),
    )


def insert_batch(table: LabelTable, roots: Array, emit: Array,
                 dists: Array) -> Tuple[LabelTable, Array]:
    """Append labels ``(roots[b], dists[b,v])`` for every ``emit[b,v]``.

    Returns the new table and a bool overflow flag (any vertex whose
    label count would exceed capacity; offending labels are dropped).
    """
    n, cap = table.n, table.cap
    B = roots.shape[0]
    off = jnp.cumsum(emit.astype(jnp.int32), axis=0) - 1          # [B, n]
    pos = table.count[None, :] + off                              # [B, n]
    ok = emit & (pos < cap)
    flat = jnp.where(ok, jnp.arange(n)[None, :] * cap + pos, n * cap)
    hubs = table.hubs.reshape(-1).at[flat.reshape(-1)].set(
        jnp.broadcast_to(roots[:, None], (B, n)).reshape(-1), mode="drop")
    dist = table.dist.reshape(-1).at[flat.reshape(-1)].set(
        dists.reshape(-1), mode="drop")
    new_count = table.count + jnp.sum(emit, axis=0, dtype=jnp.int32)
    overflow = jnp.any(new_count > cap)
    return LabelTable(hubs=hubs.reshape(n, cap), dist=dist.reshape(n, cap),
                      count=jnp.minimum(new_count, cap)), overflow


def hub_distance_map(table: LabelTable, roots: Array) -> Array:
    """Dense map ``hmap[b, x] = d(roots[b], x)`` for x in L_{roots[b]},
    ``+inf`` elsewhere — the hashed root labels of Alg. 1 line 1."""
    n, cap = table.n, table.cap
    B = roots.shape[0]
    rh = table.hubs[roots]                     # [B, L]
    rd = table.dist[roots]                     # [B, L]
    hmap = jnp.full((B, n), jnp.inf, dtype=jnp.float32)
    b_idx = jnp.broadcast_to(jnp.arange(B)[:, None], rh.shape)
    hmap = hmap.at[b_idx.reshape(-1),
                   jnp.where(rh >= 0, rh, 0).reshape(-1)].min(
        jnp.where(rh >= 0, rd, jnp.inf).reshape(-1))
    return hmap


def cover_distance(table: LabelTable, hmap: Array) -> Array:
    """``cover[b, v] = min_{x ∈ L_v} hmap[b, x] + d(v, x)`` — the
    distance-query value DQ(v, root_b) for every vertex (Alg. 1 DQ)."""
    safe_h = jnp.where(table.hubs >= 0, table.hubs, 0)     # [n, L]
    via = hmap[:, safe_h]                                   # [B, n, L]
    via = jnp.where(table.hubs[None] >= 0, via + table.dist[None], jnp.inf)
    return jnp.min(via, axis=-1)                            # [B, n]


def cover_best_rank(table: LabelTable, hmap: Array, rank: Array,
                    delta: Array) -> Array:
    """Max rank over hubs x common to L_v and the root's map with
    ``hmap[b,x] + d(v,x) <= delta[b,v]`` (-1 if none) — DQ_Clean's W."""
    safe_h = jnp.where(table.hubs >= 0, table.hubs, 0)
    via = hmap[:, safe_h] + table.dist[None]                # [B, n, L]
    good = (table.hubs[None] >= 0) & (via <= delta[:, :, None])
    cand = jnp.where(good, rank[safe_h][None], -1)
    return jnp.max(cand, axis=-1)                           # [B, n]


def intersect_rows(hu: Array, du: Array, hv: Array, dv: Array
                   ) -> Tuple[Array, Array]:
    """Min over common hubs of gathered label rows ``[Q, L]`` each.

    Returns (distance f32 [Q], best-hub id i32 [Q]; -1 when disjoint).
    The ``[Q, L, L]`` candidate cube feeds exactly one reduction (over
    v's slots), so XLA fuses it away and only the ``[Q, L]`` per-u-slot
    minima are materialized. The witnessing hub is the first u slot
    attaining the overall min — the slot a flattened argmin over the
    cube picks, so the hub is bit-identical to that formulation.
    """
    match = (hu[:, :, None] == hv[:, None, :]) & (hu[:, :, None] >= 0)
    dd = jnp.where(match, du[:, :, None] + dv[:, None, :], jnp.inf)
    row = jnp.min(dd, axis=2)                      # [Q, L]
    best = jnp.min(row, axis=1)
    bi = jnp.argmin(row, axis=1)
    hub = jnp.where(jnp.isfinite(best),
                    jnp.take_along_axis(hu, bi[:, None], axis=1)[:, 0], -1)
    return best, hub


@jax.jit
def query_pairs(table: LabelTable, u: Array, v: Array
                ) -> Tuple[Array, Array]:
    """Batched PPSD query: min over common hubs of d(u,x)+d(v,x).

    Returns (distance f32 [Q], best-hub id i32 [Q]; -1 when disjoint).
    Pure-jnp reference for the ``label_query`` kernel.
    """
    return intersect_rows(table.hubs[u], table.dist[u],
                          table.hubs[v], table.dist[v])


def merge(a: LabelTable, b: LabelTable) -> Tuple[LabelTable, Array]:
    """Append all labels of ``b`` after those of ``a`` (same n)."""
    n = a.n
    cap = a.cap
    idx = jnp.arange(b.cap)[None, :]                        # [1, Lb]
    valid = idx < b.count[:, None]
    pos = a.count[:, None] + idx
    ok = valid & (pos < cap)
    flat = jnp.where(ok, jnp.arange(n)[:, None] * cap + pos, n * cap)
    hubs = a.hubs.reshape(-1).at[flat.reshape(-1)].set(
        b.hubs.reshape(-1), mode="drop")
    dist = a.dist.reshape(-1).at[flat.reshape(-1)].set(
        b.dist.reshape(-1), mode="drop")
    new_count = a.count + b.count
    overflow = jnp.any(new_count > cap)
    return LabelTable(hubs.reshape(n, cap), dist.reshape(n, cap),
                      jnp.minimum(new_count, cap)), overflow


def delete_mask(table: LabelTable, drop: Array) -> LabelTable:
    """Remove labels where ``drop[n, L]`` is True, compacting rows."""
    keep = (~drop) & (table.hubs >= 0)
    order = jnp.argsort(~keep, axis=1, stable=True)         # keepers first
    hubs = jnp.take_along_axis(table.hubs, order, axis=1)
    dist = jnp.take_along_axis(table.dist, order, axis=1)
    kept = jnp.sum(keep, axis=1, dtype=jnp.int32)
    slot = jnp.arange(table.cap)[None, :]
    hubs = jnp.where(slot < kept[:, None], hubs, -1)
    dist = jnp.where(slot < kept[:, None], dist, jnp.inf)
    return LabelTable(hubs, dist, kept)


def to_numpy_sets(table: LabelTable) -> list[dict[int, float]]:
    """Host-side view: per-vertex {hub: dist} (tests/benchmarks).

    Vectorized with numpy masking (it runs inside ``validate_against``
    and several benchmarks): slot validity, duplicate-hub min-dist
    dedup and the (vertex, hub) grouping are all array ops; only the
    final O(total labels) dict fill remains Python — not the old
    O(n·cap) double loop over mostly-empty padding.
    """
    hubs = np.asarray(table.hubs)
    dist = np.asarray(table.dist)
    count = np.asarray(table.count)
    n, cap = hubs.shape
    mask = (np.arange(cap)[None, :] < count[:, None]) & (hubs >= 0)
    v_idx, k_idx = np.nonzero(mask)
    h = hubs[v_idx, k_idx].astype(np.int64)
    d = dist[v_idx, k_idx].astype(float)
    # keep the min distance per (vertex, hub) duplicate group
    order = np.lexsort((d, h, v_idx))
    v_s, h_s, d_s = v_idx[order], h[order], d[order]
    first = np.ones(len(v_s), dtype=bool)
    first[1:] = (v_s[1:] != v_s[:-1]) | (h_s[1:] != h_s[:-1])
    out: list[dict[int, float]] = [{} for _ in range(n)]
    for v, hub, dd in zip(v_s[first].tolist(), h_s[first].tolist(),
                          d_s[first].tolist()):
        out[v][hub] = dd
    return out


def from_numpy_sets(sets: list[dict[int, float]],
                    cap: int | None = None) -> LabelTable:
    """Inverse of :func:`to_numpy_sets`: pack per-vertex {hub: dist}
    dicts into a padded table (host oracles → device serving path)."""
    n = len(sets)
    need = max((len(s) for s in sets), default=0)
    cap = max(need, 1) if cap is None else cap
    if need > cap:
        raise LabelOverflowError(cap)
    hubs = np.full((n, cap), -1, dtype=np.int32)
    dist = np.full((n, cap), np.inf, dtype=np.float32)
    count = np.zeros(n, dtype=np.int32)
    for v, row in enumerate(sets):
        for k, (h, d) in enumerate(sorted(row.items())):
            hubs[v, k] = h
            dist[v, k] = d
        count[v] = len(row)
    return LabelTable(jnp.asarray(hubs), jnp.asarray(dist),
                      jnp.asarray(count))


def total_labels(table: LabelTable) -> int:
    return int(np.asarray(jnp.sum(table.count)))
