"""Distributed PPSD query serving — QLSN / QFDL / QDOL (§6).

- **QLSN**: every node holds all labels; the querying node intersects
  locally. Memory O(n·ALS) *per node*.
- **QFDL**: labels partitioned by hub (the construction-time layout);
  a query is broadcast, each node computes a partial min over its hub
  partition, and ``lax.pmin`` (the paper's MPI_MIN) reduces. Memory
  O(n·ALS/q) per node.
- **QDOL**: vertices split into ζ partitions with C(ζ,2) ≤ q; node k
  stores the *full* label rows of partition pair (i,j) and exclusively
  answers queries with endpoints in (i,j). Batched JAX mapping: query
  ids are replicated (the analog of the paper's routed P2P batch —
  each query is *answered* by exactly one node), non-owners contribute
  +inf, and a single pmin combines. Memory O(2·n·ALS/ζ) ≈
  O(n·ALS/√q) per node.

Throughput numbers for Table 4 come from `benchmarks/table4_query_modes`.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import shard_map
from repro.core import labels as lbl
from repro.core.labels import LabelTable

Array = jax.Array


# --------------------------------------------------------------------
# QLSN
# --------------------------------------------------------------------

@jax.jit
def qlsn(table: LabelTable, u: Array, v: Array) -> Array:
    """Single-node query: min over common hubs (f32 [Q])."""
    d, _ = lbl.query_pairs(table, u, v)
    return d


# --------------------------------------------------------------------
# QFDL
# --------------------------------------------------------------------

def qfdl_fn(mesh: Mesh):
    """Query over the hub-partitioned [q, n, L] table."""
    t_spec = LabelTable(P("node"), P("node"), P("node"))

    def step(table: LabelTable, u: Array, v: Array) -> Array:
        t = LabelTable(table.hubs[0], table.dist[0], table.count[0])
        part, _ = lbl.query_pairs(t, u, v)
        return jax.lax.pmin(part, "node")

    return jax.jit(shard_map(step, mesh=mesh,
                             in_specs=(t_spec, P(), P()),
                             out_specs=P(), check_replication=False))


# --------------------------------------------------------------------
# QDOL
# --------------------------------------------------------------------

class QdolLayout(NamedTuple):
    zeta: int
    pairs: np.ndarray        # [q, 2] partition pair per node (-1 idle)
    part_of: np.ndarray      # [n] vertex -> partition
    node_of_pair: np.ndarray  # [zeta, zeta] -> node id


def qdol_layout(n: int, q: int) -> QdolLayout:
    """ζ = largest integer with C(ζ,2) ≤ q (paper's ζ=(1+√(1+8q))/2)."""
    zeta = max(2, int((1 + np.sqrt(1 + 8 * q)) / 2))
    while zeta * (zeta - 1) // 2 > q:
        zeta -= 1
    pairs = np.full((q, 2), -1, dtype=np.int32)
    node_of_pair = np.zeros((zeta, zeta), dtype=np.int32)
    k = 0
    for i in range(zeta):
        for j in range(i + 1, zeta):
            pairs[k] = (i, j)
            node_of_pair[i, j] = node_of_pair[j, i] = k
            k += 1
    for i in range(zeta):                      # same-partition queries →
        node_of_pair[i, i] = node_of_pair[i, (i + 1) % zeta]
    part_of = (np.arange(n) * zeta // max(1, n)).astype(np.int32)
    return QdolLayout(zeta=zeta, pairs=pairs, part_of=part_of,
                      node_of_pair=node_of_pair)


class QdolStore(NamedTuple):
    hubs: Array    # [q, S, L] rows of the 2 owned partitions
    dist: Array    # [q, S, L]
    slot: Array    # [q, n] vertex -> local row (-1 absent)


def qdol_build(table: LabelTable, layout: QdolLayout, mesh: Mesh
               ) -> QdolStore:
    """Materialize per-node overlapping label stores from a full table."""
    n, L = table.hubs.shape
    q = layout.pairs.shape[0]
    sizes = np.bincount(layout.part_of, minlength=layout.zeta)
    S = int(sizes.max()) * 2
    hubs = np.full((q, S, L), -1, dtype=np.int32)
    dist = np.full((q, S, L), np.inf, dtype=np.float32)
    slot = np.full((q, n), -1, dtype=np.int32)
    th = np.asarray(table.hubs)
    td = np.asarray(table.dist)
    for k in range(q):
        i, j = layout.pairs[k]
        if i < 0:
            continue
        verts = np.nonzero((layout.part_of == i) | (layout.part_of == j))[0]
        hubs[k, :len(verts)] = th[verts]
        dist[k, :len(verts)] = td[verts]
        slot[k, verts] = np.arange(len(verts), dtype=np.int32)
    sh = NamedSharding(mesh, P("node"))      # placed from host memory
    return QdolStore(hubs=jax.device_put(hubs, sh),
                     dist=jax.device_put(dist, sh),
                     slot=jax.device_put(slot, sh))


def qdol_fn(mesh: Mesh, layout: QdolLayout):
    node_of_pair = jnp.asarray(layout.node_of_pair)
    part_of = jnp.asarray(layout.part_of)

    def step(store: QdolStore, u: Array, v: Array) -> Array:
        hubs, dist, slot = store.hubs[0], store.dist[0], store.slot[0]
        me = jax.lax.axis_index("node")
        target = node_of_pair[part_of[u], part_of[v]]
        su = slot[u]
        sv = slot[v]
        ok = (target == me) & (su >= 0) & (sv >= 0)
        su = jnp.where(ok, su, 0)
        sv = jnp.where(ok, sv, 0)
        hu, du = hubs[su], dist[su]                  # [Q, L]
        hv, dv = hubs[sv], dist[sv]
        match = (hu[:, :, None] == hv[:, None, :]) & (hu[:, :, None] >= 0)
        dd = jnp.where(match, du[:, :, None] + dv[:, None, :], jnp.inf)
        ans = jnp.min(dd, axis=(1, 2))
        ans = jnp.where(ok, ans, jnp.inf)
        return jax.lax.pmin(ans, "node")             # exactly 1 responder

    return jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(QdolStore(P("node"), P("node"), P("node")), P(), P()),
        out_specs=P(), check_replication=False))


def label_memory_bytes(table: LabelTable) -> int:
    """Bytes to store the (hub,dist) pairs actually present."""
    return int(np.asarray(jnp.sum(table.count))) * 8


def mode_memory_totals(n: int, base_bytes: int, q: int) -> dict:
    """Per-mode total label storage across the cluster (Table 4),
    from the resident label bytes alone — store backends report this
    without materializing a dense table."""
    zeta = qdol_layout(n, q).zeta
    return {
        "qlsn_total": base_bytes * q,         # replicated everywhere
        "qfdl_total": base_bytes,             # partitioned by hub
        # each of C(ζ,2) nodes stores ≈ 2·base/ζ → total ≈ base·(ζ-1)
        "qdol_total": base_bytes * (zeta - 1),
        "q": q, "zeta": zeta,
    }


def mode_memory_report(table: LabelTable, q: int) -> dict:
    """Table-4 memory report for a dense label table."""
    return mode_memory_totals(table.hubs.shape[0],
                              label_memory_bytes(table), q)
