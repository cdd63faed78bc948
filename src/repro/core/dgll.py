"""DGLL — Distributed GLL over a device mesh (§5.1, §5.3).

Faithful mapping of the paper's MPI design onto `shard_map`
(DESIGN.md §2 A4):

- roots assigned round-robin by rank: node ``i`` owns ``TQ_i = {v :
  order_index(v) mod q == i}``;
- **label-set partitioning**: node ``i`` stores only labels whose hub it
  generated (the collaborative-memory contribution, P2). Tables are a
  ``[q, n, L]`` array sharded on axis 0;
- supersteps grow geometrically by ``β`` (synchronization points set
  apriori, §5.1 optimization 2);
- superstep sync: new labels are all-gathered (the paper's broadcast);
  every node answers all cleaning queries against *its* partition
  (witness hub ``w`` lives on ``owner(w)`` — both ``(w→v)`` and
  ``(w→h)`` labels are there), and the per-node best-witness ranks are
  combined with ``lax.pmax`` — the paper's redundancy-bitvector
  all-reduce;
- optional **Common Label Table** (§5.3): labels of the top-η hubs
  replicated on every node, used for construction-time distance-query
  pruning (and for pruning PLaNTed trees in the Hybrid).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import make_mesh, shard_map
from repro.core import labels as lbl
from repro.core.labels import LabelTable
from repro.core.gll import construct_batch
from repro.core.plant import plant_batch
from repro.sssp import relax

Array = jax.Array


def make_node_mesh(q: Optional[int] = None) -> Mesh:
    """1-D mesh over up to ``q`` local devices, axis name ``node``."""
    devs = jax.devices()
    q = len(devs) if q is None else min(q, len(devs))
    return make_mesh((q,), ("node",))


def assign_roots(rank: np.ndarray, q: int) -> np.ndarray:
    """Round-robin root queues: ``queues[i, k]`` = k-th root of node i
    (descending rank), padded with -1. Paper §5.1: R(v) mod q = i."""
    order = np.argsort(-rank.astype(np.int64), kind="stable")
    n = len(order)
    per = -(-n // q)
    queues = np.full((q, per), -1, dtype=np.int32)
    for i in range(q):
        chunk = order[i::q]
        queues[i, :len(chunk)] = chunk
    return queues


class SuperstepOut(NamedTuple):
    table: LabelTable      # [q, n, L] partitioned
    new_labels: Array      # i32 [q] labels committed this superstep
    explored: Array        # i32 [q] vertices touched (Ψ numerator)
    overflow: Array        # bool [q] — label-table capacity exceeded
    compact_overflow: Array  # bool [q] — §Perf-2 broadcast budget hit


def _squeeze_table(t: LabelTable) -> LabelTable:
    return LabelTable(t.hubs[0], t.dist[0], t.count[0])


def _expand_table(t: LabelTable) -> LabelTable:
    return LabelTable(t.hubs[None], t.dist[None], t.count[None])


def dgll_superstep_fn(mesh: Mesh, n: int, batch: int, use_hc: bool,
                      plant_trees: bool, compact: int = 0):
    """Build the jitted shard_map superstep.

    ``plant_trees=True`` gives the Hybrid's PLaNT phase: construction by
    PLaNT (optionally HC-pruned), labels already canonical — **no
    gather, no cleaning, no collectives** (asserted in tests on the
    lowered HLO). Otherwise: DGLL construction + broadcast cleaning.

    ``compact > 0`` (§Perf-2): broadcast *actual labels* instead of the
    dense [T, n] emission planes — each tree ships at most ``compact``
    (vertex, distance) pairs (the paper's own design: it exchanges
    labels, not bitmaps over V). Cleaning switches from dense
    cover maps to pairwise label-row intersections. Trees emitting
    more than ``compact`` labels raise the overflow flag (callers size
    ``compact`` from the superstep's expected per-tree yield — small
    by Fig. 2 once DGLL mode starts).
    """
    specs_table = LabelTable(P("node"), P("node"), P("node"))
    hc_spec = LabelTable(P(), P(), P())
    in_specs = (specs_table, hc_spec, P(), P("node"), P("node"))
    out_specs = SuperstepOut(specs_table, P("node"), P("node"),
                             P("node"), P("node"))

    def step(table: LabelTable, hc: LabelTable, rank: Array,
             roots: Array, valid: Array, ell_src: Array, ell_w: Array
             ) -> SuperstepOut:
        # per-shard views: roots [1, T] -> [T]
        table = _squeeze_table(table)
        roots, valid = roots[0], valid[0]
        T = roots.shape[0]
        assert T % batch == 0
        emits, dists = [], []
        work = table
        explored = jnp.int32(0)
        for s in range(0, T, batch):
            rb, vb = roots[s:s + batch], valid[s:s + batch]
            rb_safe = jnp.where(rb >= 0, rb, 0)
            vb = vb & (rb >= 0)
            if plant_trees:
                tb = plant_batch(ell_src, ell_w, rank, rb_safe, vb,
                                 hc=hc if use_hc else None, use_hc=use_hc)
                emit, dist, exp = tb.emit, tb.dist, tb.explored
            else:
                bl = construct_batch(
                    ell_src, ell_w, rank, rb_safe, vb,
                    work, hc if use_hc else lbl.empty(n, 1),
                    rank_queries=True)
                emit, dist = bl.emit, bl.dist
                exp = jnp.sum(jnp.isfinite(dist), axis=-1,
                              dtype=jnp.int32)
            emits.append(emit)
            dists.append(dist)
            explored += jnp.sum(jnp.where(vb, exp, 0))
            # tentative insert so later batches this superstep can prune
            work, _ = lbl.insert_batch(work, rb_safe, emit, dist)
        emit = jnp.concatenate(emits)      # [T, n]
        dist = jnp.concatenate(dists)

        ovf_extra = jnp.zeros((), bool)
        if plant_trees:
            final_emit = emit              # canonical by construction
        elif compact > 0:
            # --- §Perf-2: compact label broadcast -------------------
            # top-`compact` emitted vertices per tree (key favors
            # emitted slots; value 0 ⇒ empty slot)
            key = jnp.where(emit, n - jnp.arange(n)[None, :], 0)
            val, ids = jax.lax.top_k(key, min(compact, n))  # [T, K]
            valid = val > 0
            ovf_extra = jnp.any(
                jnp.sum(emit, axis=1) > jnp.sum(valid, axis=1))
            ids = jnp.where(valid, ids, 0)
            d = jnp.take_along_axis(dist, ids, axis=1)
            d = jnp.where(valid, d, jnp.inf)               # [T, K]
            g_roots = jax.lax.all_gather(roots, "node")    # [q, T]
            g_ids = jax.lax.all_gather(ids, "node")        # [q, T, K]
            g_val = jax.lax.all_gather(valid, "node")
            g_d = jax.lax.all_gather(d, "node")
            Q = g_roots.shape[0]
            fr = jnp.where(g_roots >= 0, g_roots, 0)       # [q, T]
            # pairwise row intersection: witness w ∈ L_v ∩ L_h on this
            # node with d(v,w)+d(h,w) ≤ δ and R(w) > R(h)
            Hv = work.hubs[g_ids]                  # [q, T, K, L]
            Dv = work.dist[g_ids]
            Hh = work.hubs[fr]                     # [q, T, L]
            Dh = work.dist[fr]
            m = (Hv[..., :, None] == Hh[:, :, None, None, :]) & \
                (Hv[..., :, None] >= 0)
            dd = Dv[..., :, None] + Dh[:, :, None, None, :]
            good = m & (dd <= g_d[..., None, None])
            safe = jnp.where(Hv >= 0, Hv, 0)
            wr = jnp.where(good, rank[safe][..., None], -1)
            part = jnp.max(wr, axis=(-2, -1))      # [q, T, K]
            best = jax.lax.pmax(part, "node")
            red = g_val & (best > rank[fr][..., None])
            me = jax.lax.axis_index("node")
            mine_red = jax.lax.dynamic_slice_in_dim(red, me, 1, 0)[0]
            mine_ids = jax.lax.dynamic_slice_in_dim(g_ids, me, 1, 0)[0]
            mine_val = jax.lax.dynamic_slice_in_dim(g_val, me, 1, 0)[0]
            # scatter the redundancy verdicts back onto [T, n]
            drop = jnp.zeros((T, n), bool)
            tt = jnp.broadcast_to(jnp.arange(T)[:, None],
                                  mine_ids.shape)
            flat = jnp.where(mine_val & mine_red,
                             tt * n + mine_ids, T * n)
            drop = drop.reshape(-1).at[flat.reshape(-1)].set(
                True, mode="drop").reshape(T, n)
            final_emit = emit & ~drop
        else:
            # --- broadcast + distributed DQ_Clean (§5.1 sync) ---
            g_roots = jax.lax.all_gather(roots, "node")    # [q, T]
            g_emit = jax.lax.all_gather(emit, "node")      # [q, T, n]
            g_dist = jax.lax.all_gather(dist, "node")
            qT = g_roots.size
            flat_roots = jnp.where(g_roots.reshape(qT) >= 0,
                                   g_roots.reshape(qT), 0)
            flat_emit = g_emit.reshape(qT, n)
            flat_dist = g_dist.reshape(qT, n)
            delta = jnp.where(flat_emit, flat_dist, -jnp.inf)
            hmap = lbl.hub_distance_map(work, flat_roots)  # partial: own w
            part = lbl.cover_best_rank(work, hmap, rank, delta)
            best = jax.lax.pmax(part, "node")              # bitvector Σ
            red = flat_emit & (best > rank[flat_roots][:, None])
            me = jax.lax.axis_index("node")
            mine = jax.lax.dynamic_slice_in_dim(
                red.reshape(g_roots.shape[0], T, n), me, 1, 0)[0]
            final_emit = emit & ~mine

        table, ovf = lbl.insert_batch(table, jnp.where(roots >= 0, roots, 0),
                                      final_emit, dist)
        nl = jnp.sum(final_emit, dtype=jnp.int32)
        return SuperstepOut(table=_expand_table(table),
                            new_labels=nl[None],
                            explored=explored[None],
                            overflow=ovf[None],
                            compact_overflow=ovf_extra[None])

    sm = shard_map(
        lambda t, h, r, ro, va, es, ew: step(t, h, r, ro, va, es, ew),
        mesh=mesh,
        in_specs=in_specs + (P(), P()),
        out_specs=out_specs,
        check_replication=False,
    )
    return jax.jit(sm)


class DistState(NamedTuple):
    table: LabelTable       # [q, n, L] device-sharded by node
    hc: LabelTable          # [n, Lhc] replicated common labels


def init_dist_state(mesh: Mesh, n: int, cap: int, hc_cap: int) -> DistState:
    q = mesh.devices.size
    sh = NamedSharding(mesh, P("node"))
    table = LabelTable(*(jax.device_put(x, sh)
                         for x in empty_partitions(q, n, cap)))
    hc = lbl.empty(n, hc_cap)
    rep = NamedSharding(mesh, P())
    hc = LabelTable(*(jax.device_put(x, rep) for x in hc))
    return DistState(table=table, hc=hc)


def empty_partitions(q: int, n: int, cap: int) -> LabelTable:
    """An empty ``[q, n, cap]`` partitioned table in host memory —
    callers place it with a node sharding, so each device receives only
    its own partition and no chip ever holds the whole table."""
    return LabelTable(hubs=np.full((q, n, cap), -1, dtype=np.int32),
                      dist=np.full((q, n, cap), np.inf, dtype=np.float32),
                      count=np.zeros((q, n), dtype=np.int32))


def merge_partitions(table: LabelTable) -> LabelTable:
    """Collapse a [q, n, L] partitioned table into one [n, q*L] table
    in host memory (numpy arrays; used for validation, re-homing into
    a store, and QLSN)."""
    q, n, L = table.hubs.shape
    hubs = np.asarray(table.hubs).transpose(1, 0, 2).reshape(n, q * L)
    dist = np.asarray(table.dist).transpose(1, 0, 2).reshape(n, q * L)
    valid = hubs >= 0
    order = np.argsort(~valid, axis=1, kind="stable")
    hubs = np.take_along_axis(hubs, order, axis=1)
    dist = np.take_along_axis(dist, order, axis=1)
    count = valid.sum(axis=1).astype(np.int32)
    return LabelTable(hubs, dist, count)


def dgll_chl(g, rank: np.ndarray, *, mesh: Optional[Mesh] = None,
             batch: int = 4, beta: float = 8.0, first_superstep: int = 1,
             cap: Optional[int] = None,
             eta: int = 0, hc_cap: int = 32, compact: int = 0,
             **kw) -> Tuple[LabelTable, dict]:
    """Pure DGLL (optionally with an η-hub Common Label Table).

    Returns the *merged* label table (host view) and stats; the
    device-partitioned table is ``stats["partitioned"]``.
    """
    from repro.core.hybrid import run_distributed   # shared driver
    return run_distributed(g, rank, mesh=mesh, batch=batch, beta=beta,
                           first_superstep=first_superstep, cap=cap,
                           eta=eta, hc_cap=hc_cap, psi_threshold=0.0,
                           compact=compact, algo_name="dgll", **kw)
