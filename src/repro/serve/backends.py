"""Storage-mode wiring for PPSD query serving (QLSN / QFDL / QDOL).

One place that knows how to turn a **label store** into an
``answer(u, v) -> dist`` callable for each of the paper's §6.3 storage
modes — previously open-coded in the ``QueryServer`` build shim and
re-open-coded by every example/benchmark. ``CHLIndex.serve`` and the
deprecated shim both route through here; nothing in this module
reaches into a store's internal arrays except through the
``repro.index.store`` protocol.

Per store backend:

- **DenseStore** (and bare ``LabelTable``s, auto-wrapped):
  - *qlsn*: replicated table, local intersection;
  - *qfdl*: hub-partitioned ``[q, n, L]`` table + ``pmin`` reduce. If
    no construction-time partitioned table is supplied, one is
    synthesized by round-robin hub ownership (the construction layout
    of §5.1: ``owner(h) = order_index(h) mod q``);
  - *qdol*: ζ-partition overlapping stores; layout + store are built
    here so callers never touch ``qdol_layout``/``qdol_build``.
- **ShardedStore**: the store's own hub partitions answer the query —
  QFDL made real instead of synthesized. When the mesh size matches
  the shard count, shard k lives on device k and ``qfdl_fn`` runs the
  partial-min + ``pmin`` as a ``shard_map``; otherwise the identical
  computation runs time-multiplexed on one device (vmapped partial
  mins + one reduction), jitted end to end — the batch never bounces
  through host numpy.
  *qdol* materializes the dense table once (the ζ-overlap layout
  needs full label rows).
- **SpillStore**: QLSN from the memory-mapped shard segments (host
  numpy — capacity over latency). The distributed modes need labels
  in device memory; asking for them raises with guidance.
- **CompressedStore**: QLSN straight from the encoded shards — the
  store's jitted gather→dequant→intersect keeps labels narrow at rest
  and all arithmetic f32 (bit-identical to dense in the codec's exact
  mode). *qfdl*/*qdol* dequantize into a dense table once (the
  distributed layouts want f32 rows); compression is a residency
  choice, never a compute-dtype choice.

**Per-shard routing** (``routed=``): for multi-shard sharded/spill/
compressed QLSN, the answer fn from ``repro.serve.routing`` touches
only the shards in which *both* endpoints hold labels, instead of
reducing over all K — bit-identical (skipped shards contribute only
+inf) and the serving tier's default. ``routed=None`` picks automatically;
``True``/``False`` force it (``False`` = the full-reduction paths
above, which parity tests compare against).
"""

from __future__ import annotations

from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import query as qm
from repro.core.labels import LabelTable
from repro.index.store import (CompressedStore, DenseStore, LabelStore,
                               ShardedStore, SpillStore)
from repro.parallel.sharding import hub_partition_arrays

MODES = ("qlsn", "qfdl", "qdol")

AnswerFn = Callable[[jax.Array, jax.Array], jax.Array]


def partition_by_hub(table: LabelTable, rank: np.ndarray, mesh
                     ) -> LabelTable:
    """Synthesize the QFDL ``[q, n, L]`` hub-partitioned table from a
    merged table: node ``i`` keeps exactly the labels whose hub it
    would have generated (rank-order round-robin, §5.1)."""
    q = int(mesh.devices.size)
    n, L = table.hubs.shape
    hubs, dist, count = hub_partition_arrays(
        np.asarray(table.hubs), np.asarray(table.dist), rank, q,
        shard_cap=L)
    sh = NamedSharding(mesh, P("node"))      # placed from host memory
    return LabelTable(*(jax.device_put(x, sh)
                        for x in (hubs, dist, count)))


def _as_store(store_or_table: Union[LabelStore, LabelTable]) -> LabelStore:
    if isinstance(store_or_table, LabelTable):
        return DenseStore(store_or_table)
    return store_or_table


def _dense_answer_fn(table: LabelTable, mode: str, *, mesh,
                     partitioned: Optional[LabelTable],
                     rank: Optional[np.ndarray]) -> AnswerFn:
    if mode == "qlsn":
        return jax.jit(lambda u, v: qm.qlsn(table, u, v))
    if mesh is None:
        from repro.core.dgll import make_node_mesh
        mesh = make_node_mesh()
    if mode == "qfdl":
        if partitioned is None:
            if rank is None:
                raise ValueError(
                    "qfdl needs `partitioned` or `rank` to lay out the "
                    "hub partitions")
            partitioned = partition_by_hub(table, rank, mesh)
        f = qm.qfdl_fn(mesh)
        return lambda u, v: f(partitioned, u, v)
    # qdol
    layout = qm.qdol_layout(table.hubs.shape[0], int(mesh.devices.size))
    store = qm.qdol_build(table, layout, mesh)
    f = qm.qdol_fn(mesh, layout)
    return lambda u, v: f(store, u, v)


def _sharded_answer_fn(store: ShardedStore, mode: str, *, mesh,
                       partitioned: Optional[LabelTable],
                       rank: Optional[np.ndarray]) -> AnswerFn:
    if mode == "qfdl" and mesh is not None \
            and int(mesh.devices.size) == store.num_shards:
        # the real thing: shard k on device k, partial min + pmin
        part = store.as_partitioned(mesh)
        f = qm.qfdl_fn(mesh)
        return lambda u, v: f(part, u, v)
    if mode in ("qlsn", "qfdl"):
        # same partial-min + cross-shard reduction, time-multiplexed
        # on the local device(s) — jitted end to end, no host bounce
        return lambda u, v: store.query_device(u, v)[0]
    # qdol needs full label rows per vertex — materialize once
    return _dense_answer_fn(store.to_table(), mode, mesh=mesh,
                            partitioned=partitioned, rank=rank)


def make_answer_fn(store: Union[LabelStore, LabelTable],
                   mode: str = "qlsn", *,
                   mesh=None, partitioned: Optional[LabelTable] = None,
                   rank: Optional[np.ndarray] = None,
                   routed: Optional[bool] = None) -> AnswerFn:
    """Answer callable for a storage mode; absorbs mesh/layout/store
    ceremony. Accepts any ``repro.index.store`` backend (bare
    ``LabelTable``s are wrapped dense). ``mesh`` defaults to all local
    devices for the distributed modes; ``partitioned``
    (construction-time layout) is synthesized from ``rank`` when
    absent. ``routed`` turns on per-shard query routing (see module
    docstring); ``None`` = auto (on for multi-shard sharded/spill
    QLSN, off elsewhere)."""
    if mode not in MODES:
        raise ValueError(f"unknown query mode {mode!r}; one of {MODES}")
    store = _as_store(store)
    routable = (isinstance(store, (ShardedStore, SpillStore,
                                   CompressedStore))
                and store.num_shards > 1 and mode == "qlsn")
    if routed is None:
        routed = routable
    elif routed and not routable:
        routed = False        # routing degenerates: fall through to
        # the plain paths (single shard / dense / distributed modes)
    if routed:
        from repro.serve.routing import make_routed_answer_fn
        return make_routed_answer_fn(store)
    if isinstance(store, SpillStore):
        if mode != "qlsn":
            raise NotImplementedError(
                f"mode {mode!r} needs labels in device memory; a spill "
                "store serves qlsn only — reload with store='dense' or "
                "'sharded' for the distributed modes")
        return lambda u, v: jnp.asarray(
            store.query(np.asarray(u), np.asarray(v))[0])
    if isinstance(store, CompressedStore):
        if mode == "qlsn":
            # serve from the encoded shards: decode happens inside the
            # store's query jit, per touched row — never a dense copy
            return lambda u, v: store.query_device(u, v)[0]
        # distributed layouts want dense f32 rows — dequantize once
        return _dense_answer_fn(store.to_table(), mode, mesh=mesh,
                                partitioned=partitioned, rank=rank)
    if isinstance(store, ShardedStore):
        return _sharded_answer_fn(store, mode, mesh=mesh,
                                  partitioned=partitioned, rank=rank)
    return _dense_answer_fn(store.to_table(), mode, mesh=mesh,
                            partitioned=partitioned, rank=rank)
