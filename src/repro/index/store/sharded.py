"""`ShardedStore` — labels partitioned by hub rank into K shards.

The paper's §5.1 collaborative partitioning made the *first-class*
representation instead of a serving-time view: shard ``k`` holds, for
every vertex, exactly the labels whose hub it owns
(``order_index(hub) mod K``). A PPSD query is K per-shard partial
intersections plus one cross-shard ``min`` — exact, because every
common hub of a pair is intersected in exactly one shard and f32
``min`` is order-insensitive.

Residency: the stacked ``[K, n, Ls]`` arrays are kept in host memory
and placed on a device only by the path that serves from it. The
single-device path copies them to the default device on first use and
answers through a vmapped partial-min + reduce (time-multiplexed);
:meth:`as_partitioned` places shard ``k`` on device ``k`` of a mesh
straight from host memory — no chip ever holds the whole table — so
``repro.core.query.qfdl_fn`` runs the same computation as a real
``shard_map`` + ``pmin``, the QFDL mode served from the store's own
layout rather than a synthesized copy.
"""

from __future__ import annotations

from typing import Dict, Iterator, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelTable
from repro.index.store.dense import DenseStore
from repro.parallel.sharding import hub_partition_arrays


@jax.jit
def _stacked_query(hubs, dist, count, u, v):
    """Per-shard partial PPSD mins over [K, n, Ls], one cross-shard
    reduce. Bit-identical to the dense answer (disjoint hub subsets)."""
    def one(h, d, c):
        return lbl.query_pairs(LabelTable(h, d, c), u, v)

    ds, hs = jax.vmap(one)(hubs, dist, count)          # [K, Q]
    best = jnp.min(ds, axis=0)
    k = jnp.argmin(ds, axis=0)
    hub = jnp.take_along_axis(hs, k[None, :], axis=0)[0]
    return best, jnp.where(jnp.isfinite(best), hub, -1)


@jax.jit
def _one_shard_query(hubs, dist, count, u, v):
    """Partial PPSD mins over a single shard's [n, Ls] arrays —
    the per-shard routed serving path."""
    return lbl.query_pairs(LabelTable(hubs, dist, count), u, v)


class ShardedStore:
    kind = "sharded"

    def __init__(self, hubs, dist, count):
        """``hubs`` i32 [K, n, Ls], ``dist`` f32 [K, n, Ls],
        ``count`` i32 [K, n] — shard-major stacked label arrays (kept
        in host memory)."""
        self.hubs = np.asarray(hubs, dtype=np.int32)
        self.dist = np.asarray(dist, dtype=np.float32)
        self.count = np.asarray(count, dtype=np.int32)
        if self.hubs.ndim != 3 or self.count.ndim != 2:
            raise ValueError("ShardedStore wants [K, n, Ls] labels and "
                             "[K, n] counts")
        self._on_device: Optional[Tuple[jax.Array, ...]] = None
        # per-shard [n, Ls] device arrays, placed lazily for the routed
        # serving path (one transfer per shard, not one per launch)
        self._shard_views: Dict[int, Tuple] = {}
        self._placed: Dict[object, LabelTable] = {}

    # ---------------------------------------------------- protocol

    @property
    def n(self) -> int:
        return self.hubs.shape[1]

    @property
    def num_shards(self) -> int:
        return self.hubs.shape[0]

    @property
    def shard_cap(self) -> int:
        return self.hubs.shape[2]

    @property
    def total_labels(self) -> int:
        return int(self.count.sum())

    def query(self, u, v) -> Tuple[np.ndarray, np.ndarray]:
        d, h = self.query_device(u, v)
        return np.asarray(d), np.asarray(h)

    def query_device(self, u, v) -> Tuple[jax.Array, jax.Array]:
        """Full K-shard reduction, staying on device (jitted) — the
        serving-path variant of :meth:`query` (no host round trip per
        batch)."""
        if self._on_device is None:
            self._on_device = (jnp.asarray(self.hubs),
                               jnp.asarray(self.dist),
                               jnp.asarray(self.count))
        u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
        v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
        return _stacked_query(*self._on_device, u, v)

    def shard_counts(self) -> np.ndarray:
        """Host ``[K, n]`` per-shard label counts — the routing table
        for per-shard query dispatch (shard k can contribute to
        ``(u, v)`` only when both endpoints hold labels in k)."""
        return self.count

    def query_shard(self, k: int, u, v) -> Tuple[np.ndarray, np.ndarray]:
        """Partial PPSD mins over shard ``k`` only (jitted; +inf/-1
        where shard k holds no common hub). Exact per-shard routing:
        skipping shards where either endpoint has zero labels drops
        only +inf contributions from the cross-shard min."""
        views = self._shard_views.get(k)
        if views is None:
            views = (jnp.asarray(self.hubs[k]), jnp.asarray(self.dist[k]),
                     jnp.asarray(self.count[k]))
            self._shard_views[k] = views
        u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
        v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
        d, h = _one_shard_query(*views, u, v)
        return np.asarray(d), np.asarray(h)

    def to_table(self) -> LabelTable:
        return DenseStore.from_shard_arrays(
            arrs for _, arrs in self.shard_arrays()).to_table()

    def shard_arrays(self) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
        hubs, dist, count = self.hubs, self.dist, self.count
        for k in range(self.num_shards):
            # trim each shard to its own tight cap — per-shard files
            # should not pay the widest shard's padding
            cap = int(max(1, count[k].max()))
            yield k, {"hubs": hubs[k, :, :cap], "dist": dist[k, :, :cap],
                      "count": count[k]}

    def label_bytes(self) -> int:
        return self.total_labels * 8

    def shard_label_bytes(self) -> list:
        """Per-shard resident label bytes (capacity-planning view)."""
        return [int(c) * 8 for c in self.count.sum(axis=1)]

    # ------------------------------------------------------ serving

    def as_partitioned(self, mesh) -> LabelTable:
        """The stacked arrays as a mesh-placed ``[K, n, Ls]``
        LabelTable (shard k on device k, placed from host memory) for
        ``qfdl_fn`` — requires ``mesh`` size == ``num_shards``. Placed
        once per mesh; later calls return the same arrays."""
        from jax.sharding import NamedSharding, PartitionSpec as P
        if int(mesh.devices.size) != self.num_shards:
            raise ValueError(
                f"mesh has {int(mesh.devices.size)} devices but the "
                f"store has {self.num_shards} shards")
        placed = self._placed.get(mesh)
        if placed is None:
            sh = NamedSharding(mesh, P("node"))
            placed = LabelTable(*(jax.device_put(x, sh) for x in
                                  (self.hubs, self.dist, self.count)))
            self._placed[mesh] = placed
        return placed

    # ------------------------------------------------- constructors

    @classmethod
    def from_table(cls, table: LabelTable, rank: np.ndarray,
                   num_shards: int) -> "ShardedStore":
        """Partition a dense table by hub ownership (§5.1 layout)."""
        h, d, c = hub_partition_arrays(table.hubs, table.dist, rank,
                                       num_shards)
        return cls(h, d, c)

    @classmethod
    def from_accumulator(cls, acc) -> "ShardedStore":
        """Adopt an incrementally built hub partition (the engine's
        streaming emission sink — ``repro.parallel.sharding
        .ShardAccumulator``) without ever materializing the dense
        ``[n, cap]`` table; per-shard caps stay tight."""
        return cls.from_shard_arrays(
            arrs for _, arrs in acc.shard_arrays())

    @classmethod
    def from_shard_arrays(cls, shards) -> "ShardedStore":
        """Stack per-shard ``{hubs, dist, count}`` dicts (ragged
        per-shard caps are padded to the widest)."""
        shards = list(shards)
        caps = [np.asarray(s["hubs"]).shape[1] for s in shards]
        Ls = max([1] + caps)
        hubs, dist, count = [], [], []
        for s in shards:
            h = np.asarray(s["hubs"])
            d = np.asarray(s["dist"])
            pad = Ls - h.shape[1]
            if pad:
                h = np.pad(h, ((0, 0), (0, pad)), constant_values=-1)
                d = np.pad(d, ((0, 0), (0, pad)),
                           constant_values=np.inf)
            hubs.append(h)
            dist.append(d)
            count.append(np.asarray(s["count"]))
        return cls(np.stack(hubs), np.stack(dist), np.stack(count))
