"""`DenseStore` — one in-memory LabelTable; the default / v1 path.

Wraps the table the constructors produce. Everything is delegated to
``repro.core.labels``, so a v1 artifact loaded into a DenseStore
answers queries bit-identically to the pre-store ``CHLIndex``.
"""

from __future__ import annotations

from typing import Dict, Iterator, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelTable


class DenseStore:
    kind = "dense"

    def __init__(self, table: LabelTable):
        self._table = table

    # ---------------------------------------------------- protocol

    @property
    def n(self) -> int:
        return self._table.n

    @property
    def num_shards(self) -> int:
        return 1

    @property
    def total_labels(self) -> int:
        return lbl.total_labels(self._table)

    def query(self, u, v) -> Tuple[np.ndarray, np.ndarray]:
        with jax.profiler.TraceAnnotation("store.put"):
            u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
            v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
        d, h = lbl.query_pairs(self._table, u, v)
        with jax.profiler.TraceAnnotation("store.fetch"):
            return np.asarray(d), np.asarray(h)

    def shard_counts(self) -> np.ndarray:
        """``[1, n]`` label counts (routing degenerates for one shard)."""
        return np.asarray(self._table.count)[None]

    def query_shard(self, k: int, u, v) -> Tuple[np.ndarray, np.ndarray]:
        if k != 0:
            raise IndexError(f"dense store has one shard, not {k + 1}")
        return self.query(u, v)

    def to_table(self) -> LabelTable:
        return self._table

    def shard_arrays(self) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
        t = self._table
        yield 0, {"hubs": np.asarray(t.hubs),
                  "dist": np.asarray(t.dist),
                  "count": np.asarray(t.count)}

    def label_bytes(self) -> int:
        return self.total_labels * 8

    # ------------------------------------------------- constructors

    @classmethod
    def from_shard_arrays(cls, shards) -> "DenseStore":
        """Merge per-shard ``{hubs, dist, count}`` dicts back into one
        dense table (loading a sharded artifact with ``store="dense"``)."""
        shards = list(shards)
        if len(shards) == 1:
            s = shards[0]
            return cls(LabelTable(jnp.asarray(s["hubs"]),
                                  jnp.asarray(s["dist"]),
                                  jnp.asarray(s["count"])))
        h2 = np.concatenate([np.asarray(s["hubs"]) for s in shards],
                            axis=1)
        d2 = np.concatenate([np.asarray(s["dist"]) for s in shards],
                            axis=1)
        valid = h2 >= 0
        order = np.argsort(~valid, axis=1, kind="stable")  # keepers first
        h2 = np.take_along_axis(h2, order, axis=1)
        d2 = np.take_along_axis(d2, order, axis=1)
        count = valid.sum(axis=1).astype(np.int32)
        cap = int(max(1, count.max()))
        return cls(LabelTable(jnp.asarray(h2[:, :cap]),
                              jnp.asarray(d2[:, :cap]),
                              jnp.asarray(count)))
