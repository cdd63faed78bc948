"""Emission sinks — where a policy's committed labels land.

The engine separates *how labels are produced* (the policy) from
*where they live while being produced* (the sink). Three residencies:

- :class:`DenseSink` — one padded ``LabelTable`` per channel (the
  classic single-host build; directed builds use two channels).
  Overflow accumulates on device and is checked at commit points, so
  the dispatch pipeline never blocks mid-superstep.
- :class:`StreamingShardSink` — emissions hub-partitioned straight
  into per-shard host arrays (``repro.parallel.sharding
  .ShardAccumulator``); the dense ``[n, cap]`` table is never
  materialized, per-shard caps regrow independently, and overflow
  cannot happen.
- :class:`MeshTableSink` — the distributed ``[q, n, L]``
  hub-partitioned device table (§5.1); insertion happens *inside* the
  policy's ``shard_map`` superstep, so the sink only tracks the table
  reference, its overflow verdicts, and the checkpoint payload.

Every sink exposes the same checkpoint protocol (``state_arrays`` /
``load_state`` / ``meta``), which is how checkpoint/resume works for
every algorithm instead of just the distributed driver.
"""

from __future__ import annotations

from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelOverflowError, LabelTable
from repro.parallel.sharding import ShardAccumulator

Array = jax.Array


def _pad_table_arrays(hubs: np.ndarray, dist: np.ndarray,
                      cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """Widen restored ``[..., L_saved]`` label arrays to ``cap`` —
    the regrow-resume path (a checkpoint written under a smaller cap
    stays usable after ``build`` grows the capacity)."""
    have = hubs.shape[-1]
    if have > cap:
        raise ValueError(f"cannot shrink label arrays {have} -> {cap}")
    if have == cap:
        return hubs, dist
    pad = [(0, 0)] * (hubs.ndim - 1) + [(0, cap - have)]
    return (np.pad(hubs, pad, constant_values=-1),
            np.pad(dist, pad, constant_values=np.inf))


class DenseSink:
    """One (or more, for directed builds) dense ``LabelTable``."""

    kind = "dense"

    def __init__(self, n: int, cap: int,
                 channels: Sequence[str] = ("labels",)):
        self.n = int(n)
        self.cap = int(cap)
        self.channels = tuple(channels)
        self.tables: Dict[str, LabelTable] = {
            ch: lbl.empty(self.n, self.cap) for ch in self.channels}
        self._ovf = jnp.zeros((), dtype=bool)

    def insert(self, roots: Array, emit: Array, dist: Array,
               channel: Optional[str] = None) -> None:
        ch = channel or self.channels[0]
        self.tables[ch], ovf = lbl.insert_batch(
            self.tables[ch], roots, emit, dist)
        self._ovf = self._ovf | ovf

    def note_overflow(self, flag: Array) -> None:
        """Fold in an overflow verdict from outside the sink (e.g. a
        policy's local scratch table)."""
        self._ovf = self._ovf | flag

    def table(self, channel: Optional[str] = None) -> LabelTable:
        return self.tables[channel or self.channels[0]]

    def overflowed(self) -> bool:
        return bool(self._ovf)          # one host sync

    def raise_on_overflow(self) -> None:
        if self.overflowed():
            raise LabelOverflowError(self.cap)

    # --------------------------------------------- checkpoint payload

    def meta(self) -> dict:
        return {"kind": self.kind, "cap": self.cap, "n": self.n,
                "channels": list(self.channels)}

    def state_arrays(self) -> Dict[str, np.ndarray]:
        out: Dict[str, np.ndarray] = {}
        for ch, t in self.tables.items():
            out[f"{ch}_hubs"] = np.asarray(t.hubs)
            out[f"{ch}_dist"] = np.asarray(t.dist)
            out[f"{ch}_count"] = np.asarray(t.count)
        return out

    def load_state(self, arrays: Dict[str, np.ndarray]) -> None:
        for ch in self.channels:
            hubs, dist = _pad_table_arrays(
                np.asarray(arrays[f"{ch}_hubs"]),
                np.asarray(arrays[f"{ch}_dist"]), self.cap)
            self.tables[ch] = LabelTable(
                jnp.asarray(hubs), jnp.asarray(dist),
                jnp.asarray(np.asarray(arrays[f"{ch}_count"])))


class StreamingShardSink:
    """Hub-partitioned streaming residency (never a dense table).

    Each committed superstep's emission planes are fetched host-side
    once and appended to the owning shard's arrays. Per-shard caps
    regrow geometrically and independently, so there is no
    ``LabelOverflowError`` on this path — the cap ceiling is a
    property of the padded dense layout, not of the labeling.
    """

    kind = "sharded"

    def __init__(self, n: int, rank: np.ndarray, num_shards: int):
        self.n = int(n)
        self.cap = None                 # no fixed cap on this path
        self.acc = ShardAccumulator(n, rank, num_shards)
        self.num_shards = self.acc.num_shards

    def insert(self, roots: Array, emit: Array, dist: Array,
               channel: Optional[str] = None,
               valid: Optional[Array] = None) -> None:
        assert channel in (None, "labels")
        roots_h = np.asarray(roots)
        valid_h = (np.ones(len(roots_h), bool) if valid is None
                   else np.asarray(valid))
        self.acc.insert(roots_h, valid_h, np.asarray(emit),
                        np.asarray(dist))

    def note_overflow(self, flag) -> None:      # pragma: no cover
        del flag                       # shard caps regrow; nothing to do

    def overflowed(self) -> bool:
        return False

    def raise_on_overflow(self) -> None:
        return None

    def shard_arrays(self):
        return self.acc.shard_arrays()

    @property
    def total_labels(self) -> int:
        return self.acc.total_labels

    # --------------------------------------------- checkpoint payload

    def meta(self) -> dict:
        return {"kind": self.kind, "cap": None, "n": self.n,
                "shards": self.num_shards}

    def state_arrays(self) -> Dict[str, np.ndarray]:
        return self.acc.state_arrays()

    def load_state(self, arrays: Dict[str, np.ndarray]) -> None:
        self.acc.load_state(arrays)


class MeshTableSink:
    """The distributed ``[q, n, L]`` hub-partitioned device table.

    The policy's ``shard_map`` superstep inserts into the table
    in-place-functionally and hands the new table back via
    :meth:`set_table`; the sink owns placement, overflow verdicts and
    the checkpoint payload so the engine can treat distributed builds
    like any other.
    """

    kind = "mesh"

    def __init__(self, mesh, n: int, cap: int):
        from jax.sharding import NamedSharding, PartitionSpec as P
        self.mesh = mesh
        self.n = int(n)
        self.cap = int(cap)
        self.q = int(mesh.devices.size)
        from repro.core.dgll import empty_partitions
        self._node_sh = NamedSharding(mesh, P("node"))
        # placed from host memory: each device receives its partition
        self.table = LabelTable(*(
            jax.device_put(x, self._node_sh)
            for x in empty_partitions(self.q, self.n, self.cap)))
        self._host_ovf = False

    def set_table(self, table: LabelTable) -> None:
        self.table = table

    def note_overflow(self, flag: bool) -> None:
        self._host_ovf = self._host_ovf or bool(flag)

    def overflowed(self) -> bool:
        return self._host_ovf

    def raise_on_overflow(self) -> None:
        if self._host_ovf:
            raise LabelOverflowError(self.cap)

    # --------------------------------------------- checkpoint payload

    def meta(self) -> dict:
        return {"kind": self.kind, "cap": self.cap, "n": self.n,
                "q": self.q}

    def state_arrays(self) -> Dict[str, np.ndarray]:
        return {"hubs": np.asarray(self.table.hubs),
                "dist": np.asarray(self.table.dist),
                "count": np.asarray(self.table.count)}

    def load_state(self, arrays: Dict[str, np.ndarray]) -> None:
        hubs, dist = _pad_table_arrays(np.asarray(arrays["hubs"]),
                                       np.asarray(arrays["dist"]),
                                       self.cap)
        self.table = LabelTable(
            *(jax.device_put(x, self._node_sh)
              for x in (hubs, dist, np.asarray(arrays["count"]))))
