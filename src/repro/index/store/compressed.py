"""`CompressedStore` — quantized label residency behind the
``LabelStore`` protocol.

Labels live on device in their *encoded* form — hub ids as first-order
deltas of canonical order indices (``repro.index.quant.deltas``,
u8/u16/u32) and distances under a distance codec
(``repro.index.quant.codecs``, bf16 or fixed-point u16/u32 against a
per-shard scale). Queries gather only the touched rows, dequantize
them to f32 *inside the jit*, and run the exact same intersection as
``labels.query_pairs`` — the storage/computation dtype split: narrow
bytes at rest, full-precision arithmetic always. At 1 byte of hub
delta + 2 bytes of distance code, a label costs 3 bytes instead of
the dense 8 — 2.6x more labels resident before spill kicks in.

Exactness: in the codec's **exact mode** (validated at encode time —
integer-weight graphs) decoded distances are bit-identical to the f32
originals, and because per-row sorting by order index only permutes
the terms of an order-insensitive f32 min, every query answer is
bit-identical to the dense store's. Lossy mode reports the measured
max ulp error (``max_ulp_err``) instead.

Sharding follows §5.1 hub ownership exactly like
:class:`~repro.index.store.sharded.ShardedStore`; shards keep their
own tight caps, delta dtypes and scales (no cross-shard padding).
``shard_arrays`` yields the **encoded** per-shard arrays
(``{"dhub", "dcode", "count"}``) — that is what the artifact writes
and checksums; :meth:`decoded_shard_arrays` is the f32 view for
re-homing and ``to_table``.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.labels import LabelTable, intersect_rows
from repro.ft.inject import fault_site
from repro.index.quant import (decode_dist_jnp, decode_dist_np,
                               delta_decode_rows_jnp,
                               delta_decode_rows_np, delta_encode_rows,
                               encode_dist, order_permutation)
from repro.index.store.base import CorruptArtifactError
from repro.index.store.dense import DenseStore

#: npz member names of one encoded shard (the on-disk v3 layout)
ENCODED_KEYS = ("dhub", "dcode", "count")


@partial(jax.jit, static_argnames="codec")
def _shard_query(dhub, dcode, count, order, scale, u, v, *, codec):
    """Partial PPSD mins over one encoded shard: gather the touched
    rows, dequantize to f32, intersect — the same math as
    ``labels.query_pairs`` after the decode."""
    hu = delta_decode_rows_jnp(dhub[u], count[u], order)
    hv = delta_decode_rows_jnp(dhub[v], count[v], order)
    du = decode_dist_jnp(dcode[u], codec, scale)
    dv = decode_dist_jnp(dcode[v], codec, scale)
    return intersect_rows(hu, du, hv, dv)


class CompressedStore:
    kind = "compressed"

    def __init__(self, shards: List[Dict[str, np.ndarray]],
                 order: np.ndarray, *, codec: str, exact: bool,
                 scales: List[float], max_ulp_err: int = 0):
        """``shards``: per-shard encoded ``{dhub, dcode, count}``;
        ``order``: rank-descending vertex order (position → vertex);
        ``scales``: per-shard fixed-point scales (1.0 under bf16)."""
        if not shards:
            raise ValueError("CompressedStore needs at least one shard")
        if len(scales) != len(shards):
            raise ValueError("one scale per shard required")
        self.codec = codec
        self.exact = exact
        self.scales = [float(s) for s in scales]
        self.max_ulp_err = int(max_ulp_err)
        self._order_np = np.asarray(order, np.int32)
        self._order = jnp.asarray(self._order_np)
        self._shards = [{"dhub": jnp.asarray(s["dhub"]),
                         "dcode": jnp.asarray(s["dcode"]),
                         "count": jnp.asarray(s["count"], jnp.int32)}
                        for s in shards]
        self._counts = [np.asarray(s["count"], np.int32)
                        for s in shards]

    # ---------------------------------------------------- protocol

    @property
    def n(self) -> int:
        return int(self._shards[0]["dhub"].shape[0])

    @property
    def num_shards(self) -> int:
        return len(self._shards)

    @property
    def total_labels(self) -> int:
        return int(sum(int(c.sum()) for c in self._counts))

    def query(self, u, v) -> Tuple[np.ndarray, np.ndarray]:
        d, h = self.query_device(u, v)
        return np.asarray(d), np.asarray(h)

    def query_device(self, u, v) -> Tuple[jax.Array, jax.Array]:
        """Full cross-shard reduction, staying on device — exact for
        the same reason as the sharded store (disjoint hub ownership;
        f32 min is order-insensitive)."""
        u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
        v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
        best = jnp.full(u.shape, jnp.inf, jnp.float32)
        hub = jnp.full(u.shape, -1, jnp.int32)
        for k, s in enumerate(self._shards):
            d, h = _shard_query(s["dhub"], s["dcode"], s["count"],
                                self._order,
                                jnp.float32(self.scales[k]), u, v,
                                codec=self.codec)
            take = d < best
            hub = jnp.where(take, h, hub)
            best = jnp.where(take, d, best)
        return best, hub

    def shard_counts(self) -> np.ndarray:
        """Host ``[K, n]`` per-shard label counts — the routing table
        for per-shard dispatch (identical semantics to the sharded
        store: a skipped shard contributes only +inf terms)."""
        return np.stack(self._counts)

    def query_shard(self, k: int, u, v) -> Tuple[np.ndarray, np.ndarray]:
        """Partial PPSD mins over shard ``k`` only (jitted
        gather→dequant→intersect) — the routed serving path."""
        s = self._shards[k]
        u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
        v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
        d, h = _shard_query(s["dhub"], s["dcode"], s["count"],
                            self._order, jnp.float32(self.scales[k]),
                            u, v, codec=self.codec)
        return np.asarray(d), np.asarray(h)

    def to_table(self) -> LabelTable:
        """Dense f32 materialization (decodes every shard —
        O(total label slots) memory, host-side analysis / re-homing)."""
        return DenseStore.from_shard_arrays(
            arrs for _, arrs in self.decoded_shard_arrays()).to_table()

    def shard_arrays(self) -> Iterator[Tuple[int, Dict[str, np.ndarray]]]:
        """Yield the **encoded** per-shard arrays (``dhub``/``dcode``/
        ``count``) — what the v3 artifact persists and checksums. For
        the decoded f32 view use :meth:`decoded_shard_arrays`."""
        for k, s in enumerate(self._shards):
            yield k, {"dhub": np.asarray(s["dhub"]),
                      "dcode": np.asarray(s["dcode"]),
                      "count": self._counts[k]}

    def decoded_shard_arrays(self
                             ) -> Iterator[Tuple[int,
                                                 Dict[str, np.ndarray]]]:
        """Per-shard dequantized ``{hubs, dist, count}`` (one shard
        resident at a time) — the re-homing/merge view."""
        for k, s in enumerate(self._shards):
            dhub = np.asarray(s["dhub"])
            dcode = np.asarray(s["dcode"])
            hubs = delta_decode_rows_np(dhub, self._counts[k],
                                        self._order_np)
            dist = np.where(hubs >= 0,
                            decode_dist_np(dcode, self.codec,
                                           self.scales[k]),
                            np.float32(np.inf))
            yield k, {"hubs": hubs, "dist": dist.astype(np.float32),
                      "count": self._counts[k]}

    def label_bytes(self) -> int:
        """Bytes of the encoded labels actually present — the number
        the ≥2x-vs-dense compression claim is measured on."""
        return sum(self.shard_label_bytes())

    def shard_label_bytes(self) -> list:
        out = []
        for k, s in enumerate(self._shards):
            per = s["dhub"].dtype.itemsize + s["dcode"].dtype.itemsize
            out.append(int(self._counts[k].sum()) * per)
        return out

    def dtypes(self) -> dict:
        """Storage dtypes per stream (``dhub`` varies per shard)."""
        return {"dhub": [str(np.dtype(s["dhub"].dtype))
                         for s in self._shards],
                "dcode": str(np.dtype(self._shards[0]["dcode"].dtype))}

    def manifest_info(self) -> dict:
        """Codec fields of the v3 manifest ``store`` section."""
        return {"codec": self.codec, "exact": self.exact,
                "scale": self.scales, "dtype": self.dtypes(),
                "max_ulp_err": self.max_ulp_err}

    # ------------------------------------------------- constructors

    @classmethod
    def from_table(cls, table: LabelTable, rank: np.ndarray, *,
                   codec: str = "bf16", exact: bool = False,
                   shards: Optional[int] = None) -> "CompressedStore":
        """Encode a dense table, hub-partitioned into ``shards``
        (§5.1 ownership; default 1)."""
        from repro.parallel.sharding import hub_partition_arrays
        K = shards or 1
        if K == 1:
            src = [{"hubs": np.asarray(table.hubs),
                    "dist": np.asarray(table.dist),
                    "count": np.asarray(table.count)}]
        else:
            h, d, c = hub_partition_arrays(table.hubs, table.dist,
                                           rank, K)
            src = [{"hubs": h[k], "dist": d[k], "count": c[k]}
                   for k in range(K)]
        return cls._encode(src, rank, codec=codec, exact=exact)

    @classmethod
    def from_store(cls, store, rank: np.ndarray, *,
                   codec: str = "bf16", exact: bool = False,
                   shards: Optional[int] = None) -> "CompressedStore":
        """Encode any loaded store. The source's hub partitioning is
        kept when ``shards`` matches (or is None); otherwise the labels
        are repartitioned through a dense merge."""
        if shards is not None and shards != store.num_shards:
            return cls.from_table(store.to_table(), rank, codec=codec,
                                  exact=exact, shards=shards)
        if isinstance(store, CompressedStore):
            src = [arrs for _, arrs in store.decoded_shard_arrays()]
        elif store.num_shards == 1:
            return cls.from_table(store.to_table(), rank, codec=codec,
                                  exact=exact, shards=1)
        else:
            src = [dict(arrs) for _, arrs in store.shard_arrays()]
        return cls._encode(src, rank, codec=codec, exact=exact)

    @classmethod
    def _encode(cls, src: List[Dict[str, np.ndarray]],
                rank: np.ndarray, *, codec: str,
                exact: bool) -> "CompressedStore":
        order, oi = order_permutation(rank)
        shards, scales = [], []
        max_ulp = 0
        for k, s in enumerate(src):
            fault_site("quant.encode.shard")
            deltas, dist_s, count = delta_encode_rows(
                s["hubs"], s["dist"], s["count"], oi)
            codes, scale, ulp = encode_dist(dist_s, codec, exact=exact)
            max_ulp = max(max_ulp, ulp)
            shards.append({"dhub": deltas, "dcode": codes,
                           "count": count})
            scales.append(scale)
        return cls(shards, order, codec=codec, exact=exact,
                   scales=scales, max_ulp_err=max_ulp)

    @classmethod
    def from_encoded_shards(cls, shards: List[Dict[str, np.ndarray]],
                            info: dict, rank: np.ndarray
                            ) -> "CompressedStore":
        """Adopt encoded shard arrays straight off a v3 artifact,
        validating cheap structural invariants (counts within caps,
        delta sums within the vertex range) so a tampered shard that
        slipped past the checksums still raises
        :class:`CorruptArtifactError`, not an index error mid-query."""
        order, _ = order_permutation(rank)
        n = len(order)
        checked = []
        for k, s in enumerate(shards):
            fault_site("quant.decode.shard")
            dhub = np.asarray(s["dhub"])
            dcode = np.asarray(s["dcode"])
            count = np.asarray(s["count"], np.int32)
            Ls = dhub.shape[1] if dhub.ndim == 2 else -1
            if dhub.shape != dcode.shape or Ls < 0 \
                    or len(count) != dhub.shape[0]:
                raise CorruptArtifactError(
                    f"compressed shard {k}: encoded array shapes "
                    f"disagree (dhub {dhub.shape}, dcode {dcode.shape},"
                    f" count {count.shape})")
            if count.min(initial=0) < 0 or count.max(initial=0) > Ls:
                raise CorruptArtifactError(
                    f"compressed shard {k}: label counts outside "
                    f"[0, {Ls}] (corrupt artifact)")
            # pad deltas are 0, so each row's delta sum is its last
            # order index — must stay inside the vertex range
            row_oi = dhub.astype(np.int64).sum(axis=1)
            if row_oi.size and int(row_oi.max()) >= n:
                raise CorruptArtifactError(
                    f"compressed shard {k}: decoded order index "
                    f"{int(row_oi.max())} out of range for n={n} "
                    "(corrupt artifact)")
            checked.append({"dhub": dhub, "dcode": dcode,
                            "count": count})
        scales = [float(x) for x in info.get("scale", [])] \
            or [1.0] * len(checked)
        return cls(checked, order, codec=info["codec"],
                   exact=bool(info.get("exact", False)), scales=scales,
                   max_ulp_err=int(info.get("max_ulp_err", 0)))
