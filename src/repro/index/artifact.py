"""`CHLIndex` — the queryable, servable, persistable CHL artifact.

One object owns the outcome of a build: a pluggable **label store**
(``repro.index.store`` — dense / hub-sharded / memory-map-spilled
residency; the directed L_out/L_in pair stays dense), the plan that
produced it, the normalized build report, and the vertex hierarchy it
was built under. Everything downstream of construction happens through
it:

    idx = build(g, rank, BuildPlan(algo="hybrid", store="sharded"))
    idx.query(u, v)                      # batched PPSD distances
    srv = idx.serve(mode="qdol")         # QueryService, any §6.3 mode
    idx.validate_against(oracle)         # exact-CHL / distance check
    idx.save("run/index")                # versioned sharded artifact
    idx2 = CHLIndex.load("run/index", store="spill")

On-disk format (version 3):

    <dir>/manifest.json   {"format": "repro.index/chl", "version": 3,
                           "plan": BuildPlan.to_dict(),
                           "report": BuildReport.to_dict(),
                           "rank_hash": sha256(rank bytes),
                           "directed": bool, "n": int,
                           "total_labels": int, "als": float,
                           "store": {"kind": "dense"|"sharded"
                                             |"compressed",
                                     "shards": K,
                                     "shard_labels": [per-shard totals],
                                     # compressed artifacts only:
                                     "codec": "bf16"|"u16"|"u32",
                                     "exact": bool,
                                     "scale": [per-shard f32 steps],
                                     "dtype": {"dhub": [...], "dcode": s},
                                     "max_ulp_err": int}}
    <dir>/rank.npy        the vertex hierarchy
    <dir>/shard_<k>.npz   hubs/dist/count of label shard k
                          (directed: one shard of out_*/in_* pairs;
                          compressed: encoded dhub/dcode/count — the
                          checksums cover the *encoded* bytes)

Version-1 artifacts (monolithic ``arrays.npz``) and version-2
artifacts (no codec fields) still load bit-identically.
``load(store=...)`` re-homes any version: ``"dense"`` merges shards,
``"sharded"`` partitions by hub rank, ``"spill"`` memory-maps the
shard files so labels larger than host RAM stay serveable, and
``"compressed"`` (with ``codec=`` / ``quant_exact=``) encodes the
labels through ``repro.index.quant``. Loads are rejected on format/version
mismatch, rank-hash mismatch, and per-shard label-count mismatch (a
truncated shard file names itself instead of raising a numpy
traceback). Writes go through a tmp dir + ``os.replace`` swap: a fresh
save is atomic, and an overwrite never deletes the live artifact
before the replacement is staged (a crash leaves the old copy
recoverable at ``.tmp_index_<name>.old``), so a ``CheckpointManager``
run can finalize into an index safely.
"""

from __future__ import annotations

import hashlib
import json
import os
import shutil
import weakref
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core import query as qm
from repro.core.labels import LabelTable
from repro.index.plan import BuildPlan
from repro.index.report import BuildReport
from repro.ft.inject import fault_site, with_retries
from repro.index.store import (LOAD_STORE_KINDS, CompressedStore,
                               CorruptArtifactError, DenseStore,
                               LabelStore, ShardedStore, SpillStore,
                               open_shard, shard_filename)
from repro.serve import backends
from repro.serve.service import QueryService

FORMAT = "repro.index/chl"
VERSION = 3


def rank_hash(rank: np.ndarray) -> str:
    """Stable fingerprint of a vertex hierarchy."""
    r = np.ascontiguousarray(np.asarray(rank).astype(np.int64))
    return hashlib.sha256(r.tobytes()).hexdigest()


def file_sha256(path: str, chunk: int = 1 << 20) -> str:
    """Streaming sha256 of a file — bounded resident memory, so
    verifying a spill-scale shard never loads it whole."""
    h = hashlib.sha256()
    with open(path, "rb") as fh:
        while True:
            block = fh.read(chunk)
            if not block:
                break
            h.update(block)
    return h.hexdigest()


class CHLIndex:
    """A built Canonical Hub Labeling, packaged for serving.

    ``store`` (a :class:`~repro.index.store.LabelStore`) holds the
    labels for undirected graphs; ``l_out``/``l_in`` for directed
    (footnote 1 forward/backward labels, dense residency only).
    ``partitioned`` is the construction-time ``[q, n, L]``
    hub-partitioned table when the build was distributed (QFDL serves
    straight from it; otherwise the layout comes from the store or is
    synthesized from ``rank``).
    """

    def __init__(self, table: Optional[LabelTable] = None, *,
                 store: Optional[LabelStore] = None,
                 l_out: Optional[LabelTable] = None,
                 l_in: Optional[LabelTable] = None,
                 plan: BuildPlan, report: BuildReport,
                 rank: np.ndarray,
                 partitioned: Optional[LabelTable] = None):
        given = sum(x is not None for x in (table, store, l_out))
        if given != 1:
            raise ValueError("exactly one of `table`, `store`, or the "
                             "`l_out`/`l_in` pair must be given")
        if (l_out is None) != (l_in is None):
            raise ValueError("directed indices need both l_out and l_in")
        if table is not None:
            store = DenseStore(table)
        self.store = store
        self.l_out = l_out
        self.l_in = l_in
        self.plan = plan
        self.report = report
        self.rank = np.asarray(rank)
        self.partitioned = partitioned
        # live QueryServices handed out by serve(), kept weakly with
        # the knobs needed to rebuild their answer fns after apply()
        self._services: List[Tuple[weakref.ref, dict]] = []

    # ---------------------------------------------------- properties

    @property
    def directed(self) -> bool:
        return self.store is None

    @property
    def table(self) -> Optional[LabelTable]:
        """Materialized dense view of the store (undirected only).

        For a :class:`DenseStore` this is the exact underlying table;
        for sharded/spill stores it merges shards — O(total label
        slots) memory, meant for host-side analysis, not serving.
        """
        return None if self.directed else self.store.to_table()

    @property
    def n(self) -> int:
        return self.l_out.n if self.directed else self.store.n

    @property
    def total_labels(self) -> int:
        if self.directed:
            return (lbl.total_labels(self.l_out)
                    + lbl.total_labels(self.l_in))
        return self.store.total_labels

    @property
    def als(self) -> float:
        """Average label size (per direction for directed graphs)."""
        denom = self.n * (2 if self.directed else 1)
        return self.total_labels / max(1, denom)

    # --------------------------------------------------------- query

    def query(self, u, v) -> np.ndarray:
        """Batched PPSD distances (f32 [Q]; +inf when disconnected)."""
        d, _ = self.query_with_hub(u, v)
        return d

    def query_with_hub(self, u, v) -> Tuple[np.ndarray, np.ndarray]:
        """Distances plus the witnessing hub id (-1 when disjoint)."""
        with jax.profiler.TraceAnnotation("index.query"):
            if self.directed:
                from repro.core.directed import query_directed
                u = jnp.atleast_1d(jnp.asarray(u, jnp.int32))
                v = jnp.atleast_1d(jnp.asarray(v, jnp.int32))
                d, h = query_directed(self.l_out, self.l_in, u, v,
                                      with_hub=True)
                return np.asarray(d), np.asarray(h)
            # each store normalizes its own inputs (a spill store runs in
            # host numpy — don't bounce its queries through the device)
            return self.store.query(u, v)

    # --------------------------------------------------------- serve

    def serve(self, mode: str = "qlsn", *, mesh=None,
              batch_size: int = 1024, drop_first: bool = True,
              deadline_ms: float = 2.0, cache: int = 0,
              max_queue: Optional[int] = None,
              routed: Optional[bool] = None,
              timeout_ms: Optional[float] = None,
              breaker_threshold: int = 5,
              breaker_reset_s: float = 30.0) -> QueryService:
        """The serving tier (:class:`repro.serve.QueryService`) in any
        §6.3 storage mode — no mesh/layout/store ceremony at the call
        site. Routes through the label store: dense stores serve all
        three modes as before, sharded stores answer from their own
        hub partitions (per-shard routed by default for QLSN), spill
        stores serve QLSN from the memory-mapped shards. Directed
        indices serve QLSN from the dense L_out/L_in pair (the other
        modes remain a ROADMAP item), with the answer cache built
        ``symmetric=False`` — d(u→v) and d(v→u) must never share an
        entry.

        Service knobs: ``deadline_ms`` bounds how long an arrival
        waits before :meth:`~repro.serve.QueryService.pump` forces a
        partial batch out; ``cache`` sizes the hot-pair LRU (0 = off);
        ``max_queue`` bounds the admission queue (``None`` = no gate);
        ``routed`` overrides per-shard query routing (``None`` =
        auto). Degradation knobs (``repro.ft``): ``timeout_ms`` is the
        per-query expiry budget (None = none); ``breaker_threshold`` /
        ``breaker_reset_s`` configure the answer-failure circuit
        breaker — see :class:`repro.serve.QueryService`.

        The returned service stays registered (weakly) with this
        index: :meth:`apply` refreshes every live service's answer fn
        and bumps its cache epoch, so a mutated index can never serve
        a stale answer."""
        fn = self._answer_fn(mode, mesh=mesh, routed=routed)
        svc = QueryService(fn, batch_size=batch_size,
                           drop_first=drop_first,
                           deadline_s=deadline_ms * 1e-3,
                           cache_size=cache, max_queue=max_queue,
                           cache_symmetric=not self.directed,
                           timeout_s=(None if timeout_ms is None
                                      else timeout_ms * 1e-3),
                           breaker_threshold=breaker_threshold,
                           breaker_reset_s=breaker_reset_s)
        self._services.append(
            (weakref.ref(svc), {"mode": mode, "mesh": mesh,
                                "routed": routed}))
        return svc

    def _answer_fn(self, mode: str, *, mesh=None, routed=None):
        """The serving answer callable for this index's current
        labels (what serve() installs and apply() re-installs)."""
        if self.directed:
            if mode != "qlsn":
                raise NotImplementedError(
                    "directed serving currently supports mode='qlsn'")
            from repro.core.directed import query_directed
            l_out, l_in = self.l_out, self.l_in
            return jax.jit(
                lambda u, v: query_directed(l_out, l_in, u, v))
        return backends.make_answer_fn(self.store, mode, mesh=mesh,
                                       partitioned=self.partitioned,
                                       rank=self.rank, routed=routed)

    # --------------------------------------------------------- mutate

    def apply(self, mutations, *, graph, ckpt=None,
              resume: bool = False, verbose: bool = False,
              journal=None):
        """Apply a :class:`repro.dynamic.MutationBatch` to this index
        in place — re-planting only the affected trees — and
        invalidate every live service handed out by :meth:`serve`.

        ``graph`` is the **pre-mutation** graph the index was built
        on (the artifact stores labels, not edges). The repaired
        labels are bit-identical to a from-scratch rebuild on
        ``mutations.apply(graph)``; returns the
        :class:`repro.dynamic.RepairReport`.

        ``journal`` (a :class:`repro.dynamic.RepairJournal`) makes the
        repair **crash-atomic end to end**: intent plus the
        pre-mutation store fingerprint are durable before the first
        label moves, the post-repair fingerprint is recorded before
        the artifact swap, and on restart
        :meth:`repro.dynamic.RepairJournal.recover` tells from the
        on-disk fingerprint whether the saved artifact is pre- or
        post-mutation — a kill at any point leaves one of exactly
        those two states, never a half-merged store.
        """
        from repro.dynamic.repair import repair_index
        if journal is not None:
            journal.begin(mutations, self)
        report = repair_index(self, mutations, graph, ckpt=ckpt,
                              resume=resume, verbose=verbose)
        if journal is not None:
            journal.record_post(self)
        self._invalidate_services()
        return report

    def _invalidate_services(self) -> None:
        """Rebuild each live service's answer fn against the mutated
        store and bump its cache epoch; dead services are pruned."""
        alive = []
        for ref, knobs in self._services:
            svc = ref()
            if svc is None:
                continue
            svc.invalidate(self._answer_fn(knobs["mode"],
                                           mesh=knobs["mesh"],
                                           routed=knobs["routed"]))
            alive.append((ref, knobs))
        self._services = alive

    # ------------------------------------------------------ validate

    def validate_against(self, oracle) -> bool:
        """Check this index against ground truth; raises on mismatch.

        ``oracle`` is either a ``Graph`` (distances of every connected
        pair checked against Dijkstra — the cover property) or PLL
        label sets (exact CHL label-set equality; a ``(l_out, l_in)``
        tuple for directed graphs).
        """
        from repro.core import validate as val
        if hasattr(oracle, "indptr"):            # a Graph: cover check
            from repro.sssp.oracle import all_pairs
            D = all_pairs(oracle)
            n = oracle.n
            uu, vv = np.meshgrid(np.arange(n), np.arange(n),
                                 indexing="ij")
            uu, vv = uu.reshape(-1), vv.reshape(-1)
            got = np.empty(n * n, np.float32)
            B = 8192                     # bound the [Q, L, L] intermediate
            for s in range(0, n * n, B):
                got[s:s + B] = self.query(uu[s:s + B], vv[s:s + B])
            got = got.reshape(n, n)
            want = D.astype(np.float32)
            ok = np.isfinite(want)
            assert np.array_equal(got[ok], want[ok]), "distances differ"
            assert not np.isfinite(got[~ok]).any(), \
                "reports finite distance for disconnected pair"
            return True
        if self.directed:
            ref_out, ref_in = oracle
            val.check_equal(lbl.to_numpy_sets(self.l_out), ref_out)
            val.check_equal(lbl.to_numpy_sets(self.l_in), ref_in)
        else:
            val.check_equal(lbl.to_numpy_sets(self.table), oracle)
        return True

    # -------------------------------------------------------- memory

    def memory_report(self, q: Optional[int] = None) -> dict:
        """Per-mode cluster label storage (Table 4) plus the per-store
        breakdown: resident ``label_bytes``, bytes per label, and the
        compression ratio vs dense f32 (8 B/label — 1.0 for the
        uncompressed backends). ``q`` defaults to the build mesh size.
        Multi-shard stores additionally report the per-shard split and
        a compressed store its codec/dtype/scale metadata — all
        without materializing the dense table."""
        q = q or self.report.q
        if self.directed:
            return {"l_out_bytes": qm.label_memory_bytes(self.l_out),
                    "l_in_bytes": qm.label_memory_bytes(self.l_in),
                    "q": q}
        base = self.store.label_bytes()
        total = self.store.total_labels
        out = qm.mode_memory_totals(self.n, base, q)
        out["store"] = self.store.kind
        out["shards"] = self.store.num_shards
        out["label_bytes"] = base
        out["dense_f32_bytes"] = total * 8
        out["bytes_per_label"] = base / max(1, total)
        out["compression_ratio"] = (total * 8) / max(1, base)
        if hasattr(self.store, "shard_label_bytes"):
            out["shard_bytes"] = self.store.shard_label_bytes()
        if isinstance(self.store, CompressedStore):
            out["codec"] = self.store.codec
            out["quant_exact"] = self.store.exact
            out["dtypes"] = self.store.dtypes()
            out["scale"] = self.store.scales
            out["max_ulp_err"] = self.store.max_ulp_err
        return out

    # ---------------------------------------------------------- disk

    def save(self, directory: str) -> str:
        """Atomically write the versioned on-disk artifact (format
        version 3: per-shard npz segments, encoded for a compressed
        store); returns the directory path. One shard is resident at a
        time, so saving a spill store never materializes the full
        table."""
        parent = os.path.dirname(os.path.abspath(directory)) or "."
        os.makedirs(parent, exist_ok=True)
        tmp = os.path.join(parent,
                           f".tmp_index_{os.path.basename(directory)}")
        shutil.rmtree(tmp, ignore_errors=True)
        os.makedirs(tmp)
        np.save(os.path.join(tmp, "rank.npy"), np.asarray(self.rank))

        def write_shard(k: int, arrays: dict) -> str:
            path = os.path.join(tmp, shard_filename(k))
            with_retries(lambda: np.savez(path, **arrays),
                         describe=f"index shard {k}")
            fault_site("artifact.save.shard", path=path)
            return file_sha256(path)

        if self.directed:
            arrays = {}
            for pfx, t in (("out", self.l_out), ("in", self.l_in)):
                arrays[f"{pfx}_hubs"] = np.asarray(t.hubs)
                arrays[f"{pfx}_dist"] = np.asarray(t.dist)
                arrays[f"{pfx}_count"] = np.asarray(t.count)
            shard_sha = [write_shard(0, arrays)]
            store_info = {"kind": "dense", "shards": 1,
                          "shard_labels": [self.total_labels]}
        else:
            shard_labels = []
            shard_sha = []
            for k, arrs in self.store.shard_arrays():
                shard_sha.append(write_shard(k, dict(arrs)))
                shard_labels.append(int(np.sum(arrs["count"])))
            if isinstance(self.store, CompressedStore):
                # encoded shards persist as-is; the codec fields let
                # the loader dequantize (or keep serving encoded)
                kind = "compressed"
            else:
                kind = ("sharded" if self.store.num_shards > 1
                        else "dense")
            store_info = {"kind": kind,
                          "shards": self.store.num_shards,
                          "shard_labels": shard_labels}
            if isinstance(self.store, CompressedStore):
                store_info.update(self.store.manifest_info())
        # per-file integrity: verified on load (CorruptArtifactError
        # on mismatch) — a bit flip can never become a wrong answer
        store_info["shard_sha256"] = shard_sha
        manifest = {
            "format": FORMAT,
            "version": VERSION,
            "plan": self.plan.to_dict(),
            "report": self.report.to_dict(),
            "rank_hash": rank_hash(self.rank),
            "directed": self.directed,
            "n": self.n,
            "total_labels": self.total_labels,
            "als": self.als,
            "store": store_info,
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
        fault_site("artifact.save.commit",
                   path=os.path.join(tmp, "manifest.json"))
        old = tmp + ".old"
        shutil.rmtree(old, ignore_errors=True)
        if os.path.isdir(directory):
            # never rmtree the live artifact before the new one is in
            # place: move it aside, swap, then delete — a crash leaves
            # either the old or the new artifact loadable
            os.replace(directory, old)
        os.replace(tmp, directory)
        shutil.rmtree(old, ignore_errors=True)
        return directory

    @classmethod
    def load(cls, directory: str, rank: Optional[np.ndarray] = None, *,
             store: Optional[str] = None,
             shards: Optional[int] = None,
             codec: Optional[str] = None,
             quant_exact: bool = False,
             verify: bool = True) -> "CHLIndex":
        """Load a saved index. When ``rank`` is given it must hash to
        the manifest's ``rank_hash`` — a label table is meaningless
        under a different hierarchy.

        ``store`` overrides the residency the artifact was saved with:
        ``"dense"`` merges shards into one table, ``"sharded"``
        (re-)partitions by hub rank (``shards`` picks K when re-homing
        a dense artifact), ``"spill"`` memory-maps the shard segments
        instead of loading them, ``"compressed"`` re-homes any saved
        index into quantized residency (``codec`` picks the distance
        codec, default bf16 — or the artifact's own when it is already
        compressed; ``quant_exact`` demands the validated bit-exact
        encoding and raises a typed ``QuantizationError`` when the
        labels cannot satisfy it). Default: the artifact's own layout.
        A compressed artifact cannot be memory-mapped (its query path
        must dequantize) — ``store="spill"`` on one is refused with
        guidance.

        ``verify`` (default on) re-hashes every shard file against the
        sha256 the manifest recorded at save time and raises
        :class:`CorruptArtifactError` on mismatch — a flipped bit or a
        torn shard is refused, never served. Artifacts saved before
        checksums existed skip the check. ``verify=False`` trades the
        integrity pass for open latency (the per-shard label-count
        cross-check still runs).
        """
        if store is not None and store not in LOAD_STORE_KINDS:
            raise ValueError(f"store {store!r} not one of "
                             f"{LOAD_STORE_KINDS}")
        with open(os.path.join(directory, "manifest.json")) as f:
            manifest = json.load(f)
        if manifest.get("format") != FORMAT:
            raise ValueError(
                f"{directory}: not a CHL index artifact "
                f"(format={manifest.get('format')!r})")
        version = manifest.get("version", 0)
        if version > VERSION:
            raise ValueError(
                f"{directory}: index version {manifest['version']} is "
                f"newer than supported ({VERSION})")
        plan = BuildPlan.from_dict(manifest["plan"])
        report = BuildReport.from_dict(manifest["report"])

        if verify:
            cls._verify_checksums(directory, manifest)
        if version < 2:
            stored_rank, built = cls._load_v1(directory, manifest,
                                              spill=store == "spill")
        else:
            stored_rank, built = cls._load_v2(directory, manifest,
                                              spill=store == "spill")
        if rank_hash(stored_rank) != manifest["rank_hash"]:
            raise CorruptArtifactError(
                f"{directory}: stored rank does not match manifest "
                "rank_hash (corrupt artifact)")
        if rank is not None and rank_hash(rank) != manifest["rank_hash"]:
            raise ValueError(
                f"{directory}: rank-hash mismatch — this index was "
                "built under a different vertex hierarchy")

        if manifest["directed"]:
            if store not in (None, "dense"):
                raise NotImplementedError(
                    "directed indices support only dense residency")
            l_out, l_in = built
            return cls(l_out=l_out, l_in=l_in, plan=plan, report=report,
                       rank=stored_rank)
        built = cls._rehome(built, store, stored_rank, shards,
                            codec=codec, quant_exact=quant_exact)
        return cls(store=built, plan=plan, report=report,
                   rank=stored_rank)

    # ------------------------------------------------- load internals

    @staticmethod
    def _verify_checksums(directory: str, manifest: dict) -> None:
        """Refuse shard files whose bytes no longer hash to what the
        manifest recorded (pre-checksum artifacts carry none — nothing
        to verify)."""
        recorded = (manifest.get("store") or {}).get("shard_sha256")
        if not recorded:
            return
        for k, want in enumerate(recorded):
            path = os.path.join(directory, shard_filename(k))
            try:
                got = file_sha256(path)
            except FileNotFoundError as e:
                raise CorruptArtifactError(
                    f"missing shard file {path} — artifact is "
                    "incomplete (copy interrupted?)") from e
            except OSError as e:
                raise CorruptArtifactError(
                    f"{directory}: {shard_filename(k)} unreadable "
                    f"while verifying checksum ({e})") from e
            if got != want:
                raise CorruptArtifactError(
                    f"{directory}: {shard_filename(k)} sha256 mismatch "
                    f"(manifest {want[:12]}…, on disk {got[:12]}…) — "
                    "corrupt artifact (torn write or bit rot)")

    @staticmethod
    def _load_v1(directory: str, manifest: dict, spill: bool = False):
        """Version-1 monolithic ``arrays.npz`` → dense residency,
        bit-identical to the pre-store loader (``spill`` maps the
        members instead of loading them — one big shard)."""
        path = os.path.join(directory, "arrays.npz")
        if spill and not manifest["directed"]:
            from repro.index.store import open_npz_arrays
            arrs = open_npz_arrays(path, path)
            return np.asarray(arrs["rank"]), SpillStore(
                [{k: arrs[k] for k in ("hubs", "dist", "count")}])
        arrs = np.load(path)
        stored_rank = arrs["rank"]

        def tbl(pfx: str) -> LabelTable:
            return LabelTable(jnp.asarray(arrs[f"{pfx}hubs"]),
                              jnp.asarray(arrs[f"{pfx}dist"]),
                              jnp.asarray(arrs[f"{pfx}count"]))

        if manifest["directed"]:
            return stored_rank, (tbl("out_"), tbl("in_"))
        return stored_rank, DenseStore(tbl(""))

    @staticmethod
    def _load_v2(directory: str, manifest: dict, spill: bool):
        stored_rank = np.load(os.path.join(directory, "rank.npy"))
        info = manifest.get("store") or {}
        K = int(info.get("shards", 1))
        expected = info.get("shard_labels")
        shards = []
        for k in range(K):
            arrs = open_shard(directory, k)
            if expected is not None:
                got = int(np.sum(np.asarray(arrs["count"]))) \
                    if not manifest["directed"] else \
                    int(np.sum(np.asarray(arrs["out_count"]))
                        + np.sum(np.asarray(arrs["in_count"])))
                if got != int(expected[k]):
                    raise CorruptArtifactError(
                        f"{directory}: {shard_filename(k)} holds {got} "
                        f"labels but the manifest recorded "
                        f"{int(expected[k])} (corrupt or mixed-version "
                        "artifact)")
            shards.append(arrs)
        if manifest["directed"]:
            (s,) = shards

            def tbl(pfx: str) -> LabelTable:
                return LabelTable(jnp.asarray(s[f"{pfx}hubs"]),
                                  jnp.asarray(s[f"{pfx}dist"]),
                                  jnp.asarray(s[f"{pfx}count"]))

            return stored_rank, (tbl("out_"), tbl("in_"))
        if info.get("kind") == "compressed":
            if spill:
                raise ValueError(
                    "a compressed artifact cannot be memory-mapped "
                    "(queries must dequantize); load with "
                    "store='compressed' (encoded residency) or "
                    "'dense'/'sharded' (decoded)")
            return stored_rank, CompressedStore.from_encoded_shards(
                shards, info, stored_rank)
        if spill:
            return stored_rank, SpillStore(shards)
        if info.get("kind") == "sharded" or K > 1:
            return stored_rank, ShardedStore.from_shard_arrays(shards)
        return stored_rank, DenseStore.from_shard_arrays(shards)

    @staticmethod
    def _rehome(store: LabelStore, kind: Optional[str],
                rank: np.ndarray, shards: Optional[int], *,
                codec: Optional[str] = None,
                quant_exact: bool = False) -> LabelStore:
        """Convert a loaded store to the requested residency."""
        if kind is None or kind == "spill":
            return store          # spill was honored at open time
        if kind == "dense":
            if isinstance(store, DenseStore):
                return store
            return DenseStore(store.to_table())
        if kind == "compressed":
            if isinstance(store, CompressedStore) \
                    and codec in (None, store.codec) \
                    and shards in (None, store.num_shards) \
                    and (not quant_exact or store.exact):
                return store      # already encoded as requested
            return CompressedStore.from_store(
                store, rank, codec=codec or "bf16", exact=quant_exact,
                shards=shards)
        # kind == "sharded": repartition unless the shard count already
        # matches (``shards`` only forces K when it differs)
        if isinstance(store, ShardedStore) and shards in (
                None, store.num_shards):
            return store
        K = shards or max(2, store.num_shards)
        return ShardedStore.from_table(store.to_table(), rank, K)
