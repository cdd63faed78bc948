"""The superstep engine: one loop for every construction algorithm.

``run(policy, sink, ...)`` owns everything the nine algorithms used to
hand-roll separately — root scheduling, per-superstep typed records,
the packed one-fetch stats protocol, overflow bookkeeping, verbose
tracing, and checkpoint/resume:

- after every committed superstep the sink's label state plus the
  schedule cursor (root position, geometric-growth size, policy phase
  flags, records so far) are saved through a
  ``repro.checkpoint.CheckpointManager``;
- ``resume=True`` restores the newest compatible checkpoint and
  continues the schedule from the committed cursor — for *every*
  algorithm, not just the distributed driver;
- a checkpoint written under a *smaller* label cap is still usable:
  the sink pads the restored arrays to the current cap, which is how
  ``repro.index.build``'s overflow regrow resumes from the last
  committed superstep instead of restarting the whole build.

``run_build(g, rank, algo=...)`` is the factory both
``repro.index.build`` and the legacy ``*_chl`` wrappers call: it picks
the policy + sink for an algorithm and returns the
:class:`EngineResult` (typed records, counters, sink, policy extras).
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional

import jax
import numpy as np

from repro.core.labels import LabelOverflowError
from repro.engine.policies import Policy, StepOutcome
from repro.engine.records import (SuperstepRecord, fetch_stat_rows,
                                  record_from_row)
from repro.ft.inject import fault_site

#: data_state format tag for engine checkpoints
CKPT_FORMAT = 1


class EngineResult(NamedTuple):
    records: List[SuperstepRecord]
    counters: Dict[str, int]
    sink: object
    extras: dict
    resumed_from: Optional[int]      # committed cursor we restored, or None


def _encode_records(records: List[SuperstepRecord]):
    """Records as compact numeric arrays (stored in the checkpoint's
    ``arrays.npz``, NOT the per-step JSON manifest — re-serializing a
    growing JSON list every commit would make checkpoint metadata
    O(supersteps²) over a run). Returns (arrays, mode vocabulary)."""
    vocab: List[str] = []
    ids = {}
    # i32/f32 to match the packed stats protocol (and the jnp-backed
    # checkpoint restore path, which has no x64)
    packed = np.full((len(records), 5), -1, dtype=np.int32)
    psi = np.full(len(records), np.nan, dtype=np.float32)
    for i, r in enumerate(records):
        if r.mode not in ids:
            ids[r.mode] = len(vocab)
            vocab.append(r.mode)
        row = (ids[r.mode], r.labels, r.explored, r.sweeps, r.trees)
        packed[i] = [-1 if v is None else int(v) for v in row]
        if r.psi is not None:
            psi[i] = r.psi
    return {"packed": packed, "psi": psi}, vocab


def _decode_records(arrays, vocab: List[str]) -> List[SuperstepRecord]:
    packed = np.asarray(arrays["packed"])
    psi = np.asarray(arrays["psi"])
    out = []
    for row, p in zip(packed, psi):
        mode_id, labels, explored, sweeps, trees = (int(v) for v in row)
        out.append(SuperstepRecord(
            mode=vocab[mode_id],
            labels=None if labels < 0 else labels,
            explored=None if explored < 0 else explored,
            sweeps=None if sweeps < 0 else sweeps,
            psi=None if np.isnan(p) else float(p),
            trees=None if trees < 0 else trees))
    return out


def _meta_compatible(saved: Optional[dict], current: dict) -> bool:
    """Sink metadata check for resume; a saved cap *smaller* than the
    current one is compatible (restored arrays are padded — the
    regrow-resume path), anything else must match exactly."""
    if not isinstance(saved, dict):
        return False
    saved = dict(saved)
    current = dict(current)
    saved_cap = saved.pop("cap", None)
    cur_cap = current.pop("cap", None)
    if saved != current:
        return False
    if saved_cap is None or cur_cap is None:
        return saved_cap == cur_cap
    return saved_cap <= cur_cap


def _try_restore(ckpt, policy: Policy, sink):
    """Restore the newest compatible checkpoint; returns
    ``(pos, size, records)`` or None. Incompatible checkpoints — other
    algorithm, other build input (graph/rank fingerprint), other
    schedule config, other sink layout, larger cap — are cleared so
    their higher step numbers cannot shadow this run's resume points."""
    # newest *intact* step: a torn newest checkpoint (crash mid-commit)
    # falls back to the previous one instead of poisoning the resume
    step = ckpt.latest_intact_step()
    if step is None:
        return None
    meta = ckpt.peek(step)
    if (meta.get("engine") != CKPT_FORMAT
            or meta.get("algo") != policy.name
            or meta.get("kind", "build") != policy.kind
            or meta.get("fingerprint") != policy.fingerprint
            or meta.get("config") != policy.config()
            or not _meta_compatible(meta.get("sink"), sink.meta())):
        ckpt.clear()
        return None
    template = {"sink": sink.state_arrays(),
                "records": {"packed": np.zeros((0, 5), np.int32),
                            "psi": np.zeros(0, np.float32)}}
    state, _, _ = ckpt.restore(template, step=step)
    sink.load_state({k: np.asarray(v)
                     for k, v in state["sink"].items()})
    policy.load_meta(meta.get("policy") or {})
    policy.load_counters(meta.get("counters") or {})
    records = _decode_records(state["records"],
                              meta.get("mode_vocab", []))
    return int(meta["pos"]), meta.get("size"), records


def run(policy: Policy, sink, *, ckpt=None, resume: bool = False,
        verbose: bool = False) -> EngineResult:
    """Drive ``policy``'s schedule to completion, emitting into
    ``sink``; returns typed records + the filled sink."""
    schedule = policy.schedule()
    eager = policy.eager_stats or ckpt is not None
    records: List[SuperstepRecord] = []
    deferred: List[tuple] = []       # (record-index, mode, trees, row)
    pos, size = 0, None
    resumed_from: Optional[int] = None

    if ckpt is not None and resume:
        restored = _try_restore(ckpt, policy, sink)
        if restored is not None:
            pos, size, records = restored
            resumed_from = pos
            if verbose:
                print(f"[resume] superstep cursor={pos} size={size}")

    policy.begin(pos, resumed_from is not None)

    def commit(out: StepOutcome, end_pos: int,
               next_size: Optional[int]) -> None:
        if eager:
            rec = out.record if out.record is not None else \
                record_from_row(out.mode, np.asarray(out.stats),
                                trees=out.trees)
            if sink.overflowed():
                # raise BEFORE committing a checkpoint: inserts drop
                # labels on overflow, and a saved corrupt table would
                # be silently restored by --resume
                if ckpt is not None:
                    ckpt.wait()
                sink.raise_on_overflow()
                raise LabelOverflowError(sink.cap or 0)  # pragma: no cover
            records.append(rec)
            policy.observe(rec)
            if verbose:
                psi = f"{rec.psi:.1f}" if rec.psi is not None else "-"
                print(f"superstep end={end_pos:6d} mode={rec.mode} "
                      f"labels={rec.labels} psi={psi}")
            if ckpt is not None:
                fault_site("engine.commit")
                rec_arrays, vocab = _encode_records(records)
                ckpt.save(end_pos, {"sink": sink.state_arrays(),
                                    "records": rec_arrays},
                          data_state={
                              "engine": CKPT_FORMAT,
                              "algo": policy.name,
                              "kind": policy.kind,
                              "fingerprint": policy.fingerprint,
                              "config": policy.config(),
                              "sink": sink.meta(),
                              "policy": policy.meta(),
                              "counters": policy.counters(),
                              "mode_vocab": vocab,
                              "pos": end_pos,
                              "size": next_size},
                          blocking=False)
        else:
            if out.record is not None:
                records.append(out.record)
                policy.observe(out.record)
            else:
                records.append(None)        # placeholder, filled below
                deferred.append((len(records) - 1, out.mode, out.trees,
                                 out.stats))

    if resumed_from is None:
        pre = policy.prologue(sink)
        if pre is not None:
            out, pos = pre
            commit(out, pos, size)

    for st in schedule.steps(start=pos, size=size):
        with jax.profiler.StepTraceAnnotation("engine.superstep",
                                              step_num=st.pos):
            out = policy.step(st, sink)
        if out is not None:
            commit(out, st.end, st.next_size)

    tail = policy.epilogue(sink)
    if tail is not None:
        commit(tail, schedule.total, None)

    if ckpt is not None:
        ckpt.wait()

    if deferred:
        rows = fetch_stat_rows([d[3] for d in deferred])  # ONE transfer
        for (i, mode, trees, _), row in zip(deferred, rows):
            records[i] = record_from_row(mode, row, trees=trees)
    if not eager:
        sink.raise_on_overflow()

    return EngineResult(records=records, counters=policy.counters(),
                        sink=sink, extras=policy.extras(sink),
                        resumed_from=resumed_from)


# --------------------------------------------------------------------
# factory: algorithm name → (policy, sink) → EngineResult
# --------------------------------------------------------------------

#: algorithms whose emissions are final on arrival and independent of
#: any global table — the ones that can stream into shard arrays
#: without ever materializing the dense [n, cap] label table
STREAMING_ALGOS = ("plant", "pll-ref")


def run_build(g, rank: np.ndarray, *, algo: str, batch: int = 8,
              cap: Optional[int] = None, alpha: Optional[float] = 4.0,
              rank_queries: bool = True, clean: bool = True,
              plant_first_superstep: bool = False, hc=None,
              roots_order: Optional[np.ndarray] = None,
              mesh=None, beta: float = 8.0, first_superstep: int = 1,
              eta: int = 0, hc_cap: int = 64,
              psi_threshold: Optional[float] = 100.0, compact: int = 0,
              streaming_shards: Optional[int] = None,
              ckpt=None, resume: bool = False,
              verbose: bool = False) -> EngineResult:
    """Construct labels for ``algo`` through the engine.

    ``streaming_shards=K`` (only for :data:`STREAMING_ALGOS`) swaps the
    dense sink for the hub-partitioned streaming sink.
    """
    from repro.core import labels as lbl
    from repro.engine.policies import (DirectedPlantPolicy, GLLPolicy,
                                       PlantPolicy, PLLRefPolicy)
    from repro.engine.sink import (DenseSink, MeshTableSink,
                                   StreamingShardSink)

    n = g.n
    cap = cap or lbl.default_cap(n)
    if streaming_shards is not None and algo not in STREAMING_ALGOS:
        raise ValueError(
            f"streaming sharded builds support {STREAMING_ALGOS} "
            f"(algo={algo!r} needs its dense global table during "
            "construction)")

    # the policy's and sink's device set-up: adjacency upload, layout,
    # fingerprint, empty label tables
    with jax.profiler.TraceAnnotation("engine.init"):
        if algo in ("dgll", "hybrid", "plant-dist"):
            from repro.core.dgll import make_node_mesh
            from repro.engine.dist import DistributedPolicy
            mesh = mesh or make_node_mesh()
            if algo == "plant-dist":
                eta, psi_threshold = 0, float("inf")
            elif algo == "dgll":
                psi_threshold = 0.0
            policy = DistributedPolicy(
                g, rank, mesh=mesh, batch=batch, beta=beta,
                first_superstep=first_superstep, cap=cap, eta=eta,
                hc_cap=hc_cap, psi_threshold=psi_threshold, compact=compact,
                mode_name=algo, verbose=verbose)
            sink = MeshTableSink(mesh, n, cap)
        elif algo == "plant":
            policy = PlantPolicy(g, rank, batch=batch, hc=hc,
                                 roots_order=roots_order)
            sink = (StreamingShardSink(n, rank, streaming_shards)
                    if streaming_shards else DenseSink(n, cap))
        elif algo == "directed":
            policy = DirectedPlantPolicy(g, rank, batch=batch)
            sink = DenseSink(n, cap, channels=("out", "in"))
        elif algo == "pll-ref":
            policy = PLLRefPolicy(g, rank, batch=batch)
            sink = (StreamingShardSink(n, rank, streaming_shards)
                    if streaming_shards else DenseSink(n, cap))
        elif algo in ("gll", "lcc", "parapll"):
            if algo == "lcc":
                alpha = None
            elif algo == "parapll":
                alpha, rank_queries, clean = None, False, False
            policy = GLLPolicy(g, rank, batch=batch, cap=cap, alpha=alpha,
                               rank_queries=rank_queries, clean=clean,
                               plant_first_superstep=plant_first_superstep,
                               mode_name=algo)
            sink = DenseSink(n, cap)
        else:
            raise ValueError(f"unhandled algo {algo!r}")

    return run(policy, sink, ckpt=ckpt, resume=resume, verbose=verbose)
