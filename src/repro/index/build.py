"""`build(graph, rank, plan) -> CHLIndex` — the one construction facade.

Translates a validated :class:`BuildPlan` into a ``repro.engine`` run
(every algorithm — PLL reference, LCC/GLL/paraPLL §4, PLaNT §5.2, DGLL
§5.1, Hybrid §5.2.1, directed footnote-1 pairs — is an engine policy),
takes the engine's typed per-superstep records straight into a
:class:`BuildReport`, and packages the result as a :class:`CHLIndex`.

Label residency during construction follows the plan: a
``store="sharded"`` build of a streaming-capable algorithm (PLaNT,
pll-ref — emissions final on arrival) hub-partitions each superstep's
labels straight into per-shard arrays and never materializes the dense
``[n, cap]`` table; other algorithms build dense (they consult the
global table while constructing) and re-home afterwards.

Overflow is no longer terminal: a ``LabelOverflowError`` triggers a
retry with the cap grown geometrically (``plan.cap_growth``, clamped
to n, at most ``plan.max_cap_retries`` times), and every regrow is
recorded in ``report.overflow_events``. With a checkpoint manager
attached, the retry *resumes from the last committed superstep* — the
engine pads the restored smaller-cap tables to the grown cap — instead
of restarting the whole build.
"""

from __future__ import annotations

import time
from typing import Optional

import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelOverflowError
from repro.engine import STREAMING_ALGOS, EngineResult, run_build
from repro.index.artifact import CHLIndex
from repro.index.plan import BuildPlan
from repro.index.report import BuildReport, OverflowEvent
from repro.index.store import CompressedStore, DenseStore, ShardedStore


def _resolve_shards(plan: BuildPlan, extras: Optional[dict] = None
                    ) -> int:
    """The one shard-count rule: the plan's ``shards`` if set, else the
    build mesh size (distributed algos), else all local devices."""
    if plan.shards:
        return plan.shards
    K = int((extras or {}).get("q") or 1)
    if K == 1:
        import jax
        K = max(1, jax.local_device_count())
    return K


def _run(g, rank: np.ndarray, plan: BuildPlan, cap: int, mesh,
         ckpt, resume: bool, verbose: bool,
         streaming_shards: Optional[int]) -> EngineResult:
    """One engine attempt for the plan at the given cap."""
    return run_build(
        g, rank, algo=plan.algo, batch=plan.batch, cap=cap,
        alpha=plan.alpha, mesh=mesh, beta=plan.beta,
        first_superstep=plan.first_superstep, eta=plan.eta,
        hc_cap=plan.hc_cap, psi_threshold=plan.psi_th,
        compact=plan.compact, streaming_shards=streaming_shards,
        ckpt=ckpt, resume=resume, verbose=verbose)


def build(g, rank: np.ndarray, plan: Optional[BuildPlan] = None, *,
          mesh=None, ckpt=None, resume: bool = False,
          verbose: bool = False) -> CHLIndex:
    """Construct a :class:`CHLIndex` per ``plan`` (default: hybrid).

    ``mesh`` overrides the plan's mesh spec for distributed algos.
    ``ckpt`` (a ``CheckpointManager``) enables mid-run superstep
    checkpointing for **every** algorithm; ``resume`` continues from
    the last committed superstep.
    """
    plan = plan or BuildPlan()
    if plan.algo == "directed" and not g.directed:
        raise ValueError("algo='directed' needs a directed graph")
    if plan.algo != "directed" and g.directed:
        raise ValueError(f"algo={plan.algo!r} needs an undirected "
                         "graph; use algo='directed'")
    if plan.algo == "directed" and plan.store != "dense":
        raise ValueError("directed builds support only store='dense' "
                         "(sharded directed serving is a ROADMAP item)")
    n = g.n
    cap = plan.cap or lbl.default_cap(n)
    cap = min(cap, n)
    streaming_shards = None
    if plan.store in ("sharded", "compressed") \
            and plan.algo in STREAMING_ALGOS:
        # compressed builds stream through the same hub-partitioned
        # sink; the shards are encoded after construction
        streaming_shards = _resolve_shards(plan)
    notes = []
    if plan.algo != "pll-ref":           # the host oracle runs no sweeps
        from repro.kernels.ell_relax import sweep_form
        notes.append(sweep_form(n, distributed=plan.algo in (
            "dgll", "hybrid", "plant-dist")))
    overflow_events = []
    t0 = time.perf_counter()
    attempt = 0
    while True:
        try:
            # the first attempt resumes only on request; regrow
            # retries resume whenever checkpoints exist — the engine
            # pads the last committed (smaller-cap) state to the
            # grown cap and continues mid-schedule
            res = _run(g, rank, plan, cap, mesh, ckpt,
                       resume if attempt == 0 else ckpt is not None,
                       verbose, streaming_shards)
            break
        except LabelOverflowError as e:
            if e.what != "label table":
                # a different table overflowed (e.g. the common label
                # table's hc_cap) — regrowing the vertex cap can't help
                raise
            grown = min(max(cap + 1, int(cap * plan.cap_growth)), n)
            if attempt >= plan.max_cap_retries or grown == cap:
                overflow_events.append(
                    OverflowEvent(attempt=attempt, cap=cap,
                                  regrown_to=None))
                raise
            overflow_events.append(
                OverflowEvent(attempt=attempt, cap=cap, regrown_to=grown))
            if verbose:
                print(f"[build] label table overflow at cap={cap}; "
                      f"regrowing to {grown} "
                      f"(attempt {attempt + 1}/{plan.max_cap_retries})")
            cap = grown
            attempt += 1
    wall = time.perf_counter() - t0

    report_kw = dict(
        algo=plan.algo, wall_s=wall, cap=cap,
        supersteps=list(res.records), overflow_events=overflow_events,
        notes=notes,
        comm_label_slots=int(res.counters.get("comm_label_slots", 0)),
        psi_threshold=res.extras.get("psi_threshold"),
        q=int(res.extras.get("q", 1)),
        cleaned=int(res.counters.get("cleaned", 0)),
        constructed=int(res.counters.get("constructed", 0)))

    if plan.algo == "directed":
        l_out = res.sink.table("out")
        l_in = res.sink.table("in")
        total = lbl.total_labels(l_out) + lbl.total_labels(l_in)
        report = BuildReport(total_labels=total,
                             als=total / max(1, 2 * n), **report_kw)
        return CHLIndex(l_out=l_out, l_in=l_in, plan=plan, report=report,
                        rank=rank)

    partitioned = res.extras.get("partitioned")
    if res.sink.kind == "sharded":       # streamed: shards are the build
        store = ShardedStore.from_accumulator(res.sink.acc)
        if plan.store == "compressed":
            store = CompressedStore.from_store(
                store, rank, codec=plan.codec or "bf16",
                exact=plan.quant_exact)
    else:
        if res.sink.kind == "mesh":
            from repro.core.dgll import merge_partitions
            table = merge_partitions(res.sink.table)    # host memory
        else:
            table = res.sink.table()
        if plan.store == "sharded":
            store = ShardedStore.from_table(
                table, rank, _resolve_shards(plan, res.extras))
        elif plan.store == "compressed":
            store = CompressedStore.from_table(
                table, rank, codec=plan.codec or "bf16",
                exact=plan.quant_exact,
                shards=_resolve_shards(plan, res.extras))
        else:
            store = DenseStore(lbl.LabelTable(*map(jnp.asarray, table)))
    if isinstance(store, CompressedStore):
        if store.exact:
            notes.append(f"quant: codec={store.codec} exact "
                         "(bit-identical round trip validated)")
        else:
            notes.append(f"quant: codec={store.codec} lossy, max "
                         f"label ulp error {store.max_ulp_err}")
    total = store.total_labels
    report = BuildReport(total_labels=total, als=total / max(1, n),
                         **report_kw)
    return CHLIndex(store=store, plan=plan, report=report, rank=rank,
                    partitioned=partitioned)
