"""Single-host construction policies — each algorithm as a thin plug.

A *policy* is what is left of a construction algorithm once the engine
owns the loop: the per-batch device step (how trees are grown), the
emission filter (which labels are canonical / optimistic), and any
phase rule. The host superstep loops that used to live in
``core/plant.py``, ``core/gll.py`` and ``core/directed.py`` are gone —
those modules keep only their jitted batch kernels, and the policies
below wire them into :mod:`repro.engine.runner`.

Distributed policies (DGLL / Hybrid / PLaNT-dist) live in
:mod:`repro.engine.dist` — importing them pulls in ``shard_map``.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelTable
from repro.engine.records import (SuperstepRecord, make_record,
                                  pack_stats)
from repro.engine.scheduler import BatchSchedule, Step, rank_order
from repro.sssp.relax import ell_layout

Array = jax.Array


class StepOutcome(NamedTuple):
    """What a policy hands back when a superstep commits.

    ``stats`` is a packed device row (deferred single-fetch protocol);
    ``record`` is a ready host-side record for policies that already
    synced this superstep. Exactly one of the two is set.
    """

    mode: str
    stats: Optional[Array] = None
    record: Optional[SuperstepRecord] = None
    trees: Optional[int] = None


def build_fingerprint(g, rank: np.ndarray) -> str:
    """Stable fingerprint of (graph, hierarchy) — engine checkpoints
    carry it so a resume can never silently adopt label state that was
    committed for a *different* build sharing the checkpoint
    directory."""
    import hashlib
    h = hashlib.sha256()
    h.update(np.ascontiguousarray(
        np.asarray(rank).astype(np.int64)).tobytes())
    h.update(np.ascontiguousarray(
        np.asarray(g.ell_src).astype(np.int64)).tobytes())
    h.update(np.ascontiguousarray(
        np.asarray(g.ell_w).astype(np.float64)).tobytes())
    return h.hexdigest()


class Policy:
    """Interface the engine drives. Subclasses override what they use."""

    name: str = "?"
    #: checkpoint-compatibility class: construction policies are
    #: "build", incremental repair is "repair" — a checkpoint written
    #: under one kind is never adoptable by the other even when name /
    #: fingerprint / config happen to collide
    kind: str = "build"
    #: True → the engine fetches stats (and checks overflow) at every
    #: commit; False → one batched fetch after the loop.
    eager_stats: bool = False
    #: set by every concrete policy: sha256 of (graph, rank) — resume
    #: refuses checkpoints from a different build input
    fingerprint: Optional[str] = None

    def config(self) -> dict:
        """Schedule-shaping knobs; a checkpoint written under a
        different config must not be resumed (batch grouping changes
        the committed boundaries and, for optimistic algorithms, the
        labels themselves)."""
        return {}

    def schedule(self):
        raise NotImplementedError

    def begin(self, start_pos: int, resumed: bool) -> None:
        """Called once before the loop (after any resume restore)."""

    def prologue(self, sink) -> Optional[Tuple[StepOutcome, int]]:
        """Optional pre-loop phase consuming roots (e.g. the Hybrid's
        Common-Label-Table supersteps); returns (outcome, new_pos).
        Only called on fresh (non-resumed) runs."""
        return None

    def step(self, st: Step, sink) -> Optional[StepOutcome]:
        """Process one scheduled step; ``None`` = buffered, no commit."""
        raise NotImplementedError

    def epilogue(self, sink) -> Optional[StepOutcome]:
        """Commit any buffered tail work (e.g. GLL's final flush)."""
        return None

    def observe(self, record: SuperstepRecord) -> None:
        """Committed-record hook (the Hybrid's Ψ switch lives here)."""

    # ------------------------------------------------ checkpoint bits

    def meta(self) -> dict:
        return {}

    def load_meta(self, meta: dict) -> None:
        del meta

    def counters(self) -> Dict[str, int]:
        return {}

    def load_counters(self, counters: Dict[str, int]) -> None:
        del counters

    def extras(self, sink) -> dict:
        return {}


# ---------------------------------------------------------------- plant

class PlantPolicy(Policy):
    """PLaNT (§5.2): unpruned max-rank-ancestor trees, zero
    cross-tree dependence — emissions are canonical on arrival."""

    name = "plant"

    def __init__(self, g, rank: np.ndarray, *, batch: int,
                 hc: Optional[LabelTable] = None,
                 roots_order: Optional[np.ndarray] = None):
        self.batch = int(batch)
        self.order = (np.asarray(roots_order) if roots_order is not None
                      else rank_order(rank))
        self.ell_src = jnp.asarray(g.ell_src)
        self.ell_w = jnp.asarray(g.ell_w)
        # the sweep's layout (in-degree width classes for the XLA
        # form): built eagerly here, from the host arrays, because
        # inside the jitted plant_batch the adjacency is a tracer
        self.layout = ell_layout(g.ell_src, g.ell_w)
        self.rank_d = jnp.asarray(np.asarray(rank).astype(np.int32))
        self.hc = hc
        self.fingerprint = build_fingerprint(g, rank)
        # a custom root order or a Common Label Table changes which
        # labels each superstep emits — both are part of the build
        # input, so both join the resume fingerprint
        import hashlib
        if roots_order is not None:
            self.fingerprint += ":" + hashlib.sha256(
                np.ascontiguousarray(
                    self.order.astype(np.int64)).tobytes()).hexdigest()
        if hc is not None:
            h = hashlib.sha256()
            h.update(np.ascontiguousarray(
                np.asarray(hc.hubs).astype(np.int64)).tobytes())
            h.update(np.ascontiguousarray(
                np.asarray(hc.dist).astype(np.float64)).tobytes())
            self.fingerprint += ":hc:" + h.hexdigest()

    def config(self) -> dict:
        return {"batch": self.batch, "use_hc": self.hc is not None}

    def schedule(self) -> BatchSchedule:
        return BatchSchedule(self.order, self.batch)

    def step(self, st: Step, sink) -> StepOutcome:
        from repro.core.plant import plant_batch
        roots_d = jnp.asarray(st.roots)
        valid_d = jnp.asarray(st.valid)
        tb = plant_batch(self.ell_src, self.ell_w, self.rank_d, roots_d,
                         valid_d, hc=self.hc, use_hc=self.hc is not None,
                         layout=self.layout)
        sink.insert(roots_d, tb.emit, tb.dist)
        stats = pack_stats(jnp.sum(tb.emit, dtype=jnp.int32),
                           jnp.sum(tb.explored * valid_d,
                                   dtype=jnp.int32),
                           tb.sweeps)
        return StepOutcome(mode=self.name, stats=stats,
                           trees=int(st.valid.sum()))


# ------------------------------------------------------------- directed

class DirectedPlantPolicy(Policy):
    """Footnote-1 digraph labeling: per batch, one PLaNTed tree on G
    (fills ``L_in``) and one on Gᵀ (fills ``L_out``)."""

    name = "directed"

    def __init__(self, g, rank: np.ndarray, *, batch: int):
        assert g.directed
        gr = g.reverse()
        self.batch = int(batch)
        self.order = rank_order(rank)
        self.fwd = (jnp.asarray(g.ell_src), jnp.asarray(g.ell_w))
        self.bwd = (jnp.asarray(gr.ell_src), jnp.asarray(gr.ell_w))
        self.fwd_layout = ell_layout(g.ell_src, g.ell_w)
        self.bwd_layout = ell_layout(gr.ell_src, gr.ell_w)
        self.rank_d = jnp.asarray(np.asarray(rank).astype(np.int32))
        self.fingerprint = build_fingerprint(g, rank)

    def config(self) -> dict:
        return {"batch": self.batch}

    def schedule(self) -> BatchSchedule:
        return BatchSchedule(self.order, self.batch)

    def step(self, st: Step, sink) -> StepOutcome:
        from repro.core.plant import plant_batch
        r = jnp.asarray(st.roots)
        v = jnp.asarray(st.valid)
        tb_f = plant_batch(self.fwd[0], self.fwd[1], self.rank_d, r, v,
                           layout=self.fwd_layout)
        sink.insert(r, tb_f.emit, tb_f.dist, channel="in")
        tb_b = plant_batch(self.bwd[0], self.bwd[1], self.rank_d, r, v,
                           layout=self.bwd_layout)
        sink.insert(r, tb_b.emit, tb_b.dist, channel="out")
        stats = pack_stats(
            jnp.sum(tb_f.emit, dtype=jnp.int32)
            + jnp.sum(tb_b.emit, dtype=jnp.int32),
            jnp.sum((tb_f.explored + tb_b.explored) * v,
                    dtype=jnp.int32),
            jnp.maximum(tb_f.sweeps, tb_b.sweeps))
        return StepOutcome(mode="directed", stats=stats,
                           trees=int(st.valid.sum()))


# ------------------------------------------------------------ GLL / LCC

class GLLPolicy(Policy):
    """Optimistic construction + interleaved DQ_Clean (§4).

    A *superstep* is one α-threshold flush: batches accumulate
    optimistic emissions in a local table; when the local label count
    crosses ``α·n`` (never, for LCC/paraPLL) the pending emissions are
    cleaned against global ∪ local and committed to the sink — whose
    table doubles as the *global* table the distance queries consult.
    """

    eager_stats = True          # the α-threshold decision is host-side

    def __init__(self, g, rank: np.ndarray, *, batch: int, cap: int,
                 alpha: Optional[float] = 4.0, rank_queries: bool = True,
                 clean: bool = True, plant_first_superstep: bool = False,
                 mode_name: str = "gll"):
        self.name = mode_name
        self.n = g.n
        self.cap = int(cap)
        self.batch = int(batch)
        self.order = rank_order(rank)
        self.ell_src = jnp.asarray(g.ell_src)
        self.ell_w = jnp.asarray(g.ell_w)
        self.layout = ell_layout(g.ell_src, g.ell_w)
        self.rank_d = jnp.asarray(np.asarray(rank).astype(np.int32))
        self.alpha = alpha
        self.rank_queries = rank_queries
        self.clean = clean
        self.plant_first = plant_first_superstep
        self.threshold = (np.inf if alpha is None
                          else float(alpha) * self.n)
        self.loc = lbl.empty(self.n, self.cap)
        self.pending: List = []
        self.local_labels = 0
        self._trees_pending = 0
        self._first = True
        self._cleaned = 0
        self._constructed = 0
        self.fingerprint = build_fingerprint(g, rank)

    def config(self) -> dict:
        return {"batch": self.batch,
                "alpha": None if self.alpha is None else float(self.alpha),
                "rank_queries": self.rank_queries, "clean": self.clean,
                "plant_first": self.plant_first}

    def schedule(self) -> BatchSchedule:
        return BatchSchedule(self.order, self.batch)

    def begin(self, start_pos: int, resumed: bool) -> None:
        # a resumed run re-enters at a flush boundary: the local table
        # and pending buffer start empty, and the PLaNTed first
        # superstep (if any) is already committed
        self._first = start_pos == 0

    def step(self, st: Step, sink) -> Optional[StepOutcome]:
        from repro.core.gll import BatchLabels, construct_batch
        from repro.core.plant import plant_batch
        roots_d = jnp.asarray(st.roots)
        valid_d = jnp.asarray(st.valid)
        if self._first and self.plant_first:
            tb = plant_batch(self.ell_src, self.ell_w, self.rank_d,
                             roots_d, valid_d, layout=self.layout)
            bl = BatchLabels(roots=roots_d, emit=tb.emit, dist=tb.dist)
        else:
            bl = construct_batch(self.ell_src, self.ell_w, self.rank_d,
                                 roots_d, valid_d, sink.table(),
                                 self.loc,
                                 rank_queries=self.rank_queries,
                                 layout=self.layout)
        self._first = False
        self.loc, ovf = lbl.insert_batch(self.loc, roots_d, bl.emit,
                                         bl.dist)
        sink.note_overflow(ovf)
        self.pending.append(bl)
        self._trees_pending += int(bl.roots.shape[0])
        nl = int(jnp.sum(bl.emit))
        self.local_labels += nl
        self._constructed += nl
        if self.local_labels >= self.threshold:
            return self._flush(sink)
        return None

    def epilogue(self, sink) -> Optional[StepOutcome]:
        return self._flush(sink)

    def _flush(self, sink) -> Optional[StepOutcome]:
        from repro.core.gll import clean_superstep
        if not self.pending:
            return None
        roots = jnp.concatenate([b.roots for b in self.pending])
        emit = jnp.concatenate([b.emit for b in self.pending])
        dist = jnp.concatenate([b.dist for b in self.pending])
        if self.clean:
            red = clean_superstep(sink.table(), self.loc, self.rank_d,
                                  roots, emit, dist)
            self._cleaned += int(jnp.sum(red))
            emit = emit & ~red
        sink.insert(roots, emit, dist)
        committed = int(jnp.sum(emit))
        trees = self._trees_pending
        self.loc = lbl.empty(self.n, self.cap)
        self.pending = []
        self.local_labels = 0
        self._trees_pending = 0
        return StepOutcome(
            mode=self.name, trees=trees,
            record=make_record(self.name, labels=committed, trees=trees))

    def counters(self) -> Dict[str, int]:
        return {"cleaned": self._cleaned,
                "constructed": self._constructed}

    def load_counters(self, counters: Dict[str, int]) -> None:
        self._cleaned = int(counters.get("cleaned", 0))
        self._constructed = int(counters.get("constructed", 0))


# -------------------------------------------------------------- pll-ref

class PLLRefPolicy(Policy):
    """Sequential PLL oracle (Akiba et al.) driven through the engine:
    the host oracle computes the exact CHL once, then the emissions
    replay through the scheduler in rank order — so even the reference
    path exercises sinks, checkpoints and streaming sharding."""

    name = "pll-ref"

    def __init__(self, g, rank: np.ndarray, *, batch: int):
        self.g = g
        self.n = g.n
        self.batch = int(batch)
        self.rank = np.asarray(rank)
        self.order = rank_order(rank)
        self._by_hub: Optional[Dict[int, List[Tuple[int, float]]]] = None
        self.fingerprint = build_fingerprint(g, rank)

    def config(self) -> dict:
        return {"batch": self.batch}

    def schedule(self) -> BatchSchedule:
        return BatchSchedule(self.order, self.batch)

    def begin(self, start_pos: int, resumed: bool) -> None:
        from repro.core.pll import pll_undirected
        sets = pll_undirected(self.g, self.rank)
        by_hub: Dict[int, List[Tuple[int, float]]] = {}
        for v, row in enumerate(sets):
            for h, d in row.items():
                by_hub.setdefault(int(h), []).append((v, float(d)))
        self._by_hub = by_hub

    def step(self, st: Step, sink) -> StepOutcome:
        B = len(st.roots)
        emit = np.zeros((B, self.n), dtype=bool)
        dd = np.full((B, self.n), np.inf, dtype=np.float32)
        for b in range(B):
            if not st.valid[b]:
                continue
            for v, d in self._by_hub.get(int(st.roots[b]), ()):
                emit[b, v] = True
                dd[b, v] = d
        sink.insert(jnp.asarray(st.roots), jnp.asarray(emit),
                    jnp.asarray(dd))
        return StepOutcome(
            mode=self.name, trees=int(st.valid.sum()),
            record=make_record(self.name, labels=int(emit.sum()),
                               trees=int(st.valid.sum())))
