"""Distributed policies — DGLL / Hybrid / PLaNT-dist over a mesh.

One policy covers the whole §5 family: PLaNT supersteps while
``Ψ ≤ Ψ_th``, DGLL supersteps after (``psi_threshold=inf`` → pure
PLaNT, ``0`` → pure DGLL), optional Common-Label-Table prologue
(§5.3), and the §Perf-2 compact-broadcast fallback. The superstep
``shard_map`` kernels stay in ``repro.core.dgll``; this module only
*drives* them — scheduling, growth, the Ψ switch and checkpointing all
belong to the engine.

Kept separate from :mod:`repro.engine.policies` so importing the
engine does not pull in ``shard_map``/mesh machinery for single-host
builds.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import labels as lbl
from repro.core.labels import LabelTable
from repro.engine.policies import Policy, StepOutcome, build_fingerprint
from repro.engine.records import make_record, pack_stats
from repro.engine.scheduler import QueueSchedule, Step, pad_step, \
    rank_order
from repro.ft.elastic import HeartbeatMonitor, lost_roots

Array = jax.Array


def auto_psi_threshold(q: int, gamma: float = 12.0) -> float:
    """Ψ_th as a function of cluster size (the paper's §8 future work:
    "make … the switching point from PLaNT to DGLL a function of both
    q and Ψ").

    Cost model: a PLaNTed tree costs Ψ explored-vertex relaxations per
    label with zero communication; a DGLL tree costs ~O(1) pruned
    relaxations per label plus a broadcast+cleaning share in which
    *every* node answers every query — growing with q. Equating the
    two gives a switch point linear in q: Ψ_th = γ·q (γ calibrated on
    the Fig. 6 sweeps, where road/scale-free optima cross at
    γ ≈ 10–15 for q ∈ {1..8})."""
    return gamma * max(1, q)


def build_common_table(g, rank: np.ndarray, eta_roots: np.ndarray,
                       hc_cap: int) -> LabelTable:
    """Replicated Common Label Table from the top-η PLaNTed trees.

    Beyond-paper twist: recomputed on every node instead of broadcast —
    PLaNT trees depend on nothing, so replication costs zero
    communication (η extra tree constructions amortized over the run).
    """
    from repro.core.plant import plant_batch
    from repro.sssp.relax import ell_layout
    n = g.n
    hc = lbl.empty(n, hc_cap)
    roots = jnp.asarray(np.asarray(eta_roots).astype(np.int32))
    valid = jnp.ones(len(eta_roots), dtype=bool)
    es = jnp.asarray(g.ell_src)
    ew = jnp.asarray(g.ell_w)
    tb = plant_batch(es, ew,
                     jnp.asarray(np.asarray(rank).astype(np.int32)),
                     roots, valid, layout=ell_layout(g.ell_src, g.ell_w))
    hc, ovf = lbl.insert_batch(hc, roots, tb.emit, tb.dist)
    if bool(ovf):
        raise lbl.LabelOverflowError(hc_cap, "common label table")
    return hc


def _fetch_mesh_stats(out) -> Tuple[int, int, bool, bool]:
    """All of a superstep's scalar stats in ONE blocking device fetch —
    the ``SuperstepOut`` collective outputs reduced through the shared
    packed protocol (``repro.engine.records.pack_stats``)."""
    packed = np.asarray(pack_stats(
        jnp.sum(out.new_labels, dtype=jnp.int32),
        jnp.sum(out.explored, dtype=jnp.int32),
        overflow=jnp.any(out.overflow),
        compact_overflow=jnp.any(out.compact_overflow)))
    return (int(packed[0]), int(packed[1]),
            bool(packed[3]), bool(packed[4]))


class DistributedPolicy(Policy):
    """The §5 superstep family as one engine policy."""

    eager_stats = True          # Ψ switch + compact fallback are host
                                # decisions per superstep

    def __init__(self, g, rank: np.ndarray, *, mesh, batch: int = 4,
                 beta: float = 8.0, first_superstep: int = 1,
                 cap: int, eta: int = 0, hc_cap: int = 64,
                 psi_threshold: Optional[float] = 100.0,
                 compact: int = 0, mode_name: str = "dgll",
                 verbose: bool = False,
                 monitor: Optional[HeartbeatMonitor] = None,
                 silent_after: Optional[Dict[int, int]] = None):
        from repro.core import dgll as dist
        self.name = mode_name
        self._dist = dist
        self.g = g
        self.n = g.n
        self.cap = int(cap)
        self.mesh = mesh
        self.q = int(mesh.devices.size)
        if psi_threshold is None:
            psi_threshold = auto_psi_threshold(self.q)
        self.psi_threshold = float(psi_threshold)
        self.batch = int(batch)
        self.beta = float(beta)
        self.first_superstep = int(first_superstep)
        self.eta = int(eta)
        self.hc_cap = int(hc_cap)
        self.compact = int(compact)
        self.verbose = verbose
        self.rank = np.asarray(rank)
        self.queues = dist.assign_roots(self.rank, self.q)
        self.rank_d = jnp.asarray(self.rank.astype(np.int32))
        # NOTE: the adjacency enters the shard_map supersteps as traced
        # operands, so past the single-window VMEM budget those sweeps
        # fall back to the jnp reference (one-time warning). Threading
        # a BucketedEll through dgll_superstep_fn's collectives is the
        # documented follow-on; single-host policies already stream
        # the source-windowed kernel.
        self.ell_src = jnp.asarray(g.ell_src)
        self.ell_w = jnp.asarray(g.ell_w)
        self._rep = NamedSharding(mesh, P())
        self._node_sh = NamedSharding(mesh, P("node"))
        self.plant_mode = self.psi_threshold > 0.0
        self.hc: Optional[LabelTable] = None
        self._fns: Dict[tuple, object] = {}    # (T, mode-key) → jitted
        self._comm_label_slots = 0
        self.fingerprint = build_fingerprint(g, rank)
        # fault tolerance (repro.ft): ``monitor`` detects nodes gone
        # silent; ``silent_after`` is the simulation hook — node → last
        # superstep it completes before going dark (the masked columns
        # honestly never run). Detected-dead nodes' unfinished roots
        # are re-PLaNTed on the survivors (§5.2: trees depend on
        # nothing, so recovery is just more planting).
        self.monitor = monitor
        self.silent_after = dict(silent_after or {})
        self.dead_nodes: list = []
        self._silent_from_pos: Dict[int, int] = {}
        self._superstep = 0
        self._replanted_trees = 0
        self._replanted_labels = 0

    def config(self) -> dict:
        return {"batch": self.batch, "beta": self.beta,
                "first_superstep": self.first_superstep,
                "eta": self.eta, "hc_cap": self.hc_cap,
                "psi_threshold": self.psi_threshold,
                "compact": self.compact, "q": self.q}

    # ------------------------------------------------------- schedule

    def schedule(self) -> QueueSchedule:
        return QueueSchedule(self.queues, self.batch, self.beta,
                             self.first_superstep)

    def begin(self, start_pos: int, resumed: bool) -> None:
        # the Common Label Table is stateless (PLaNT trees depend on
        # nothing), so it is rebuilt even on resume instead of being
        # checkpointed
        if self.eta > 0:
            k0 = -(-self.eta // self.q)
            eta_eff = min(k0 * self.q, self.n)
            order = rank_order(self.rank)
            hc = build_common_table(self.g, self.rank, order[:eta_eff],
                                    self.hc_cap)
            self.hc = LabelTable(*(jax.device_put(x, self._rep)
                                   for x in hc))
        else:
            hc = lbl.empty(self.n, 1)
            self.hc = LabelTable(*(jax.device_put(x, self._rep)
                                   for x in hc))

    def prologue(self, sink) -> Optional[Tuple[StepOutcome, int]]:
        if self.eta <= 0:
            return None
        # the η trees' labels also enter the owners' partitions
        k0 = -(-self.eta // self.q)
        fn = self._step_fn(T=k0, batch=k0, plant=True, use_hc=False,
                           compact=0)
        roots = pad_step(self.queues, 0, k0, batch=k0)
        out = fn(sink.table, self.hc, self.rank_d,
                 jax.device_put(jnp.asarray(roots), self._node_sh),
                 jax.device_put(jnp.asarray(roots >= 0), self._node_sh),
                 self.ell_src, self.ell_w)
        sink.set_table(out.table)
        nl, exp, ovf, _ = _fetch_mesh_stats(out)
        sink.note_overflow(ovf)
        rec = make_record("plant-hc", labels=nl, explored=exp,
                          trees=int((roots >= 0).sum()))
        return StepOutcome(mode="plant-hc", record=rec,
                           trees=rec.trees), k0

    # ----------------------------------------------------------- step

    def _step_fn(self, T: int, batch: int, plant: bool, use_hc: bool,
                 compact: int):
        key = (T, batch, plant, use_hc, compact)
        if key not in self._fns:
            # one live entry per shape/mode — a growing schedule never
            # revisits old T, so don't hoard stale jitted closures
            self._fns = {k: v for k, v in self._fns.items()
                         if k[0] == T}
            self._fns[key] = self._dist.dgll_superstep_fn(
                self.mesh, self.n, batch=batch, use_hc=use_hc,
                plant_trees=plant, compact=compact)
        return self._fns[key]

    # -------------------------------------------------- heartbeats

    def _silent_nodes(self) -> set:
        """Nodes dark at the current superstep (simulation hook)."""
        return {node for node, last in self.silent_after.items()
                if self._superstep > int(last)}

    def _heartbeat(self, st: Step) -> Step:
        """Report live nodes to the monitor and mask silent nodes'
        work — a dead node's supersteps genuinely do not run."""
        if self.monitor is None and not self.silent_after:
            return st
        silent = self._silent_nodes()
        if self.monitor is not None:
            for node in range(self.q):
                if node not in silent:
                    self.monitor.report(node, self._superstep)
        if not silent:
            return st
        valid = np.asarray(st.valid).copy()
        for node in silent:
            # queue position where this node's committed work ends —
            # everything from here on is its lost tail
            self._silent_from_pos.setdefault(node, st.pos)
            valid[node, :] = False
        return st._replace(valid=valid)

    def _recover(self, sink) -> None:
        """Declare nodes the monitor lost and re-PLaNT their
        unfinished queues on the survivors."""
        if self.monitor is None:
            return
        for node in self.monitor.lost(self._superstep):
            if node in self.dead_nodes:
                continue
            self.dead_nodes.append(node)
            completed = self._silent_from_pos.get(
                node, self.queues.shape[1])
            roots = lost_roots(self.queues, [node], completed)
            if self.verbose:
                print(f"  node {node} lost at superstep "
                      f"{self._superstep}; re-planting "
                      f"{len(roots)} roots on survivors")
            if len(roots):
                self._replant(sink, roots)

    def _replant(self, sink, roots: np.ndarray) -> None:
        """One extra communication-free plant launch over the lost
        roots, spread round-robin across surviving rows (any row may
        plant any tree — canonical emissions are order-independent,
        so the labels land set-identical to an undisturbed run)."""
        survivors = [r for r in range(self.q)
                     if r not in set(self.dead_nodes)]
        if not survivors:
            raise RuntimeError("no surviving nodes to re-plant on")
        roots = np.asarray(roots, np.int32)
        S = len(survivors)
        T = -(-len(roots) // S)
        mat = np.full((self.q, T), -1, np.int32)
        for i, r in enumerate(roots):
            mat[survivors[i % S], i // S] = r
        fn = self._step_fn(T, T, plant=True, use_hc=self.eta > 0,
                           compact=0)
        out = fn(sink.table, self.hc, self.rank_d,
                 jax.device_put(jnp.asarray(mat), self._node_sh),
                 jax.device_put(jnp.asarray(mat >= 0), self._node_sh),
                 self.ell_src, self.ell_w)
        sink.set_table(out.table)
        nl, _, ovf, _ = _fetch_mesh_stats(out)
        sink.note_overflow(ovf)
        self._replanted_trees += int(len(roots))
        self._replanted_labels += nl

    # ----------------------------------------------------------------

    def step(self, st: Step, sink) -> StepOutcome:
        self._superstep += 1
        st = self._heartbeat(st)
        T = st.roots.shape[1]
        roots_d = jax.device_put(jnp.asarray(st.roots), self._node_sh)
        valid_d = jax.device_put(jnp.asarray(st.valid), self._node_sh)
        use_hc = self.eta > 0
        if self.plant_mode:
            fn = self._step_fn(T, self.batch, plant=True, use_hc=use_hc,
                               compact=0)
            out = fn(sink.table, self.hc, self.rank_d, roots_d, valid_d,
                     self.ell_src, self.ell_w)
            mode = "plant"
            nl, exp, ovf, _ = _fetch_mesh_stats(out)
        else:
            fn = self._step_fn(T, self.batch, plant=False, use_hc=use_hc,
                               compact=self.compact)
            out = fn(sink.table, self.hc, self.rank_d, roots_d, valid_d,
                     self.ell_src, self.ell_w)
            mode = "dgll"
            slots = (self.q * T * min(self.compact, self.n)
                     if self.compact else self.q * T * self.n)
            nl, exp, ovf, compact_ovf = _fetch_mesh_stats(out)
            if self.compact and compact_ovf:
                # §Perf-2 fallback: budget too small for this
                # superstep's label yield → redo densely (correctness
                # over speed; rare once DGLL mode starts — Fig. 2)
                fn = self._step_fn(T, self.batch, plant=False,
                                   use_hc=use_hc, compact=0)
                out = fn(sink.table, self.hc, self.rank_d, roots_d,
                         valid_d, self.ell_src, self.ell_w)
                mode = "dgll-dense-fallback"
                slots = self.q * T * self.n
                nl, exp, ovf, _ = _fetch_mesh_stats(out)
            self._comm_label_slots += slots
        sink.set_table(out.table)
        sink.note_overflow(ovf)
        self._recover(sink)
        rec = make_record(mode, labels=nl, explored=exp,
                          trees=int(st.valid.sum()))
        return StepOutcome(mode=mode, record=rec, trees=rec.trees)

    def observe(self, record) -> None:
        if (self.plant_mode and record.mode != "plant-hc"
                and record.psi is not None
                and record.psi > self.psi_threshold):
            self.plant_mode = False    # Ψ too high → switch (§5.2.1)
            if self.verbose:
                print(f"  Ψ={record.psi:.1f} > "
                      f"Ψ_th={self.psi_threshold:.1f} → "
                      "switching to DGLL")

    # ------------------------------------------------ checkpoint bits

    def meta(self) -> dict:
        return {"plant_mode": bool(self.plant_mode),
                "dead_nodes": [int(x) for x in self.dead_nodes]}

    def load_meta(self, meta: dict) -> None:
        self.plant_mode = bool(meta.get("plant_mode", self.plant_mode))
        self.dead_nodes = [int(x) for x in meta.get("dead_nodes", [])]

    def counters(self) -> Dict[str, int]:
        return {"comm_label_slots": self._comm_label_slots,
                "replanted_trees": self._replanted_trees,
                "replanted_labels": self._replanted_labels}

    def load_counters(self, counters: Dict[str, int]) -> None:
        self._comm_label_slots = int(
            counters.get("comm_label_slots", 0))
        self._replanted_trees = int(
            counters.get("replanted_trees", 0))
        self._replanted_labels = int(
            counters.get("replanted_labels", 0))

    def extras(self, sink) -> dict:
        return {"partitioned": sink.table, "hc": self.hc, "q": self.q,
                "psi_threshold": self.psi_threshold,
                "comm_label_slots": self._comm_label_slots}
