"""PLaNT — Prune Labels And (do) Not (prune) Trees (paper §5.2).

The paper's key contribution: construct *unpruned* SPTs that carry the
max-rank-ancestor along shortest paths, and select labels by a local
criterion — no dependence on labels from other trees, hence an
embarrassingly parallel, zero-communication CHL construction.

TPU adaptation (DESIGN.md §2 A2): the ancestor array ``a[v]`` of Alg. 3
becomes the ``mrank`` plane of the batched relaxation; the label
criterion ``max(R(v), R(a[v])) ≤ R(h)`` becomes the pointwise
post-filter ``mrank[v] == R(root)``. Early termination is subsumed by
fixpoint detection. Optional common-label pruning (§5.3) blocks
propagation out of vertices already covered by a top-η hub and masks
emission at covered vertices (both provably CHL-safe — DESIGN.md §2).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelTable
from repro.sssp import relax

Array = jax.Array


class TreeBatch(NamedTuple):
    """Result of one batch of PLaNTed trees."""
    emit: Array       # bool [B, n] — label (root_b, v) is canonical
    dist: Array       # f32  [B, n]
    explored: Array   # i32  [B] — vertices touched per tree (Ψ numerator)
    sweeps: Array     # i32  [] — relaxation sweeps to fixpoint


@functools.partial(jax.jit, static_argnames=("use_hc",))
def plant_batch(ell_src: Array, ell_w: Array, rank: Array, roots: Array,
                valid: Array, hc: LabelTable | None = None,
                use_hc: bool = False, layout=None) -> TreeBatch:
    """PLaNT a batch of trees rooted at ``roots`` (mask via ``valid``).

    ``hc``/``use_hc``: the Common Label Table of §5.3 — labels of the
    top-η hubs, used as a distance-query pruning oracle for PLaNTed
    trees.

    ``layout``: optional precomputed sweep layout
    (`repro.sssp.relax.ell_layout`), built outside because the
    adjacency is a tracer in here: the in-degree width classes the XLA
    sweep gathers through (`DegreeBuckets`), or the source-bucketed
    ELL that keeps the fused kernel past the single-window VMEM budget
    (`BucketedEll`). Both are pytrees, so they thread through this jit
    like any other operand.
    """
    if use_hc:
        assert hc is not None
        hmap = lbl.hub_distance_map(hc, roots)          # [B, n]
        cover = lbl.cover_distance(hc, hmap)            # loop-invariant

        def block(dist: Array, roots_: Array) -> Array:
            return cover <= dist
        block_fn = block
    else:
        block_fn = None

    st = relax.batched_sssp_maxrank(ell_src, ell_w, rank, roots,
                                    block_fn=block_fn, layout=layout)
    root_rank = rank[roots][:, None]
    emit = (st.mrank == root_rank) & jnp.isfinite(st.dist)
    if use_hc:
        emit &= ~(cover <= st.dist)
    emit &= valid[:, None]
    return TreeBatch(emit=emit, dist=st.dist, explored=st.explored,
                     sweeps=st.sweeps)


def plant_chl(g, rank: np.ndarray, *, batch: int = 16,
              cap: Optional[int] = None,
              hc: Optional[LabelTable] = None,
              roots_order: Optional[np.ndarray] = None,
              ckpt=None, resume: bool = False,
              ) -> Tuple[LabelTable, dict]:
    """Full CHL construction with pure PLaNT.

    Thin wrapper over the superstep engine (``repro.engine`` owns the
    batching, the deferred one-fetch stats protocol, and — new with
    the engine — checkpoint/resume via ``ckpt``). Embarrassingly
    parallel over root batches; each batch's labels are final (no
    cleaning — the paper's minimality-by-construction). Returns the
    label table and a stats dict (Ψ per batch etc.).
    """
    from repro.engine import run_build
    res = run_build(g, rank, algo="plant", batch=batch, cap=cap, hc=hc,
                    roots_order=roots_order, ckpt=ckpt, resume=resume)
    stats = {"explored": [r.explored for r in res.records],
             "labels": [r.labels for r in res.records],
             "sweeps": [r.sweeps for r in res.records],
             "psi": [r.psi for r in res.records]}
    return res.sink.table(), stats
