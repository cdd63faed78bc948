"""LCC and GLL — optimistic parallel CHL construction + cleaning (§4).

Shared-memory mapping (DESIGN.md §2 A4): the paper's ``p`` concurrent
threads popping rank-ordered roots become a vmapped *batch* of ``B``
trees per step. Trees inside a batch cannot see each other's labels —
exactly the paper's optimistic mistakes — and the interleaved cleaning
(DQ_Clean) removes every redundant label, yielding the CHL.

- LCC  = construct everything, clean once at the end (§4.1).
- GLL  = clean whenever the *local* table exceeds ``α·n`` labels, then
  commit to the *global* table (§4.2). Construction-time distance
  queries consult global ∪ local (footnote 4); cleaning probes only the
  superstep's own labels (the paper's repeated-work optimization).
- ``plant_first_superstep`` reproduces the paper's §7.2 suggestion:
  PLaNT the first superstep (no pruning labels exist yet anyway).

The construction/cleaning correctness argument under batching —
including why optimistically emitted labels can carry inflated
distances and why DQ_Clean provably removes exactly the non-canonical
ones — is spelled out in DESIGN.md §2 A3.

This module keeps only the jitted batch kernels
(``construct_batch`` / ``clean_superstep``); the host superstep loop —
batching, α-threshold flushes, stats, checkpoint/resume — lives in
``repro.engine`` (``GLLPolicy``), and the ``*_chl`` functions are thin
wrappers over it.
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


class BatchLabels(NamedTuple):
    roots: Array   # i32 [B]
    emit: Array    # bool [B, n]
    dist: Array    # f32 [B, n]


@functools.partial(jax.jit, static_argnames=("rank_queries",))
def construct_batch(ell_src: Array, ell_w: Array, rank: Array,
                    roots: Array, valid: Array,
                    glob: LabelTable, loc: LabelTable,
                    rank_queries: bool = True,
                    layout=None) -> BatchLabels:
    """One batch of pruned trees (LCC-I / paraPLL inner step).

    Blocking = [rank query] ∨ distance query vs (global ∪ local)
    committed tables; emission = reached ∧ unblocked at fixpoint.

    ``layout``: optional precomputed sweep layout
    (`repro.sssp.relax.ell_layout`, a pytree) — the in-degree width
    classes of the XLA sweep, or the source-bucketed ELL that keeps
    the fused kernel past the single-window VMEM budget; without it
    the sweep reads the dense (traced) ELL.
    """
    hmap_g = lbl.hub_distance_map(glob, roots)
    hmap_l = lbl.hub_distance_map(loc, roots)
    cover = jnp.minimum(lbl.cover_distance(glob, hmap_g),
                        lbl.cover_distance(loc, hmap_l))    # [B, n]

    def dq_block(dist: Array, roots_: Array) -> Array:
        return cover <= dist

    fns = [dq_block]
    if rank_queries:
        fns.append(relax.rank_block(rank))
    block_fn = relax.combine_blocks(*fns)

    st = relax.batched_sssp_maxrank(ell_src, ell_w, rank, roots,
                                    block_fn=block_fn, layout=layout)
    emit = jnp.isfinite(st.dist) & ~(cover <= st.dist)
    if rank_queries:
        emit &= rank[None, :] <= rank[roots][:, None]
    # roots always label themselves
    B = roots.shape[0]
    emit = emit.at[jnp.arange(B), roots].set(True)
    emit &= valid[:, None]
    return BatchLabels(roots=roots, emit=emit, dist=st.dist)


@jax.jit
def clean_superstep(glob: LabelTable, loc: LabelTable, rank: Array,
                    batches_roots: Array, batches_emit: Array,
                    batches_dist: Array) -> Array:
    """DQ_Clean for every label emitted this superstep.

    Args are the stacked superstep emissions ``[T, n]`` (T = #roots this
    superstep). A label (h→v, δ) is redundant iff the best-rank common
    hub w of L_v and L_h with d(v,w)+d(h,w) ≤ δ outranks h
    (Alg. 2 lines 12–16). Probes global ∪ local (both contain exact
    distances for every canonical label at this point).

    Returns ``redundant [T, n]`` bool.
    """
    roots, emit, dist = batches_roots, batches_emit, batches_dist
    delta = jnp.where(emit, dist, -jnp.inf)      # never matches when ~emit
    hg = lbl.hub_distance_map(glob, roots)
    hl = lbl.hub_distance_map(loc, roots)
    best = jnp.maximum(
        lbl.cover_best_rank(glob, hg, rank, delta),
        lbl.cover_best_rank(loc, hl, rank, delta))
    return emit & (best > rank[roots][:, None])


def _legacy_stats(res) -> dict:
    """Engine records → the historical GLL counters dict."""
    return {"supersteps": len(res.records),
            "cleaned": res.counters.get("cleaned", 0),
            "constructed": res.counters.get("constructed", 0),
            "superstep_sizes": [r.trees for r in res.records]}


def gll_chl(g, rank: np.ndarray, *, batch: int = 8,
            alpha: Optional[float] = 4.0, cap: Optional[int] = None,
            rank_queries: bool = True, clean: bool = True,
            plant_first_superstep: bool = False,
            ckpt=None, resume: bool = False,
            ) -> Tuple[LabelTable, dict]:
    """GLL (α finite), LCC (``alpha=None`` → clean once at end), or the
    paraPLL baseline (``rank_queries=False, clean=False``).

    Thin wrapper over the superstep engine: ``repro.engine`` owns the
    batching, α-threshold flush commits, and (new) checkpoint/resume
    at flush boundaries via ``ckpt``. Returns (global label table,
    stats).
    """
    from repro.engine import run_build
    res = run_build(g, rank, algo="gll", batch=batch, cap=cap,
                    alpha=alpha, rank_queries=rank_queries, clean=clean,
                    plant_first_superstep=plant_first_superstep,
                    ckpt=ckpt, resume=resume)
    return res.sink.table(), _legacy_stats(res)


def lcc_chl(g, rank: np.ndarray, *, batch: int = 8,
            cap: Optional[int] = None, ckpt=None,
            resume: bool = False) -> Tuple[LabelTable, dict]:
    """LCC (§4.1): construct everything, one cleaning pass at the end."""
    from repro.engine import run_build
    res = run_build(g, rank, algo="lcc", batch=batch, cap=cap,
                    ckpt=ckpt, resume=resume)
    return res.sink.table(), _legacy_stats(res)


def parapll_chl(g, rank: np.ndarray, *, batch: int = 8,
                cap: Optional[int] = None, ckpt=None,
                resume: bool = False) -> Tuple[LabelTable, dict]:
    """SparaPLL-style baseline [19]: concurrent pruned trees with root-
    label hashing, **no rank queries, no cleaning** — satisfies cover
    but not minimality (redundant labels grow with ``batch``)."""
    from repro.engine import run_build
    res = run_build(g, rank, algo="parapll", batch=batch, cap=cap,
                    ckpt=ckpt, resume=resume)
    return res.sink.table(), _legacy_stats(res)
