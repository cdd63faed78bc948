"""Batched lexicographic shortest-path relaxation (the TPU engine).

This module is the hardware adaptation of the paper's per-thread
binary-heap Dijkstra (DESIGN.md §2 A1/A2): a *pull-based* iterate
over a padded ELL adjacency that relaxes a **batch of trees** per
sweep, to fixpoint. Two quantities propagate jointly:

- ``dist[b, v]``  — tentative distance from ``roots[b]`` to ``v``;
- ``mrank[b, v]`` — the maximum rank over the *union of all shortest
  roots[b]→v paths discovered so far* (endpoints inclusive). This is
  the dense-form equivalent of PLaNT's ancestor array ``a[v]`` with the
  equal-distance merge of Alg. 3 line 12.

The PLaNT label criterion then reads pointwise:

    emit (h, δ_v) into L_v   ⇔   mrank[v] == R(h)   (h = root)

since the root lies on every path, ``mrank[v] ≥ R(root)`` whenever v is
reached, with equality iff the root is the highest-ranked vertex on the
union of shortest paths — exactly the CHL membership condition.

Pruning (LCC rank/distance queries, Hybrid common-label queries) is
expressed as a *blocking mask* recomputed every sweep: blocked vertices
do not propagate outward and never emit. Re-evaluating the mask at each
sweep converges to the pruned-Dijkstra semantics: along any surviving
shortest path the chain of vertices unblocks inductively from the root
(see the correctness discussion in DESIGN.md §2 A3).

Execution model (this is the single hottest path in the repo):

- each sweep is the jnp reference (`ell_sweep_ref`) compiled by XLA
  on every platform (Mosaic refuses the fused Pallas kernel's gather
  for TPU), or the bit-identical fused Pallas ELL (min,+,max-rank)
  kernel when asked for (``use_kernel`` / ``REPRO_ELL_RELAX=kernel``;
  `REPRO_PALLAS_BACKEND` picks the Pallas execution mode underneath);
- the XLA form gathers through the graph's in-degree width classes
  (`DegreeBuckets`, built by `ell_layout` and threaded in through
  ``layout=``): its gathers, adds and reductions scale with the slots
  of the classes — the arcs, rounded up per class — instead of n
  times the widest row, with one gather per class
  (`repro.kernels.ell_relax.degree`). The driver moves ``rank``,
  ``roots`` and the initial planes into the layout's vertex order
  once, runs the fixpoint loop there with no per-sweep permutation
  (a ``block_fn`` is applied through the permutation), and moves
  ``dist``/``mrank`` back once. Without a
  layout (traced adjacency, as in the distributed shard_map
  supersteps) it sweeps the dense ELL;
- sweeps are **frontier-gated** (default on the kernel path): only
  vertices whose (dist, mrank) changed last sweep — plus vertices
  that just *unblocked*, whose pending contribution was masked while
  blocked — propagate. The blocked semantics are preserved exactly:
  the propagation plane is re-derived every sweep as
  ``where(blocked | ~frontier, +inf, dist)`` and monotonicity of
  (min-dist, max-mrank) makes gated fixpoints equal to dense ones (a
  non-frontier source's contribution was already folded the sweep
  after it last changed or unblocked);
- trees whose frontier is empty are **retired**: an ``alive`` flag per
  tree lets the kernel skip their tiles, so converged roots stop
  paying sweep cost while the batch's stragglers finish. On the
  dense-XLA reference path masking cannot skip gather work, so
  gating defaults off there (``frontier_gating`` overrides either
  way; fixpoints are identical);
- the fixpoint condition is checked every ``check_every`` sweeps
  (strided convergence checks) instead of reducing ``any(changed)``
  over ``[B, n]`` after every sweep — overshoot past the fixpoint is
  a no-op (empty frontier ⇒ identity sweep), bounded by
  ``check_every - 1`` cheap extra sweeps. Default stride follows the
  backend too: ``DEFAULT_CHECK_EVERY`` on the kernel path (amortizes
  the per-iteration cond sync), 1 on the jnp path.
"""

from __future__ import annotations

from typing import Callable, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp

from repro.kernels.ell_relax import (BucketedEll, DegreeBuckets,
                                     degree_buckets, ell_sweep,
                                     resolve_sweep_backend,
                                     resolve_use_kernel, sweep_degree,
                                     sweep_layout)

Array = jax.Array
BlockFn = Callable[[Array, Array], Array]   # (dist [B,n], roots [B]) -> blocked [B,n]
Layout = Union[BucketedEll, DegreeBuckets]

DEFAULT_CHECK_EVERY = 4


def ell_layout(ell_src: Array, ell_w: Array, *,
               max_window: Optional[int] = None) -> Optional[Layout]:
    """The layout the sweep over this adjacency takes, built once per
    graph on the host.

    - The XLA form (the default on every platform): the in-degree
      width classes (`DegreeBuckets`), so each sweep gathers only the
      slots real in-edges use — on a road graph the arcs themselves,
      not every row padded to the widest — bit-identically.
    - The Pallas kernel (``REPRO_ELL_RELAX=kernel``): the
      source-bucketed layout (`BucketedEll`, cached) past the
      single-window VMEM budget, or None when one window covers the
      graph.

    Callers that relax the same graph repeatedly under jit (engine
    policies, `plant_batch`) build this *eagerly* — the adjacency is a
    tracer inside their jitted step functions, where neither layout
    can be built — and thread it through ``layout=``. Returns None
    when the adjacency is itself traced.
    """
    if not resolve_use_kernel(None):
        return degree_buckets(ell_src, ell_w)
    return sweep_layout(ell_src, ell_w, max_window=max_window)


class RelaxState(NamedTuple):
    dist: Array     # f32 [B, n]
    mrank: Array    # i32 [B, n] ; -1 where unreached
    sweeps: Array   # i32 scalar — sweeps executed (diagnostic / Ψ input;
    #                 counts up to check_every-1 no-op sweeps past fixpoint)
    explored: Array  # i32 [B] — #vertices each tree touched (Ψ numerator)


def _sweep(dist: Array, mrank: Array, blocked: Array,
           ell_src: Array, ell_w: Array, rank: Array):
    """One dense (ungated) relaxation sweep — the historical pure-jnp
    reference, retained as the parity oracle for the fused kernel and
    the frontier-gated driver. Shapes: dist/mrank [B,n]; ell_* [n,deg].
    """
    # Gather neighbor states along in-edges: [B, n, deg]
    nd = dist[:, ell_src]
    nm = mrank[:, ell_src]
    nblk = blocked[:, ell_src]
    cand = jnp.where(nblk, jnp.inf, nd + ell_w[None, :, :])
    best = jnp.min(cand, axis=-1)
    new_dist = jnp.minimum(dist, best)
    # Ranks over candidate edges attaining the (finite) new distance.
    attains = (cand <= new_dist[..., None]) & jnp.isfinite(cand)
    cr = jnp.where(attains, nm, -1)
    best_in = jnp.max(cr, axis=-1)                       # [B, n]
    through = jnp.where(best_in >= 0,
                        jnp.maximum(best_in, rank[None, :]), -1)
    keep = jnp.where(dist <= new_dist, mrank, -1)        # == only when kept
    new_mrank = jnp.maximum(keep, through)
    return new_dist, new_mrank


def _init(n: int, roots: Array, rank: Array):
    B = roots.shape[0]
    dist = jnp.full((B, n), jnp.inf, dtype=jnp.float32)
    dist = dist.at[jnp.arange(B), roots].set(0.0)
    mrank = jnp.full((B, n), -1, dtype=jnp.int32)
    mrank = mrank.at[jnp.arange(B), roots].set(rank[roots])
    return dist, mrank


def batched_sssp_maxrank(
    ell_src: Array,
    ell_w: Array,
    rank: Array,
    roots: Array,
    *,
    block_fn: Optional[BlockFn] = None,
    max_sweeps: Optional[int] = None,
    check_every: Optional[int] = None,
    use_kernel: Optional[bool] = None,
    frontier_gating: Optional[bool] = None,
    layout: Optional[Layout] = None,
) -> RelaxState:
    """Relax a batch of trees to fixpoint.

    Args:
      ell_src: int32 [n, deg] — in-edge sources (pull layout).
      ell_w:   f32  [n, deg] — in-edge weights, ``inf`` padding.
      rank:    int32 [n] — network hierarchy (larger = more important).
      roots:   int32 [B] — tree roots of this batch.
      block_fn: optional per-sweep pruning mask (rank/distance queries).
        Roots are force-unblocked.
      max_sweeps: safety bound (default: n sweeps — Bellman–Ford bound).
      check_every: sweeps between fixpoint checks; 1 = check after
        every sweep. Default: ``DEFAULT_CHECK_EVERY`` on the fused
        kernel path (amortizes the per-iteration cond sync), 1 on the
        jnp path (XLA cannot skip the overshoot sweeps, so striding
        only adds work there).
      use_kernel: fused Pallas ELL kernel vs jnp reference; ``None`` =
        `resolve_use_kernel` (the jnp reference unless
        ``REPRO_ELL_RELAX=kernel``).
      frontier_gating: mask propagation down to the active frontier
        and retire converged trees. Default: follows the kernel
        decision — gating lets the kernel skip retired tiles, while
        on the dense-XLA path masking cannot reduce the gather cost
        and would only add per-sweep mask work. Either setting
        reaches the identical fixpoint (monotone lattice).
      layout: optional precomputed layout (see `ell_layout`). A
        `DegreeBuckets` makes the XLA form gather through the graph's
        in-degree width classes (the kernel ignores it). A
        `BucketedEll` selects the source-windowed kernel for
        adjacencies past the single-window VMEM budget; when it is
        omitted and the adjacency is concrete, the backend resolver
        builds + caches one on demand; when the adjacency is traced
        (this function called under an outer jit) the sweep falls back
        to the jnp reference with a one-time warning — thread a layout
        in to keep the kernel.

    Returns:
      RelaxState with fixpoint ``dist``/``mrank``.
    """
    n = ell_src.shape[0]
    B = roots.shape[0]
    rank = rank.astype(jnp.int32)
    cap = n if max_sweeps is None else max_sweeps
    # gating/stride defaults must track the path that actually runs:
    # oversized adjacencies get the source-windowed kernel when a
    # bucketed layout is available (given or buildable), and only fall
    # back to the reference — where gating + striding would only add
    # work — when the adjacency is traced with no layout threaded in
    buckets = layout if isinstance(layout, DegreeBuckets) else None
    kern, layout = resolve_sweep_backend(
        ell_src, ell_w, use_kernel=use_kernel,
        layout=None if buckets is not None else layout)
    if kern:
        buckets = None                 # the kernel reads the dense ELL
    gated = kern if frontier_gating is None else bool(frontier_gating)
    stride = ((DEFAULT_CHECK_EVERY if kern else 1)
              if check_every is None else check_every)
    stride = max(1, min(stride, cap))

    # the loop runs in the layout's vertex order: rank and roots move
    # there once, the planes move back once after the loop
    if buckets is not None:
        perm, inv = buckets.perm, buckets.inv
        rank_s, roots_s = rank[perm], inv[roots]

        def sweep(dist, mrank, prop, alive):
            del alive                  # the XLA form cannot skip trees
            return sweep_degree(dist, mrank, prop, mrank, buckets,
                                rank_s)

        def block_s(dist):
            return block_fn(dist[:, inv], roots)[:, perm]
    else:
        rank_s, roots_s = rank, roots

        def sweep(dist, mrank, prop, alive):
            return ell_sweep(dist, mrank, prop, alive, ell_src, ell_w,
                             rank, use_kernel=kern, layout=layout)

        def block_s(dist):
            return block_fn(dist, roots)
    dist0, mrank0 = _init(n, roots_s, rank_s)

    def blocked_of(dist):
        if block_fn is None:
            return jnp.zeros(dist.shape, dtype=bool)
        blk = block_s(dist)
        # the root of each tree never blocks its own propagation
        return blk.at[jnp.arange(B), roots_s].set(False)

    has_block = block_fn is not None
    carry_blocked = has_block and gated

    def sweep_once(carry, _):
        if carry_blocked:
            dist, mrank, prev_blocked, frontier = carry
        else:
            dist, mrank, frontier = carry
        if gated:
            if has_block:
                blocked = blocked_of(dist)
                # frontier ∪ newly-unblocked: a vertex that unblocks
                # without a state change still owes its (previously
                # masked) contribution
                active = frontier | (prev_blocked & ~blocked)
                prop = jnp.where(blocked | ~active, jnp.inf, dist)
            else:
                active = frontier
                prop = jnp.where(active, dist, jnp.inf)
            alive = jnp.any(active, axis=1)
        else:
            prop = (jnp.where(blocked_of(dist), jnp.inf, dist)
                    if has_block else dist)
            alive = jnp.ones((B,), dtype=bool)
        nd, nm = sweep(dist, mrank, prop, alive)
        new_frontier = (nd < dist) | (nm != mrank)
        if carry_blocked:
            return (nd, nm, blocked, new_frontier), None
        return (nd, nm, new_frontier), None

    def cond(carry):
        state, it = carry
        return jnp.any(state[-1]) & (it < cap)

    def body(carry):
        state, it = carry
        for _ in range(stride):          # unrolled: XLA fuses sweeps
            state, _ = sweep_once(state, None)
        return state, it + stride

    # first sweep is dense (everything is in the initial frontier);
    # prev_blocked is seeded consistently so no spurious unblocks fire
    frontier0 = jnp.ones((B, n), dtype=bool)
    state0 = ((dist0, mrank0, blocked_of(dist0), frontier0)
              if carry_blocked else (dist0, mrank0, frontier0))
    state, sweeps = jax.lax.while_loop(cond, body, (state0, jnp.int32(0)))
    dist, mrank = state[0], state[1]
    if buckets is not None:
        dist, mrank = dist[:, inv], mrank[:, inv]
    explored = jnp.sum(jnp.isfinite(dist), axis=-1).astype(jnp.int32)
    return RelaxState(dist=dist, mrank=mrank, sweeps=sweeps,
                      explored=explored)


def batched_sssp(ell_src: Array, ell_w: Array, roots: Array,
                 *, max_sweeps: Optional[int] = None,
                 check_every: Optional[int] = None,
                 use_kernel: Optional[bool] = None,
                 frontier_gating: Optional[bool] = None,
                 layout: Optional[Layout] = None) -> Array:
    """Plain batched SSSP distances (no rank tracking): f32 [B, n].

    Runs through the same fused/gated engine with a constant-zero rank
    plane (the mrank lattice is then reachability, which converges with
    dist and adds no sweeps).
    """
    n = ell_src.shape[0]
    st = batched_sssp_maxrank(
        ell_src, ell_w, jnp.zeros((n,), dtype=jnp.int32), roots,
        max_sweeps=max_sweeps, check_every=check_every,
        use_kernel=use_kernel, frontier_gating=frontier_gating,
        layout=layout)
    return st.dist


def rank_block(rank: Array) -> BlockFn:
    """Rank-query pruning mask (LCC Alg. 1 line 5): block v with
    ``R(v) > R(root)``."""
    def fn(dist: Array, roots: Array) -> Array:
        del dist
        return rank[None, :] > rank[roots][:, None]
    return fn


def combine_blocks(*fns: BlockFn) -> BlockFn:
    def fn(dist: Array, roots: Array) -> Array:
        out = fns[0](dist, roots)
        for f in fns[1:]:
            out = out | f(dist, roots)
        return out
    return fn
