"""Fused ELL relaxation kernel vs the retained jnp reference.

Three layers of parity, all bit-exact (integral float weights make
(min,+,max) arithmetic exact in f32):

1. sweep level — `ell_sweep(use_kernel=True)` (Pallas, via the compat
   backend dispatch) against `ell_sweep_ref` across odd shapes,
   inf-padded ELL rows, equal-distance rank ties, unreachable
   vertices and frontier/blocked masks;
2. fixpoint level — `batched_sssp_maxrank` with the fused kernel vs
   the jnp path, with and without block_fn pruning;
3. driver level — frontier gating + strided convergence checks
   (``check_every > 1``) against per-sweep checking and against a
   dense ungated loop built from the retained `relax._sweep`
   reference;
4. window level — the source-windowed kernel (bucketed layout +
   scalar-prefetched window table) against the dense kernel and both
   references, at boundary sizes, under forced VMEM budgets, through
   the gated fixpoint driver, and up into `build()`'s report notes.
"""

import warnings

import numpy as np
import pytest

import jax.numpy as jnp

from repro.compat import pallas as pallas_compat
from repro.graphs import grid_road, random_connected, scale_free
from repro.graphs.ranking import degree_ranking, random_ranking
from repro.kernels.ell_relax import (ELL_RELAX_ENV_VAR,
                                     VMEM_BUDGET_ENV_VAR, ell_sweep,
                                     ell_sweep_bucketed_ref,
                                     ell_sweep_ref, kernel_fits,
                                     clear_layout_cache, reset_warnings,
                                     resolve_sweep_backend,
                                     resolve_use_kernel, sweep_layout,
                                     vmem_budget, window_plan)
from repro.sssp import relax
from repro.sssp.relax import batched_sssp_maxrank, ell_layout


def _rand_sweep_state(rng, B, n, deg, reach=0.5, density=0.3):
    dist = np.where(rng.random((B, n)) < reach,
                    rng.integers(0, 9, (B, n)), np.inf).astype(np.float32)
    mrank = np.where(np.isfinite(dist),
                     rng.integers(0, 99, (B, n)), -1).astype(np.int32)
    blocked = rng.random((B, n)) < 0.2
    frontier = rng.random((B, n)) < 0.7
    prop = np.where(blocked | ~frontier, np.inf, dist).astype(np.float32)
    alive = frontier.any(axis=1)
    ell_src = rng.integers(0, n, (n, deg)).astype(np.int32)
    ell_w = np.where(rng.random((n, deg)) < density,
                     rng.integers(1, 9, (n, deg)), np.inf).astype(np.float32)
    rank = rng.permutation(n).astype(np.int32)
    return dist, mrank, prop, alive, ell_src, ell_w, rank


@pytest.mark.parametrize("B,n,deg", [
    (1, 1, 1), (3, 5, 7), (8, 128, 8), (16, 130, 17), (5, 260, 140),
    (2, 40, 3), (9, 300, 33),
])
@pytest.mark.parametrize("seed", [0, 1])
def test_ell_sweep_kernel_matches_ref(B, n, deg, seed):
    rng = np.random.default_rng(seed)
    dist, mrank, prop, alive, es, ew, rank = _rand_sweep_state(
        rng, B, n, deg)
    args = [jnp.asarray(x) for x in
            (dist, mrank, prop, alive, es, ew, rank)]
    dk, mk = ell_sweep(*args, use_kernel=True)
    dr, mr = ell_sweep(*args, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(mk), np.asarray(mr))


def test_ell_sweep_ref_equals_retained_dense_sweep():
    """prop-plane form == the historical blocked-gather `_sweep`."""
    rng = np.random.default_rng(5)
    B, n, deg = 6, 90, 11
    dist, mrank, _, _, es, ew, rank = _rand_sweep_state(rng, B, n, deg)
    blocked = rng.random((B, n)) < 0.25
    prop = np.where(blocked, np.inf, dist).astype(np.float32)
    j = jnp.asarray
    nd_ref, nm_ref = relax._sweep(j(dist), j(mrank), j(blocked),
                                  j(es), j(ew), j(rank))
    nd, nm = ell_sweep_ref(j(dist), j(mrank), j(prop), j(mrank),
                           j(es), j(ew), j(rank))
    np.testing.assert_array_equal(np.asarray(nd), np.asarray(nd_ref))
    np.testing.assert_array_equal(np.asarray(nm), np.asarray(nm_ref))


def test_ell_sweep_all_unreachable_and_padded_rows():
    B, n, deg = 4, 37, 5
    dist = np.full((B, n), np.inf, np.float32)
    mrank = np.full((B, n), -1, np.int32)
    alive = np.ones(B, bool)
    ell_src = np.zeros((n, deg), np.int32)
    ell_w = np.full((n, deg), np.inf, np.float32)   # fully inf-padded ELL
    rank = np.arange(n, dtype=np.int32)
    args = [jnp.asarray(x) for x in
            (dist, mrank, dist, alive, ell_src, ell_w, rank)]
    nd, nm = ell_sweep(*args, use_kernel=True)
    assert not np.isfinite(np.asarray(nd)).any()
    assert (np.asarray(nm) == -1).all()


def test_ell_sweep_equal_distance_rank_tie():
    # v=2 reachable from u=0 (mrank 7) and u=1 (mrank 9) at equal
    # distance: the payload must merge to max(9, rank[2])
    dist = np.array([[1.0, 1.0, np.inf]], np.float32)
    mrank = np.array([[7, 9, -1]], np.int32)
    ell_src = np.array([[0, 0], [0, 0], [0, 1]], np.int32)
    ell_w = np.array([[np.inf, np.inf], [np.inf, np.inf], [2.0, 2.0]],
                     np.float32)
    rank = np.array([7, 9, 3], np.int32)
    alive = np.ones(1, bool)
    args = [jnp.asarray(x) for x in
            (dist, mrank, dist, alive, ell_src, ell_w, rank)]
    for uk in (True, False):
        nd, nm = ell_sweep(*args, use_kernel=uk)
        assert np.asarray(nd)[0, 2] == 3.0
        assert np.asarray(nm)[0, 2] == 9


def test_ell_sweep_retired_tree_is_identity():
    rng = np.random.default_rng(3)
    B, n, deg = 5, 64, 6
    dist, mrank, _, _, es, ew, rank = _rand_sweep_state(rng, B, n, deg)
    prop = np.full((B, n), np.inf, np.float32)      # empty frontier
    alive = np.zeros(B, bool)
    args = [jnp.asarray(x) for x in
            (dist, mrank, prop, alive, es, ew, rank)]
    for uk in (True, False):
        nd, nm = ell_sweep(*args, use_kernel=uk)
        np.testing.assert_array_equal(np.asarray(nd), dist)
        np.testing.assert_array_equal(np.asarray(nm), mrank)


GRAPHS = [
    ("grid", lambda s: grid_road(6, 7, seed=s)),
    ("ba", lambda s: scale_free(48, attach=2, seed=s)),
    ("tree+", lambda s: random_connected(35, extra_edges=25, seed=s)),
    ("digraph", lambda s: random_connected(25, extra_edges=40, seed=s,
                                           directed=True)),
]


@pytest.mark.parametrize("name,gen", GRAPHS)
@pytest.mark.parametrize("seed", [0, 1])
def test_fixpoint_kernel_matches_ref_path(name, gen, seed):
    g = gen(seed)
    rank = random_ranking(g.n, seed=seed + 11)
    roots = np.arange(0, g.n, max(1, g.n // 6), dtype=np.int32)
    j = jnp.asarray
    kw = dict(block_fn=relax.rank_block(j(rank.astype(np.int32))))
    st_k = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                                j(roots), use_kernel=True, **kw)
    st_r = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                                j(roots), use_kernel=False, **kw)
    np.testing.assert_array_equal(np.asarray(st_k.dist),
                                  np.asarray(st_r.dist))
    np.testing.assert_array_equal(np.asarray(st_k.mrank),
                                  np.asarray(st_r.mrank))


@pytest.mark.parametrize("name,gen", GRAPHS[:2])
@pytest.mark.parametrize("check_every", [1, 2, 3, 7])
def test_strided_checks_and_gating_reach_same_fixpoint(name, gen,
                                                       check_every):
    """Frontier gating + check_every > 1 == per-sweep dense checking,
    including against an ungated loop over the retained `_sweep`."""
    g = gen(0)
    rank = degree_ranking(g)
    roots = np.arange(0, g.n, max(1, g.n // 5), dtype=np.int32)
    j = jnp.asarray
    st = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                              j(roots), check_every=check_every,
                              frontier_gating=True)
    st1 = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                               j(roots), check_every=1,
                               frontier_gating=False)
    np.testing.assert_array_equal(np.asarray(st.dist),
                                  np.asarray(st1.dist))
    np.testing.assert_array_equal(np.asarray(st.mrank),
                                  np.asarray(st1.mrank))
    # dense ungated fixpoint via the retained reference sweep
    rank_d = j(rank.astype(np.int32))
    dist, mrank = relax._init(g.n, j(roots), rank_d)
    blocked = jnp.zeros(dist.shape, dtype=bool)
    for _ in range(g.n):
        nd, nm = relax._sweep(dist, mrank, blocked, j(g.ell_src),
                              j(g.ell_w), rank_d)
        if bool(jnp.all(nd == dist) & jnp.all(nm == mrank)):
            break
        dist, mrank = nd, nm
    np.testing.assert_array_equal(np.asarray(st.dist), np.asarray(dist))
    np.testing.assert_array_equal(np.asarray(st.mrank),
                                  np.asarray(mrank))


def test_gated_fixpoint_with_cover_block_fn():
    """Distance-query (cover) pruning under gating: the blocked mask is
    re-derived from frontier ∪ newly-unblocked every sweep and must
    agree with the ungated pruned fixpoint."""
    g = scale_free(60, attach=2, seed=4)
    rank = degree_ranking(g)
    roots = np.arange(8, dtype=np.int32)
    j = jnp.asarray
    # a synthetic cover plane: pretend the top hub covers everything at
    # distance <= 3 (exercises blocked→unblocked transitions as dist
    # tightens under it)
    cover = jnp.full((len(roots), g.n), 3.0, dtype=jnp.float32)

    def block(dist, roots_):
        return cover <= dist

    out = {}
    for gated in (False, True):
        for uk in (False, True):
            st = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                                      j(roots), block_fn=block,
                                      use_kernel=uk,
                                      frontier_gating=gated)
            out[gated, uk] = st
    ref = out[False, False]
    for key, st in out.items():
        np.testing.assert_array_equal(np.asarray(st.dist),
                                      np.asarray(ref.dist))
        np.testing.assert_array_equal(np.asarray(st.mrank),
                                      np.asarray(ref.mrank))


def test_resolve_use_kernel_env(monkeypatch):
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "kernel")
    assert resolve_use_kernel(None) is True
    # the XLA form is the default and has no second spelling
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "ref")
    with pytest.raises(ValueError):
        resolve_use_kernel(None)
    # auto is the XLA form on every platform (Mosaic refuses the
    # kernel's gather for TPU), whatever the Pallas backend resolves to
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "auto")
    assert resolve_use_kernel(None) is False
    monkeypatch.setattr(pallas_compat, "target_platform", lambda: "tpu")
    assert resolve_use_kernel(None) is False
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        resolve_use_kernel(None)
    monkeypatch.delenv(ELL_RELAX_ENV_VAR, raising=False)
    assert resolve_use_kernel(None) is False
    # explicit arg always wins
    assert resolve_use_kernel(True) is True
    assert resolve_use_kernel(False) is False


def test_explicit_env_kernel_end_to_end(monkeypatch):
    """REPRO_ELL_RELAX=kernel routes the whole construction through
    the Pallas path (interpret mode here) with identical labels.

    The backend choice is resolved at trace time, so the jit caches
    are cleared between runs — same caveat as REPRO_PALLAS_BACKEND
    under an outer jit (see `kernels.minplus`).
    """
    import jax

    from repro.core import labels as lbl
    from repro.core.plant import plant_chl
    g = grid_road(4, 4, seed=2)
    rank = degree_ranking(g)
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "auto")
    jax.clear_caches()
    t_ref, _ = plant_chl(g, rank, batch=8)
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "kernel")
    jax.clear_caches()
    t_k, _ = plant_chl(g, rank, batch=8)
    jax.clear_caches()
    assert lbl.to_numpy_sets(t_k) == lbl.to_numpy_sets(t_ref)


# ------------------------------------------------- source windowing


def test_vmem_budget_env_parsing(monkeypatch):
    monkeypatch.delenv(VMEM_BUDGET_ENV_VAR, raising=False)
    assert vmem_budget() == 8 * 1024 * 1024
    for raw, want in [("4096", 4096), ("16k", 16 * 1024),
                      ("2m", 2 * 1024 ** 2), ("1g", 1024 ** 3),
                      ("8M", 8 * 1024 ** 2)]:
        monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, raw)
        assert vmem_budget() == want, raw
    for raw in ("bogus", "12q", "", "0", "-8k"):
        monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, raw)
        if raw == "":
            assert vmem_budget() == 8 * 1024 * 1024
        else:
            with pytest.raises(ValueError):
                vmem_budget()


def test_window_plan_geometry_and_kernel_fits(monkeypatch):
    monkeypatch.delenv(VMEM_BUDGET_ENV_VAR, raising=False)
    # the default budget reproduces the historical n = 131072 wall as
    # the single-window boundary
    assert kernel_fits(131072)
    assert not kernel_fits(131073)
    p = window_plan(131072)
    assert p == (131072, 1, 131072)
    p = window_plan(131073)
    assert p.num_windows == 2 and p.window * 2 == p.n_pad
    assert p.n_pad >= 131073 and p.window % 128 == 0
    # balanced non-divisible split under a forced cap
    p = window_plan(1000, max_window=384)
    assert p == (384, 3, 1152)
    # forced cap rounds down to the vertex tile
    assert window_plan(1000, max_window=300).window <= 256
    # small n: one tile-rounded window
    assert window_plan(100) == (128, 1, 128)


def test_bucketed_layout_conserves_edges():
    rng = np.random.default_rng(7)
    n, deg = 700, 9
    _, _, _, _, es, ew, _ = _rand_sweep_state(rng, 1, n, deg)
    layout = sweep_layout(es, ew, max_window=256)
    assert layout is not None and layout.num_windows == 3
    src_b = np.asarray(layout.src)
    w_b = np.asarray(layout.w)
    cw = np.asarray(layout.chunk_win)
    assert cw.shape == (layout.n_pad // layout.bn, layout.num_chunks)
    assert ((cw >= 0) & (cw < layout.num_windows)).all()
    # window-local sources stay inside their window
    fin = np.isfinite(w_b)
    assert ((src_b >= 0) & (src_b < layout.window))[fin].all()
    # per-row multiset of finite (global source, weight) edges survives
    wincol = np.repeat(np.repeat(cw, layout.bn, 0), layout.dk, 1)
    gsrc = src_b + wincol * layout.window
    for v in range(0, n, 97):
        orig = sorted((int(s), float(x)) for s, x in
                      zip(es[v], ew[v]) if np.isfinite(x))
        got = sorted((int(s), float(x)) for s, x in
                     zip(gsrc[v][fin[v]], w_b[v][fin[v]]))
        assert got == orig, v
    # padding rows carry no edges
    assert not fin[n:].any()


@pytest.mark.parametrize("n", [255, 256, 257, 300, 513])
def test_windowed_sweep_matches_dense_and_ref(n):
    rng = np.random.default_rng(n)
    B, deg = 8, 7
    dist, mrank, prop, alive, es, ew, rank = _rand_sweep_state(
        rng, B, n, deg)
    layout = sweep_layout(es, ew, max_window=128)
    assert layout is not None and layout.num_windows > 1
    args = [jnp.asarray(x) for x in
            (dist, mrank, prop, alive, es, ew, rank)]
    dw, mw = ell_sweep(*args, use_kernel=True, layout=layout)
    dd, md = ell_sweep(*args, use_kernel=True)
    dr, mr = ell_sweep(*args, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dd))
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(md))
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(mr))


def test_bucketed_ref_matches_dense_ref():
    rng = np.random.default_rng(12)
    B, n, deg = 5, 413, 11
    dist, mrank, prop, _, es, ew, rank = _rand_sweep_state(
        rng, B, n, deg)
    layout = sweep_layout(es, ew, max_window=256)
    assert layout is not None
    j = jnp.asarray
    want = ell_sweep_ref(j(dist), j(mrank), j(prop), j(mrank),
                         j(es), j(ew), j(rank))
    got = ell_sweep_bucketed_ref(j(dist), j(mrank), j(prop), j(mrank),
                                 layout, j(rank))
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(want[0]))
    np.testing.assert_array_equal(np.asarray(got[1]),
                                  np.asarray(want[1]))


def test_env_budget_forces_windowed_auto_layout(monkeypatch):
    """REPRO_ELL_VMEM_BUDGET shrinks the window cap so small graphs
    exercise multi-window streaming — the CI smoke knob."""
    monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, "16k")   # window cap = 256
    clear_layout_cache()
    assert not kernel_fits(600)
    rng = np.random.default_rng(2)
    B, n, deg = 8, 600, 6
    dist, mrank, prop, alive, es, ew, rank = _rand_sweep_state(
        rng, B, n, deg)
    kern, layout = resolve_sweep_backend(es, ew, use_kernel=True)
    assert kern and layout is not None and layout.num_windows > 1
    args = [jnp.asarray(x) for x in
            (dist, mrank, prop, alive, es, ew, rank)]
    dw, mw = ell_sweep(*args, use_kernel=True)       # auto-built layout
    dr, mr = ell_sweep(*args, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dw), np.asarray(dr))
    np.testing.assert_array_equal(np.asarray(mw), np.asarray(mr))
    clear_layout_cache()


def test_fixpoint_with_windowed_layout_and_gating():
    """The gated driver (frontier masks, retirement, strided checks)
    reaches the identical fixpoint on the windowed kernel path."""
    g = scale_free(300, attach=2, seed=4)
    rank = degree_ranking(g)
    roots = np.arange(0, g.n, 23, dtype=np.int32)
    j = jnp.asarray
    es, ew = j(g.ell_src), j(g.ell_w)
    layout = sweep_layout(es, ew, max_window=128)
    assert layout is not None and layout.num_windows > 1
    ref = batched_sssp_maxrank(es, ew, j(rank), j(roots),
                               use_kernel=False)
    for gated in (False, True):
        st = batched_sssp_maxrank(es, ew, j(rank), j(roots),
                                  use_kernel=True, layout=layout,
                                  frontier_gating=gated)
        np.testing.assert_array_equal(np.asarray(st.dist),
                                      np.asarray(ref.dist))
        np.testing.assert_array_equal(np.asarray(st.mrank),
                                      np.asarray(ref.mrank))


def test_traced_fallback_warns_per_size_with_reset(monkeypatch):
    from repro.kernels.ell_relax import ops
    monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, "16k")
    reset_warnings()
    assert not ops.warn_vmem_fallback(100)           # fits: no warning
    with pytest.warns(UserWarning, match="VMEM"):
        assert ops.warn_vmem_fallback(600)
    with warnings.catch_warnings():                  # same n: quiet
        warnings.simplefilter("error")
        assert ops.warn_vmem_fallback(600)
    with pytest.warns(UserWarning, match="VMEM"):    # new n: warns
        assert ops.warn_vmem_fallback(601)
    reset_warnings()
    with pytest.warns(UserWarning, match="VMEM"):    # reset re-arms
        assert ops.warn_vmem_fallback(600)
    reset_warnings()


def test_traced_adjacency_falls_back_to_ref(monkeypatch):
    """Inside an outer jit with no threaded layout the adjacency is a
    tracer — the sweep must fall back to the reference (not crash) and
    still produce the reference fixpoint."""
    import jax
    monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, "16k")
    clear_layout_cache()
    reset_warnings()
    g = scale_free(300, attach=2, seed=9)
    rank = degree_ranking(g).astype(np.int32)
    roots = np.arange(6, dtype=np.int32)
    j = jnp.asarray

    @jax.jit
    def traced(es, ew, rk, rt):
        st = batched_sssp_maxrank(es, ew, rk, rt, use_kernel=True)
        return st.dist, st.mrank

    with pytest.warns(UserWarning, match="traced"):
        dist, mrank = traced(j(g.ell_src), j(g.ell_w), j(rank),
                             j(roots))
    ref = batched_sssp_maxrank(j(g.ell_src), j(g.ell_w), j(rank),
                               j(roots), use_kernel=False)
    np.testing.assert_array_equal(np.asarray(dist), np.asarray(ref.dist))
    np.testing.assert_array_equal(np.asarray(mrank),
                                  np.asarray(ref.mrank))
    reset_warnings()
    clear_layout_cache()


def test_engine_layout_threading_survives_jit(monkeypatch):
    """The engine policies build the layout eagerly and thread it as a
    pytree through the jitted batch kernels — the windowed kernel runs
    *inside* plant_batch's jit with identical labels."""
    import jax

    from repro.core import labels as lbl
    from repro.core.plant import plant_chl
    g = scale_free(300, attach=2, seed=2)
    rank = degree_ranking(g)
    order = np.argsort(-rank.astype(np.int64))[:32].astype(np.int64)
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "auto")
    jax.clear_caches()
    t_ref, _ = plant_chl(g, rank, batch=32, roots_order=order)
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "kernel")
    monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, "16k")
    clear_layout_cache()
    assert ell_layout(g.ell_src, g.ell_w) is not None
    jax.clear_caches()
    t_win, _ = plant_chl(g, rank, batch=32, roots_order=order)
    jax.clear_caches()
    clear_layout_cache()
    assert lbl.to_numpy_sets(t_win) == lbl.to_numpy_sets(t_ref)


def test_build_report_records_windowed_note(monkeypatch):
    """`build()` past the (forced) VMEM budget records the windowing
    advisory — window geometry included — and it survives the manifest
    roundtrip."""
    import jax

    from repro.index import BuildPlan, build
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "kernel")
    monkeypatch.setenv(VMEM_BUDGET_ENV_VAR, "16k")
    clear_layout_cache()
    jax.clear_caches()
    g = scale_free(300, attach=2, seed=0)
    assert not kernel_fits(g.n)
    idx = build(g, degree_ranking(g), BuildPlan(algo="plant", batch=64))
    assert any("source-windowed" in note for note in idx.report.notes)
    plan = window_plan(g.n)
    assert any(f"window={plan.window}" in note
               for note in idx.report.notes)
    assert any("source-windowed" in n2 for n2 in
               type(idx.report).from_dict(idx.report.to_dict()).notes)
    assert idx.report.total_labels > 0
    jax.clear_caches()
    clear_layout_cache()
