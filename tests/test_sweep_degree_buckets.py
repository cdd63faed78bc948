"""The XLA sweep through in-degree width classes (`DegreeBuckets`)
against the dense ELL, bit for bit (integral float weights make
(min,+,max) arithmetic exact in f32, and ties common):

1. one sweep — `sweep_degree` on the permuted planes against
   `ell_sweep_ref`;
2. the fixpoint — `batched_sssp_maxrank` with and without the layout:
   ``dist``, ``mrank``, ``explored`` and the ``sweeps`` count;
3. the batch kernels — `plant_batch` (with and without the Common
   Label Table's blocking) and GLL's `construct_batch` (rank and
   distance-query blocking, applied through the permutation);
4. the layout itself — how its classes tile the permuted order, slots
   against arcs, one class for a graph of uniform degree, its cache
   (one layout per graph, a changed graph its own), and what
   `ell_layout` and `sweep_form` say.

Graphs: a small road network with isolated vertices (in-degrees 0..4),
a Graph500 Kronecker graph at scale 8 (power-of-two classes, a hub far
above the mean), a directed graph, and a torus of uniform degree 4.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from bench.data import graphs as bench_graphs
from repro.core import labels as lbl
from repro.core.gll import construct_batch
from repro.core.plant import plant_batch
from repro.graphs import from_edges
from repro.graphs.ranking import degree_ranking
from repro.kernels.ell_relax import (ELL_RELAX_ENV_VAR, DegreeBuckets,
                                     clear_layout_cache, degree_buckets,
                                     ell_sweep_ref, sweep_degree,
                                     sweep_form)
from repro.sssp.relax import batched_sssp_maxrank, ell_layout

GRAPHS = ["road", "kron", "directed", "uniform"]
B = 16


def _road():
    e = bench_graphs.road_network(9, 9, arcs_per_node=2.6, seed=3)
    # three vertices past the network's: in-degree 0
    return from_edges(e.n + 3, e.src, e.dst, e.w)


def _kron():
    e = bench_graphs.kronecker(8, 16, seed=1)
    return from_edges(e.n, e.src, e.dst, e.w)


def _directed():
    rng = np.random.default_rng(5)
    n, m = 150, 420
    return from_edges(n, rng.integers(0, n, m), rng.integers(0, n, m),
                      rng.integers(1, 9, m).astype(np.float32),
                      directed=True)


def _uniform():
    side = 10
    v = np.arange(side * side).reshape(side, side)
    src = np.concatenate([v.ravel(), v.ravel()])
    dst = np.concatenate([np.roll(v, 1, axis=1).ravel(),
                          np.roll(v, 1, axis=0).ravel()])
    w = np.random.default_rng(2).integers(1, 5, len(src))
    return from_edges(side * side, src, dst, w.astype(np.float32))


MAKE = {"road": _road, "kron": _kron, "directed": _directed,
        "uniform": _uniform}
_cache = {}


def _graph(name):
    if name not in _cache:
        _cache[name] = MAKE[name]()
    return _cache[name]


def _setup(name):
    g = _graph(name)
    rank = jnp.asarray(degree_ranking(g).astype(np.int32))
    es, ew = jnp.asarray(g.ell_src), jnp.asarray(g.ell_w)
    rng = np.random.default_rng(11)
    roots = jnp.asarray(rng.choice(g.n, B, replace=False).astype(np.int32))
    return g, es, ew, rank, roots, degree_buckets(g.ell_src, g.ell_w)


def _equal(a, b):
    for x, y in zip(a, b):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


def _sweep_state(rng, n):
    dist = np.where(rng.random((B, n)) < 0.5, rng.integers(0, 9, (B, n)),
                    np.inf).astype(np.float32)
    mrank = np.where(np.isfinite(dist), rng.integers(0, 99, (B, n)),
                     -1).astype(np.int32)
    masked = rng.random((B, n)) < 0.3
    prop = np.where(masked, np.inf, dist).astype(np.float32)
    return jnp.asarray(dist), jnp.asarray(mrank), jnp.asarray(prop)


def _scattered():
    """An ELL whose rows hold their edges anywhere, not first."""
    rng = np.random.default_rng(4)
    n, deg = 90, 12
    src = rng.integers(0, n, (n, deg)).astype(np.int32)
    w = np.where(rng.random((n, deg)) < 0.3, rng.integers(1, 9, (n, deg)),
                 np.inf).astype(np.float32)
    return src, w


@pytest.mark.parametrize("name", GRAPHS + ["scattered"])
def test_one_bucketed_sweep_equals_the_dense_sweep(name):
    if name == "scattered":
        es, ew = _scattered()
    else:
        g = _graph(name)
        es, ew = g.ell_src, g.ell_w
    n = es.shape[0]
    rng = np.random.default_rng(8)
    dist, mrank, prop = _sweep_state(rng, n)
    rank = jnp.asarray(rng.permutation(n).astype(np.int32))
    buckets = degree_buckets(es, ew)
    want = ell_sweep_ref(dist, mrank, prop, mrank, jnp.asarray(es),
                         jnp.asarray(ew), rank)
    perm, inv = buckets.perm, buckets.inv
    nd, nm = jax.jit(sweep_degree)(dist[:, perm], mrank[:, perm],
                                   prop[:, perm], mrank[:, perm], buckets,
                                   rank[perm])
    _equal(want, (nd[:, inv], nm[:, inv]))


@pytest.mark.parametrize("name", GRAPHS)
def test_fixpoint_equals_the_dense_fixpoint(name):
    g, es, ew, rank, roots, buckets = _setup(name)
    run = jax.jit(lambda layout: batched_sssp_maxrank(es, ew, rank, roots,
                                                      layout=layout))
    want, got = run(None), run(buckets)
    _equal(want, got)
    assert int(got.sweeps) > 1


@pytest.mark.parametrize("use_hc", [False, True])
@pytest.mark.parametrize("name", GRAPHS)
def test_plant_batch_emits_equal(name, use_hc):
    g, es, ew, rank, roots, buckets = _setup(name)
    valid = jnp.arange(B) % 5 != 4
    hc = None
    if use_hc:
        # the Common Label Table of the four highest-rank roots
        top = jnp.asarray(np.argsort(-np.asarray(rank))[:4]
                          .astype(np.int32))
        tb = plant_batch(es, ew, rank, top, jnp.ones(4, bool))
        hc, ovf = lbl.insert_batch(lbl.empty(g.n, g.n), top, tb.emit,
                                   tb.dist)
        assert not bool(ovf)
    want = plant_batch(es, ew, rank, roots, valid, hc=hc, use_hc=use_hc)
    got = plant_batch(es, ew, rank, roots, valid, hc=hc, use_hc=use_hc,
                      layout=buckets)
    _equal(want, got)
    assert bool(jnp.any(got.emit))


@pytest.mark.parametrize("name", GRAPHS)
def test_gll_batch_blocks_through_the_permutation(name):
    g, es, ew, rank, roots, buckets = _setup(name)
    valid = jnp.ones(B, bool)
    top = jnp.asarray(np.argsort(-np.asarray(rank))[:B].astype(np.int32))
    glob = lbl.empty(g.n, g.n)
    first = construct_batch(es, ew, rank, top, valid, glob, glob)
    glob, _ = lbl.insert_batch(glob, top, first.emit, first.dist)
    loc = lbl.empty(g.n, g.n)
    want = construct_batch(es, ew, rank, roots, valid, glob, loc)
    got = construct_batch(es, ew, rank, roots, valid, glob, loc,
                          layout=buckets)
    _equal(want, got)


@pytest.mark.parametrize("name", GRAPHS)
def test_classes_tile_the_permuted_order(name):
    g = _graph(name)
    buckets = degree_buckets(g.ell_src, g.ell_w)
    deg = np.isfinite(g.ell_w).sum(axis=1)
    assert buckets.arcs == int(deg.sum())
    widths = [wd for _, _, wd in buckets.classes]
    assert widths == sorted(set(widths)) and len(widths) <= 16
    if name == "road":
        assert widths == [0, 1, 2, 3, 4]
    if name == "uniform":
        assert widths == [4]
    inv, perm = np.asarray(buckets.inv), np.asarray(buckets.perm)
    np.testing.assert_array_equal(np.sort(perm), np.arange(g.n))
    np.testing.assert_array_equal(perm[inv], np.arange(g.n))
    assert buckets.classes[0][0] == 0
    assert buckets.classes[-1][1] == g.n
    for (_, hi, _), (lo, _, _) in zip(buckets.classes, buckets.classes[1:]):
        assert hi == lo
    wide = [c for c in buckets.classes if c[2]]
    for (lo, hi, wd), src in zip(wide, buckets.src):
        assert src.shape == (hi - lo, wd)
    for lo, hi, wd in buckets.classes:
        # each class holds exactly the vertices its width fits
        cls_deg = deg[perm[lo:hi]]
        assert cls_deg.max(initial=0) <= wd
        assert wd == 0 or cls_deg.min() > (wd // 2 if wd > 4 else wd - 1)
    assert buckets.arcs <= buckets.slots < g.ell_src.size
    assert buckets.slots <= 2 * buckets.arcs


def test_road_slots_within_five_percent_of_arcs():
    """At a size where a road's rows, not the padding, hold the slots:
    a 120 x 120 network at NY's arcs per node."""
    e = bench_graphs.road_network(120, 120, arcs_per_node=2.7760813,
                                  seed=0)
    g = from_edges(e.n, e.src, e.dst, e.w)
    buckets = degree_buckets(g.ell_src, g.ell_w)
    assert buckets.arcs == 2 * len(e.src)
    assert buckets.slots <= 1.05 * buckets.arcs
    assert g.ell_src.size > 2.5 * buckets.arcs


def test_kron_slots_within_twice_the_arcs():
    g = _graph("kron")
    buckets = degree_buckets(g.ell_src, g.ell_w)
    assert buckets.slots <= 2 * buckets.arcs
    assert g.ell_src.size > 5 * buckets.slots


def test_one_layout_per_graph_until_the_cache_is_cleared():
    g = _graph("road")
    one = degree_buckets(g.ell_src, g.ell_w)
    assert degree_buckets(g.ell_src, g.ell_w) is one
    # a copy of the adjacency is another graph to the cache
    assert degree_buckets(g.ell_src.copy(), g.ell_w.copy()) is not one
    clear_layout_cache()
    two = degree_buckets(g.ell_src, g.ell_w)
    assert two is not one and two.classes == one.classes


def test_a_changed_graph_gets_classes_of_its_own():
    """A repaired graph's degrees differ: its layout is built anew from
    its own rows and sweeps to the dense fixpoint."""
    e = bench_graphs.road_network(20, 20, arcs_per_node=2.6, seed=1)
    g = from_edges(e.n, e.src, e.dst, e.w)
    # one more edge between two vertices of degree 2: both become 3
    deg = np.isfinite(g.ell_w).sum(axis=1)
    a, b = [int(v) for v in np.flatnonzero(deg == 2)[:2]]
    g2 = from_edges(e.n, np.append(e.src, a), np.append(e.dst, b),
                    np.append(e.w, np.float32(7)))
    one, two = (degree_buckets(x.ell_src, x.ell_w) for x in (g, g2))
    assert two.arcs == one.arcs + 2
    size = {wd: hi - lo for lo, hi, wd in one.classes}
    size2 = {wd: hi - lo for lo, hi, wd in two.classes}
    assert size2[2] == size[2] - 2 and size2[3] == size[3] + 2
    rank = jnp.asarray(degree_ranking(g2).astype(np.int32))
    roots = jnp.asarray([a, b, 0, 1], dtype=jnp.int32)
    es, ew = jnp.asarray(g2.ell_src), jnp.asarray(g2.ell_w)
    run = jax.jit(lambda layout: batched_sssp_maxrank(es, ew, rank, roots,
                                                      layout=layout))
    _equal(run(None), run(two))


def test_ell_layout_picks_the_layout_of_the_sweep_form(monkeypatch):
    g = _graph("road")
    monkeypatch.delenv(ELL_RELAX_ENV_VAR, raising=False)
    layout = ell_layout(g.ell_src, g.ell_w)
    assert isinstance(layout, DegreeBuckets)
    note = sweep_form(g.n, layout=layout)
    assert note.startswith("sweep: xla") and "width classes" in note
    assert f"{layout.slots} slots for {layout.arcs} arcs" in note
    assert "dense ELL" in sweep_form(g.n, distributed=True)
    # traced adjacency: no layout, the dense ELL
    assert jax.jit(lambda s, w: jnp.float32(
        ell_layout(s, w) is None))(g.ell_src, g.ell_w) == 1.0
    monkeypatch.setenv(ELL_RELAX_ENV_VAR, "kernel")
    assert not isinstance(ell_layout(g.ell_src, g.ell_w), DegreeBuckets)
