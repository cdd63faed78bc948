"""The yardstick's own data and arithmetic: graph generators, samplers,
roofline byte counts, the reference and its comparisons, the peaks."""

from __future__ import annotations

import numpy as np
import pytest

from bench.data import graphs, ranking, reference, roofline, samplers
from bench.peaks import PEAKS, UnknownDevice, peaks


@pytest.mark.parametrize("rows,cols,seed", [(6, 9, 0), (20, 20, 3),
                                            (1, 12, 1), (200, 200, 0)])
def test_grid_copy_is_the_programs_grid(rows, cols, seed):
    from repro.graphs import grid_road
    from repro.graphs.graph import from_edges

    e = graphs.grid_road(rows, cols, seed=seed)
    mine = from_edges(e.n, e.src, e.dst, e.w)
    theirs = grid_road(rows, cols, seed=seed)
    for key in ("ell_src", "ell_w", "indptr", "indices", "weights"):
        np.testing.assert_array_equal(getattr(mine, key),
                                      getattr(theirs, key))


def _road_config():
    import json
    import os
    here = os.path.dirname(os.path.abspath(__file__))
    path = os.path.join(here, "..", "..", "bench", "configs",
                        "road-dimacs-ny40k.json")
    with open(path) as f:
        return json.load(f)


def test_road_network_is_the_configured_deployment():
    """The road configuration's graph: NY's arcs per node on 40,000
    intersections, connected, at most 4 roads to a node, travel-time
    weights ordered by road class."""
    from scipy.sparse.csgraph import connected_components

    cfg = _road_config()
    e = graphs.make(cfg["graph"])
    a = reference.arcs(e)
    deg = graphs.degrees(e)
    assert (e.n, len(a.tail)) == (cfg["n"], cfg["arcs"])
    assert cfg["arcs"] / cfg["n"] == pytest.approx(733846 / 264346,
                                                   rel=1e-3)
    assert int(deg.max()) == cfg["max_degree"] == 4 and deg.min() >= 1
    assert connected_components(a.csr)[0] == 1
    assert np.all((e.w >= 1) & (e.w == np.round(e.w)))
    # a block of about 100 m takes 12 s at 30 km/h, 3.6 s at 100 km/h
    assert 100 < np.median(e.w) < 130 and e.w.min() < 60
    again = graphs.make(cfg["graph"])
    for x, y in zip(e, again):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("rows,cols,apn,seed", [(6, 9, 2.4, 0),
                                                (30, 30, 2.776, 5)])
def test_road_network_keeps_the_arterial_grid_and_its_degree(rows, cols,
                                                             apn, seed):
    from scipy.sparse.csgraph import connected_components

    e = graphs.road_network(rows, cols, apn, seed=seed, arterial_every=3,
                            highway_every=6)
    assert len(e.src) == round(apn * rows * cols / 2)
    assert connected_components(reference.arcs(e).csr)[0] == 1
    # every segment along an arterial row is kept
    r, c = e.src // cols, e.src % cols
    along_row0 = (r == 0) & (e.dst == e.src + 1)
    assert along_row0.sum() == cols - 1
    # lattice neighbours only
    assert np.all(np.isin(e.dst - e.src, [1, cols]))
    with pytest.raises(ValueError):
        graphs.road_network(rows, cols, 4.5, seed=seed)


@pytest.mark.parametrize("make,kind", [
    (lambda: graphs.road_network(30, 40, 2.6, seed=3), "betweenness"),
    (lambda: graphs.grid_road(20, 20, seed=1), "betweenness"),
    (lambda: graphs.kronecker(9, 16, seed=2), "degree"),
    (lambda: graphs.kronecker(12, 16, seed=0), "degree")])
def test_ranking_copy_is_the_programs_ranking(make, kind):
    from repro.graphs import ranking as theirs
    from repro.graphs.graph import from_edges

    e = make()
    a = reference.arcs(e)
    g = from_edges(e.n, e.src, e.dst, e.w)
    spec = {"kind": kind, "samples": 16, "seed": 0}
    mine = ranking.make(spec, a)
    want = (theirs.betweenness_ranking(g, samples=16, seed=0)
            if kind == "betweenness" else theirs.degree_ranking(g))
    np.testing.assert_array_equal(mine, want)
    assert sorted(mine.tolist()) == list(range(e.n))


def test_kronecker_follows_the_spec_at_scale_10():
    scale, ef = 10, 16
    ij = graphs.kronecker_pairs(scale, ef, np.random.default_rng(0))
    assert ij.shape == (2, ef << scale)
    assert ij.min() >= 0 and ij.max() < 1 << scale
    # before the vertex permutation, the top bit of (i, j) falls in the
    # initiator's quadrants with probabilities A, B, C, D
    rng = np.random.default_rng(5)
    m = 200_000
    ab = graphs.KRON_A + graphs.KRON_B
    ii = rng.random(m) > ab
    jj = rng.random(m) > np.where(
        ii, graphs.KRON_C / (1 - ab), graphs.KRON_A / ab)
    quad = np.bincount(ii * 2 + jj, minlength=4) / m
    np.testing.assert_allclose(quad, [0.57, 0.19, 0.19, 0.05], atol=0.005)

    e = graphs.kronecker(scale, ef, seed=0)
    deg = graphs.degrees(e)
    assert e.n == 1024
    assert np.all(e.src < e.dst)                     # no loops, one copy
    assert len(np.unique(e.src.astype(np.int64) * e.n + e.dst)) == len(e.src)
    assert len(e.src) == 10474
    assert int(deg.max()) == 465
    live = deg[deg > 0]
    assert 0.10 < np.mean(deg == 0) < 0.20           # isolated, kept
    assert 20 < live.mean() < 30
    assert deg.max() > 15 * live.mean()              # heavy tail
    assert np.all((e.w >= 1) & (e.w < 32) & (e.w == np.round(e.w)))


def test_kronecker_scale_12_is_the_configured_graph():
    e = graphs.kronecker(12, 16, seed=0)
    deg = graphs.degrees(e)
    assert (len(e.src), int(deg.max())) == (48500, 1306)


@pytest.mark.parametrize("k", [1, 5, 12])
def test_stratified_batches_span_the_rank_order(k):
    n, batch = 100, 8
    rank = np.random.default_rng(2).permutation(n)
    order = samplers.rank_order(rank)
    got = samplers.systematic_batches(rank, batch, k,
                                      samplers.rng_of(2**40 + 7, "roots"))
    assert got.shape == (k, batch)
    assert len(np.unique(got)) == k * batch
    pos = np.argsort(order)
    full = n // batch
    edges = np.linspace(0, full, k + 1)
    firsts = sorted(pos[b].min() // batch for b in got)
    for i, b in enumerate(sorted(got, key=lambda b: pos[b].min())):
        p = pos[b]
        # each batch is one whole batch of the full build's schedule ...
        assert p.min() % batch == 0 and p.max() - p.min() == batch - 1
        # ... drawn from its own stratum of the batch sequence
        assert edges[i] <= p.min() // batch < edges[i + 1]
    if k == full:
        assert firsts == list(range(full))
    # every seed plants the same batches, in its own order
    other = samplers.systematic_batches(rank, batch, k,
                                        samplers.rng_of(3, "roots"))
    assert sorted(map(tuple, other)) == sorted(map(tuple, got))
    if k > 2:
        assert not np.array_equal(other, got)


def test_samplers_are_seeded_and_sized():
    a = samplers.poisson_due_times(500.0, 2.0, samplers.rng_of(9, "x"))
    b = samplers.poisson_due_times(500.0, 2.0, samplers.rng_of(9, "x"))
    c = samplers.poisson_due_times(500.0, 2.0, samplers.rng_of(10, "x"))
    np.testing.assert_array_equal(a, b)
    assert len(a) == len(c) == 1000 and not np.array_equal(a, c)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 2.0
    pool = np.array([3, 5, 7])
    u, v = samplers.block_pairs(pool, 4, samplers.rng_of(1, "p"))
    assert len(u) == 16 and set(u) | set(v) <= set(pool)
    assert len(np.unique(u.reshape(4, 4), axis=1)) == 4


def test_roofline_byte_counts():
    assert roofline.tree_bytes(n=10, arcs=30) == 8 * 30 + 8 * 10
    # the road configuration: 111,044 arcs, 40,000 vertices
    assert roofline.tree_bytes(40000, 111044) == 1_208_352
    count = np.array([3, 0, 5, 2])
    u, v = np.array([0, 2, 3]), np.array([2, 2, 1])
    assert roofline.query_bytes(count, u, v) == 8 * (3 + 5 + 2 + 5 + 5 + 0) \
        + 12 * 3
    assert roofline.share_pct(819e6, 1e-3, 819e9) == pytest.approx(100.0)


def _small_graph():
    return graphs.grid_road(7, 9, seed=4)


def test_reference_labels_are_canonical():
    """The reference agrees with a brute force over all shortest paths
    on a small graph, and with the program's own oracle."""
    import networkx as nx
    from repro.graphs.graph import from_edges
    from repro.sssp.oracle import dijkstra_maxrank

    e = _small_graph()
    a = reference.arcs(e)
    rank = np.random.default_rng(1).permutation(e.n)
    G = nx.Graph()
    for s, t, w in zip(e.src.tolist(), e.dst.tolist(), e.w.tolist()):
        if not G.has_edge(s, t) or G[s][t]["weight"] > w:
            G.add_edge(s, t, weight=w)
    g = from_edges(e.n, e.src, e.dst, e.w)
    for root in (0, 17, 40):
        dist = reference.distances(a, [root])[0]
        got = reference.canonical_labels(a, rank, root, dist)
        _, mrank = dijkstra_maxrank(g, root, rank)
        assert set(got) == set(np.nonzero(mrank == rank[root])[0])
        for v in (5, 33, 62):
            paths = nx.all_shortest_paths(G, root, v, weight="weight")
            top = max(max(rank[list(p)]) for p in paths)
            assert (v in got) == (top == rank[root])
            if v in got:
                assert got[v] == nx.shortest_path_length(
                    G, root, v, weight="weight")


def test_reference_comparisons_catch_one_wrong_label_and_distance():
    e = _small_graph()
    a = reference.arcs(e)
    rank = np.random.default_rng(3).permutation(e.n)
    root = int(np.argsort(rank)[e.n * 3 // 4])     # labels some, not all
    dist = reference.distances(a, [root])[0]
    want = reference.canonical_labels(a, rank, root, dist)
    assert 1 < len(want) < e.n
    assert reference.label_mismatches(want, want.items()) == 0
    v = sorted(want)[len(want) // 2]
    wrong = dict(want)
    wrong[v] += 1.0
    assert reference.label_mismatches(want, wrong.items()) == 1
    missing = dict(want)
    del missing[v]
    assert reference.label_mismatches(want, missing.items()) == 1
    extra = dict(want)
    extra[max(set(range(e.n)) - set(want))] = 3.0
    assert reference.label_mismatches(want, extra.items()) == 1

    u, t = np.array([0, 1, 2, 3]), np.array([9, 30, 50, 62])
    d = reference.pair_distances(a, u, t)
    np.testing.assert_array_equal(d, reference.distances(a, u)[
        np.arange(4), t])
    assert reference.answer_mismatches(d, d.astype(np.float32)) == 0
    bad = d.copy()
    bad[2] += 1
    assert reference.answer_mismatches(d, bad) == 1
    assert reference.answer_mismatches(d, np.where(
        np.arange(4) == 1, np.nan, d)) == 1
    assert reference.answer_mismatches(np.array([np.inf]),
                                       np.array([np.inf])) == 0


def test_round_to_narrows_and_keeps_inf():
    x = np.array([3.0, 257.0, 18384.0, np.inf])
    np.testing.assert_array_equal(reference.round_to(x, "bfloat16"),
                                  [3.0, 256.0, 18432.0, np.inf])
    assert reference.round_to(np.array([17.0]), "float8_e4m3fn")[0] != 17.0


def test_peaks_table_refuses_an_unknown_device_kind():
    assert peaks("TPU v5 lite").hbm_bytes_per_s == 819e9
    for kind, p in PEAKS.items():
        assert p.source and p.hbm_bytes_per_s > 0
    with pytest.raises(UnknownDevice):
        peaks("TPU v9 imaginary")
    with pytest.raises(UnknownDevice):
        peaks("cpu")
