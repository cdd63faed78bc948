"""Compile-only checks for a described (not attached) TPU v5e.

The TPU compiler is installed everywhere JAX is, and it compiles for a
v5e:2x2 topology described from nothing — it refuses what a chip
would refuse (a kernel Mosaic cannot lower, a program that does not
fit HBM) without running anything. These tests compile the programs
the chip path runs, at deployment widths:

- (a) the relaxation sweep (`plant_batch`, XLA form) at the one-chip
  smoke's n and road degree, and the label-table insert behind it;
- (b) the jitted store and directed queries and the QLSN answer fn at
  L = 2088 (the default cap at NY road scale, n = 264,346) and
  Q = 1024, whose temporaries plus arguments must fit one chip's
  16 GiB;
- (c) one distributed PLaNT superstep, and the widest dense DGLL
  superstep of the four-chip smoke, on a four-device mesh built from
  the topology's devices.

The topology is described inside a module-scoped fixture — never at
import, in a ``skipif`` or a ``parametrize`` argument — because only
one process may hold the TPU library, and every test worker imports
this file. The ``label_query`` kernel is left out: compiling it aborts
the whole process inside the TPU compiler.
"""

import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.core import labels as lbl
from repro.core.labels import LabelTable

GiB = 1024 ** 3
SMOKE_N = 200 * 200           # chip_smoke.py's one-chip graph
FOUR_CHIP_N = 64 * 64         # chip_smoke.py's four-chip graph
ROAD_DEG = 8                  # grid_road's ELL width (degree ≤ 6, padded)
NY_CAP = lbl.default_cap(264_346)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off here
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernel dispatch decides for the target, not for this process."""
    from repro.compat import pallas
    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_ELL_RELAX", raising=False)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _table_spec(n, cap, sharding, lead=()):
    return LabelTable(_spec(lead + (n, cap), jnp.int32, sharding),
                      _spec(lead + (n, cap), jnp.float32, sharding),
                      _spec(lead + (n,), jnp.int32, sharding))


def _fits(compiled, limit=16 * GiB):
    m = compiled.memory_analysis()
    used = m.temp_size_in_bytes + m.argument_size_in_bytes
    assert used < limit, (used, m)
    return used


@pytest.mark.parametrize("batch", [8, 256])
def test_plant_sweep_compiles_for_v5e(one_chip, on_tpu, batch):
    from repro.core.plant import plant_batch
    n = SMOKE_N
    compiled = plant_batch.lower(
        _spec((n, ROAD_DEG), jnp.int32, one_chip),
        _spec((n, ROAD_DEG), jnp.float32, one_chip),
        _spec((n,), jnp.int32, one_chip),
        _spec((batch,), jnp.int32, one_chip),
        _spec((batch,), jnp.bool_, one_chip)).compile()
    _fits(compiled)
    # the chip path is the XLA form: no Pallas custom call in it
    assert "tpu_custom_call" not in compiled.as_text()


def test_label_insert_compiles_for_v5e(one_chip, on_tpu):
    n, B = SMOKE_N, 256
    cap = lbl.default_cap(n)
    compiled = jax.jit(lbl.insert_batch).lower(
        _table_spec(n, cap, one_chip),
        _spec((B,), jnp.int32, one_chip),
        _spec((B, n), jnp.bool_, one_chip),
        _spec((B, n), jnp.float32, one_chip)).compile()
    _fits(compiled)


@pytest.mark.parametrize("n,cap,Q", [(SMOKE_N, lbl.default_cap(SMOKE_N),
                                      SMOKE_N),
                                     (264_346, NY_CAP, 1024)])
def test_store_query_fits_one_chip(one_chip, n, cap, Q):
    """The store query (hub witness included) never materializes the
    [Q, L, L] candidate cube: at L = 2088, Q = 1024 that cube alone
    would be 17.9 GB."""
    compiled = lbl.query_pairs.lower(
        _table_spec(n, cap, one_chip),
        _spec((Q,), jnp.int32, one_chip),
        _spec((Q,), jnp.int32, one_chip)).compile()
    used = _fits(compiled)
    assert used < 4 * n * cap * 4 + 8 * Q * cap * 4


def test_directed_query_fits_one_chip(one_chip):
    """The directed query (hub witness included) fuses its cube too."""
    from repro.core.directed import query_directed
    n, cap, Q = 264_346, NY_CAP, 1024
    compiled = query_directed.lower(_table_spec(n, cap, one_chip),
                                    _table_spec(n, cap, one_chip),
                                    _spec((Q,), jnp.int32, one_chip),
                                    _spec((Q,), jnp.int32, one_chip),
                                    with_hub=True).compile()
    used = _fits(compiled)
    assert used < 2 * 4 * n * cap * 4 + 8 * Q * cap * 4


def test_qlsn_answer_fn_fits_one_chip(one_chip):
    from repro.core.query import qlsn
    n, cap, Q = 264_346, NY_CAP, 1024
    compiled = qlsn.lower(_table_spec(n, cap, one_chip),
                          _spec((Q,), jnp.int32, one_chip),
                          _spec((Q,), jnp.int32, one_chip)).compile()
    _fits(compiled)


def test_distributed_plant_superstep_compiles_for_v5e_2x2(topo, on_tpu):
    from repro.compat import make_mesh
    from repro.core.dgll import dgll_superstep_fn
    mesh = make_mesh((4,), ("node",), devices=topo.devices)
    node = NamedSharding(mesh, P("node"))
    rep = NamedSharding(mesh, P())
    n, cap, T, batch = 64 * 64, lbl.default_cap(64 * 64), 16, 8
    fn = dgll_superstep_fn(mesh, n, batch=batch, use_hc=True,
                           plant_trees=True)
    compiled = fn.lower(
        _table_spec(n, cap, node, lead=(4,)),
        _table_spec(n, 64, rep),
        _spec((n,), jnp.int32, rep),
        _spec((4, T), jnp.int32, node),
        _spec((4, T), jnp.bool_, node),
        _spec((n, ROAD_DEG), jnp.int32, rep),
        _spec((n, ROAD_DEG), jnp.float32, rep)).compile()
    _fits(compiled)
    hlo = compiled.as_text()
    # PLaNT supersteps need no communication (§5.2)
    for op in ("all-gather", "all-reduce", "collective-permute"):
        assert op not in hlo, op


def test_distributed_dgll_superstep_fits_v5e_2x2(topo, on_tpu):
    """The Hybrid's dense DGLL superstep at the four-chip smoke's size,
    at the widest superstep its schedule reaches: its cleaning cube
    [q*T, n, cap] sets how large that smoke can be."""
    from repro.compat import make_mesh
    from repro.core.dgll import assign_roots, dgll_superstep_fn
    from repro.engine.scheduler import QueueSchedule
    mesh = make_mesh((4,), ("node",), devices=topo.devices)
    node = NamedSharding(mesh, P("node"))
    rep = NamedSharding(mesh, P())
    n, batch = FOUR_CHIP_N, 64
    cap = lbl.default_cap(n)
    queues = assign_roots(np.arange(n), 4)
    # BuildPlan's defaults: beta 8, and 16 common-table hubs, whose
    # prologue takes the first 16 / 4 columns
    T = max(st.roots.shape[1] for st in
            QueueSchedule(queues, batch, beta=8.0).steps(start=4))
    fn = dgll_superstep_fn(mesh, n, batch=batch, use_hc=True,
                           plant_trees=False)
    compiled = fn.lower(
        _table_spec(n, cap, node, lead=(4,)),
        _table_spec(n, 64, rep),
        _spec((n,), jnp.int32, rep),
        _spec((4, T), jnp.int32, node),
        _spec((4, T), jnp.bool_, node),
        _spec((n, ROAD_DEG), jnp.int32, rep),
        _spec((n, ROAD_DEG), jnp.float32, rep)).compile()
    _fits(compiled)
    # DGLL supersteps broadcast their trees and all-reduce the verdicts
    hlo = compiled.as_text()
    for op in ("all-gather", "all-reduce"):
        assert op in hlo, op


def test_qfdl_answer_fn_compiles_for_v5e_2x2(topo):
    from repro.compat import make_mesh
    from repro.core.query import qfdl_fn
    mesh = make_mesh((4,), ("node",), devices=topo.devices)
    node = NamedSharding(mesh, P("node"))
    rep = NamedSharding(mesh, P())
    n, cap, Q = 264_346, NY_CAP // 4, 1024
    compiled = qfdl_fn(mesh).lower(
        _table_spec(n, cap, node, lead=(4,)),
        _spec((Q,), jnp.int32, rep),
        _spec((Q,), jnp.int32, rep)).compile()
    _fits(compiled)
    assert "all-reduce" in compiled.as_text()


def test_intersect_rows_matches_flattened_argmin():
    """The fused intersection returns the distance and the witnessing
    hub a flattened argmin over the full cube picks — ties included."""
    rng = np.random.default_rng(0)
    Q, L = 64, 12
    hu = rng.integers(-1, 6, (Q, L)).astype(np.int32)
    hv = rng.integers(-1, 6, (Q, L)).astype(np.int32)
    du = rng.integers(0, 4, (Q, L)).astype(np.float32)
    dv = rng.integers(0, 4, (Q, L)).astype(np.float32)
    best, hub = lbl.intersect_rows(*map(jnp.asarray, (hu, du, hv, dv)))
    match = (hu[:, :, None] == hv[:, None, :]) & (hu[:, :, None] >= 0)
    dd = np.where(match, du[:, :, None] + dv[:, None, :], np.inf)
    want = dd.min(axis=(1, 2))
    bi = dd.reshape(Q, -1).argmin(axis=1) // L
    want_hub = np.where(np.isfinite(want), hu[np.arange(Q), bi], -1)
    np.testing.assert_array_equal(np.asarray(best), want)
    np.testing.assert_array_equal(np.asarray(hub), want_hub)
