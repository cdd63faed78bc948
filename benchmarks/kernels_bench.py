"""Kernel micro-benchmarks: Pallas vs pure-jnp ref on the three
kernel hot-spots (minplus, label_query, ell_relax) plus an end-to-end
`plant_chl` wall-clock row — correctness-weighted; real perf numbers
come from the roofline (TPU is the target, CPU interpret mode is an
emulation).

Besides the CSV rows for `benchmarks.run`, this module regenerates
``BENCH_kernels.json`` at the repo root — the perf-trajectory artifact
CI smokes in interpret mode (``REPRO_PALLAS_BACKEND=interpret``).
"""

import json
import pathlib
from typing import List

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks.common import Row, row, timed
from repro.compat import resolve_interpret
from repro.kernels.ell_relax import ell_sweep
from repro.kernels.label_query import label_query_padded, label_query_ref
from repro.kernels.minplus import minplus_padded, minplus_ref

BENCH_JSON = pathlib.Path(__file__).resolve().parents[1] / \
    "BENCH_kernels.json"


def run() -> List[Row]:
    out: List[Row] = []
    # the compat dispatcher picks the backend; label rows truthfully
    interp = resolve_interpret()
    mode = "interpret" if interp else "compiled"
    note = "CPU emul" if interp else "compiled"
    rng = np.random.default_rng(0)
    B, K, N = 16, 512, 512
    dist = jnp.asarray(np.where(rng.random((B, K)) < 0.5,
                                rng.integers(0, 9, (B, K)), np.inf),
                       jnp.float32)
    mrank = jnp.asarray(np.where(np.isfinite(dist),
                                 rng.integers(0, 99, (B, K)), -1),
                        jnp.int32)
    w = jnp.asarray(np.where(rng.random((K, N)) < 0.05,
                             rng.integers(1, 9, (K, N)), np.inf),
                    jnp.float32)
    _, t = timed(lambda: minplus_ref(dist, mrank, w)[0]
                 .block_until_ready(), repeat=3)
    out.append(row("kernels/minplus/ref_jnp", t, f"B={B} K={K} N={N}"))
    _, t = timed(lambda: minplus_padded(dist, mrank, w)[0]
                 .block_until_ready(), repeat=3)
    out.append(row(f"kernels/minplus/pallas_{mode}", t, note))

    Q, L = 512, 128
    hu = jnp.asarray(rng.integers(-1, 60, (Q, L)), jnp.int32)
    du = jnp.asarray(rng.integers(0, 30, (Q, L)), jnp.float32)
    hv = jnp.asarray(rng.integers(-1, 60, (Q, L)), jnp.int32)
    dv = jnp.asarray(rng.integers(0, 30, (Q, L)), jnp.float32)
    _, t = timed(lambda: label_query_ref(hu, du, hv, dv)
                 .block_until_ready(), repeat=3)
    out.append(row("kernels/label_query/ref_jnp", t, f"Q={Q} L={L}"))
    _, t = timed(lambda: label_query_padded(hu, du, hv, dv)
                 .block_until_ready(), repeat=3)
    out.append(row(f"kernels/label_query/pallas_{mode}", t, note))

    relax_rows, label_bytes = _run_ell_relax(mode, note, rng)
    out += relax_rows
    _write_json(out, mode, label_bytes)
    return out


def _run_ell_relax(mode: str, note: str, rng):
    """Fused ELL relaxation sweep: ref vs Pallas, plus an end-to-end
    PLaNT construction row (the hot path the kernel serves)."""
    from benchmarks.common import bench_graphs
    from repro.index import BuildPlan, build

    out: List[Row] = []
    B, n, deg = 16, 512, 16
    dist = jnp.asarray(np.where(rng.random((B, n)) < 0.5,
                                rng.integers(0, 9, (B, n)), np.inf),
                       jnp.float32)
    mrank = jnp.asarray(np.where(np.isfinite(dist),
                                 rng.integers(0, 99, (B, n)), -1),
                        jnp.int32)
    alive = jnp.ones(B, dtype=bool)
    ell_src = jnp.asarray(rng.integers(0, n, (n, deg)), jnp.int32)
    ell_w = jnp.asarray(np.where(rng.random((n, deg)) < 0.4,
                                 rng.integers(1, 9, (n, deg)), np.inf),
                        jnp.float32)
    rank = jnp.asarray(rng.permutation(n), jnp.int32)
    _, t = timed(lambda: ell_sweep(dist, mrank, dist, alive, ell_src,
                                   ell_w, rank, use_kernel=False)[0]
                 .block_until_ready(), repeat=3)
    out.append(row("kernels/ell_relax/ref_jnp", t,
                   f"B={B} n={n} deg={deg}"))
    _, t = timed(lambda: ell_sweep(dist, mrank, dist, alive, ell_src,
                                   ell_w, rank, use_kernel=True)[0]
                 .block_until_ready(), repeat=3)
    out.append(row(f"kernels/ell_relax/pallas_{mode}", t, note))

    out += _run_ell_relax_windowed(mode, note, rng)

    # end-to-end: full PLaNT construction (sweep loop + frontier
    # gating + strided fixpoint checks) on a small paper-style graph
    name, g, gr = bench_graphs("small")[1]       # scale-free
    plan = BuildPlan(algo="plant", batch=16)
    idx, t = timed(lambda: build(g, gr, plan), repeat=1)
    out.append(row("kernels/ell_relax/plant_chl_e2e", t,
                   f"{name} n={g.n} batch=16"))

    # same construction forced past the (shrunk) VMEM budget: every
    # sweep streams the source-windowed kernel end-to-end — tracks the
    # windowing tax on a whole build, not just one sweep
    out.append(_run_plant_e2e_windowed(name, g, gr))

    # engine streaming build: same construction, emissions
    # hub-partitioned straight into 2 shard arrays (the dense [n, cap]
    # table is never materialized) — tracks the streaming-sink tax
    # alongside the dense path above
    splan = BuildPlan(algo="plant", batch=16, store="sharded", shards=2)
    sidx, t = timed(lambda: build(g, gr, splan), repeat=1)
    assert sidx.store.kind == "sharded"
    out.append(row("engine/streaming_sharded_build_e2e", t,
                   f"{name} n={g.n} batch=16 shards=2"))
    store_rows, label_bytes = _run_label_store(idx, g, rng)
    out += store_rows
    return out, label_bytes


def _run_ell_relax_windowed(mode: str, note: str, rng) -> List[Row]:
    """Source-windowed sweep at n past the old single-window wall.

    This row used to be impossible: the sweep fell back to the jnp
    reference beyond n = 131072. The default size sits just past that
    wall (two 81920-wide windows); ``REPRO_BENCH_WINDOWED_N`` shrinks
    it for CI smoke runs — the layout is forced to two windows either
    way, so the scalar-prefetch streaming path is what gets timed.
    """
    import os

    from repro.kernels.ell_relax import sweep_layout

    out: List[Row] = []
    n = int(os.environ.get("REPRO_BENCH_WINDOWED_N", "163840"))
    B, deg = 8, 8
    n_bn = -(-n // 128) * 128
    mw = -(-(n_bn // 2) // 128) * 128            # force >= 2 windows
    dist = jnp.asarray(np.where(rng.random((B, n)) < 0.5,
                                rng.integers(0, 9, (B, n)), np.inf),
                       jnp.float32)
    mrank = jnp.asarray(np.where(np.isfinite(dist),
                                 rng.integers(0, 99, (B, n)), -1),
                        jnp.int32)
    alive = jnp.ones(B, dtype=bool)
    ell_src = jnp.asarray(rng.integers(0, n, (n, deg)), jnp.int32)
    ell_w = jnp.asarray(np.where(rng.random((n, deg)) < 0.4,
                                 rng.integers(1, 9, (n, deg)), np.inf),
                        jnp.float32)
    rank = jnp.asarray(rng.permutation(n), jnp.int32)
    layout = sweep_layout(ell_src, ell_w, max_window=mw)
    assert layout is not None and layout.num_windows >= 2
    (dr, mr), t = timed(
        lambda: [x.block_until_ready() for x in
                 ell_sweep(dist, mrank, dist, alive, ell_src, ell_w,
                           rank, use_kernel=False)], repeat=1)
    out.append(row("kernels/ell_relax/windowed_ref_jnp", t,
                   f"B={B} n={n} deg={deg}"))
    (dw, mw_), t = timed(
        lambda: [x.block_until_ready() for x in
                 ell_sweep(dist, mrank, dist, alive, ell_src, ell_w,
                           rank, use_kernel=True, layout=layout)],
        repeat=1)
    assert np.array_equal(np.asarray(dw), np.asarray(dr))
    assert np.array_equal(np.asarray(mw_), np.asarray(mr))
    out.append(row(f"kernels/ell_relax/windowed_pallas_{mode}", t,
                   f"{note} windows={layout.num_windows} "
                   f"window={layout.window} dk={layout.dk}"))
    return out


def _run_plant_e2e_windowed(name: str, g, gr) -> Row:
    import os

    import jax

    from repro.index import BuildPlan, build
    from repro.kernels.ell_relax import (ELL_RELAX_ENV_VAR,
                                         VMEM_BUDGET_ENV_VAR,
                                         clear_layout_cache)

    forced = {VMEM_BUDGET_ENV_VAR: "16k", ELL_RELAX_ENV_VAR: "kernel"}
    saved = {k: os.environ.get(k) for k in forced}
    os.environ.update(forced)
    clear_layout_cache()
    jax.clear_caches()                 # env resolved at trace time
    try:
        plan = BuildPlan(algo="plant", batch=64)
        widx, t = timed(lambda: build(g, gr, plan), repeat=1)
        assert any("source-windowed" in s for s in widx.report.notes)
        return row("kernels/ell_relax/plant_chl_e2e_windowed", t,
                   f"{name} n={g.n} batch=64 budget=16k")
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        clear_layout_cache()
        jax.clear_caches()


def _run_label_store(idx, g, rng):
    """Serving trajectory: dense vs sharded vs spill vs compressed
    label-store query latency (QLSN probes over the same index, all
    four answers asserted equal — the compressed leg uses the u16
    exact codec on the integer-weight bench graph), plus the at-rest
    label_bytes per residency, so BENCH_kernels.json tracks the
    storage backends alongside the kernels."""
    import os
    import tempfile

    from repro.index import CHLIndex

    out: List[Row] = []
    Q = 512
    u = rng.integers(0, g.n, Q).astype(np.int32)
    v = rng.integers(0, g.n, Q).astype(np.int32)
    label_bytes = {}
    with tempfile.TemporaryDirectory() as tmp:
        path = idx.save(os.path.join(tmp, "index"))
        stores = [
            ("dense", CHLIndex.load(path, store="dense")),
            ("sharded", CHLIndex.load(path, store="sharded", shards=4)),
            ("spill", CHLIndex.load(path, store="spill")),
            ("compressed", CHLIndex.load(path, store="compressed",
                                         codec="u16", quant_exact=True,
                                         shards=2)),
        ]
        ref = None
        for kind, loaded in stores:
            srv = loaded.serve(mode="qlsn", batch_size=Q)
            srv.warmup()
            srv.submit(u, v)
            got = srv.flush()
            if ref is None:
                ref = got
            assert np.array_equal(ref, got), kind
            _, t = timed(lambda s=srv: (s.submit(u, v), s.flush()),
                         repeat=3)
            out.append(row(f"serve/store_{kind}", t / Q,
                           f"qlsn Q={Q} "
                           f"shards={loaded.store.num_shards}"))
            label_bytes[kind] = int(loaded.store.label_bytes())
    label_bytes["compression_ratio"] = round(
        label_bytes["dense"] / label_bytes["compressed"], 3)
    return out, label_bytes


def _write_json(rows: List[Row], mode: str, label_bytes=None) -> None:
    BENCH_JSON.write_text(json.dumps({
        "generated_by": "benchmarks/kernels_bench.py",
        "jax": jax.__version__,
        "pallas_backend": mode,
        "label_bytes": label_bytes or {},
        "rows": rows,
    }, indent=2) + "\n")


if __name__ == "__main__":
    print("name,us_per_call,derived")
    for r in run():
        d = str(r.get("derived", "")).replace(",", ";")
        print(f"{r['name']},{r['us_per_call']},{d}")
    print(f"wrote {BENCH_JSON}")
