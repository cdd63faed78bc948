"""Table 4: query-mode throughput + memory (QLSN / QFDL / QDOL) on an
8-device subprocess mesh, plus the label-store serving trajectory
(dense vs sharded vs spill residency over the same saved artifact).
Memory = label bytes per node & total; throughput = batched queries/s
(1-core caveat in EXPERIMENTS.md)."""

import json
import os
import subprocess
import sys
from typing import List

from benchmarks.common import Row, row

_CHILD = r"""
import os, json, time
from repro.compat import set_host_device_count
set_host_device_count(8)
import numpy as np
import jax
from repro.core.dgll import make_node_mesh
from repro.core.query import qdol_layout
from repro.graphs import scale_free
from repro.graphs.ranking import degree_ranking
from repro.index import BuildPlan, build
g = scale_free(240, attach=2, seed=2)
rank = degree_ranking(g)
mesh = make_node_mesh(8)
idx = build(g, rank, BuildPlan(algo="hybrid", batch=4, eta=8,
                               psi_th=50.0), mesh=mesh)
rng = np.random.default_rng(0)
Q = 1024
u = rng.integers(0, g.n, Q).astype(np.int32)
v = rng.integers(0, g.n, Q).astype(np.int32)
base = idx.store.label_bytes()
zeta = qdol_layout(g.n, 8).zeta
out = {"base_bytes": base, "n": g.n, "Q": Q, "zeta": zeta}
answers = {}
for mode, per_node in (("qlsn", base), ("qfdl", base // 8),
                       ("qdol", 2 * base // zeta)):
    srv = idx.serve(mode=mode, mesh=mesh, batch_size=Q)
    srv.warmup()                       # compile outside the timing
    t0 = time.perf_counter()
    for _ in range(2):
        srv.submit(u, v)
        answers[mode] = srv.flush()
    out[f"{mode}_s"] = (time.perf_counter() - t0) / 2
    out[f"{mode}_bytes_per_node"] = per_node
# answers agree
assert np.array_equal(answers["qlsn"], answers["qfdl"])
assert np.array_equal(answers["qlsn"], answers["qdol"])
# label-store residency trajectory over the same saved artifact
import tempfile, os as _os
from repro.index import CHLIndex
with tempfile.TemporaryDirectory() as tmp:
    path = idx.save(_os.path.join(tmp, "index"))
    for kind, kw in (("dense", {}), ("sharded", {"shards": 8}),
                     ("spill", {})):
        loaded = CHLIndex.load(path, store=kind, **kw)
        srv = loaded.serve(mode="qlsn", batch_size=Q)
        srv.warmup()
        t0 = time.perf_counter()
        for _ in range(2):
            srv.submit(u, v)
            got = srv.flush()
        out[f"store_{kind}_s"] = (time.perf_counter() - t0) / 2
        assert np.array_equal(got, answers["qlsn"]), kind
print("RESULT" + json.dumps(out))
"""


def run() -> List[Row]:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    p = subprocess.run([sys.executable, "-c", _CHILD],
                       capture_output=True, text=True, env=env,
                       timeout=2700)
    line = [l for l in p.stdout.splitlines() if l.startswith("RESULT")]
    if p.returncode != 0 or not line:
        raise RuntimeError(f"table4 child failed with exit code "
                           f"{p.returncode}:\n{p.stderr[-2000:]}")
    res = json.loads(line[0][len("RESULT"):])
    Q = res["Q"]
    out: List[Row] = []
    for mode in ("qlsn", "qfdl", "qdol"):
        s = res[f"{mode}_s"]
        out.append(row(
            f"table4/{mode}", s / Q,
            f"throughput={Q/s:,.0f} q/s "
            f"bytes/node={res[f'{mode}_bytes_per_node']:,}"
            + (f" zeta={res['zeta']}" if mode == "qdol" else "")))
    for kind in ("dense", "sharded", "spill"):
        s = res[f"store_{kind}_s"]
        out.append(row(f"table4/store_{kind}", s / Q,
                       f"qlsn residency={kind} "
                       f"throughput={Q/s:,.0f} q/s"))
    return out
