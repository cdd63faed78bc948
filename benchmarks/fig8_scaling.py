"""Fig. 8: strong scaling over q nodes. On this 1-core container real
speedup is unmeasurable (all host "devices" share one core), so we
report the two quantities that *determine* scaling and are exact in
the dry-run sense: per-node work (trees × exploration) and
communication volume (label slots broadcast), for PLaNT / DGLL /
Hybrid at q ∈ {1, 2, 4, 8} via subprocess runs with forced device
counts. PLaNT: comm = 0 at every q (the paper's headline); DGLL: comm
grows with q·labels; Hybrid: bounded comm."""

import json
import os
import subprocess
import sys
from typing import List

from benchmarks.common import Row, row

_CHILD = r"""
import os, json, sys
from repro.compat import set_host_device_count
set_host_device_count(%d)
import numpy as np
from repro.core.dgll import make_node_mesh
from repro.graphs import scale_free
from repro.graphs.ranking import degree_ranking
from repro.index import BuildPlan, build
g = scale_free(240, attach=2, seed=1)
rank = degree_ranking(g)
mesh = make_node_mesh()
out = {}
for name, plan in (
    ("plant", BuildPlan(algo="plant-dist", batch=4)),
    ("dgll", BuildPlan(algo="dgll", batch=4, beta=8.0, eta=0)),
    ("hybrid", BuildPlan(algo="hybrid", batch=4, eta=8, psi_th=50.0)),
):
    idx = build(g, rank, plan, mesh=mesh)
    r = idx.report
    out[name] = {
        "t": r.wall_s,
        "comm": r.comm_label_slots,
        "explored": sum(s.explored or 0 for s in r.supersteps),
        "labels": sum(s.labels or 0 for s in r.supersteps),
    }
print("RESULT" + json.dumps(out))
"""


def run() -> List[Row]:
    out: List[Row] = []
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    for q in (1, 2, 4, 8):
        p = subprocess.run([sys.executable, "-c", _CHILD % q],
                           capture_output=True, text=True, env=env,
                           timeout=1200)
        line = [l for l in p.stdout.splitlines()
                if l.startswith("RESULT")]
        if p.returncode != 0 or not line:
            raise RuntimeError(f"fig8 child (q={q}) failed with exit "
                               f"code {p.returncode}:\n{p.stderr[-2000:]}")
        res = json.loads(line[0][len("RESULT"):])
        for algo, st in res.items():
            out.append(row(
                f"fig8/{algo}/q={q}", st["t"],
                f"comm_slots={st['comm']} explored={st['explored']} "
                f"labels={st['labels']}"))
    return out
