"""Directed-graph hub labeling (paper footnote 1: forward/backward
labels). A digraph query u→v intersects ``L_out[u]`` with ``L_in[v]``.

PLaNTing a tree from ``h`` *forward* (pull over in-edges of G) yields
``d(h→v)`` and populates ``L_in``; a tree on the reversed graph yields
``d(v→h)`` and populates ``L_out``. The PLaNT max-rank-on-path
criterion applies per direction.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import numpy as np

from repro.core import labels as lbl
from repro.core.labels import LabelTable


def plant_directed_chl(g, rank: np.ndarray, *, batch: int = 16,
                       cap: Optional[int] = None, ckpt=None,
                       resume: bool = False
                       ) -> Tuple[LabelTable, LabelTable]:
    """Returns ``(L_out, L_in)`` tables for a directed graph.

    Thin wrapper over the superstep engine
    (``repro.engine.DirectedPlantPolicy`` — two PLaNTed trees per root
    batch, emitted into the sink's ``out``/``in`` channels), which also
    gives directed builds checkpoint/resume via ``ckpt``.
    """
    assert g.directed
    from repro.engine import run_build
    res = run_build(g, rank, algo="directed", batch=batch, cap=cap,
                    ckpt=ckpt, resume=resume)
    return res.sink.table("out"), res.sink.table("in")


@functools.partial(jax.jit, static_argnames=("with_hub",))
def query_directed(l_out: LabelTable, l_in: LabelTable, u, v, *,
                   with_hub: bool = False):
    """min over common hubs of d(u→x) + d(x→v).

    ``with_hub=True`` also returns the witnessing hub id per query
    (-1 when the label sets are disjoint). Jitted, so the ``[Q, L, L]``
    candidate cube of `intersect_rows` fuses into its reduction."""
    best, hub = lbl.intersect_rows(l_out.hubs[u], l_out.dist[u],
                                   l_in.hubs[v], l_in.dist[v])
    return (best, hub) if with_hub else best
