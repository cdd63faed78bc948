"""Bulk window: one caller asking for distance tables, closed loop.

Each call is ``CHLIndex.query(u, v)`` over one ``side x side`` table:
``side`` sources and ``side`` targets drawn uniformly from the
vertices that have an edge, the rule by which Graph500 draws its
search keys. The caller asks the next table when the answer to the
last is back, for ``--seconds``; the serving tier's queue is bypassed.
A sample of the tables, drawn from the seed as they are answered, is
compared with the reference once the window has closed.

With ``--trace 1`` the first ``trace_seconds`` of the same window run
under the profiler, and the labels the calls made then read are
counted for the roofline.

Traffic keys: ``driver`` ("bulk"), ``side``, ``check_tables``,
``trace_seconds``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List

import numpy as np

from bench import deploy
from bench.data import reference, roofline, samplers
from bench.harness import Check


@dataclasses.dataclass
class State:
    index: Any
    count: np.ndarray        # labels per vertex, as the index holds them


def setup(ctx, index=None) -> State:
    index = deploy.index(ctx) if index is None else index
    side = int(ctx.traffic["side"])
    u, v = samplers.block_pairs(ctx.deployment.pool, side,
                                samplers.rng_of(ctx.seed, "warm"))
    for _ in range(2):                   # the one launch shape
        index.query(u, v)
    count = np.asarray(index.store.shard_counts()).sum(axis=0)
    return State(index=index, count=count)


def window(state: State, ctx) -> dict:
    side = int(ctx.traffic["side"])
    keep = int(ctx.traffic["check_tables"])
    pool, query, tracer = ctx.deployment.pool, state.index.query, ctx.tracer
    pairs = samplers.rng_of(ctx.seed, "pairs")
    pick = samplers.rng_of(ctx.seed, "check")
    kept: List[tuple] = []               # reservoir of answered tables
    calls = traced_calls = traced_bytes = 0
    clock = time.perf_counter
    with tracer.window(float(ctx.traffic["trace_seconds"])):
        t0 = clock()
        t_stop = t0 + ctx.seconds
        while clock() < t_stop:
            u, v = samplers.block_pairs(pool, side, pairs)
            with tracer.span("bench.query"):
                d = query(u, v)
            calls += 1
            if tracer.active:
                traced_calls += 1
                traced_bytes += roofline.query_bytes(state.count, u, v)
            if len(kept) < keep:
                kept.append((u, v, d))
            else:
                j = int(pick.integers(0, calls))
                if j < keep:
                    kept[j] = (u, v, d)
        window_s = clock() - t0
    answered = calls * side * side
    return {
        "metrics": {"bulk_queries_per_s": answered / window_s},
        "attempted": answered, "failed": 0, "window_s": window_s,
        "calls": calls, "traced_calls": traced_calls,
        "traced_bytes": traced_bytes, "kept": kept,
    }


def release(state: State, record: dict) -> None:
    state.index = None


def check(state: State, record: dict, ctx) -> List[Check]:
    u = np.concatenate([k[0] for k in record["kept"]])
    v = np.concatenate([k[1] for k in record["kept"]])
    got = np.concatenate([k[2] for k in record["kept"]])
    want = reference.pair_distances(ctx.deployment.arcs, u, v)
    ctx.log(f"bulk check: {len(record['kept'])} tables, {len(u)} answers")
    return [Check("wrong_answers", reference.answer_mismatches(want, got),
                  0)]
