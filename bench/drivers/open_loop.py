"""Serving window: an open loop of independent clients.

Queries are due at Poisson times, at the fixed rate of the traffic
file, each a pair of vertices drawn uniformly (the DIMACS challenge's
random point-to-point queries). They enter the program's serving tier
(``CHLIndex.serve`` -> ``QueryService.try_submit`` / ``pump``) from
one thread, as they fall due; a query's latency runs from when it was
*due* to when its answer was back, so a generator that falls behind
shows as latency and is reported apart (``gen_late``). Queries that
are refused or fail count as misses: their latency is infinite.

The window offers the queries due in ``--seconds``; those still
pending then are served under the service's own deadline and are
waited for. With ``--trace 1`` the first ``trace_seconds`` of the
same window run under the profiler.

Traffic keys: ``driver`` ("open_loop"), ``rate_qps``,
``check_queries`` (served answers compared with the reference),
``trace_seconds``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, List

import numpy as np

from bench import deploy
from bench.data import reference, samplers
from bench.harness import Check


@dataclasses.dataclass
class State:
    index: Any
    service: Any
    due: np.ndarray          # seconds from the window's start
    u: np.ndarray
    v: np.ndarray


def make_service(index, config: dict):
    svc = config["service"]
    return index.serve(mode=svc["mode"], batch_size=svc["batch_size"],
                       deadline_ms=svc["deadline_ms"], cache=svc["cache"])


def setup(ctx, index=None, service=None) -> State:
    """``index`` and ``service``, when given, are reused (a sweep of
    rates in one process); otherwise both are made and warmed here."""
    if service is None:
        index = deploy.index(ctx) if index is None else index
        service = make_service(index, ctx.config)
        t0 = time.perf_counter()
        # every launch shape the service can use: the full batch and
        # each power-of-two bucket of a deadline-forced partial batch
        service.warmup(buckets=True)
        ctx.log(f"open loop: service warm-up "
                f"{time.perf_counter() - t0:.3f} s")
    rate = float(ctx.traffic["rate_qps"])
    due = samplers.poisson_due_times(rate, ctx.seconds,
                                     samplers.rng_of(ctx.seed, "arrivals"))
    u, v = samplers.uniform_pairs(ctx.deployment.pool, len(due),
                                  samplers.rng_of(ctx.seed, "pairs"))
    return State(index=index, service=service, due=due, u=u, v=v)


def window(state: State, ctx) -> dict:
    svc, due = state.service, state.due
    count = len(due)
    u, v = state.u.tolist(), state.v.tolist()
    tickets: List[Any] = [None] * count
    submitted = np.zeros(count)
    clock, tracer = time.perf_counter, ctx.tracer
    with tracer.window(float(ctx.traffic["trace_seconds"])):
        t0 = clock()
        i = 0
        while i < count:
            now = clock()
            while i < count and t0 + due[i] <= now:
                submitted[i] = clock()
                with tracer.span("bench.submit"):
                    tickets[i] = svc.try_submit(u[i], v[i])
                i += 1
            with tracer.span("bench.pump"):
                svc.pump()
        while svc.queue_depth:               # the stragglers, on deadline
            svc.pump()
        t_end = clock()
    due_abs = t0 + due
    done = np.array([tk is not None and tk.done and tk.error is None
                     for tk in tickets], dtype=bool)
    lat = np.full(count, np.inf)
    lat[done] = np.array([tk.t_done for tk, ok in zip(tickets, done)
                          if ok]) - due_abs[done]
    st = svc.stats_
    return {
        "metrics": {f"query_p{q}_ms": float(np.percentile(lat, q)) * 1e3
                    for q in (50, 90, 95, 99)},
        "attempted": count, "failed": int(count - done.sum()),
        "window_s": t_end - t0, "latency_s": lat,
        "gen_late_s": submitted - due_abs,
        "queue_wait_s": np.asarray(st.queue_wait_samples),
        "real_slots": st.real_slots, "launched_slots": st.launched_slots,
        "batches": st.batches,
        "values": np.array([tk.value if ok else np.nan
                            for tk, ok in zip(tickets, done)],
                           dtype=np.float32),
    }


def release(state: State, record: dict) -> None:
    state.service = None
    state.index = None


def check_sample(state: State, ctx) -> np.ndarray:
    rng = samplers.rng_of(ctx.seed, "check")
    count = len(state.due)
    return np.sort(rng.choice(count, min(count,
                                         int(ctx.traffic["check_queries"])),
                              replace=False))


def check(state: State, record: dict, ctx) -> List[Check]:
    pick = check_sample(state, ctx)
    want = reference.pair_distances(ctx.deployment.arcs, state.u[pick],
                                    state.v[pick])
    got = record["values"][pick]
    ctx.log(f"open loop check: {len(pick)} served answers")
    return [Check("wrong_answers",
                  reference.answer_mismatches(want, got), 0)]
