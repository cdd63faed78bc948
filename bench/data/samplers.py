"""Traffic samplers: which roots a build window plants, which pairs a
query window asks, and when each query is due. Every draw comes from
the run's ``--seed``; the graph and hierarchy do not.
"""

from __future__ import annotations

import numpy as np


def rng_of(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per purpose, so adding a draw to one
    stream never shifts another. Takes any non-negative seed,
    including those wider than 32 bits."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([int(seed), tag])


def rank_order(rank: np.ndarray) -> np.ndarray:
    """Vertices by rank, highest first: the order a full PLaNT build
    plants its roots in."""
    return np.argsort(-np.asarray(rank, dtype=np.int64), kind="stable")


def systematic_batches(rank: np.ndarray, batch: int, k: int,
                       rng: np.random.Generator) -> np.ndarray:
    """``int32 [k, batch]``: ``k`` whole root batches of a full build,
    the middle one of each of ``k`` equal strata of its batch
    sequence, in an order drawn from ``rng``.

    A full build plants ``n // batch`` full batches of rank-consecutive
    roots (and one partial batch, left out here so every superstep has
    the same shape). A batch's cost depends on which roots share it,
    so the window plants real batches, not loose roots; one from each
    stratum spreads them over the whole rank order, so the window's
    rate estimates the full build's. The batches are the same for
    every seed, which changes only their order: every run does the
    same work."""
    order = rank_order(rank)
    full = len(order) // batch
    if not 1 <= k <= full:
        raise ValueError(f"k={k} batches, but the build has {full}")
    picks = ((np.arange(k) + 0.5) * full / k).astype(np.int64)
    picks = picks[rng.permutation(k)]
    return np.stack([order[b * batch:(b + 1) * batch]
                     for b in picks]).astype(np.int32)


def uniform_pairs(pool: np.ndarray, count: int,
                  rng: np.random.Generator) -> tuple:
    """``count`` pairs, each endpoint uniform over ``pool``: the DIMACS
    challenge's random point-to-point queries."""
    u = pool[rng.integers(0, len(pool), count)]
    v = pool[rng.integers(0, len(pool), count)]
    return u.astype(np.int32), v.astype(np.int32)


def block_pairs(pool: np.ndarray, side: int,
                rng: np.random.Generator) -> tuple:
    """One ``side x side`` distance table: ``side`` sources and
    ``side`` targets uniform over ``pool``, as flat pair arrays."""
    s = pool[rng.integers(0, len(pool), side)].astype(np.int32)
    t = pool[rng.integers(0, len(pool), side)].astype(np.int32)
    return np.repeat(s, side), np.tile(t, side)


def poisson_due_times(rate: float, seconds: float,
                      rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds from the window's start) of an open-loop
    Poisson stream, conditioned on its count: exactly
    ``round(rate * seconds)`` arrivals, uniform over the window, so
    every seed offers the same amount of work in another order."""
    count = int(round(rate * seconds))
    return np.sort(rng.uniform(0.0, seconds, count))
