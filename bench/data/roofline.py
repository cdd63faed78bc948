"""Least bytes each kernel must move, from the algorithm's own sizes.

These counts do not depend on how the program lays data out (ELL
width, label capacity, sweep count): a faster layout moves the share
up, a padded one moves it down.
"""

from __future__ import annotations

import numpy as np

#: bytes per arc read (source id and weight, 4 B each)
ARC_BYTES = 8
#: bytes per vertex written (distance and max-rank ancestor, 4 B each)
VERTEX_BYTES = 8
#: bytes per label read (hub id and distance, 4 B each)
LABEL_BYTES = 8
#: bytes per query pair besides its labels (two ids read, one answer
#: written, 4 B each)
PAIR_BYTES = 12


def tree_bytes(n: int, arcs: int) -> int:
    """One PLaNT tree: every arc read once, every vertex written once."""
    return ARC_BYTES * arcs + VERTEX_BYTES * n


def query_bytes(count: np.ndarray, u: np.ndarray, v: np.ndarray) -> int:
    """Pairs ``(u, v)``: the real labels of both ends, plus the pair."""
    count = np.asarray(count, dtype=np.int64)
    labels = int(count[np.asarray(u)].sum() + count[np.asarray(v)].sum())
    return LABEL_BYTES * labels + PAIR_BYTES * len(u)


def share_pct(least_bytes: float, device_s: float,
              bytes_per_s: float) -> float:
    """Share of the bandwidth roofline, in percent: the least time the
    bytes need at peak over the time the device took."""
    return 100.0 * least_bytes / (device_s * bytes_per_s)
