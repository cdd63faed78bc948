"""Source-windowed ELL layout + VMEM window planning.

The fused relaxation kernel gathers from the ``[B, n]`` prop/mrank
planes, so the dense layout must stage ``2 · BB · n · 4`` bytes of
source plane per grid cell — a hard VMEM wall at large n. This module
removes the wall by *source-bucketing* the pull-ELL adjacency: each
vertex's in-edges are grouped by which ``[BB, W]`` window of the
source planes their source vertex falls in, and a per-(vertex-tile,
chunk) window table drives scalar-prefetched block index maps, so each
grid cell streams only one ``window``-wide slice of the planes plus
that window's ``[BN, DK]`` edge chunk. VMEM cost becomes O(window),
independent of n.

Window sizing: ``REPRO_ELL_VMEM_BUDGET`` bounds the bytes the two
staged source-plane slices may occupy (default 8 MiB → a 131072-wide
window at BB=8, the historical single-window cap). The plan balances
windows — ``num_windows = ceil(n / max_window)`` and
``window = ceil(n / num_windows)`` rounded to the vertex tile — so a
graph just past the cap gets two half-width windows instead of one
full window plus a sliver.

Bit-identity: bucketing only re-chunks the in-edge multiset of each
vertex. The kernel's lexicographic (min, max-at-min) fold is
insensitive to how edges are partitioned into chunks (min/max/add
over exact floats), and dropped ``+inf``-weight padding edges fold as
the identity — so the windowed kernel is bit-identical to the dense
kernel and the jnp reference (`ref.ell_sweep_bucketed_ref` is the
oracle for exactly this claim).

The builder runs on host numpy once per graph; `sweep_layout` caches
by adjacency identity so repeated sweeps (and the engine policies,
which build eagerly in ``__init__``) pay it once.
"""

from __future__ import annotations

import os
import weakref
from collections import OrderedDict
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

VMEM_BUDGET_ENV_VAR = "REPRO_ELL_VMEM_BUDGET"

#: bytes the two staged [BB, window] source-plane slices (f32 + i32)
#: may occupy; 8 MiB at BB=8 → window ≤ 131072, the historical
#: whole-plane cap — so default behavior at small n is unchanged
DEFAULT_VMEM_BUDGET = 8 * 1024 * 1024

_SUFFIX = {"k": 1024, "m": 1024 ** 2, "g": 1024 ** 3}


def vmem_budget(env=None) -> int:
    """The source-plane VMEM budget in bytes
    (``REPRO_ELL_VMEM_BUDGET``, optional k/m/g suffix)."""
    env = os.environ if env is None else env
    raw = env.get(VMEM_BUDGET_ENV_VAR, "").strip().lower()
    if not raw:
        return DEFAULT_VMEM_BUDGET
    mult = 1
    digits = raw
    if raw[-1] in _SUFFIX:
        mult = _SUFFIX[raw[-1]]
        digits = raw[:-1]
    try:
        val = int(digits)
    except ValueError:
        raise ValueError(
            f"{VMEM_BUDGET_ENV_VAR}={raw!r}; expected an integer byte "
            "count with optional k/m/g suffix (e.g. 8m, 512k)") from None
    if val <= 0:
        raise ValueError(f"{VMEM_BUDGET_ENV_VAR}={raw!r}; budget must "
                         "be positive")
    return val * mult


def max_window(*, bb: int = 8, bn: int = 128,
               budget: Optional[int] = None) -> int:
    """Widest source window whose two staged plane slices
    (f32 dist + i32 mrank, ``2 · bb · W · 4`` bytes) fit the budget,
    rounded down to the vertex tile (never below one tile)."""
    budget = vmem_budget() if budget is None else int(budget)
    return max(bn, (budget // (2 * 4 * bb)) // bn * bn)


class WindowPlan(NamedTuple):
    """How the n source vertices split into gather windows."""
    window: int        # window width (multiple of bn)
    num_windows: int
    n_pad: int         # window * num_windows ≥ roundup(n, bn)


def window_plan(n: int, *, bb: int = 8, bn: int = 128,
                max_window: Optional[int] = None) -> WindowPlan:
    """Balanced window split for an n-vertex graph.

    ``max_window`` overrides the budget-derived cap (tests/benchmarks
    force multi-window execution at small n this way; normal callers
    leave it None and control sizing via ``REPRO_ELL_VMEM_BUDGET``).
    """
    if max_window is None:
        cap = globals()["max_window"](bb=bb, bn=bn)
    else:
        cap = max(bn, int(max_window) // bn * bn)
    n_bn = max(bn, -(-int(n) // bn) * bn)
    if n_bn <= cap:
        return WindowPlan(window=n_bn, num_windows=1, n_pad=n_bn)
    nw = -(-n_bn // cap)
    w = -(-(-(-n_bn // nw)) // bn) * bn
    return WindowPlan(window=w, num_windows=nw, n_pad=nw * w)


def kernel_fits(n: int, *, bb: int = 8, bn: int = 128) -> bool:
    """Whether a single window covers the whole source plane (the
    dense fast path — no bucketing needed). Past this, `ell_sweep`
    runs the source-windowed kernel over a bucketed layout."""
    return -(-int(n) // bn) * bn <= max_window(bb=bb, bn=bn)


@jax.tree_util.register_pytree_node_class
class BucketedEll:
    """Source-bucketed pull-ELL adjacency for the windowed kernel.

    Array children (jit-traceable):

    - ``src``: i32 ``[n_pad, num_chunks · dk]`` — *window-local*
      in-edge sources (global source minus its window's base);
    - ``w``:   f32 ``[n_pad, num_chunks · dk]`` — weights, ``+inf``
      padding (padding edges fold as the identity);
    - ``chunk_win``: i32 ``[n_pad // bn, num_chunks]`` — which source
      window chunk c of vertex tile t gathers from. Scalar-prefetched:
      the kernel's block index maps read it to pick the plane slice.
      Trailing padding chunks repeat the tile's last real window so
      they never trigger a fresh window DMA.

    Static aux (part of the jit cache key): n, deg, window,
    num_windows, n_pad, bn, dk, num_chunks.
    """

    def __init__(self, src, w, chunk_win, *, n: int, deg: int,
                 window: int, num_windows: int, n_pad: int, bn: int,
                 dk: int, num_chunks: int):
        self.src = src
        self.w = w
        self.chunk_win = chunk_win
        self.n = n
        self.deg = deg
        self.window = window
        self.num_windows = num_windows
        self.n_pad = n_pad
        self.bn = bn
        self.dk = dk
        self.num_chunks = num_chunks

    def plan(self) -> WindowPlan:
        return WindowPlan(self.window, self.num_windows, self.n_pad)

    def tree_flatten(self):
        aux = (self.n, self.deg, self.window, self.num_windows,
               self.n_pad, self.bn, self.dk, self.num_chunks)
        return (self.src, self.w, self.chunk_win), aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        src, w, chunk_win = children
        n, deg, window, num_windows, n_pad, bn, dk, num_chunks = aux
        return cls(src, w, chunk_win, n=n, deg=deg, window=window,
                   num_windows=num_windows, n_pad=n_pad, bn=bn, dk=dk,
                   num_chunks=num_chunks)

    def __repr__(self) -> str:                       # pragma: no cover
        return (f"BucketedEll(n={self.n}, deg={self.deg}, "
                f"window={self.window}, num_windows={self.num_windows},"
                f" dk={self.dk}, num_chunks={self.num_chunks})")


def build_bucketed_ell(ell_src, ell_w, plan: WindowPlan, *,
                       bn: int = 128, dk_max: int = 128) -> BucketedEll:
    """Bucket a pull ELL by source window (host numpy, once per graph).

    Per vertex tile, each window's in-edges pack into consecutive
    ``dk``-wide chunks; ``dk`` adapts to the densest (row, window)
    bucket (a scattered-source row never inflates every tile). Edges
    with ``+inf`` weight (ELL padding) are dropped — they fold as the
    identity, so dropping them is bit-safe and keeps buckets tight.
    """
    src = np.asarray(ell_src, dtype=np.int64)
    w = np.asarray(ell_w, dtype=np.float32)
    n, deg = src.shape
    W, nw, n_pad = plan
    ntiles = n_pad // bn
    finite = np.isfinite(w)
    win_of = np.where(finite, src // W, 0)

    rows = np.broadcast_to(np.arange(n)[:, None], src.shape)
    counts = np.zeros((n, nw), np.int64)       # per-(row, window) edges
    np.add.at(counts, (rows[finite], win_of[finite]), 1)
    maxc = int(counts.max()) if counts.size else 0
    dk = max(8, min(int(dk_max), -(-max(maxc, 1) // 8) * 8))

    counts_pad = np.zeros((ntiles * bn, nw), np.int64)
    counts_pad[:n] = counts
    tile_max = counts_pad.reshape(ntiles, bn, nw).max(axis=1)
    chunks_tw = -(-tile_max // dk)             # [ntiles, nw]
    num_chunks = max(1, int(chunks_tw.sum(axis=1).max()))
    chunk_off = np.concatenate(
        [np.zeros((ntiles, 1), np.int64),
         np.cumsum(chunks_tw, axis=1)[:, :-1]], axis=1)

    chunk_win = np.zeros((ntiles, num_chunks), np.int32)
    for t in range(ntiles):
        slot, last = 0, 0
        for wd in range(nw):
            c = int(chunks_tw[t, wd])
            if c:
                chunk_win[t, slot:slot + c] = wd
                slot += c
                last = wd
        chunk_win[t, slot:] = last             # pads reuse the last DMA

    tile_of = np.arange(n) // bn
    dst = np.full((n, deg), -1, np.int64)      # destination column
    for wd in range(nw):
        m = finite & (win_of == wd)
        pos = np.cumsum(m, axis=1) - 1         # index inside the bucket
        base = chunk_off[tile_of, wd] * dk
        dst = np.where(m, base[:, None] + pos, dst)

    src_b = np.zeros((n_pad, num_chunks * dk), np.int32)
    w_b = np.full((n_pad, num_chunks * dk), np.inf, np.float32)
    keep = dst >= 0
    src_b[rows[keep], dst[keep]] = (src - win_of * W)[keep]
    w_b[rows[keep], dst[keep]] = w[keep]
    return BucketedEll(jnp.asarray(src_b), jnp.asarray(w_b),
                       jnp.asarray(chunk_win), n=n, deg=deg, window=W,
                       num_windows=nw, n_pad=n_pad, bn=bn, dk=dk,
                       num_chunks=num_chunks)


def _host(x) -> Optional[np.ndarray]:
    """Concrete host copy, or None for traced values (inside jit the
    adjacency is a tracer and host bucketing is impossible — callers
    fall back and the engine threads a precomputed layout instead)."""
    try:
        return np.asarray(x)
    except Exception:                          # noqa: BLE001 — tracers
        return None


_CACHE_MAX = 4
#: (kind, id(ell_src), id(ell_w)) -> (weakref, weakref, layout), newest
#: last; shared by every layout builder of the sweep
_cache: "OrderedDict[tuple, tuple]" = OrderedDict()


def clear_layout_cache() -> None:
    _cache.clear()


def cached_layout(kind, ell_src, ell_w, build):
    """``build(host_src, host_w)`` for this adjacency, cached by the
    arrays' identity under ``kind`` (id-keyed, weakref-validated, small
    LRU), so drivers and policies can call it eagerly once per graph
    and repeated sweeps hit the cache; None when the inputs are traced
    (inside a jit the adjacency cannot be read on the host)."""
    key = (kind, id(ell_src), id(ell_w))
    hit = _cache.get(key)
    if hit is not None:
        ref_s, ref_w, layout = hit
        if ref_s() is ell_src and ref_w() is ell_w:
            _cache.move_to_end(key)
            return layout
        del _cache[key]                        # id reused by a new array
    hs, hw = _host(ell_src), _host(ell_w)
    if hs is None or hw is None:
        return None
    layout = build(hs, hw)
    try:
        _cache[key] = (weakref.ref(ell_src), weakref.ref(ell_w), layout)
        while len(_cache) > _CACHE_MAX:
            _cache.popitem(last=False)
    except TypeError:
        pass                 # plain numpy inputs aren't weakref-able
    return layout


def sweep_layout(ell_src, ell_w, *, bb: int = 8, bn: int = 128,
                 max_window: Optional[int] = None,
                 dk_max: int = 128) -> Optional[BucketedEll]:
    """The one layout entry point: bucketed layout for this adjacency,
    or None when a single window fits (dense fast path) or the inputs
    are traced (caller falls back to the reference). Cached per graph
    (`cached_layout`).
    """
    plan = window_plan(int(ell_src.shape[0]), bb=bb, bn=bn,
                       max_window=max_window)
    if plan.num_windows <= 1:
        return None
    return cached_layout(
        ("bucketed", plan, bn, dk_max), ell_src, ell_w,
        lambda hs, hw: build_bucketed_ell(hs, hw, plan, bn=bn,
                                          dk_max=dk_max))
