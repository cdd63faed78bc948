"""Sweep dispatch: the XLA form, or the fused ELL relaxation kernel.

``ell_sweep`` is the single entry point the sweep driver
(`repro.sssp.relax`) calls: it pads every operand to tile multiples,
invokes the Pallas kernel (or the bit-identical jnp reference) and
slices the padding back off.

Backend selection (``use_kernel``):

- ``True``  — always run the Pallas kernel (via the compat
  ``pallas_call`` dispatcher, so `REPRO_PALLAS_BACKEND` still decides
  compiled-TPU vs interpreter execution);
- ``False`` — always run the jnp reference;
- ``None``  — auto: the jnp reference (the XLA form) on every
  platform. Compiled for a TPU, Mosaic refuses the kernel's in-VMEM
  gather, so the chip path is the XLA form by decision, not by a
  runtime fallback. ``REPRO_ELL_RELAX=kernel`` (its one non-default
  value) selects the kernel
  (with ``REPRO_PALLAS_BACKEND=interpret`` it runs emulated
  end-to-end, as CI's bench smoke does).

VMEM windowing: the kernel gathers from ``[BB, W]`` source-plane
slices. When the whole plane fits the budget
(``REPRO_ELL_VMEM_BUDGET``, default 8 MiB → W ≤ 131072 at BB=8) a
single window covers it and the dense kernel runs unchanged — the
small-n fast path. Past that, the sweep runs the source-windowed
kernel over a bucketed layout (`layout.BucketedEll`): pass one via
``layout=`` (the sweep driver and engine policies build it once per
graph via `sweep_layout`), or let `ell_sweep` build and cache it when
the adjacency is concrete. Only when the adjacency is *traced* (an
outer jit with no threaded layout — e.g. the distributed shard_map
supersteps) does the sweep still fall back to the jnp reference,
announced by a one-time-per-(n, reason) ``UserWarning``
(`reset_warnings` is the test hook).
"""

from __future__ import annotations

import functools
import os
import warnings
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.compat import resolve_interpret
from repro.kernels.ell_relax.ell_relax import ell_relax, ell_relax_windowed
from repro.kernels.ell_relax.layout import (  # noqa: F401 — re-exported
    DEFAULT_VMEM_BUDGET, VMEM_BUDGET_ENV_VAR, BucketedEll, WindowPlan,
    build_bucketed_ell, clear_layout_cache, kernel_fits, max_window,
    sweep_layout, vmem_budget, window_plan)
from repro.kernels.ell_relax.ref import ell_sweep_ref

ELL_RELAX_ENV_VAR = "REPRO_ELL_RELAX"


#: (n, reason) pairs already warned about — one warning per distinct
#: situation instead of a process-global latch, so a second build at a
#: different size (or after `reset_warnings`) still announces itself
_warned: set = set()


def reset_warnings() -> None:
    """Test hook: clear the one-per-(n, reason) warning registry."""
    _warned.clear()


def _warn_once(n: int, reason: str, message: str) -> None:
    if (int(n), reason) in _warned:
        return
    _warned.add((int(n), reason))
    warnings.warn(message, stacklevel=4)


def vmem_fallback_note(n: int) -> str:
    return (f"ell_relax: n={n} exceeds the single-window VMEM budget "
            f"({vmem_budget()} B) and the adjacency is traced (no "
            "precomputed bucketed layout reaches this sweep); "
            "relaxation runs the jnp reference. Thread a "
            "`sweep_layout(...)` result through ``layout=`` to run "
            "the windowed kernel.")


def warn_vmem_fallback(n: int, reason: str = "traced") -> bool:
    """If the fused kernel was *wanted* but the sweep must fall back to
    the reference (oversized n with only traced adjacency in reach),
    emit a ``UserWarning`` once per (n, reason). Returns True when the
    fallback engaged."""
    if kernel_fits(n):
        return False
    _warn_once(n, reason, vmem_fallback_note(n))
    return True


def resolve_use_kernel(use_kernel: bool | None = None) -> bool:
    """Kernel-vs-XLA dispatch for the relaxation sweep.

    ``auto`` is the XLA form (`ell_sweep_ref`) on every platform:
    Mosaic refuses the kernel's in-VMEM gather (``jnp.take`` of a
    ``[BB, W]`` plane by a ``[BN, DK]`` index tile) when compiling for
    a TPU — "Shape mismatch in input, indices and output" — so the
    kernel stays reachable only when asked for explicitly
    (``use_kernel=True`` or ``REPRO_ELL_RELAX=kernel``, which the
    interpreter-mode parity tests use)."""
    if use_kernel is not None:
        return bool(use_kernel)
    mode = os.environ.get(ELL_RELAX_ENV_VAR, "auto").strip().lower()
    if mode == "kernel":
        return True
    if mode not in ("", "auto"):
        raise ValueError(f"{ELL_RELAX_ENV_VAR}={mode!r}; expected "
                         "auto or kernel")
    return False


def sweep_form(n: int, use_kernel: bool | None = None, *,
               distributed: bool = False) -> str:
    """One line naming the sweep form a build over ``n`` vertices runs
    (for `BuildReport.notes` and launch logs). ``distributed`` builds
    pass traced adjacency into their shard_map supersteps, so past the
    VMEM budget they run the jnp reference even when the kernel is
    asked for."""
    if not resolve_use_kernel(use_kernel):
        return ("sweep: xla (ell_sweep_ref, one whole-plane gather; "
                "windows=1) — the Pallas ell_relax kernel is not used: "
                "Mosaic refuses its in-VMEM gather on TPU")
    if kernel_fits(n):
        return "sweep: pallas ell_relax kernel (dense; windows=1)"
    if distributed:
        return f"sweep: {vmem_fallback_note(n)}"
    plan = window_plan(n)
    return (f"sweep: pallas ell_relax kernel (source-windowed; "
            f"window={plan.window}, windows={plan.num_windows}) — n={n} "
            f"exceeds the single-window VMEM budget ({vmem_budget()} B)")


def resolve_sweep_backend(ell_src, ell_w, *,
                          use_kernel: bool | None = None,
                          layout: Optional[BucketedEll] = None
                          ) -> Tuple[bool, Optional[BucketedEll]]:
    """One place that decides how a sweep over this adjacency runs.

    Returns ``(use_kernel, layout)``: ``(False, None)`` → jnp
    reference; ``(True, None)`` → dense single-window kernel;
    ``(True, layout)`` → source-windowed kernel. A caller-provided
    multi-window ``layout`` always wins (that is how tests and
    benchmarks force windowed execution at small n); otherwise the
    VMEM budget decides, building (and caching) the layout on demand
    when the adjacency is concrete, warning + falling back to the
    reference when it is traced.
    """
    kern = resolve_use_kernel(use_kernel)
    if not kern:
        return False, None
    if layout is not None and layout.num_windows > 1:
        return True, layout
    n = ell_src.shape[0]
    if kernel_fits(n):
        return True, None
    layout = sweep_layout(ell_src, ell_w)
    if layout is None:
        warn_vmem_fallback(n)
        return False, None
    return True, layout


def _pad_to(x: jax.Array, mult: int, axis: int, fill) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def _pad_axis(x: jax.Array, axis: int, size: int, fill) -> jax.Array:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=fill)


def ell_sweep(dist, mrank, prop, alive, ell_src, ell_w, rank, *,
              use_kernel: bool | None = None,
              interpret: bool | None = None,
              layout: Optional[BucketedEll] = None):
    """One frontier-gated relaxation sweep; shape-safe.

    Args:
      dist:  f32 [B, n];  mrank: i32 [B, n];
      prop:  f32 [B, n] — dist masked to +inf at blocked/inactive
        sources (frontier gating);
      alive: bool/i32 [B] — False retires the whole tree;
      ell_src/ell_w: [n, deg] pull ELL; rank: i32 [n];
      layout: optional precomputed `BucketedEll` (see `sweep_layout`)
        selecting the source-windowed kernel — required past the VMEM
        budget when the adjacency is traced, optional (auto-built and
        cached) when it is concrete.
    Returns (new_dist f32 [B, n], new_mrank i32 [B, n]).
    """
    interp = resolve_interpret(interpret)
    kern, layout = resolve_sweep_backend(
        ell_src, ell_w, use_kernel=use_kernel, layout=layout)
    if kern and layout is not None:
        return _ell_sweep_windowed_jit(dist, mrank, prop, alive,
                                       layout, rank, interpret=interp)
    return _ell_sweep_jit(dist, mrank, prop, alive, ell_src, ell_w,
                          rank, use_kernel=kern, interpret=interp)


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def _ell_sweep_jit(dist, mrank, prop, alive, ell_src, ell_w, rank, *,
                   use_kernel: bool, interpret: bool):
    if not use_kernel:
        return ell_sweep_ref(dist, mrank, prop, mrank,
                             ell_src, ell_w, rank)
    B, n = dist.shape
    deg = ell_src.shape[1]
    bb, bn = 8, 128
    dk = min(128, -(-deg // 8) * 8)     # single chunk for small degrees
    d = _pad_to(_pad_to(dist, bb, 0, jnp.inf), bn, 1, jnp.inf)
    m = _pad_to(_pad_to(mrank, bb, 0, -1), bn, 1, -1)
    p = _pad_to(_pad_to(prop, bb, 0, jnp.inf), bn, 1, jnp.inf)
    a = _pad_to(alive.astype(jnp.int32)[:, None], bb, 0, 0)
    es = _pad_to(_pad_to(ell_src, bn, 0, 0), dk, 1, 0)
    ew = _pad_to(_pad_to(ell_w, bn, 0, jnp.inf), dk, 1, jnp.inf)
    r = _pad_to(rank.astype(jnp.int32)[None, :], bn, 1, 0)
    nd, nm = ell_relax(d, m, p, m, a, es, ew, r,
                       bb=bb, bn=bn, dk=dk, interpret=interpret)
    return nd[:B, :n], nm[:B, :n]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ell_sweep_windowed_jit(dist, mrank, prop, alive, layout, rank, *,
                            interpret: bool):
    B, n = dist.shape
    assert n == layout.n, (n, layout.n)
    bb, bn = 8, layout.bn
    n_pad = layout.n_pad
    d = _pad_axis(_pad_to(dist, bb, 0, jnp.inf), 1, n_pad, jnp.inf)
    m = _pad_axis(_pad_to(mrank, bb, 0, -1), 1, n_pad, -1)
    p = _pad_axis(_pad_to(prop, bb, 0, jnp.inf), 1, n_pad, jnp.inf)
    a = _pad_to(alive.astype(jnp.int32)[:, None], bb, 0, 0)
    r = _pad_axis(rank.astype(jnp.int32)[None, :], 1, n_pad, 0)
    nd, nm = ell_relax_windowed(d, m, p, m, a, layout.src, layout.w,
                                r, layout.chunk_win,
                                window=layout.window, bb=bb, bn=bn,
                                dk=layout.dk, interpret=interpret)
    return nd[:B, :n], nm[:B, :n]
