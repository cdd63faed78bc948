"""Pallas backend dispatch.

``pallas_call(...)`` is the single place that decides *how* a kernel
runs. Kernels declare what they need (grid/specs/
``dimension_semantics``); the dispatcher reads the platform and picks
compiled-TPU vs ``interpret=True`` emulation, overridable with one env
var::

    REPRO_PALLAS_BACKEND=auto|compiled|interpret   (default: auto)

``auto`` compiles on TPU and interprets everywhere else. Callers may
still force a mode programmatically (tests of the compiled path), but
the default everywhere is ``interpret=None`` → dispatch.

The platform is read through :func:`target_platform`, never straight from
``jax.default_backend()`` at the call sites, so a compile-only test
for a described (not attached) TPU can steer it.
"""

from __future__ import annotations

import os
from typing import Mapping, Optional, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BACKEND_ENV_VAR = "REPRO_PALLAS_BACKEND"

_TRUTHY = ("interpret", "1", "true", "yes")
_FALSY = ("compiled", "tpu", "0", "false", "no")


def target_platform() -> str:
    """The platform Pallas kernels are lowered for."""
    return jax.default_backend()


def resolve_interpret(interpret: Optional[bool] = None, *,
                      platform: Optional[str] = None,
                      env: Optional[Mapping[str, str]] = None) -> bool:
    """Decide interpret mode: explicit arg > env override > platform.

    On anything but TPU the compiled Pallas path is either unavailable
    or not what we target, so ``auto`` falls back to the interpreter.
    """
    if interpret is not None:
        return bool(interpret)
    env = os.environ if env is None else env
    mode = env.get(BACKEND_ENV_VAR, "auto").strip().lower()
    if mode in _TRUTHY:
        return True
    if mode in _FALSY:
        return False
    if mode not in ("", "auto"):
        raise ValueError(f"{BACKEND_ENV_VAR}={mode!r}; expected auto, "
                         "compiled, or interpret")
    plat = target_platform() if platform is None else platform
    return plat != "tpu"


def prefetch_scalar_grid_spec(*, num_scalar_prefetch: int, grid,
                              in_specs, out_specs):
    """The Pallas TPU scalar-prefetch grid spec.

    ``PrefetchScalarGridSpec`` marks the first ``num_scalar_prefetch``
    operands as scalar tables available *before* kernel launch: block
    index maps receive them as trailing ref arguments and may compute
    data-dependent block indices from them (the mechanism behind the
    source-windowed ``ell_relax`` gather). Honored by the interpreter
    on every backend.
    """
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=num_scalar_prefetch, grid=grid,
        in_specs=in_specs, out_specs=out_specs)


def pallas_call(kernel, *, out_shape,
                grid=None, in_specs=None, out_specs=None,
                dimension_semantics: Optional[Sequence[str]] = None,
                interpret: Optional[bool] = None, **kwargs):
    """Backend-dispatching ``pl.pallas_call``.

    ``dimension_semantics`` is the portable spelling of the grid
    annotation: it is packed into whichever compiler-params class the
    installed Pallas has, and omitted entirely in interpret mode
    (the interpreter runs the grid sequentially, so ``arbitrary``
    accumulation semantics hold by construction).
    """
    interp = resolve_interpret(interpret)
    if not interp and dimension_semantics is not None \
            and "compiler_params" not in kwargs:
        plat = target_platform()
        if plat == "tpu":
            kwargs["compiler_params"] = pltpu.CompilerParams(
                dimension_semantics=tuple(dimension_semantics))
        elif "arbitrary" in dimension_semantics:
            # 'arbitrary' promises sequential grid execution along that
            # axis (kernels accumulate into their output block under
            # it); a non-TPU compiled lowering has no way to honor the
            # annotation, and running the grid concurrently would race
            # the accumulation — refuse rather than return garbage
            raise NotImplementedError(
                f"compiled Pallas dispatch on backend {plat!r} cannot "
                "honor 'arbitrary' dimension semantics "
                f"{tuple(dimension_semantics)}; use the TPU backend or "
                f"interpret mode ({BACKEND_ENV_VAR}=interpret)")
    if grid is not None:
        kwargs["grid"] = grid
    if in_specs is not None:
        kwargs["in_specs"] = in_specs
    if out_specs is not None:
        kwargs["out_specs"] = out_specs
    return pl.pallas_call(kernel, out_shape=out_shape,
                          interpret=interp, **kwargs)
