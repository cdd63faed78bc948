"""``jax.make_mesh`` with every axis typed ``Auto``."""

from __future__ import annotations

from typing import Sequence

import jax


def make_mesh(axis_shapes: Sequence[int], axis_names: Sequence[str], *,
              devices=None):
    """A mesh whose axes are all ``AxisType.Auto`` (the compiler picks
    the sharding of intermediates, which every call site relies on).
    ``devices`` may be described devices (a compile-only topology)."""
    names = tuple(axis_names)
    kwargs = {"axis_types": (jax.sharding.AxisType.Auto,) * len(names)}
    if devices is not None:
        kwargs["devices"] = devices
    return jax.make_mesh(tuple(axis_shapes), names, **kwargs)
