"""``repro.compat`` — the only place this repo touches unstable JAX API.

The installed stack is jax/jaxlib 0.9.0: top-level ``jax.shard_map``
with ``check_vma``, ``jax.make_mesh(axis_types=)`` and
``pltpu.CompilerParams``. XLA aborts the process at backend init on an
unknown flag in ``XLA_FLAGS``, so flags are probed in a subprocess
first (``repro.compat.xla``).

Everything else in the repo goes through the helpers here; nothing
outside ``repro.compat`` may import ``shard_map``, construct TPU
compiler params, reference ``AxisType``, or write raw ``XLA_FLAGS``
(enforced by ``tests/test_compat.py::test_no_direct_unstable_imports``).
"""

from repro.compat.cache import enable_compile_cache
from repro.compat.shardmap import shard_map
from repro.compat.meshes import make_mesh
from repro.compat.pallas import (pallas_call, prefetch_scalar_grid_spec,
                                 resolve_interpret, target_platform)
from repro.compat.xla import (COLLECTIVE_TIMEOUT_FLAGS, apply_xla_flags,
                              host_device_flags, set_host_device_count,
                              supported_xla_flags, xla_flags)


def capabilities() -> dict:
    """What the installed JAX runs on — for logs and bug reports.

    Best-effort by design: a diagnostics helper must not raise on the
    very misconfigurations it exists to surface. Note that reading the
    default backend finalizes jax backend init — call
    ``set_host_device_count`` *before* logging capabilities if you
    need forced host devices.
    """
    import os

    import jax

    try:
        backend = jax.default_backend()
    except Exception as e:                       # noqa: BLE001
        backend = f"error: {e}"
    try:
        interpret = resolve_interpret(platform=backend)
    except Exception as e:                       # noqa: BLE001
        interpret = f"error: {e}"
    return {
        "jax_version": jax.__version__,
        "default_backend": backend,
        "pallas_backend_env": os.environ.get(
            "REPRO_PALLAS_BACKEND", None),
        "pallas_interpret": interpret,
    }


__all__ = [
    "enable_compile_cache", "shard_map", "make_mesh",
    "pallas_call", "resolve_interpret", "target_platform",
    "prefetch_scalar_grid_spec",
    "COLLECTIVE_TIMEOUT_FLAGS", "supported_xla_flags", "xla_flags",
    "apply_xla_flags", "host_device_flags", "set_host_device_count",
    "capabilities",
]
