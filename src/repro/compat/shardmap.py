"""``shard_map`` with the replication check under one spelling.

Call sites say ``check_replication=``; this forwards it as jax's
``check_vma``.
"""

from __future__ import annotations

from typing import Callable

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_replication: bool = True) -> Callable:
    """``jax.shard_map`` with ``check_replication`` → ``check_vma``."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs,
                         check_vma=check_replication)
