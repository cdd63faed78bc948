"""XLA_FLAGS acceptance probing.

XLA parses ``XLA_FLAGS`` at backend initialization and **aborts the
process** (SIGABRT, returncode −6: ``Unknown flags in XLA_FLAGS``) on
any flag the linked runtime does not define. Flag availability tracks
the bundled XLA, not the jax version string, so the only honest test
is to try them: each candidate is probed in a throwaway subprocess
(``import jax; jax.devices()`` with only the candidate in
``XLA_FLAGS``) and the verdict cached — in memory and on disk keyed by
jax version, so a test session pays the probe once ever per machine.

``REPRO_XLA_FLAG_PROBE=off`` skips subprocess probing entirely and
treats every non-allowlisted flag as unsupported (for sandboxes where
spawning interpreters is unwanted); ``=on`` is the default.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
from typing import Dict, Iterable, List, Optional, Sequence

import jax

PROBE_ENV_VAR = "REPRO_XLA_FLAG_PROBE"

# Flags predating every jax this repo supports — never worth a probe.
_ALWAYS_ACCEPTED_NAMES = frozenset({
    "--xla_force_host_platform_device_count",
})

# CPU-collective watchdog timeouts: present in newer XLA only; on a
# 1-core host the collectives in the 8-way tests are slow enough to
# trip the default watchdogs, so inject these wherever accepted.
COLLECTIVE_TIMEOUT_FLAGS = (
    "--xla_cpu_collective_call_terminate_timeout_seconds=1200",
    "--xla_cpu_collective_call_warn_stuck_timeout_seconds=600",
)

_PROBE_SNIPPET = "import jax; jax.devices()"
_CACHE: Dict[str, bool] = {}


def flag_name(flag: str) -> str:
    return flag.split("=", 1)[0]


def _cache_path() -> str:
    # flag acceptance tracks the bundled XLA runtime, so key on the
    # jaxlib version too — it can change under a fixed jax version.
    # User-scoped: the shared tempdir filename must not collide (or be
    # pre-seedable) across users on a multi-user host.
    try:
        import jaxlib
        runtime = getattr(jaxlib, "__version__", "unknown")
    except ImportError:                                  # pragma: no cover
        runtime = "none"
    uid = os.getuid() if hasattr(os, "getuid") else "na"
    return os.path.join(
        tempfile.gettempdir(),
        f"repro_compat_xla_flags_{uid}_{jax.__version__}_{runtime}.json")


def _load_disk_cache() -> Dict[str, bool]:
    try:
        with open(_cache_path()) as f:
            data = json.load(f)
        return {k: bool(v) for k, v in data.items()}
    except (OSError, ValueError):
        return {}


def _store_disk_cache(cache: Dict[str, bool]) -> None:
    try:
        path = _cache_path()
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path))
        with os.fdopen(fd, "w") as f:
            json.dump(cache, f)
        os.replace(tmp, path)
    except OSError:                                      # pragma: no cover
        pass                                  # cache is an optimization only


def _subprocess_accepts(flags: Sequence[str],
                        timeout: float = 300.0) -> Optional[bool]:
    """True/False = the runtime's verdict; None = inconclusive (probe
    timeout/fork error, or a crash that does not match the
    flag-rejection signature) — inconclusive is never cached."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = " ".join(flags)
    # CPU suffices for flag parsing, and the child must never reach for
    # an accelerator: the parent may hold the chip (one process per chip)
    env["JAX_PLATFORMS"] = "cpu"
    try:
        proc = subprocess.run([sys.executable, "-c", _PROBE_SNIPPET],
                              env=env, capture_output=True,
                              timeout=timeout)
    except (OSError, subprocess.TimeoutExpired):
        return None
    if proc.returncode == 0:
        return True
    # rejection signature: XLA SIGABRTs (-6) after printing the
    # offending env var; anything else (OOM kill, broken venv, ...)
    # is a transient environment failure, not a verdict on the flag
    stderr = proc.stderr or b""
    if proc.returncode == -6 or b"XLA_FLAGS" in stderr \
            or b"Unknown flag" in stderr:
        return False
    return None


def supported_xla_flags(candidates: Iterable[str],
                        probe=None) -> List[str]:
    """Filter ``candidates`` down to flags the runtime accepts.

    ``probe``: injectable ``Sequence[str] -> bool`` acceptance test
    (tests substitute a fake; default is the subprocess probe).
    """
    candidates = list(candidates)
    if probe is None:
        if os.environ.get(PROBE_ENV_VAR, "on").lower() in ("off", "0"):
            return [c for c in candidates
                    if flag_name(c) in _ALWAYS_ACCEPTED_NAMES]
        probe = _subprocess_accepts
        if not _CACHE:
            _CACHE.update(_load_disk_cache())
        cache: Optional[Dict[str, bool]] = _CACHE
    else:
        cache = None                      # injected probes are never cached

    verdicts: Dict[str, bool] = {}
    unknown: List[str] = []
    for c in candidates:
        name = flag_name(c)
        if name in _ALWAYS_ACCEPTED_NAMES:
            verdicts[c] = True
        elif cache is not None and name in cache:
            verdicts[c] = cache[name]
        else:
            unknown.append(c)

    if unknown:
        # one batch probe covers the common all-accepted case; on
        # rejection (False), bisect to per-flag verdicts; on an
        # inconclusive probe (None — probing itself unavailable),
        # don't serialize more doomed subprocess timeouts
        batch = probe(unknown)
        if batch:
            results = {c: True for c in unknown}
        elif batch is None:
            results = {c: None for c in unknown}
        else:
            results = {c: (probe([c]) if len(unknown) > 1 else False)
                       for c in unknown}
        # None = inconclusive probe (timeout / fork failure): treat as
        # unsupported for this run but never persist — a transient
        # failure must not poison the per-machine cache
        verdicts.update({c: bool(ok) for c, ok in results.items()})
        if cache is not None:
            conclusive = {flag_name(c): ok for c, ok in results.items()
                          if ok is not None}
            if conclusive:
                cache.update(conclusive)
                _store_disk_cache(cache)

    return [c for c in candidates if verdicts[c]]


def xla_flags(candidates: Iterable[str], base: Optional[str] = None,
              probe=None, override: bool = False) -> str:
    """An ``XLA_FLAGS`` value: accepted candidates + existing flags.

    ``override=False``: candidates already present (by name) in
    ``base`` are skipped — the environment's value wins.
    ``override=True``: same-name flags are stripped from ``base`` —
    the candidate's value wins (for sweep drivers that *must* control
    a flag regardless of inherited environment).
    """
    candidates = list(candidates)
    base = os.environ.get("XLA_FLAGS", "") if base is None else base
    base_toks = base.split()
    if override:
        accepted = supported_xla_flags(candidates, probe=probe)
        # strip an inherited flag only when an accepted candidate
        # actually replaces it — a rejected/unprobeable candidate must
        # not silently delete the user's own flag
        replaced = {flag_name(c) for c in accepted}
        base_toks = [t for t in base_toks
                     if flag_name(t) not in replaced]
    else:
        have = {flag_name(t) for t in base_toks}
        accepted = supported_xla_flags(
            [c for c in candidates if flag_name(c) not in have],
            probe=probe)
    return " ".join(accepted + base_toks).strip()


def apply_xla_flags(*candidates: str, override: bool = False) -> str:
    """Inject accepted candidates into ``os.environ["XLA_FLAGS"]``.

    Must run before jax initializes its backends (first device query /
    first computation) — merely importing jax or repro.compat is fine.
    Returns the value set.
    """
    value = xla_flags(candidates, override=override)
    os.environ["XLA_FLAGS"] = value
    return value


def host_device_flags(n: int, collective_timeouts: bool = True
                      ) -> List[str]:
    """Candidate flags for an ``n``-way forced host-platform mesh."""
    flags = [f"--xla_force_host_platform_device_count={n}"]
    if collective_timeouts:
        flags.extend(COLLECTIVE_TIMEOUT_FLAGS)
    return flags


def set_host_device_count(n: int) -> str:
    """Force ``n`` host (CPU) devices, with collective watchdog relief
    where the runtime accepts it. Call before any jax computation.

    Overrides any inherited same-name flags: every caller's intent is
    "this process needs exactly ``n`` devices", so a stale
    device-count flag left in the shell must not win.
    """
    return apply_xla_flags(*host_device_flags(n), override=True)
