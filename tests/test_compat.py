"""Unit tests for ``repro.compat`` — the only sanctioned unstable-JAX
surface in the repo.

Each helper is exercised against the installed jax. A hygiene test
scans the tree to keep direct unstable imports from creeping back in
outside ``src/repro/compat/``.

Forbidden spellings are assembled by string concatenation throughout
so this file itself stays clean under that same scan (and under the
repo-level acceptance grep).
"""

import pathlib
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import compat
from repro.compat import pallas, xla

NEW_CP_NAME = "Compiler" + "Params"
OLD_CP_NAME = "TPU" + "Compiler" + "Params"


# --------------------------------------------------------------------
# shard_map / make_mesh
# --------------------------------------------------------------------

def test_shard_map_real_roundtrip():
    from jax.sharding import PartitionSpec as P
    mesh = compat.make_mesh((1,), ("node",))
    f = compat.shard_map(lambda x: x + 1, mesh=mesh, in_specs=P(),
                         out_specs=P(), check_replication=False)
    np.testing.assert_array_equal(np.asarray(f(jnp.arange(3))),
                                  [1, 2, 3])


def test_make_mesh_installed_jax():
    m = compat.make_mesh((1,), ("node",))
    assert m.axis_names == ("node",)
    assert m.devices.size == 1


def test_make_mesh_axes_are_auto():
    m = compat.make_mesh((1,), ("node",))
    assert [t.name for t in m.axis_types] == ["Auto"]


# --------------------------------------------------------------------
# Pallas: compiler params + backend dispatch
# --------------------------------------------------------------------

def test_pallas_call_packs_compiler_params_for_tpu(monkeypatch):
    """On a TPU target the dispatcher turns ``dimension_semantics``
    into the TPU compiler params (read through the steerable
    ``target_platform`` hook, not the process's own backend)."""
    seen = {}

    def fake_pallas_call(kernel, *, out_shape, interpret, **kw):
        seen.update(kw, interpret=interpret)
        return "called"

    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")
    monkeypatch.setattr(pallas.pl, "pallas_call", fake_pallas_call)
    monkeypatch.delenv(pallas.BACKEND_ENV_VAR, raising=False)
    out = compat.pallas_call(
        lambda x_ref, o_ref: None, grid=(1,),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        dimension_semantics=("parallel", "arbitrary"))
    assert out == "called"
    assert seen["interpret"] is False
    assert tuple(seen["compiler_params"].dimension_semantics) == (
        "parallel", "arbitrary")


def test_resolve_interpret_explicit_wins():
    env = {pallas.BACKEND_ENV_VAR: "compiled"}
    assert pallas.resolve_interpret(True, env=env) is True
    env = {pallas.BACKEND_ENV_VAR: "interpret"}
    assert pallas.resolve_interpret(False, env=env) is False


def test_resolve_interpret_env_override():
    assert pallas.resolve_interpret(
        env={pallas.BACKEND_ENV_VAR: "interpret"}, platform="tpu") is True
    assert pallas.resolve_interpret(
        env={pallas.BACKEND_ENV_VAR: "compiled"}, platform="cpu") is False
    with pytest.raises(ValueError):
        pallas.resolve_interpret(env={pallas.BACKEND_ENV_VAR: "bogus"})


def test_resolve_interpret_platform_probe():
    assert pallas.resolve_interpret(env={}, platform="cpu") is True
    assert pallas.resolve_interpret(env={}, platform="gpu") is True
    assert pallas.resolve_interpret(env={}, platform="tpu") is False


def test_pallas_call_dispatches_without_per_site_interpret():
    """A kernel invoked with NO interpret plumbing runs green on the
    host platform (on CPU that means the interpreter was selected)."""
    from jax.experimental import pallas as pl

    def scale(x_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0

    fn = compat.pallas_call(
        scale, grid=(1,),
        in_specs=[pl.BlockSpec((8, 128), lambda i: (0, 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
        dimension_semantics=("parallel",))
    out = fn(jnp.ones((8, 128), jnp.float32))
    np.testing.assert_array_equal(np.asarray(out), 2.0)


def test_prefetch_scalar_grid_spec_drives_index_maps():
    """The compat scalar-prefetch resolver: a prefetched index table
    picks which input block each grid step reads (the mechanism behind
    the source-windowed ell_relax gather), honored by the interpreter
    on every backend."""
    from jax.experimental import pallas as pl

    def pick(tbl_ref, x_ref, o_ref):
        del tbl_ref                    # consumed by the index maps
        o_ref[...] = x_ref[...]

    spec = compat.prefetch_scalar_grid_spec(
        num_scalar_prefetch=1, grid=(2,),
        in_specs=[pl.BlockSpec((8, 128), lambda i, tbl: (tbl[i], 0))],
        out_specs=pl.BlockSpec((8, 128), lambda i, tbl: (i, 0)))
    fn = compat.pallas_call(
        pick, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((16, 128), jnp.float32))
    x = jnp.concatenate([jnp.zeros((8, 128), jnp.float32),
                         jnp.ones((8, 128), jnp.float32)])
    out = fn(jnp.asarray([1, 0], jnp.int32), x)   # swap the two blocks
    np.testing.assert_array_equal(np.asarray(out[:8]), 1.0)
    np.testing.assert_array_equal(np.asarray(out[8:]), 0.0)


def test_kernel_wrappers_resolve_backend_per_call(monkeypatch):
    """The backend decision must be consulted on every call (outside
    jit), not baked into a stale trace keyed on interpret=None."""
    from repro.kernels.minplus import ops as mops
    seen = []

    def recording_resolve(x=None, **kw):
        seen.append(x)
        return True

    monkeypatch.setattr(mops, "resolve_interpret", recording_resolve)
    dist = jnp.zeros((1, 2), jnp.float32)
    mrank = jnp.zeros((1, 2), jnp.int32)
    w = jnp.zeros((2, 2), jnp.float32)
    mops.minplus_padded(dist, mrank, w)
    mops.minplus_padded(dist, mrank, w)
    assert seen == [None, None]


def test_kernel_wrappers_default_to_dispatch():
    """End-to-end: the real kernels, no interpret argument anywhere."""
    from repro.kernels.minplus import minplus_padded, minplus_ref
    rng = np.random.default_rng(0)
    dist = jnp.asarray(rng.random((4, 16)).astype(np.float32))
    mrank = jnp.asarray(rng.integers(0, 9, (4, 16)).astype(np.int32))
    w = jnp.asarray(rng.random((16, 8)).astype(np.float32))
    od, om = minplus_padded(dist, mrank, w)
    od_r, om_r = minplus_ref(dist, mrank, w)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(od_r))
    np.testing.assert_array_equal(np.asarray(om), np.asarray(om_r))


# --------------------------------------------------------------------
# XLA flag probing
# --------------------------------------------------------------------

def test_supported_flags_filters_rejected():
    calls = []

    def probe(flags):
        calls.append(tuple(flags))
        return all("good" in f for f in flags)

    cands = ["--xla_good_flag=1", "--xla_bad_flag=2"]
    assert xla.supported_xla_flags(cands, probe=probe) == \
        ["--xla_good_flag=1"]
    # batch probe first, then per-flag bisect after the batch rejects
    assert calls[0] == tuple(cands)
    assert len(calls) == 3


def test_supported_flags_batch_accept_probes_once():
    calls = []

    def probe(flags):
        calls.append(tuple(flags))
        return True

    cands = ["--xla_a=1", "--xla_b=2"]
    assert xla.supported_xla_flags(cands, probe=probe) == cands
    assert len(calls) == 1


def test_host_device_count_flag_never_probed():
    def probe(flags):
        raise AssertionError("allowlisted flag must not be probed")

    flag = "--xla_force_host_platform_device_count=8"
    assert xla.supported_xla_flags([flag], probe=probe) == [flag]


def test_probe_off_env_keeps_only_allowlisted(monkeypatch):
    monkeypatch.setenv(xla.PROBE_ENV_VAR, "off")
    got = xla.supported_xla_flags(
        ["--xla_force_host_platform_device_count=4", "--xla_mystery=1"])
    assert got == ["--xla_force_host_platform_device_count=4"]


def test_xla_flags_merges_base_and_dedupes():
    out = xla.xla_flags(["--xla_a=1", "--xla_b=2"],
                        base="--xla_a=9 --other",
                        probe=lambda flags: True)
    # the base's --xla_a wins (already configured), --xla_b is added
    assert out.split() == ["--xla_b=2", "--xla_a=9", "--other"]


def test_xla_flags_override_replaces_same_name():
    out = xla.xla_flags(["--xla_a=2"], base="--xla_a=1 --other",
                        probe=lambda flags: True, override=True)
    # override: the candidate's value wins over the inherited one
    assert out.split() == ["--xla_a=2", "--other"]


def test_xla_flags_override_preserves_base_when_candidate_rejected():
    out = xla.xla_flags(["--xla_a=2"], base="--xla_a=1 --other",
                        probe=lambda flags: False, override=True)
    # a rejected candidate must not delete the user's inherited flag
    assert out.split() == ["--xla_a=1", "--other"]


def test_supported_flags_inconclusive_batch_short_circuits():
    calls = []

    def probe(flags):
        calls.append(tuple(flags))
        return None              # probing unavailable (e.g. timeout)

    cands = ["--xla_a=1", "--xla_b=2"]
    assert xla.supported_xla_flags(cands, probe=probe) == []
    assert len(calls) == 1       # no doomed per-flag bisection


def test_pallas_call_compiled_non_tpu_rejects_arbitrary_semantics():
    if jax.default_backend() == "tpu":
        pytest.skip("non-TPU-only behavior")
    with pytest.raises(NotImplementedError):
        compat.pallas_call(
            lambda x_ref, o_ref: None, grid=(1,),
            out_shape=jax.ShapeDtypeStruct((8, 128), jnp.float32),
            dimension_semantics=("arbitrary",), interpret=False)


def test_flag_probe_child_runs_on_cpu(monkeypatch):
    """The probe child must never reach for an accelerator, even when
    the parent inherited ``JAX_PLATFORMS=tpu``: the parent may hold the
    chip, and a chip serves one process."""
    seen = {}

    def fake_run(cmd, env, **kw):
        seen.update(env)
        return types.SimpleNamespace(returncode=0, stderr=b"")

    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(xla.subprocess, "run", fake_run)
    assert xla._subprocess_accepts(["--xla_a=1"]) is True
    assert seen["JAX_PLATFORMS"] == "cpu"
    assert seen["XLA_FLAGS"] == "--xla_a=1"


def test_host_device_flags_contents():
    flags = xla.host_device_flags(8)
    assert flags[0] == "--xla_force_host_platform_device_count=8"
    assert tuple(flags[1:]) == xla.COLLECTIVE_TIMEOUT_FLAGS


def test_capabilities_report():
    caps = compat.capabilities()
    assert caps["jax_version"] == jax.__version__
    assert isinstance(caps["pallas_interpret"], bool)


# --------------------------------------------------------------------
# hygiene: no direct unstable-JAX use outside repro.compat
# --------------------------------------------------------------------

FORBIDDEN = (
    "from jax import " + "shard_map",
    "from jax.experimental." + "shard_map",
    "check_" + "vma",
    "check_" + "rep=",
    "pltpu." + NEW_CP_NAME,
    "pltpu." + OLD_CP_NAME,
    "pltpu." + "PrefetchScalarGridSpec",
    "jax.sharding." + "AxisType",
    "--xla_cpu_" + "collective_call",  # raw watchdog flags: probe only
)


def test_no_direct_unstable_imports():
    root = pathlib.Path(__file__).resolve().parents[1]
    offenders = []
    for base in ("src", "tests", "examples", "benchmarks"):
        for path in sorted((root / base).rglob("*.py")):
            rel = path.relative_to(root).as_posix()
            if rel.startswith("src/repro/compat/"):
                continue
            text = path.read_text()
            for pat in FORBIDDEN:
                if pat in text:
                    offenders.append(f"{rel}: contains {pat!r}")
    assert not offenders, (
        "direct unstable-JAX usage outside repro.compat:\n  "
        + "\n  ".join(offenders))
