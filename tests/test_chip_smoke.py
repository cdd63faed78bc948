"""CPU rehearsal of ``chip_smoke.py`` and of the one-process-per-chip
rules around it (compile cache, benchmark harness).

The smoke's phase functions run here at a tiny size with the Pallas
dispatcher told it targets a TPU: a Pallas kernel on the main path
would then try a compiled TPU lowering on this CPU and fail, so an
exact answer also shows the main path runs the XLA form. The script
itself must refuse to report success anywhere but on a TPU.
"""

import importlib.util
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ)
    env.pop("REPRO_PALLAS_BACKEND", None)
    env.pop("REPRO_ELL_RELAX", None)
    env["PYTHONPATH"] = str(ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra)
    return env


@pytest.fixture
def steered_to_tpu(monkeypatch):
    from repro.compat import pallas
    monkeypatch.setattr(pallas, "target_platform", lambda: "tpu")
    monkeypatch.delenv("REPRO_PALLAS_BACKEND", raising=False)
    monkeypatch.delenv("REPRO_ELL_RELAX", raising=False)


def test_one_chip_phase_answers_exactly(steered_to_tpu, tmp_path, capsys):
    smoke = _load_smoke()
    bad = smoke.run_one_chip(side=10, batch=32, pair_sources=4,
                             pairs=64, full_rows=2, workdir=str(tmp_path))
    out = capsys.readouterr().out
    assert bad == 0
    assert "sweep: xla" in out
    assert "served pairs: 64 answers, 0 mismatches" in out
    assert out.count("answers, 0 mismatches") == 3


def test_four_chip_phase_answers_exactly_on_virtual_devices():
    code = ("from repro.compat import set_host_device_count\n"
            "set_host_device_count(4)\n"
            "import importlib.util, sys\n"
            f"spec = importlib.util.spec_from_file_location('s', "
            f"{str(ROOT / 'chip_smoke.py')!r})\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "sys.exit(m.run_four_chips(side=12, batch=8, "
            "pair_sources=4, pairs=64))\n")
    p = subprocess.run([sys.executable, "-c", code], env=_env(),
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "qfdl pairs: 64 answers, 0 mismatches" in p.stdout
    assert "placement: bytes per device" in p.stdout


def test_script_fails_without_a_tpu():
    p = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=_env(), capture_output=True, text=True,
                       timeout=300, cwd=str(ROOT))
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stderr


def test_script_fails_alone_in_a_directory(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    env = _env()
    env.pop("PYTHONPATH")
    p = subprocess.run([sys.executable, "chip_smoke.py"], env=env,
                       capture_output=True, text=True, timeout=300,
                       cwd=str(tmp_path))
    assert p.returncode != 0
    assert '"ok": true' not in p.stdout


@pytest.mark.parametrize("var,value", [
    ("REPRO_PALLAS_BACKEND", "interpret"),
    ("REPRO_ELL_RELAX", "ref"),
    ("REPRO_ELL_RELAX", "kernel"),
])
def test_script_refuses_a_forced_sweep_form(monkeypatch, capsys, var,
                                            value):
    smoke = _load_smoke()
    monkeypatch.setenv(var, value)
    assert smoke.main([]) == 2
    assert '"ok": true' not in capsys.readouterr().out


# ---------------------------------------------------------- compile cache

def _cache_dir_in_child(env):
    p = subprocess.run(
        [sys.executable, "-c",
         "from repro.compat.cache import compile_cache_dir; "
         "print(compile_cache_dir())"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    return p.stdout.strip()


def test_compile_cache_honors_env_var(monkeypatch, tmp_path):
    import jax

    from repro.compat import cache
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert cache.enable_compile_cache() == str(tmp_path)
    # jax reads the variable itself; no other directory is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_inside_checkout():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    first, second = _cache_dir_in_child(env), _cache_dir_in_child(env)
    assert first == second == str(ROOT / ".jax_cache")


def test_library_import_leaves_compile_cache_alone():
    env = _env()
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    p = subprocess.run(
        [sys.executable, "-c",
         "import jax, repro.index, repro.launch.chl, repro.launch.serve_chl"
         "; print(jax.config.jax_compilation_cache_dir)"],
        env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "None"


# ------------------------------------------------------ benchmark harness

def test_benchmark_parent_never_imports_jax():
    env = _env()
    env["PYTHONPATH"] = os.pathsep.join([str(ROOT / "src"), str(ROOT)])
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys, benchmarks.run; print('jax' in sys.modules)"],
        env=env, capture_output=True, text=True, timeout=300,
        cwd=str(ROOT))
    assert p.returncode == 0, p.stderr
    assert p.stdout.strip() == "False"


@pytest.mark.parametrize("module", ["fig8_scaling", "table4_query_modes"])
def test_benchmark_child_failure_raises(monkeypatch, module):
    import types

    sys.path.insert(0, str(ROOT))
    try:
        mod = importlib.import_module(f"benchmarks.{module}")
    finally:
        sys.path.remove(str(ROOT))

    def failed_child(*a, **kw):
        return types.SimpleNamespace(returncode=1, stdout="",
                                     stderr="boom")

    monkeypatch.setattr(mod.subprocess, "run", failed_child)
    with pytest.raises(RuntimeError, match="boom"):
        mod.run()
