"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV. Select with --only.

Each module runs in a process of its own, started by a parent that
never imports JAX: a chip serves one process at a time, so a parent
that had touched the backend would keep the chip from the modules
(fig8 and table4 start JAX children of their own)."""

import argparse
import importlib
import os
import subprocess
import sys

MODULES = [
    "table3_shared",
    "fig2_labels_per_tree",
    "fig3_psi",
    "fig4_common_hubs",
    "fig5_alpha",
    "fig6_psith",
    "fig8_scaling",
    "fig9_als_vs_q",
    "table4_query_modes",
    "kernels_bench",
    "serving_bench",
    "roofline_report",
]

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def run_module(name: str) -> None:
    """Child side: run one module and print its CSV rows."""
    from repro.compat import enable_compile_cache
    enable_compile_cache()
    mod = importlib.import_module(f"benchmarks.{name}")
    for r in mod.run():
        d = str(r.get("derived", "")).replace(",", ";")
        print(f"{r['name']},{r['us_per_call']},{d}", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--module", default=None, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.module:
        run_module(args.module)
        return
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(ROOT, "src"), ROOT]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    print("name,us_per_call,derived", flush=True)
    failed = 0
    for m in args.only or MODULES:
        p = subprocess.run([sys.executable, "-m", "benchmarks.run",
                            "--module", m], cwd=ROOT, env=env)
        if p.returncode != 0:
            failed += 1
            print(f"{m},0,MODULE_FAILED", flush=True)
    if failed:
        sys.exit(1)


if __name__ == "__main__":
    main()
