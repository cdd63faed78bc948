"""Run one benchmark cell once on the chip and print its result.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``workloads`` in ``BENCHMARK.json``. JAX's
persistent compilation cache is kept at ``<checkout>/.jax_cache``,
whatever the environment said, so that only the first run of a cell
in a checkout compiles and two checkouts share nothing. The last
line of standard output is one JSON object (``correct``, ``attempted``,
``failed``, ``metrics``, ``device``, with ``--trace 1`` ``breakdown``,
and ``checks`` last); the numbers compared with the reference and
their limits are also the last lines of standard error. Without an
accelerator, or with fewer chips than the cell needs, it exits 2 and
prints no result.
"""

import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
# before JAX is imported, which reads it once
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
