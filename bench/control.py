"""Readings that set the limits of ``correct``: the program's and the
control's, over many seeds, in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed it runs the cell's set-up and window as ``bench/run.py``
does (untraced) and prints the numbers compared with the reference,
then the same numbers for the control. The control breaks the
configuration's guarantee of exact distances by one step of precision
(``control_dtype`` in the configuration):

- where the program has such a path of its own, it serves: a served
  cell with ``control_dtype`` ``bfloat16`` loads its index with the
  program's lossy bfloat16 label codec (``store="compressed"``);
- otherwise the reference takes the program's place with its
  distances stored in ``control_dtype``.

Each line of standard output is one JSON object per seed. The
benchmark's own runs never run this.
"""

from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def control_readings(driver, state, record, ctx) -> dict:
    """The control's numbers for the seed ``ctx.seed`` whose program
    run gave ``state`` and ``record``."""
    from bench import deploy
    from bench.data import reference

    dtype = ctx.config["control_dtype"]
    kind = ctx.traffic["driver"]
    if kind == "plant":
        roots = driver.check_sample(state, ctx)
        want = driver.reference_labels(ctx, roots)
        got = driver.reference_labels(ctx, roots, dtype)
        return {c.name: c.value for c in driver.compare(want, got)}
    if dtype == "bfloat16":
        index = deploy.index(ctx, store="compressed", codec="bf16")
        c_state = driver.setup(ctx, index=index)
        c_record = driver.window(c_state, ctx)
        driver.release(c_state, c_record)
        return {c.name: c.value
                for c in driver.check(c_state, c_record, ctx)}
    if kind == "bulk":
        import numpy as np
        u = np.concatenate([k[0] for k in record["kept"]])
        v = np.concatenate([k[1] for k in record["kept"]])
        want = reference.pair_distances(ctx.deployment.arcs, u, v)
        got = reference.round_to(want, dtype)
        return {"wrong_answers": reference.answer_mismatches(want, got)}
    raise ValueError(f"no {dtype} control for driver {kind!r}")


def readings(root: str, cell: str, seeds, seconds: float, *,
             require_chip: bool = True, log=None):
    """Yield ``{"seed", "program", "control"}`` for each seed."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    import jax

    from bench import deploy, harness, tracing

    if require_chip and jax.devices()[0].platform != "tpu":
        raise harness.NoChip("JAX found no TPU")
    ctx = harness.resolve(root, cell)
    ctx.seconds, ctx.trace, ctx.log = float(seconds), False, log
    ctx.tracer = tracing.Tracer(None)
    if require_chip:
        from repro.compat import enable_compile_cache
        enable_compile_cache()
    ctx.deployment = deploy.make(ctx)
    driver = harness.load_module(os.path.join(
        root, "bench", "drivers", ctx.traffic["driver"] + ".py"), "driver")
    index = None
    for seed in seeds:
        ctx.seed = int(seed)
        t0 = time.perf_counter()
        if ctx.traffic["driver"] == "plant":
            state = driver.setup(ctx)
        else:
            index = index or deploy.index(ctx)
            state = driver.setup(ctx, index=index)
        record = driver.window(state, ctx)
        if ctx.traffic["driver"] == "plant":
            driver.release(state, record)
        program = {c.name: c.value
                   for c in driver.check(state, record, ctx)}
        program["failed"] = record["failed"]
        control = control_readings(driver, state, record, ctx)
        yield {"seed": ctx.seed, "program": program, "control": control,
               "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from bench.harness import NoChip
    try:
        for row in readings(ROOT, args.workload, args.seeds, args.seconds):
            print(json.dumps(row), flush=True)
    except NoChip as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.exit(main())
