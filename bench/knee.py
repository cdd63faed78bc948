"""Sweep of offered rates for an open-loop cell, to find its knee.

    python bench/knee.py --workload road-qlsn-open --seconds 10 --rates 1000 2000 4000

For each rate, in one process on one loaded index, it runs the cell's
window at that rate and prints one JSON line: the offered and the
completed rate, the latency quartiles of the first and the last
quarter of the window's queries, and how late the generator ran. The
knee is the highest rate at which the completed rate keeps up with
the offered one and the last quarter's latency has not grown past the
first's: the queue does not grow over the window. The cell's fixed
rate (``rate_qps`` in its traffic file) is set from it once; the
benchmark's own runs never sweep.
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: completed over offered rate that still counts as keeping up
KEEP_UP = 0.99
#: last-quarter over first-quarter median latency that counts as growth
GROWTH = 2.0


def sweep(root: str, cell: str, rates, seconds: float, seed: int = 1,
          *, require_chip: bool = True, log=None):
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    import jax

    from bench import deploy, harness, tracing

    if require_chip and jax.devices()[0].platform != "tpu":
        raise harness.NoChip("JAX found no TPU")
    ctx = harness.resolve(root, cell)
    ctx.seconds, ctx.trace, ctx.log, ctx.seed = (float(seconds), False,
                                                 log, int(seed))
    ctx.tracer = tracing.Tracer(None)
    if require_chip:
        from repro.compat import enable_compile_cache
        enable_compile_cache()
    ctx.deployment = deploy.make(ctx)
    driver = harness.load_module(os.path.join(
        root, "bench", "drivers", ctx.traffic["driver"] + ".py"), "driver")
    service = driver.setup(ctx).service
    for rate in rates:
        ctx.traffic = dict(ctx.traffic, rate_qps=float(rate))
        st = service.stats_
        real, launched, batches = (st.real_slots, st.launched_slots,
                                   st.batches)
        state = driver.setup(ctx, service=service)
        rec = driver.window(state, ctx)
        rec["real_slots"] -= real
        rec["launched_slots"] -= launched
        rec["batches"] -= batches
        count = rec["attempted"]
        quarter = max(1, count // 4)
        lat = np.asarray(rec["latency_s"]) * 1e3
        first, last = lat[:quarter], lat[-quarter:]
        yield {
            "rate_qps": float(rate), "offered": count,
            "completed_per_s": (count - rec["failed"]) / rec["window_s"],
            "window_s": rec["window_s"],
            "p50_ms": float(np.percentile(lat, 50)),
            "p99_ms": float(np.percentile(lat, 99)),
            "first_quarter_p50_p99_ms": [float(np.percentile(first, 50)),
                                         float(np.percentile(first, 99))],
            "last_quarter_p50_p99_ms": [float(np.percentile(last, 50)),
                                        float(np.percentile(last, 99))],
            "gen_late_p99_ms": float(np.percentile(rec["gen_late_s"], 99))
            * 1e3,
            "batch_fill_pct": 100.0 * rec["real_slots"]
            / max(1, rec["launched_slots"]),
            "launches": rec["batches"],
            "sustained": bool(
                (count - rec["failed"]) / rec["window_s"]
                >= KEEP_UP * float(rate)
                and np.percentile(last, 50)
                <= GROWTH * np.percentile(first, 50)),
        }


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    from bench.harness import NoChip
    try:
        knee = None
        for row in sweep(ROOT, args.workload, args.rates, args.seconds,
                         args.seed):
            print(json.dumps(row), flush=True)
            if row["sustained"]:
                knee = max(knee or 0.0, row["rate_qps"])
        print(json.dumps({"knee_qps": knee}), flush=True)
    except NoChip as e:
        print(f"knee: {e}", file=sys.stderr)
        return 2
    return 0


if __name__ == "__main__":
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                           ".jax_cache")
    sys.exit(main())
