"""Sweep: share of the HBM bandwidth roofline that ``plant_batch``
reaches. The least bytes a batch of trees needs are those of reading
each arc once and writing each vertex once per tree
(`bench.data.roofline.tree_bytes`), whatever the ELL width or sweep
count; the time is the mean device time of a ``plant_batch`` launch in
the traced window. Every batch of the window is full."""

from bench.data import roofline

MODULES = ("jit_plant_batch",)


def read(record, trace, ctx):
    launches = trace.launch_s(MODULES)
    if not launches or ctx.peaks is None or "batch" not in record:
        return None
    least = record["batch"] * roofline.tree_bytes(record["n"],
                                                  record["arcs"])
    return roofline.share_pct(least, sum(launches) / len(launches),
                              ctx.peaks.hbm_bytes_per_s)
