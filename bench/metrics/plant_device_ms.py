"""Sweep: device milliseconds of one PLaNT superstep's tree batch,
from the trace: the mean launch of the jitted ``plant_batch`` (the
relaxation sweeps to fixpoint, ``repro.sssp.relax``) in the traced
window."""

#: XLA modules of this layer
MODULES = ("jit_plant_batch",)


def read(record, trace, ctx):
    launches = trace.launch_s(MODULES)
    if not launches:
        return None
    return 1e3 * sum(launches) / len(launches)
