"""Device: share of the traced window, from the first ``plant_batch``
launch on, in which no operation ran on the device (1 - union of op
intervals / window), averaged over the chips in use. The set-up the
engine does before its first superstep is left out."""


def read(record, trace, ctx):
    if not trace.has_device():
        return None
    return trace.steady(("jit_plant_batch",)).idle_pct()
