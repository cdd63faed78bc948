"""Label sink: device milliseconds per superstep of everything the
build launches besides ``plant_batch``, in the traced window from the
first ``plant_batch`` launch on. ``DenseSink.insert`` runs
``labels.insert_batch`` op by op, so its work reaches the device as
many small modules named by their primitive (``jit_scatter``,
``jit_cumsum``, ...); the engine's per-superstep stats packing is a
few of the same kind and costs microseconds beside them."""

#: XLA modules that are not this layer's
SWEEP = ("jit_plant_batch",)


def read(record, trace, ctx):
    steady = trace.steady(SWEEP)
    steps = steady.module_launches(SWEEP)
    if not steps:
        return None
    other = sum(s for name, s in steady.module_names().items()
                if name not in SWEEP)
    return 1e3 * other / steps
