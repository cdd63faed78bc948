"""Engine: host milliseconds per superstep, the whole window over the
supersteps the engine committed in it (``repro.engine.runner``)."""


def read(record, trace, ctx):
    if not record.get("supersteps"):
        return None
    return 1e3 * record["window_s"] / record["supersteps"]
