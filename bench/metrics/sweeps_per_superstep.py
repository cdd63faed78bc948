"""Engine: relaxation sweeps each superstep ran to reach its fixpoint,
from the engine's per-superstep records (``pack_stats``)."""


def read(record, trace, ctx):
    if not record.get("supersteps"):
        return None
    return record["sweeps"] / record["supersteps"]
