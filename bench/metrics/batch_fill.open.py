"""Service: share of launched query slots that held a real query
(``ServiceStats.real_slots / launched_slots``); the rest is the padding
of partial batches."""


def read(record, trace, ctx):
    if not record.get("launched_slots"):
        return None
    return 100.0 * record["real_slots"] / record["launched_slots"]
