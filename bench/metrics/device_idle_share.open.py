"""Device: share of the traced window in which no operation ran on
the device (1 - union of op intervals / window), averaged over the
chips in use."""


def read(record, trace, ctx):
    if not trace.has_device():
        return None
    return trace.idle_pct()
