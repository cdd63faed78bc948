"""Service: time a query waited in the service's queue before its
batch launched, 99th percentile (``ServiceStats.queue_wait_samples``,
which keeps the most recent 65,536), over the queries launched before
the traced part of the window began."""

import numpy as np


def read(record, trace, ctx):
    waits = record.get("queue_wait_s")
    if waits is None or len(waits) == 0:
        return None
    return 1e3 * float(np.percentile(waits, 99))
