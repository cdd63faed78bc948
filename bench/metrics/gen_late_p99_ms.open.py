"""Load generator: how late the open loop submitted a query after it
was due, 99th percentile, over the queries due before the traced part
of the window began."""

import numpy as np


def read(record, trace, ctx):
    late = record.get("gen_late_s")
    if late is None or len(late) == 0:
        return None
    return 1e3 * float(np.percentile(late, 99))
