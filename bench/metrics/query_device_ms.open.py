"""Store query: device milliseconds of one launch of the service's
answer function, the mean over the launches in the traced window.
``CHLIndex.serve(mode="qlsn")`` jits ``lambda u, v: qlsn(table, u,
v)`` (``labels.query_pairs``), so its launches are the modules named
``jit__lambda...``."""

MODULES = ("jit__lambda*",)


def read(record, trace, ctx):
    launches = trace.launch_s(MODULES)
    if not launches:
        return None
    return 1e3 * sum(launches) / len(launches)
