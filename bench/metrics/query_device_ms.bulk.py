"""Store query: device milliseconds of one ``CHLIndex.query`` call of
a distance table: the mean launch of the jitted ``labels.query_pairs``
in the traced window."""

MODULES = ("jit_query_pairs",)


def read(record, trace, ctx):
    launches = trace.launch_s(MODULES)
    if not launches:
        return None
    return 1e3 * sum(launches) / len(launches)
