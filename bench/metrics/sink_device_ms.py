"""Label sink: device milliseconds of one ``sink.insert``, the launches
that the program's ``sink.insert`` spans dispatched, over those spans;
a span counts only when every launch it dispatched lies in the traced
window (`bench.spans`). Unlike ``insert_device_ms`` it holds no
program that another span dispatched."""

from bench import spans


def read(record, trace, ctx):
    sp = spans.read(trace, ctx)
    whole = {} if sp is None else sp.whole_spans("sink.insert")
    if not whole:
        return None
    ns = sum(launch.end - launch.start
             for ls in whole.values() for launch in ls)
    return 1e-6 * ns / len(whole)
