"""Engine: device programs one superstep launches, the launches that
the program's ``engine.superstep`` spans dispatched (the sink's
``sink.insert`` inside them included), over those spans; a span
counts only when every launch it dispatched lies in the traced
window (`bench.spans`). The runtime's cap on programs in flight is
counted in these programs."""

from bench import spans


def read(record, trace, ctx):
    sp = spans.read(trace, ctx)
    whole = {} if sp is None else sp.whole_spans("engine.superstep")
    if not whole:
        return None
    return sum(len(ls) for ls in whole.values()) / len(whole)
