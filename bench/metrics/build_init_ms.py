"""Engine: host milliseconds of the engine's set-up in one
``run_build`` call, the program's ``engine.init`` span (the policy's
and sink's construction: adjacency upload, ELL layout, build
fingerprint, empty label table); the first such span in the trace.
The device idles through it."""

from bench import spans


def read(record, trace, ctx):
    sp = spans.read(trace, ctx)
    inits = [] if sp is None else sp.named("engine.init")
    if not inits:
        return None
    first = sp.spans[inits[0]]
    return 1e-6 * (first.end - first.start)
