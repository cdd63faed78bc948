"""Sweep: gather slots the XLA sweep reads per in-edge of the graph.
The program's own layout builder (``repro.sssp.relax.ell_layout``,
what the build's policy threads into ``plant_batch``) is run on the
deployment's graph, and where it returns a layout that counts its
``slots`` and ``arcs`` (in-degree width classes), their ratio is read:
1.0 when every slot holds a real in-edge. A program whose layout
counts neither (a dense ELL sweep) reads nothing."""


def read(record, trace, ctx):
    from repro.sssp.relax import ell_layout

    g = ctx.deployment.graph
    layout = ell_layout(g.ell_src, g.ell_w)
    slots = getattr(layout, "slots", None)
    arcs = getattr(layout, "arcs", None)
    if not slots or not arcs:
        return None
    return slots / arcs
