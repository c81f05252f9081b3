"""``kernel_roofline`` for a lookup that reads a volume at 1/4 resolution
(arch 'unimatch''s refinement): the kind reports the 1/8 map under the
run's ``lookup`` facts, and the least time of a call is counted here over
the 1/4 map, twice as high and twice as wide.  Nothing to read: nothing
returned (never 0)."""

from benchmark.readers import kernel_roofline


def read(ctx, kernels, **kw):
    look = ctx["facts"].get("lookup")
    if not look:
        return None
    facts = dict(ctx["facts"], lookup=dict(look, h=2 * look["h"],
                                           w=2 * look["w"]))
    return kernel_roofline.read(dict(ctx, facts=facts), kernels, **kw)
