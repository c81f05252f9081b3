"""A stage's milliseconds per item: the stage's seconds summed over the
window's units, over the sum of the field that counts a unit's items (a
serve batch's ``real`` requests)."""

from benchmark.readers import stage_clock


def read(ctx, loop, stage, per):
    recs = stage_clock.window(ctx, loop)
    items = sum(r.get(per) or 0 for r in recs)
    if not items:
        return None
    return 1e3 * sum(r["stages"].get(stage, 0.0) for r in recs) / items
