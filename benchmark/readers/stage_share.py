"""The share of a loop's cycles that the named stages took: the sum of
their seconds over the sum of the units' cycles (a serve batch's cycle
runs from the previous batch's end to its own), in per cent."""

from benchmark.readers import stage_clock


def read(ctx, loop, stages):
    recs = stage_clock.window(ctx, loop)
    whole = sum(stage_clock.cycle(r) for r in recs)
    if whole <= 0:
        return None
    return 100.0 * sum(r["stages"].get(s, 0.0) for r in recs
                       for s in stages) / whole
