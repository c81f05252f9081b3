"""The loop's worst stall in the window: the longest unit cycle less the
window's median cycle, in milliseconds.

The per-layer metrics are read in the traced run, and a capture stretches
every unit that ends in it (each serve batch by 50-80 ms, in `launch`; my
chip runs, PR 26).  So the units that ended after the capture began are
left out: it begins the traffic's ``trace_seconds`` before the window
closes, and the newest record ends the last ``clients`` units' cycles
after that (the requests in flight drain one by one, stretched
themselves), with half a second of room."""

import statistics

from benchmark.readers import stage_clock


def read(ctx, loop):
    recs = stage_clock.window(ctx, loop)
    if not recs:
        return None
    tr = ctx["traffic"]
    cycles = [stage_clock.cycle(r) for r in recs]
    traced, clients = float(tr.get("trace_seconds", 3.0)), int(
        tr.get("clients", 0))
    began = recs[-1]["t_end"] - (
        traced + sum(cycles[len(cycles) - clients:]) + 0.5 if traced else 0.0)
    before = [c for r, c in zip(recs, cycles) if r["t_end"] <= began]
    if not before:
        return None
    return 1e3 * (max(before) - statistics.median(cycles))
