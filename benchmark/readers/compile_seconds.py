"""Seconds the process spent in XLA's compiler or loading from its
persistent cache before the window: the sum over the ``compile`` ring's
records of kind ``compile`` or ``cache_load`` (one for each program) that
ended before the first in-window unit of ``loop`` did, so that the
reference's own compiles, which come after the window, stay out.  (The
ring's ``trace`` and ``lower`` records overlap one another and are not
summed; ``tools/gap_causes.py`` lists them.)"""

from benchmark.readers import stage_clock


def read(ctx, loop):
    recs = stage_clock.window(ctx, loop)
    if not recs:
        return None
    built = [c["seconds"] for c in stage_clock.ring("compile")
             if c["t_end"] <= recs[0]["t_end"]
             and c["kind"] in ("compile", "cache_load")]
    return sum(built) if built else None
