"""What the stage-clock readers share: the program's own unit records
(``raft_tpu.obs.stages``, an always-on ring in the process) that ended in
the window's seconds before the loop's newest one.  The harness has
deleted the trace and the telemetry by the time a reader runs, so the
ring is all a reader can see of the program's stages; it trails the
stream, so a serve window's tail holds the up to 16 requests that drain
after the callers stop (steady-state batches like the rest)."""


def ring(loop, window_s=None):
    """-> the loop's records, oldest first (all the ring holds, or the
    trailing ``window_s`` seconds of them); [] where the program has no
    stage clock (a parent commit from before it) or the loop never ran."""
    try:
        from raft_tpu.obs import stages
    except ImportError:
        return []
    return stages.recent(loop, window_s)


def window(ctx, loop):
    return ring(loop, ctx["facts"]["window_s"])


def cycle(rec):
    """A unit's whole cycle: a serve batch's record starts where the
    worker's previous batch ended."""
    return rec["t_end"] - rec["t_start"]
