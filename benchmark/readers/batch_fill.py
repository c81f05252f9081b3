"""Engine counters over the window: pairs completed / (batches x the
engine's largest batch).  1 when every batch left full; 1/8 when every
request was a batch of its own under ``--max-batch 8``."""


def read(ctx):
    e = ctx["facts"].get("engine")
    if not e or not e["batches"]:
        return None
    return e["completed"] / (e["batches"] * e["max_batch"])
