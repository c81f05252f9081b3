"""Device seconds of a refinement at 1/4 resolution (arch 'unimatch'), over
the device's busy seconds in the traced window, in per cent:
``matched_share`` with its patterns filled from the run's facts, ``{p}``
the pairs a call, ``{h4}`` and ``{w4}`` the 1/4 map (twice the 1/8 map the
kind reports under ``lookup``), so that no pattern carries a crop or a
batch.  A run without those facts, or nothing matched: nothing returned
(never 0)."""

from benchmark.readers import matched_share


def read(ctx, match):
    look = ctx["facts"].get("lookup")
    if not look:
        return None
    size = {"p": look["pairs_per_call"], "h4": 2 * look["h"],
            "w4": 2 * look["w"]}
    filled = []
    for m in match:
        for name, value in size.items():
            m = m.replace("{%s}" % name, str(value))
        filled.append(m)
    return matched_share.read(ctx, filled)
