"""Device seconds of the operations whose labels match, over the device's
busy seconds in the traced window, in per cent.  ``match``: a list of
regexes over the trace's operation labels (a label matched by several
counts once).  ``fill`` names an entry of the run's facts whose ``n``
stands where a regex says ``{n}``; a run without that entry (a program
that lacks what the metric reads) returns nothing.  Nothing matched:
nothing returned (never 0)."""

import re


def read(ctx, match, fill=None):
    t = ctx["trace"]
    if not t or t["busy_s"] <= 0:
        return None
    n = ""
    if fill is not None:
        if not ctx["facts"].get(fill):
            return None
        n = str(ctx["facts"][fill]["n"])
    rxs = [re.compile(m.replace("{n}", n)) for m in match]
    chips = len(t.get("planes") or [None])
    spent = sum(s for name, s in t["by_name_s"].items()
                if any(rx.search(name) for rx in rxs))
    if spent <= 0:
        return None
    return 100.0 * spent / (chips * t["busy_s"])
