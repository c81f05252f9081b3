"""A kernel's share of its roofline from the device trace.

``kernels``: a list of entries, each

- ``match``: regex over the trace's operation labels; the device seconds of
  every label it matches are the time spent;
- ``count`` (default: ``match``): regex that picks the events to count the
  calls by; a named group ``lanes`` in it gives the pairs one such event
  covers (the batch dimension of its result), otherwise the cell's own
  pairs per call;
- ``events_per_call``: events ``count`` matches for one call of the lookup;
- ``backward``: whether the call is the lookup's backward.

The least time one call can take for one pair is the larger of
operations / peak and bytes / bandwidth, counted by
``benchmark/flops.lookup_cost``; the share is that least time over all calls
seen in the trace, divided by the time spent.  Nothing matched: nothing
returned (never 0)."""

import re

from benchmark import flops


def read(ctx, kernels, store_bytes=2, tap_bytes=2):
    t, peaks, f = ctx["trace"], ctx["peaks"], ctx["facts"]
    look = f.get("lookup")
    if not t or not peaks or not look:
        return None
    least = spent = 0.0
    for k in kernels:
        time_rx = re.compile(k["match"])
        count_rx = re.compile(k.get("count", k["match"]))
        ops, nbytes = flops.lookup_cost(ctx["config"], look["h"], look["w"],
                                        store_bytes, tap_bytes,
                                        backward=bool(k.get("backward")))
        per_pair = max(ops / peaks["flops_bf16"],
                       nbytes / peaks["hbm_bytes_per_s"])
        for name, seconds in t["by_name_s"].items():
            if time_rx.search(name):
                spent += seconds
            m = count_rx.search(name)
            if m:
                lanes = float(m.groupdict().get("lanes")
                              or look["pairs_per_call"])
                least += (t["by_name_n"][name] * lanes * per_pair
                          / float(k.get("events_per_call", 1)))
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
