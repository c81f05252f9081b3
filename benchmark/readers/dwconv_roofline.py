"""A depthwise convolution's share of its roofline from the device trace,
for a configuration whose operations module has ``dwconv_cost``.

``kernels``: a list of entries, each with a ``match``, a regex over the
trace's operation labels: every matched event is one product of the
convolution (forward, recomputed forward, or a cotangent) over the chip's
pairs, and its device seconds are time spent.  ``channels`` and ``nbytes``
are the width of the array the convolution runs over and the bytes an
element; its height and width are the cell's 1/8 map (``facts["lookup"]``).

The least time one product can take is the larger of operations / peak and
bytes / bandwidth by ``dwconv_cost(h, w, pairs, channels, nbytes)``.  The
share is that least time over all events seen in the trace, divided by the
time spent.  Nothing matched, or a configuration that counts no depthwise
convolution: nothing returned (never 0)."""

import importlib
import re


def read(ctx, kernels, channels, nbytes=2):
    t, peaks, look = ctx["trace"], ctx["peaks"], ctx["facts"].get("lookup")
    module = ctx["config"].get("operations")
    if not t or not peaks or not look or not module:
        return None
    ops_mod = importlib.import_module(f"benchmark.{module}")
    if not hasattr(ops_mod, "dwconv_cost"):
        return None
    ops, moved = ops_mod.dwconv_cost(look["h"], look["w"],
                                     look["pairs_per_call"], channels, nbytes)
    per_product = max(ops / peaks["flops_bf16"],
                      moved / peaks["hbm_bytes_per_s"])
    least = spent = 0.0
    for k in kernels:
        rx = re.compile(k["match"])
        for name, seconds in t["by_name_s"].items():
            if rx.search(name):
                spent += seconds
                least += t["by_name_n"][name] * per_product
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
