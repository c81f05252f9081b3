"""The global aggregate's (``A v``) share of its roofline from the device
trace, for a configuration whose operations module has ``aggregate_cost``.

``kernels``: a list of entries, each

- ``match``: regex over the trace's operation labels (``{n}`` stands for
  the cell's number of positions, ``facts["aggregate"]["n"]``); the device
  seconds of every label it matches are time spent;
- ``product`` (default true): whether a matched event is one of the
  aggregate's products (``A v``, ``dv``, ``dA``: ``aggregate_cost``), over
  the chip's pairs; false for operations that only serve the products
  (operand relayouts): their time is spent and nothing is needed for them.

The least time one product can take is the larger of operations / peak and
bytes / bandwidth, from the cell's shapes (positions, pairs a call, bytes an
entry of ``A``).  The share is that least time over all
events seen in the trace, divided by the time spent.  Nothing matched, or
a program with no aggregate: nothing returned (never 0)."""

import importlib
import re


def read(ctx, kernels):
    t, peaks, agg = ctx["trace"], ctx["peaks"], ctx["facts"].get("aggregate")
    module = ctx["config"].get("operations")
    if not t or not peaks or not agg or not module:
        return None
    ops_mod = importlib.import_module(f"benchmark.{module}")
    if not hasattr(ops_mod, "aggregate_cost"):
        return None
    ops, nbytes = ops_mod.aggregate_cost(agg["n"], agg["pairs_per_call"],
                                         agg["bytes"])
    per_product = max(ops / peaks["flops_bf16"],
                      nbytes / peaks["hbm_bytes_per_s"])
    least = spent = 0.0
    for k in kernels:
        rx = re.compile(k["match"].replace("{n}", str(agg["n"])))
        for name, seconds in t["by_name_s"].items():
            if rx.search(name):
                spent += seconds
                if k.get("product", True):
                    least += t["by_name_n"][name] * per_product
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
