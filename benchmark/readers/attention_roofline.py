"""Window attention's share of its roofline from the device trace, for a
configuration whose operations module has ``attention_cost``.

``kernels``: a list of entries, each

- ``match``: regex over the trace's operation labels, filled by ``sizes``
  (``{n}`` stands for the tokens of a window, ``{b}`` for the windows a call
  runs over: both images of the chip's pairs, ``attn_splits``² windows each);
  the device seconds of every label it matches are time spent;
- ``products`` (default 1): how many of an attention's products of ``2 n² C``
  operations a window (``q kᵀ``, ``P v``; backward ``dv``, ``dP``, ``dq``,
  ``dk``) one matched event is.  0 for operations that only serve them, or
  rebuild one in the backward pass: their time is spent and nothing is
  needed for them.  0.5 where half of a label's events are a rebuilt product.

A forward attention is two products; the least time it can take is the larger
of operations / peak and bytes / bandwidth by ``attention_cost(n, windows,
channels, nbytes)``, so one product needs half of that.  The share is that
least time over all events seen in the trace, divided by the time spent.
Nothing matched, or a configuration that counts no attention: nothing
returned (never 0)."""

import importlib
import re


def sizes(ctx):
    """What a pattern's ``{...}`` stand for in this run, from its facts and
    its configuration: ``p`` pairs a call, ``N`` positions of the 1/8 map,
    ``n`` tokens of a window, ``b`` windows a call.  A run or a configuration
    without them: nothing."""
    look, cfg = ctx["facts"].get("lookup"), ctx["config"]
    if not look or "attn_splits" not in cfg:
        return None
    k2 = int(cfg["attn_splits"]) ** 2
    N = look["h"] * look["w"]
    return {"p": look["pairs_per_call"], "N": N, "n": N // k2,
            "b": 2 * look["pairs_per_call"] * k2}


def filled(pattern, sizes):
    for name, value in sizes.items():
        pattern = pattern.replace("{%s}" % name, str(value))
    return pattern


def read(ctx, kernels, nbytes=2):
    t, peaks, cfg = ctx["trace"], ctx["peaks"], ctx["config"]
    module, size = cfg.get("operations"), sizes(ctx)
    if not t or not peaks or not size or not module:
        return None
    ops_mod = importlib.import_module(f"benchmark.{module}")
    if not hasattr(ops_mod, "attention_cost"):
        return None
    ops, moved = ops_mod.attention_cost(size["n"], size["b"],
                                        int(cfg["feature_channels"]), nbytes)
    per_product = max(ops / peaks["flops_bf16"],
                      moved / peaks["hbm_bytes_per_s"]) / 2.0
    least = spent = 0.0
    for k in kernels:
        rx = re.compile(filled(k["match"], size))
        for name, seconds in t["by_name_s"].items():
            if rx.search(name):
                spent += seconds
                least += (t["by_name_n"][name] * float(k.get("products", 1))
                          * per_product)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
