"""Device seconds of the operations whose labels match, over the device's
busy seconds in the traced window, in per cent: ``matched_share`` with its
patterns filled as ``attention_roofline.sizes`` fills them (``{n}`` tokens
of a window, ``{b}`` windows a call, ``{N}`` positions of the 1/8 map,
``{p}`` pairs a call), so that no pattern carries a crop or a batch.

``match``: a list of regexes; or ``like``: the name of a metric read by
``attention_roofline``, whose kernels' patterns are then the list, so that
a share and the roofline of the same fusions match the same labels.  A run
without the sizes, or nothing matched: nothing returned (never 0)."""

import json
import os

from benchmark.readers import attention_roofline, matched_share

METRICS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "metrics")


def read(ctx, match=None, like=None):
    size = attention_roofline.sizes(ctx)
    if size is None:
        return None
    if like is not None:
        with open(os.path.join(METRICS, like + ".json")) as f:
            match = [k["match"] for k in json.load(f)["args"]["kernels"]]
    return matched_share.read(
        ctx, [attention_roofline.filled(m, size) for m in match])
