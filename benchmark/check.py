"""The comparison that decides ``correct``: numbers, each beside its limit.

Limits are data (``benchmark/limits/<workload>.json``), set from readings
on the chip as PERF.md records; this file only does the arithmetic.
"""

from __future__ import annotations

import sys

import numpy as np


def leaf_norms(tree):
    """{path: l2 norm} over a nested dict of arrays."""
    out = {}

    def walk(t, prefix):
        if isinstance(t, dict) or hasattr(t, "items"):
            for k, v in t.items():
                walk(v, prefix + (str(k),))
        else:
            a = np.asarray(t, np.float64)
            out["/".join(prefix)] = float(np.sqrt(np.sum(a * a)))

    walk(tree, ())
    return out


def worst_leaf_gap(prog, ref, skip=()):
    """Worst leaf of |norm_prog - norm_ref| / max(norm_ref, median norm_ref):
    the gap between the norms, not the norm of the difference, against the
    reference's norm of that leaf or of the median leaf, whichever is larger
    (some gradients are all but zero).  -> (gap, leaf name)."""
    med = float(np.median(list(ref.values())))
    worst = (0.0, "")
    for k, r in ref.items():
        if k in skip:
            continue
        gap = abs(prog[k] - r) / max(r, med, 1e-30)
        if gap > worst[0]:
            worst = (gap, k)
    return worst


def median_leaf_gap(prog, ref, skip=()):
    """The median leaf of the same measure as :func:`worst_leaf_gap`."""
    med = float(np.median(list(ref.values())))
    return float(np.median([abs(prog[k] - r) / max(r, med, 1e-30)
                            for k, r in ref.items() if k not in skip]))


def tree_diff(prog, ref):
    """||prog - ref|| / ||ref|| over every leaf of two trees together: for
    the clipped first gradient, how far its direction is off."""
    num = den = 0.0
    for a, b in zip(_leaves(prog), _leaves(ref)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        num += float(np.sum((a - b) ** 2))
        den += float(np.sum(b * b))
    return float(np.sqrt(num / max(den, 1e-300)))


def median_leaf_diff(prog, ref):
    """Median over the leaves of ||prog_leaf - ref_leaf|| / ||ref_leaf||."""
    out = []
    for a, b in zip(_leaves(prog), _leaves(ref)):
        a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
        den = float(np.sqrt(np.sum(b * b)))
        if den > 0:
            out.append(float(np.sqrt(np.sum((a - b) ** 2))) / den)
    return float(np.median(out))


def _leaves(tree):
    if isinstance(tree, dict) or hasattr(tree, "items"):
        for k in sorted(tree):
            yield from _leaves(tree[k])
    else:
        yield tree


def dead_leaves(ref_grad_norms, ratio=1e-3):
    """Leaves whose reference gradient is nought to rounding (a bias ahead
    of a norm): under ``ratio`` of the median leaf's.  Under Adam they move
    by round-off alone, so the change is not compared on them."""
    med = float(np.median(list(ref_grad_norms.values())))
    return {k for k, v in ref_grad_norms.items() if v < ratio * med}


def touchy_leaves(rounded, ref, ratio=0.1):
    """Leaves whose gradient norm moves by more than ``ratio`` of itself
    inside the reference when nothing but its weights are rounded to the
    configuration's compute type (``rounded``: that run's leaf norms).  On
    such a leaf the gap to a reference at float32 weights reads the leaf's
    response to the rounding that the configuration states, and not the
    program (PERF.md section 2).  A rule on the reference, not a name; the
    leaves :func:`dead_leaves` names have rules of their own and are not
    looked at.  -> {leaf: gap}."""
    dead = dead_leaves(ref)
    gaps = {k: abs(rounded[k] - r) / r for k, r in ref.items()
            if k not in dead}
    return {k: g for k, g in gaps.items() if g > ratio}


def rel_gap(a, b):
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-30)


def flow_gap(served, ref):
    """||served - ref|| / ||ref|| over one whole flow field."""
    s, r = np.asarray(served, np.float64), np.asarray(ref, np.float64)
    return float(np.sqrt(np.sum((s - r) ** 2))
                 / max(np.sqrt(np.sum(r ** 2)), 1e-30))


def judge(numbers, limits):
    """``numbers``: {name: value} as read; ``limits``: {name: limit}, the
    numbers that are compared.  A limit whose number is missing or not
    finite fails; a number with no limit is not compared (the caller keeps
    it as information).  -> (correct, {name: {"value":, "limit":}})."""
    table, ok = {}, bool(limits)
    for name in sorted(limits):
        v, lim = numbers.get(name), limits[name]
        table[name] = {"value": v, "limit": lim}
        if v is None or not np.isfinite(v) or v > lim:
            ok = False
    return ok, table


def print_table(table, correct, file=sys.stderr):
    for name, row in table.items():
        print(f"check {name} value={row['value']} limit={row['limit']}",
              file=file)
    print(f"check correct={str(bool(correct)).lower()}", file=file,
          flush=True)
