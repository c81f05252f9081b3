"""The whole step's share of the chip's peak: operations the algorithm needs
per pair (benchmark/flops.py, recomputation not counted) x pairs per second
of this run's window / (chips x peak)."""


def read(ctx):
    f, peaks = ctx["facts"], ctx["peaks"]
    if not peaks or not f.get("pairs_per_s"):
        return None
    return 100.0 * f["ops_per_pair"] * f["pairs_per_s"] / (
        f["chips"] * peaks["flops_bf16"])
