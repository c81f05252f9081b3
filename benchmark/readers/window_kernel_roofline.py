"""The window attention kernels' share of their roofline from the device
trace, at whatever window geometries the program runs them (each scale of
a model that matches at two has its own).

A forward kernel's label carries its result: ``%window_attention_fwd.<i>
custom-call (bf16[B,K,hk,K,wk,C], f32[B,K,K,1,n])``, the maps of ``B``
images cut into ``K x K`` windows of ``hk x wk = n`` tokens; the backward's
(three such arrays) is longer than the trace reducer keeps, so its events
are counted by the forward ones: a training step runs one backward for
each forward.  The least time of a forward over ``B K^2`` windows is the
larger of ``attention_cost``'s operations / peak and bytes / bandwidth (``q
k^T`` and ``P v``); a backward's is twice the operations (``dv``, ``dP``,
``dq``, ``dk``) and 7/4 of the bytes (``q``, ``k``, ``v``, ``dO`` read,
three cotangents written).  The share is the least time of every forward
and its backward over the device time of every matched event.  Nothing
matched: nothing returned (never 0)."""

import re

from benchmark.flops_gmflow import attention_cost

FWD = re.compile(r"^%window_attention_fwd\.\d+ custom-call \((?P<t>\w+)"
                 r"\[(?P<b>\d+),(?P<k>\d+),(?P<hk>\d+),\d+,(?P<wk>\d+),"
                 r"(?P<c>\d+)\]")
ANY = re.compile(r"^%window_attention_(fwd|bwd)\.\d+ custom-call")
NBYTES = {"bf16": 2, "f32": 4}


def read(ctx):
    t, peaks = ctx["trace"], ctx["peaks"]
    if not t or not peaks:
        return None
    least = spent = 0.0
    for name, seconds in t["by_name_s"].items():
        if ANY.search(name):
            spent += seconds
        m = FWD.search(name)
        if not m:
            continue
        k = int(m["k"])
        ops, moved = attention_cost(int(m["hk"]) * int(m["wk"]),
                                    int(m["b"]) * k * k, int(m["c"]),
                                    NBYTES.get(m["t"], 2))
        fwd = max(ops / peaks["flops_bf16"],
                  moved / peaks["hbm_bytes_per_s"])
        bwd = max(2 * ops / peaks["flops_bf16"],
                  1.75 * moved / peaks["hbm_bytes_per_s"])
        least += t["by_name_n"][name] * (fwd + bwd)
    if spent <= 0 or least <= 0:
        return None
    return 100.0 * least / spent
