"""On-demand correlation benchmark in the beyond-HBM regime.

VERDICT round 1, item 4: the on-demand path exists to serve inputs whose
materialized all-pairs volume exceeds HBM (the reference serves these with
``alt_cuda_corr``, correlation_kernel.cu:19-119).  This benchmark runs a
test-mode forward at a shape where the all-pairs volume CANNOT fit
(1440x2560 -> N = (1440/8)*(2560/8) = 57600 queries; the fp32 level-0
volume alone is N^2*4 = 13.3 GB, ~17.7 GB with the pyramid, > the 16 GB
v5e HBM before counting activations) and compares the fused Pallas
on-demand kernel against the chunked XLA formulation.

Usage: python scripts/bench_ondemand.py [HxW] [iters] [impls]
``impls``: comma list (default "chunked,pallas" — the working number
prints first; the fused pallas kernels' Mosaic compile is known to blow
20-40 min budgets on the round-2 toolchain, see ROADMAP.md).  Prints
one JSON line per implementation.
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main():
    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import make_eval_fn
    from raft_tpu.models.raft import RAFT

    H, W = (int(x) for x in (sys.argv[1] if len(sys.argv) > 1
                             else "1440x2560").split("x"))
    iters = int(sys.argv[2]) if len(sys.argv) > 2 else 12
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (1, H, W, 3), np.float32) * 255.0

    # chunked first: the fused pallas on-demand kernels are correct in
    # interpret mode but their Mosaic compile exceeded 20-40 min budgets
    # on the round-2 toolchain (ROADMAP.md) — running it second means the
    # working number always prints.
    impls = (sys.argv[3] if len(sys.argv) > 3 else "chunked,pallas") \
        .split(",")
    # Init once with the known-good impl (params are impl-independent);
    # Jit the init (unjitted, flax init dispatches and compiles op by
    # op) and init at a tiny shape (conv params are size-independent).
    init_model = RAFT(RAFTConfig.full(compute_dtype="bfloat16",
                                      corr_impl="chunked"))
    small = jax.random.uniform(rng, (1, 64, 96, 3), np.float32)
    variables = jax.jit(
        lambda k: init_model.init({"params": k, "dropout": k},
                                  small, small, iters=1, train=False)
    )(rng)
    for impl in impls:
        cfg = RAFTConfig.full(compute_dtype="bfloat16", corr_impl=impl)
        fwd = make_eval_fn(cfg, iters)
        try:
            for _ in range(2):
                low, up = fwd(variables, img, img)
            float(up.sum())
            n = 5
            t0 = time.perf_counter()
            for _ in range(n):
                low, up = fwd(variables, img, img)
            float(up.sum())
            dt = (time.perf_counter() - t0) / n
            print(json.dumps({
                "metric": f"ondemand_eval_{H}x{W}_iters{iters}_{impl}",
                "value": round(1.0 / dt, 3),
                "unit": "frames/sec/chip",
                "vs_baseline": 0.0,
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "metric": f"ondemand_eval_{H}x{W}_iters{iters}_{impl}",
                "error": repr(e)[:200],
            }), flush=True)


if __name__ == "__main__":
    main()
