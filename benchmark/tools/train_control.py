"""The training control, read without the program: the reference in the
program's place, one precision below the configuration's, against the
reference proper, at the cell's own size, on several seeds in one process.

    python3 benchmark/tools/train_control.py --workload train_full_chairs \\
        --quant fp8 --seeds 1 2 3 [--rehearse-tiny]

Weights come from each seed as in a run; the three batches are the traffic
generator's pairs at the crop size (no augmentor: the control needs rows that
differ, not the loader).  Prints one JSON line a seed: the numbers of
``benchmark/kinds/train.compare`` for the control and for the planted fault
"half of the batch left out".
"""
import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--workload", default="train_full_chairs")
    p.add_argument("--quant", default="fp8")
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--rehearse-tiny", action="store_true")
    args = p.parse_args()

    import jax

    from raft_tpu.cli import train as cli
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    from benchmark import reference, traffic, weights
    from benchmark.kinds.train import compare, gradient_unit, half_batches
    from benchmark.run import load_json

    jax.config.update("jax_compilation_cache_max_size", 4 * 2 ** 30)
    if jax.devices()[0].platform == "tpu":
        from raft_tpu.utils.profiling import enable_persistent_compile_cache

        enable_persistent_compile_cache()
    elif not args.rehearse_tiny:
        raise SystemExit("needs a TPU (or --rehearse-tiny)")
    bench = load_json(ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = load_json(ROOT, next(c["file"] for c in bench["configs"]
                               if c["name"] == cell["config"]))
    tr = load_json(ROOT, "benchmark", "traffic", cell["traffic"] + ".json")
    if args.rehearse_tiny:
        tr = {**tr, **tr["tiny"]}
    crop, batch = tuple(tr["crop"]), int(tr["batch_per_chip"])
    iters = int(tr.get("iters", cfg["train_iters"]))
    cli_args = cli.parse_args(["--stage", "chairs"])
    model = RAFT((RAFTConfig.small_model if cfg["small"]
                  else RAFTConfig.full)())
    for seed in args.seeds:
        t = time.perf_counter()
        variables = jax.device_get(weights.make_variables(model, seed))
        rng = traffic.rng_for(seed, 5)
        batches = []
        for _ in range(3):
            rows = [traffic.make_pair(rng, crop) for _ in range(batch)]
            batches.append({
                "image1": np.stack([r[0] for r in rows]).astype(np.float32),
                "image2": np.stack([r[1] for r in rows]).astype(np.float32),
                "flow": np.stack([r[2] for r in rows]),
                "valid": np.ones((batch,) + crop, np.float32)})

        def follow(b, variables=variables, quant=None):
            return jax.device_get(reference.train_steps(
                cfg, variables, b, iters, cli_args.lr, cli_args.num_steps,
                quant=quant))

        ref = follow(batches)
        unit, touchy = gradient_unit(variables, ref, follow, batches)
        out = {"seed": seed, "quant": args.quant,
               "unit": unit,
               "control": compare(variables, ref, follow(
                   batches, quant=reference.QUANTS[args.quant]), {}, unit,
                   touchy),
               "half_batch": compare(variables, ref,
                                     follow(half_batches(batches)), {}, unit,
                                     touchy),
               "seconds": time.perf_counter() - t}
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
