"""With no profiler attached: how long one request's upload takes until the
images are on the chip, and how long its 1+32 programs take once they are.
A capture stretches every serve batch by 50-80 ms (PERF.md section 6, PR
26), so what a traced window says of one request cannot be taken for the
untraced path; this reads the two figures that decide which is which, on
the host clock around ``block_until_ready``, outside the engine's worker.

    python3 benchmark/tools/put_probe.py [--seed N] [--repeats N]

Prints one JSON line a configuration (``raft_full``, ``raft_small``) with
the medians in milliseconds: ``put_ready_ms`` (``jax.device_put`` of the
two padded 440x1024x3 float32 stacks until both are ready),
``put_returns_ms`` (until the call returns), ``programs_ms`` (the engine's
own pipeline of 1+32 program calls on resident inputs until the flow is
ready) and ``launch_ms`` (until the last call returned).
"""
import argparse
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def probe(small, seed, repeats, shape=(436, 1024), iters=32):
    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.ops.pad import InputPadder, bucket_hw
    from raft_tpu.serve import InferenceEngine, ServeConfig

    from benchmark import traffic, weights

    cfg = (RAFTConfig.small_model if small else RAFTConfig.full)(
        compute_dtype="bfloat16")
    scfg = ServeConfig(iters=iters)
    engine = InferenceEngine(weights.make_variables(RAFT(cfg), seed), cfg,
                             scfg)
    engine.start()
    try:
        engine.warmup([shape])
        bucket = bucket_hw(shape[0], shape[1], scfg.bucket_multiple,
                           scfg.buckets)
        exe = engine._get_executable(bucket, 1)
        im1, im2 = traffic.make_pool(seed, shape, 1)[0]
        padder = InputPadder(shape, mode=scfg.pad_mode, target=bucket)
        a1 = np.stack([padder.pad_np(np.asarray(im1, np.float32))])
        a2 = np.stack([padder.pad_np(np.asarray(im2, np.float32))])
        rows = []
        for _ in range(repeats + 1):          # the first one warms
            t0 = time.perf_counter()
            d1, d2 = jax.device_put(a1), jax.device_put(a2)
            t1 = time.perf_counter()
            jax.block_until_ready((d1, d2))
            t2 = time.perf_counter()
            _, flow = exe(engine._variables, d1, d2)
            t3 = time.perf_counter()
            jax.block_until_ready(flow)
            t4 = time.perf_counter()
            rows.append((t1 - t0, t2 - t0, t3 - t2, t4 - t2))
        cols = [1e3 * statistics.median(c) for c in zip(*rows[1:])]
    finally:
        engine.stop()
    return dict(zip(("put_returns_ms", "put_ready_ms", "launch_ms",
                     "programs_ms"), cols))


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--repeats", type=int, default=30)
    args = p.parse_args()
    import jax

    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    enable_persistent_compile_cache()
    for name, small in (("raft_full", False), ("raft_small", True)):
        out = probe(small, args.seed, args.repeats)
        print(json.dumps({"config": name, "repeats": args.repeats,
                          "device": jax.devices()[0].device_kind, **out}),
              flush=True)


if __name__ == "__main__":
    main()
