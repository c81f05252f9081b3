"""Input-pipeline microbench: decode+stack+H2D pairs/sec, prefetch A/B.

Measures the training INPUT path in isolation — per-sample "decode"
(synthetic, optionally slowed to model IO-bound storage), per-batch
stacking, optional noise prep, and the sharded ``device_put`` — driven
through :class:`raft_tpu.data.prefetch.DevicePipeline` by a consumer
whose synthetic "device step" sleeps ``--step-ms``.  One run measures
both arms: the overlapped pipeline at ``--depth`` and the serial path
(depth 0), so the JSON line answers "what does background device
prefetch buy at this shape?" without a second invocation.

Prints ONE JSON line (metric / value / unit / vs_baseline) on a
per-stage metric name built from ``scripts/telemetry_summary.py``'s
stage table, so both series name a crop alike.  ``value`` is the
overlapped arm's pairs/sec; the serial arm and the queue-wait split
land in ``config``.

``--tiny``: CPU smoke preset (tiny shapes, few batches, fake step) so
the pipeline stays testable without hardware; wired into the test tier
(tests/test_prefetch.py)::

    JAX_PLATFORMS=cpu python scripts/bench_input.py --tiny
    python scripts/bench_input.py --slow-ms 20   # IO-bound loader model
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="RAFT-TPU input-pipeline microbenchmark")
    p.add_argument("--image", default="368x496",
                   help="HxW batch shape (chairs crop default)")
    p.add_argument("--batch", type=int, default=16,
                   help="per-host batch size")
    p.add_argument("--batches", type=int, default=30,
                   help="batches measured per arm")
    p.add_argument("--depth", type=int, default=2,
                   help="device-prefetch depth of the overlapped arm")
    p.add_argument("--step-ms", type=float, default=None,
                   help="synthetic consumer step time; default = 0 "
                        "(drain at full speed: the pure pipeline "
                        "throughput bound).  Set it near your real "
                        "step time to read steady-state queue wait")
    p.add_argument("--slow-ms", type=float, default=0.0,
                   help="synthetic per-batch decode delay (slow-loader "
                        "mode: models IO-bound storage)")
    p.add_argument("--noise", action="store_true",
                   help="include the gaussian-noise host prep stage")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="CPU smoke preset (tiny shape, few batches)")
    args = p.parse_args(argv)
    if args.tiny:
        args.image = "32x48"
        args.batch = 8   # divisible by the test env's 8 virtual devices
        args.batches = 8
        args.depth = 2
        args.step_ms = 2.0 if args.step_ms is None else args.step_ms
        args.noise = True
    if args.step_ms is None:
        args.step_ms = 0.0
    return args


def _sample_stream(n_batches, batch, hw, seed, slow_s):
    """Synthetic decoded samples -> stacked host batches.

    Per-sample arrays are generated up front (one template mutated per
    index — we are benchmarking stack+prep+H2D, not numpy's RNG) and
    stacked per batch like ``ShardedLoader.batches`` does; ``slow_s``
    sleeps per BATCH to model an IO-bound decode stage."""
    import numpy as np

    H, W = hw
    rng = np.random.default_rng(seed)
    base = {
        "image1": rng.uniform(0, 255, (H, W, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (H, W, 3)).astype(np.float32),
        "flow": (8 * rng.standard_normal((H, W, 2))).astype(np.float32),
        "valid": np.ones((H, W), np.float32),
    }
    for i in range(n_batches):
        if slow_s > 0:
            time.sleep(slow_s)
        samples = []
        for j in range(batch):
            s = {k: v.copy() for k, v in base.items()}
            s["image1"][0, 0, 0] = float(i * batch + j)  # unique content
            samples.append(s)
        yield {k: np.stack([s[k] for s in samples]) for k in base}


def _run_arm(args, hw, depth, put_fn, prep_fn):
    """Drive one pipeline arm; returns (pairs_per_sec, stats dict)."""
    from raft_tpu.data.prefetch import DevicePipeline

    step_s = args.step_ms / 1e3
    pipe = DevicePipeline(
        _sample_stream(args.batches, args.batch, hw, args.seed,
                       args.slow_ms / 1e3),
        put_fn=put_fn, prep_fn=prep_fn, depth=depth)
    waits = []
    t0 = time.perf_counter()
    try:
        for _ in range(args.batches):
            t = time.perf_counter()
            batch = next(pipe)
            waits.append(time.perf_counter() - t)
            del batch
            if step_s > 0:
                time.sleep(step_s)  # the synthetic "device step"
        dt = time.perf_counter() - t0
    finally:
        pipe.close()
    # Steady state: drop the fill of the first `depth + 1` batches.
    steady = waits[depth + 1:] or waits
    return args.batches * args.batch / dt, {
        "pairs_per_sec": round(args.batches * args.batch / dt, 3),
        "queue_wait_mean_s": round(sum(steady) / len(steady), 6),
        "h2d_total_s": round(pipe.h2d_total_s, 6),
        "prep_total_s": round(pipe.prep_total_s, 6),
    }


def main(argv=None):
    args = parse_args(argv)

    import jax

    from raft_tpu.parallel.mesh import make_batch_sharder, make_mesh
    from raft_tpu.train.loop import add_image_noise
    from scripts.telemetry_summary import _stage_name

    h, w = (int(x) for x in args.image.lower().split("x"))
    mesh = make_mesh()
    put_fn = make_batch_sharder(mesh)

    def make_prep():
        if not args.noise:
            return None
        import numpy as np

        rng = np.random.default_rng(args.seed + 1)
        return lambda b: add_image_noise(rng, b)

    _, warm = _run_arm(args, (h, w), 0, put_fn, make_prep())  # compile/alloc warmup
    value, overlapped = _run_arm(args, (h, w), args.depth, put_fn,
                                 make_prep())
    _, serial = _run_arm(args, (h, w), 0, put_fn, make_prep())
    del warm

    print(json.dumps({
        "metric": f"input_pipeline_{_stage_name(h, w)}_{h}x{w}",
        "value": round(value, 3),
        "unit": "image-pairs/sec",
        # No external input-pipeline baseline exists (the reference's
        # torch DataLoader was never measured in isolation); the serial
        # arm in config IS the comparison.
        "vs_baseline": 0.0,
        "config": {
            "image_size": [h, w], "batch": args.batch,
            "batches": args.batches, "depth": args.depth,
            "step_ms": args.step_ms, "slow_ms": args.slow_ms,
            "noise": args.noise, "devices": jax.device_count(),
            "overlapped": overlapped, "serial": serial,
            "overlap_speedup": round(
                overlapped["pairs_per_sec"]
                / max(serial["pairs_per_sec"], 1e-9), 3),
        },
    }))


if __name__ == "__main__":
    main()
