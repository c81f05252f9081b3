"""Per-kernel microbench: fused Pallas kernels vs their unfused arms.

Benchmarks the two default-off fused kernels in isolation, outside
the full model step:

- ``lookup_encoder`` — ``ops/pallas_corr.pallas_pyramid_lookup_encode``
  (quantized pyramid lookup + motion-encoder convc1 + relu in one
  kernel) vs the stock ``pallas_pyramid_lookup`` followed by the XLA
  1x1 conv, the exact pair ``RAFTConfig.fused_lookup_encoder`` toggles.
- ``gru`` — ``ops/pallas_gru.gru_gate_rh``/``gru_gate_blend`` gate
  chains around the XLA convs vs the all-XLA ConvGRU cell, the pair
  ``RAFTConfig.fused_gru`` toggles.

Both arms of each kernel land in ONE JSON line (metric / value / unit /
vs_baseline); per-kernel timings and speedups go under
``config.kernels``.

``--tiny``: CPU interpret-mode smoke (tiny shapes, 1 rep) wired into
the test tier (tests/test_bench_kernels.py)::

    JAX_PLATFORMS=cpu python scripts/bench_kernels.py --tiny
    python scripts/bench_kernels.py --image 368x496 --batch 8
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_MODEL_DIMS = {
    # levels, radius, convc1 out features, GRU hidden, GRU x-input dim
    "full": dict(levels=4, radius=4, features=256, hidden=128, xdim=256),
    "small": dict(levels=4, radius=3, features=96, hidden=96, xdim=146),
}


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="RAFT-TPU fused-kernel microbenchmark")
    p.add_argument("--image", default="368x496",
                   help="full-res HxW (kernels run at 1/8 resolution)")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--model", choices=sorted(_MODEL_DIMS), default="full")
    p.add_argument("--corr-dtype", default="float32",
                   choices=["float32", "bfloat16", "int8"],
                   help="pyramid storage dtype for lookup_encoder")
    p.add_argument("--kernels", default="lookup_encoder,gru",
                   help="comma list: lookup_encoder,gru")
    p.add_argument("--reps", type=int, default=20)
    p.add_argument("--warmup", type=int, default=3)
    p.add_argument("--interpret", action="store_true",
                   help="force Pallas interpreter (any backend)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--tiny", action="store_true",
                   help="CPU interpret smoke preset (tiny shape, 1 rep)")
    args = p.parse_args(argv)
    if args.tiny:
        args.image = "64x128"   # 1/8 res 8x16 -> exactly one 128-query block
        args.batch = 1
        args.model = "small"
        args.reps = 1
        args.warmup = 0
        args.interpret = True
    return args


def _time_ms(fn, reps, warmup):
    """Median wall ms of ``fn()`` (jitted; blocks on the result)."""
    import jax

    for _ in range(warmup):
        jax.block_until_ready(fn())
    times = []
    for _ in range(max(reps, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        times.append((time.perf_counter() - t0) * 1e3)
    times.sort()
    return times[len(times) // 2]


def _bench_lookup_encoder(args, h8, w8, dims, interpret):
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.corr import build_corr_pyramid_flat
    from raft_tpu.ops.pallas_corr import (pallas_pyramid_lookup,
                                          pallas_pyramid_lookup_encode,
                                          pallas_pyramid_lookup_quantized)
    from raft_tpu.ops.sampler import coords_grid

    B, r, L, F = args.batch, dims["radius"], dims["levels"], dims["features"]
    kk = L * (2 * r + 1) ** 2
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    f1 = jax.random.normal(keys[0], (B, h8, w8, 256), jnp.float32)
    f2 = jax.random.normal(keys[1], (B, h8, w8, 256), jnp.float32)
    pyr = build_corr_pyramid_flat(f1, f2, L, out_dtype=args.corr_dtype)
    coords = coords_grid(B, h8, w8) + jax.random.uniform(
        keys[2], (B, h8, w8, 2), minval=-2.0, maxval=2.0)
    w = jax.random.normal(keys[3], (kk, F), jnp.float32) * kk ** -0.5
    b = jnp.zeros((F,), jnp.float32)
    lookup = (pallas_pyramid_lookup_quantized
              if args.corr_dtype == "int8" else pallas_pyramid_lookup)

    @jax.jit
    def unfused(coords, w, b):
        taps = lookup(pyr, coords, r, interpret=interpret)
        return jax.nn.relu(
            jnp.einsum("bhwk,kf->bhwf", taps, w) + b)

    @jax.jit
    def fused(coords, w, b):
        return pallas_pyramid_lookup_encode(
            pyr, coords, w, b, r, 128, interpret)

    # AOT-compile each arm once: the SAME executable is timed and
    # cost-queried, so flops/bytes come free at compile time
    # (obs/cost.py).  Analytic twin: the kernel's hand-derived formula,
    # what real-TPU custom_call arms fall back to.
    from raft_tpu.obs.cost import analytic_lookup_encode_cost

    unfused_c = unfused.lower(coords, w, b).compile()
    fused_c = fused.lower(coords, w, b).compile()
    level_hw = [(max(h8 >> lv, 1), max(w8 >> lv, 1)) for lv in range(L)]
    analytic = analytic_lookup_encode_cost(
        B, level_hw, h8 * w8, r, F,
        pyramid_bytes=jnp.dtype(args.corr_dtype).itemsize)
    return {
        "unfused_ms": _time_ms(lambda: unfused_c(coords, w, b),
                               args.reps, args.warmup),
        "fused_ms": _time_ms(lambda: fused_c(coords, w, b),
                             args.reps, args.warmup),
    }, (unfused_c, fused_c, analytic)


def _bench_gru(args, h8, w8, dims, interpret):
    import jax
    import jax.numpy as jnp

    from raft_tpu.ops.pallas_gru import gru_gate_blend, gru_gate_rh

    B, hid, xdim = args.batch, dims["hidden"], dims["xdim"]
    cin = hid + xdim
    keys = jax.random.split(jax.random.PRNGKey(args.seed + 1), 6)
    hstate = jnp.tanh(jax.random.normal(keys[0], (B, h8, w8, hid)))
    x = jax.random.normal(keys[1], (B, h8, w8, xdim))
    wzr = jax.random.normal(keys[2], (3, 3, cin, 2 * hid)) * cin ** -0.5
    bzr = jax.random.normal(keys[3], (2 * hid,)) * 0.01
    wq = jax.random.normal(keys[4], (3, 3, cin, hid)) * cin ** -0.5
    bq = jax.random.normal(keys[5], (hid,)) * 0.01

    def _conv(v, w, b):
        return jax.lax.conv_general_dilated(
            v, w, (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC")) + b

    @jax.jit
    def unfused(hstate, x):
        zr = jax.nn.sigmoid(_conv(
            jnp.concatenate([hstate, x], -1), wzr, bzr))
        z, rg = jnp.split(zr, 2, axis=-1)
        q = jnp.tanh(_conv(
            jnp.concatenate([rg * hstate, x], -1), wq, bq))
        return (1 - z) * hstate + z * q

    @jax.jit
    def fused(hstate, x):
        zr_raw = _conv(jnp.concatenate([hstate, x], -1), wzr, bzr)
        z_raw, r_raw = jnp.split(zr_raw, 2, axis=-1)
        q_raw = _conv(jnp.concatenate(
            [gru_gate_rh(r_raw, hstate, interpret), x], -1), wq, bq)
        return gru_gate_blend(z_raw, q_raw, hstate, interpret)

    from raft_tpu.obs.cost import analytic_gru_gate_cost

    unfused_c = unfused.lower(hstate, x).compile()
    fused_c = fused.lower(hstate, x).compile()
    # Analytic cost of the two Pallas gate kernels only (the 3x3 convs
    # around them are XLA-counted even on TPU; the custom_call bodies
    # are what XLA can't see there).
    gshape = (B, h8, w8, hid)
    rh = analytic_gru_gate_cost(gshape, "rh")
    blend = analytic_gru_gate_cost(gshape, "blend")
    analytic = (rh[0] + blend[0], rh[1] + blend[1])
    return {
        "unfused_ms": _time_ms(lambda: unfused_c(hstate, x),
                               args.reps, args.warmup),
        "fused_ms": _time_ms(lambda: fused_c(hstate, x),
                             args.reps, args.warmup),
    }, (unfused_c, fused_c, analytic)


def main(argv=None):
    args = parse_args(argv)

    import jax

    from raft_tpu.obs import cost as cost_mod

    h, w = (int(x) for x in args.image.lower().split("x"))
    h8, w8 = h // 8, w // 8
    dims = _MODEL_DIMS[args.model]
    interpret = True if args.interpret else None

    bench_fns = {"lookup_encoder": _bench_lookup_encoder,
                 "gru": _bench_gru}
    kernels = {}
    for name in args.kernels.split(","):
        name = name.strip()
        if not name:
            continue
        if name not in bench_fns:
            raise SystemExit(f"unknown kernel {name!r}; "
                             f"choose from {sorted(bench_fns)}")
        rec, (unfused_c, fused_c, analytic) = bench_fns[name](
            args, h8, w8, dims, interpret)
        rec["speedup"] = round(
            rec["unfused_ms"] / max(rec["fused_ms"], 1e-9), 3)
        rec["unfused_ms"] = round(rec["unfused_ms"], 4)
        rec["fused_ms"] = round(rec["fused_ms"], 4)
        # Per-arm cost accounting (obs/cost.py): XLA's count where it
        # sees the body (interpret mode, unfused arm), the analytic
        # formula on real-TPU custom_call arms; MFU only on known
        # device peaks and non-interpret timings.
        interp = bool(args.interpret)
        for arm, exe in (("unfused", unfused_c), ("fused", fused_c)):
            pc = cost_mod.program_cost(
                exe, program=f"kernel_{name}_{arm}",
                pairs_per_call=args.batch, interpret=interp,
                analytic=analytic if arm == "fused" else None)
            rec[f"{arm}_flops"] = pc.flops
            rec[f"{arm}_bytes"] = pc.bytes
            rec[f"{arm}_cost_source"] = pc.source
            if arm == "fused":
                rec["flops_per_pair"] = pc.flops_per_pair
                secs = rec["fused_ms"] / 1e3
                at = pc.achieved_tflops(secs)
                rec["achieved_tflops"] = (round(at, 4)
                                          if at is not None else None)
                m = pc.mfu(secs)
                rec["mfu"] = round(m, 4) if m is not None else None
                rec["bound_by"] = pc.bound_by
                rec["analytic_flops"], rec["analytic_bytes"] = analytic
        kernels[name] = rec

    print(json.dumps({
        "metric": "kernel_fused_speedup_min",
        "value": min(k["speedup"] for k in kernels.values()),
        "unit": "x",
        # No external per-kernel baseline; the unfused arm in config IS
        # the comparison (speedup 1.0 == parity with unfused).
        "vs_baseline": 0.0,
        "config": {
            "device_kind": jax.devices()[0].device_kind,
            "interpret": bool(args.interpret),
            "image": [h, w], "batch": args.batch, "model": args.model,
            "corr_dtype": args.corr_dtype, "reps": args.reps,
            "tiny": bool(args.tiny),
            "kernels": kernels,
        },
    }))


if __name__ == "__main__":
    main()
