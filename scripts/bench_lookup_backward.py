"""The differentiated lookup's backward alone on the chip: the dense volume
cotangent (``ops/pallas_corr.py _pyr_bwd``, XLA's sum over the iterations,
the volume's product backward) against the window kernels that take the
features' gradients directly (``pallas_pyramid_lookup_windows``).

For each geometry: device milliseconds of one call of the whole mechanism
-- ``iters`` lookups at drifting coordinates into a volume built from the
two maps, and the gradient of the taps' product with a fixed cotangent
with respect to both maps -- by each backward, read from a profiler trace
(the union of the device's operations); the window kernels alone (one
``window_grads`` call) at each row count and query block of the sweep;
the gap between the two paths' gradients, and of each to a float32
volume's through XLA's lookup on the first pair; and what
``lookup_backward_path`` chooses there.  One JSON line on stdout::

    python scripts/bench_lookup_backward.py                # both cells
    python scripts/bench_lookup_backward.py --geometry 2x24x32x32x1x6x1
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# batch x h x w x channels x levels x iterations x bf16 maps (1) or f32 (0):
# arch 'unimatch''s refinement volume at 1/4 of a 384x512 crop, batch 8;
# RAFT-full's at 1/8 of a 368x496 crop, batch 16
GEOMETRIES = ("8x96x128x128x1x6x1", "16x46x62x256x4x12x0")


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--geometry", action="append", default=None)
    p.add_argument("--calls", type=int, default=5)
    p.add_argument("--rows", default="4,8,16",
                   help="row counts of the window kernels to sweep")
    p.add_argument("--blocks", default="128,256",
                   help="query blocks of the window kernels to sweep")
    args = p.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import trace as btrace
    from raft_tpu.ops import pallas_corr
    from raft_tpu.ops.corr import (build_corr_pyramid,
                                   build_corr_pyramid_flat, corr_lookup,
                                   pool_fmap_pyramid)
    from raft_tpu.ops.sampler import coords_grid

    def timed(fn, *operands):
        fn = jax.jit(fn)
        out = jax.block_until_ready(fn(*operands))
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        wall = (time.perf_counter() - t0) / args.calls
        d = tempfile.mkdtemp(prefix="lb_trace_")
        jax.profiler.start_trace(d)
        for _ in range(args.calls):
            out = fn(*operands)
        jax.block_until_ready(out)
        jax.profiler.stop_trace()
        r = btrace.reduce_trace(d, 1, top=6)
        shutil.rmtree(d, ignore_errors=True)
        if r is None:      # no device plane: off the chip, no device time
            return out, {"wall_ms": wall * 1e3}
        return out, {"device_ms": r["busy_s"] / args.calls * 1e3,
                     "wall_ms": wall * 1e3,
                     "ops_ms": [[name, s / args.calls * 1e3]
                                for name, s in r["device_ops"]]}

    defaults = pallas_corr._WIN_ROWS, pallas_corr._WIN_BLOCK_Q
    result = {"device": jax.devices()[0].device_kind, "cells": []}
    for geo in args.geometry or GEOMETRIES:
        B, h, w, C, L, iters, half = (int(x) for x in geo.split("x"))
        dt = jnp.bfloat16 if half else jnp.float32
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        f1 = jax.random.normal(keys[0], (B, h, w, C), jnp.float32).astype(dt)
        f2 = jax.random.normal(keys[1], (B, h, w, C), jnp.float32).astype(dt)
        yy, xx = jnp.meshgrid(jnp.arange(h), jnp.arange(w), indexing="ij")
        # a smooth flow of a few pixels and a little noise, drifting
        flow = 4.0 * jnp.stack([jnp.sin(2 * jnp.pi * (xx / w + yy / h)),
                                jnp.cos(2 * jnp.pi * (2 * xx / w - yy / h))],
                               -1)
        noise = 0.5 * jax.random.normal(keys[2], (iters, B, h, w, 2))
        coords = (coords_grid(B, h, w)[None] + flow[None, None] + noise
                  + 0.3 * jnp.arange(iters)[:, None, None, None, None])
        k = 9
        g = jax.random.normal(keys[3], (iters, B, h, w, L * k * k),
                              jnp.float32).astype(jnp.bfloat16)

        def build(a, b):
            return build_corr_pyramid_flat(a, b, L, "highest", pad_q=128,
                                           out_dtype=jnp.bfloat16)

        def volume(a, b):
            pyr = build(a, b)
            return sum(jnp.sum(pallas_corr.pallas_pyramid_lookup(
                pyr, coords[i], 4, 128, None, jnp.bfloat16)
                .astype(jnp.float32) * g[i]) for i in range(iters))

        def windows(a, b):
            # as models/raft.py _build_corr_state builds it: the maps in
            # float32 beside a volume of the stopped maps
            sg = jax.lax.stop_gradient
            pyr = build(sg(a), sg(b))
            f32 = jnp.float32
            a32 = a.astype(f32)
            levels = [lv.astype(f32) for lv in pool_fmap_pyramid(b, L)]
            return sum(jnp.sum(pallas_corr.pallas_pyramid_lookup_windows(
                pyr, a32, levels, coords[i], 4, 128, None,
                jnp.bfloat16, bool(half))
                .astype(jnp.float32) * g[i]) for i in range(iters))

        cell = {"geometry": [B, h, w, C, L, iters, dt.__name__],
                "path_chosen": pallas_corr.lookup_backward_path(
                    jax.default_backend(),
                    [(B, h >> lvl, w >> lvl, -(-h * w // 128) * 128)
                     for lvl in range(L)], 4, C, 2, 2 if half else 4)}
        grads = {}
        for name, fn in (("volume", volume), ("windows", windows)):
            out, t = timed(jax.grad(fn, argnums=(0, 1)), f1, f2)
            grads[name] = out
            cell[name] = t
        cell["gap"] = [
            float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)
                         ).max() / np.abs(np.asarray(b, np.float32)).max())
            for a, b in zip(grads["windows"], grads["volume"])]

        def exact(a, b):
            # float32 volume and XLA's lookup, the first pair alone
            pyr = build_corr_pyramid(a, b, L, "highest")
            return sum(jnp.sum(corr_lookup(pyr, coords[i, :1], 4,
                                           "highest")
                               * g[i, :1].astype(jnp.float32))
                       for i in range(iters))

        want = jax.jit(jax.grad(exact, argnums=(0, 1)))(
            f1[:1].astype(jnp.float32), f2[:1].astype(jnp.float32))
        cell["gap_to_float32"] = {
            name: [float(np.abs(np.asarray(a[:1], np.float32)
                                - np.asarray(b)).max()
                         / np.abs(np.asarray(b)).max())
                   for a, b in zip(grads[name], want)]
            for name in grads}
        f1_32 = f1.astype(jnp.float32)
        levels = [lv.astype(jnp.float32) for lv in pool_fmap_pyramid(f2, L)]
        sweep = {}
        for rows in (int(x) for x in args.rows.split(",")):
            for bq in (int(x) for x in args.blocks.split(",")):
                pallas_corr._WIN_ROWS = rows
                pallas_corr._WIN_BLOCK_Q = bq

                def one(a, lv, c, gg):
                    # a bfloat16 volume's D, rounded once as in the model
                    return pallas_corr.window_grads(
                        a, lv, c, gg, 4, d_halves=False,
                        bf16_values=bool(half))

                _, t = timed(one, f1_32, levels, coords[0], g[0])
                t.pop("ops_ms", None)
                sweep[f"rows{rows}_bq{bq}"] = t
        pallas_corr._WIN_ROWS, pallas_corr._WIN_BLOCK_Q = defaults
        cell["window_kernels"] = sweep
        # the window kernels' worst case: centres anywhere on the map, so
        # every query block reaches every row
        far = jax.random.uniform(keys[2], (B, h, w, 2)) * jnp.asarray(
            [w, h], jnp.float32)
        _, t = timed(one, f1_32, levels, far, g[0])
        t.pop("ops_ms", None)
        cell["window_kernels_scattered"] = t
        result["cells"].append(cell)
        print(json.dumps(cell), file=sys.stderr, flush=True)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
