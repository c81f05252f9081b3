"""Spatially-sharded beyond-HBM TRAINING, demonstrated virtually.

VERDICT r3 ask #2: real multi-chip hardware is unavailable in this
container, so prove the §5 long-context story end-to-end on the virtual
CPU mesh:

1. At a REAL beyond-HBM shape (1088x1920, the round-3 single-chip
   blocker), AOT-compile the FULL training step and read XLA's buffer
   assignment (``memory_analysis``): the single-device peak exceeds the
   16 GB v5e HBM budget, while the ``--shard_spatial`` form's
   *per-device* peak fits — GSPMD splits the activations, the on-demand
   correlation query rows, and the conv halos across the ``spatial``
   mesh axis, which is exactly how a pod trains frames one chip cannot
   hold.  (Compile-only: one host CPU core cannot execute a 1088x1920
   step in reasonable time; the buffer assignment is the same object
   the TPU runtime allocates.)
2. EXECUTE one spatially-sharded training step at a scaled shape with
   the identical mesh/sharding config and assert a finite loss
   (sharded == unsharded numerics are pinned separately by
   tests/test_spatial_shard.py).

The on-demand path here is ``corr_impl='chunked'`` (the XLA blockwise
lookup, SURVEY C5) because the Pallas kernels would run in interpret
mode on a CPU mesh; the sharding partition — query rows over
``spatial`` — is identical for ``'pallas'``, whose single-chip beyond-
HBM training is certified on hardware in BENCH_BEYOND_HBM_r04.json.

Reference analog: ``--alternate_corr`` + DataParallel
(/root/reference/README.md:75-80, train.py:138) — which could shard
batch but never the frame; spatial sharding is the TPU-native extension
that actually covers beyond-HBM frames.

Usage: python scripts/shard_beyond_hbm.py [--out SHARD_BEYOND_HBM.json]
"""

from __future__ import annotations

import argparse
import json
import os
import os.path as osp
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

V5E_HBM_GB = 16.0   # spec fallback when no measured artifact exists


def _hbm_limit():
    """Measured limit from HBM_LIMIT.json (scripts/hbm_limit.py, run on
    the TPU) when available; the v5e spec constant otherwise (the spec
    overstates headroom by the runtime's own reservation, VERDICT r4
    weak #4).  Shared validation lives in profiling.load_hbm_limit."""
    from raft_tpu.utils.profiling import load_hbm_limit

    limit, src = load_hbm_limit(default_gb=V5E_HBM_GB)
    if src.startswith("no "):
        src = "v5e spec constant (" + src + ")"
    return limit, src


def _setup_cpu_mesh(n_devices: int) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def _make_step(H, W, num_spatial, iters, corr_impl="chunked"):
    import jax

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh, shard_batch
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    mesh = make_mesh(num_data=1, num_spatial=num_spatial,
                     devices=jax.devices()[:num_spatial])
    # scan_unroll=1: at beyond-HBM shapes each iteration is O(100ms+) of
    # device work, so unroll buys nothing and the 12x graph is brutal to
    # compile.
    model_cfg = RAFTConfig.full(compute_dtype="bfloat16",
                                corr_impl=corr_impl, remat=True,
                                remat_policy="save_corr", scan_unroll=1)
    cfg = TrainConfig(num_steps=1000, batch_size=1, image_size=(H, W),
                     iters=iters)
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    state = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
    spatial = num_spatial > 1
    step_fn = make_train_step(model, tx, cfg, mesh, donate=False,
                              shard_spatial=spatial)

    def batch_for(rng):
        import numpy as np

        return shard_batch({
            "image1": rng.uniform(0, 255, (1, H, W, 3)).astype(np.float32),
            "image2": rng.uniform(0, 255, (1, H, W, 3)).astype(np.float32),
            "flow": (8 * rng.standard_normal((1, H, W, 2))).astype(
                np.float32),
            "valid": np.ones((1, H, W), np.float32),
        }, mesh, spatial=spatial)

    return step_fn, state, batch_for


def analyze(H, W, num_spatial, iters=12):
    """Per-device HBM peak of the compiled training step (no execution)."""
    import jax
    import numpy as np

    from raft_tpu.utils.profiling import hbm_usage

    step_fn, state, batch_for = _make_step(H, W, num_spatial, iters)
    batch = batch_for(np.random.default_rng(0))
    key = jax.random.PRNGKey(1)
    t0 = time.perf_counter()
    usage = hbm_usage(step_fn, state, batch, key)
    usage.update({
        "shape": f"{H}x{W}", "num_spatial": num_spatial, "iters": iters,
        "compile_s": round(time.perf_counter() - t0, 1),
    })
    if "temp_gb" in usage:
        # The CPU backend's peak_memory_in_bytes is not populated the way
        # the TPU one is (reports ~0.2 GB against a 12 GB temp), so the
        # per-device footprint here is args + outputs + temps — on the
        # real chip (BENCH_BEYOND_HBM_r04.json) peak tracks that sum to
        # within ~1%.
        usage["footprint_gb"] = round(
            usage["args_gb"] + usage["output_gb"] + usage["temp_gb"], 3)
        limit, _src = _hbm_limit()
        usage["fits_hbm_limit"] = bool(usage["footprint_gb"] < limit)
        usage["headroom_pct"] = round(
            100.0 * (1.0 - usage["footprint_gb"] / limit), 1)
    return usage


def run_scaled(H, W, num_spatial, iters=4):
    """Actually execute one sharded step at a scaled shape."""
    import numpy as np

    import jax

    step_fn, state, batch_for = _make_step(H, W, num_spatial, iters)
    batch = batch_for(np.random.default_rng(0))
    t0 = time.perf_counter()
    state, metrics = step_fn(state, batch, jax.random.PRNGKey(1))
    loss = float(metrics["loss"])
    return {
        "shape": f"{H}x{W}", "num_spatial": num_spatial, "iters": iters,
        "executed": True, "loss": round(loss, 4),
        "loss_finite": bool(np.isfinite(loss)),
        "wall_s": round(time.perf_counter() - t0, 1),
    }


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="SHARD_BEYOND_HBM.json")
    ap.add_argument("--spatial", type=int, default=4)
    args = ap.parse_args(argv)
    _setup_cpu_mesh(max(args.spatial, 16))  # spatial8/16 cases need 16

    limit, src = _hbm_limit()
    results = {"hbm_limit_gb": limit, "hbm_limit_source": src}
    for name, fn in [
        # 1088x1920 is single-chip-trainable when configured well (the
        # r04 TPU run: 12.7 GB peak with corr_impl='pallas', unroll 1);
        # sharding still cuts the footprint ~4x.  2176x3840 (4K-class)
        # is the shape NO single v5e chip can train — and spatial=4
        # brings it back under the 16 GB budget.
        ("single_device_1088x1920",
         lambda: analyze(1088, 1920, num_spatial=1)),
        (f"spatial{args.spatial}_1088x1920",
         lambda: analyze(1088, 1920, num_spatial=args.spatial)),
        ("single_device_1440x2560",
         lambda: analyze(1440, 2560, num_spatial=1)),
        (f"spatial{args.spatial}_1440x2560",
         lambda: analyze(1440, 2560, num_spatial=args.spatial)),
        ("single_device_2176x3840",
         lambda: analyze(2176, 3840, num_spatial=1)),
        (f"spatial{args.spatial}_2176x3840",
         lambda: analyze(2176, 3840, num_spatial=args.spatial)),
        ("spatial8_2176x3840",
         lambda: analyze(2176, 3840, num_spatial=8)),
        # spatial=16: the 4K datapoint with real headroom even against a
        # conservatively-measured limit (VERDICT r4 weak #4 asked for
        # >=10% headroom at spatial=8 OR this datapoint).
        ("spatial16_2176x3840",
         lambda: analyze(2176, 3840, num_spatial=16)),
        ("executed_spatial2_272x480",
         lambda: run_scaled(272, 480, num_spatial=2)),
    ]:
        try:
            results[name] = fn()
        except Exception as e:  # record honestly
            results[name] = {"error": f"{type(e).__name__}: {str(e)[:300]}"}
        print(name, "->", json.dumps(results[name]), flush=True)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"-> {args.out}", flush=True)


if __name__ == "__main__":
    main()
