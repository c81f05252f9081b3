"""Benchmark: RAFT training throughput, image-pairs/sec/chip.

Runs the full jitted SPMD training step (forward + backward + AdamW update,
bf16 compute, 12 refinement iterations) on synthetic FlyingChairs-shaped
batches (reference train_standard.sh chairs stage: 368x496 crops) and
prints ONE JSON line.  Baseline: 30 image-pairs/sec/chip
(BASELINE.json north_star, v5e).
"""

from __future__ import annotations

import json
import time

import jax
import numpy as np

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.parallel.mesh import make_mesh, shard_batch
from raft_tpu.train.optim import make_optimizer
from raft_tpu.train.step import init_state, make_train_step

BASELINE_PAIRS_PER_SEC_PER_CHIP = 30.0

# Training-stage names for the reference curriculum's crop shapes
# (train_standard.sh).  One mapping shared by main() and the
# backend-failure handler so a failure record lands on the SAME metric
# series as the successful runs it stands in for (the old handler
# fell back to the raw "HxW" string where main() used "custom").
_STAGE_NAMES = {(368, 496): "flyingchairs", (400, 720): "flyingthings",
                (368, 768): "sintelstage", (288, 960): "kittistage"}


def _stage_name(h: int, w: int) -> str:
    return _STAGE_NAMES.get((h, w), "custom")


def _train_metric_name(h: int, w: int) -> str:
    return f"train_throughput_{_stage_name(h, w)}_{h}x{w}_bf16_iters12"


def _input_metric_name(h: int, w: int) -> str:
    """scripts/bench_input.py series — registered here next to the train
    metric so input-pipeline records land on one stable per-stage name
    (same sharing rule that keeps telemetry_summary.py from drifting)."""
    return f"input_pipeline_{_stage_name(h, w)}_{h}x{w}"


def bench_eval():
    """BENCH_MODE=eval: test-mode forward at the Sintel validation shape
    (436x1024 padded to 440x1024, 32 iters — reference evaluate.py:96),
    frames/sec on one chip."""
    import os

    H, W = 440, 1024
    iters = int(os.environ.get("BENCH_EVAL_ITERS", 32))
    # allpairs (XLA einsums) wins at eval shapes: Sintel's 1/8-res width
    # is 128 = a full lane tile, so the einsum contraction keeps the MXU
    # busy (allpairs_pallas's VPU cost scales with the larger Hl*Wl);
    # the Pallas kernel won at training crops (62-wide rows, see
    # main()).  The margins are not measured on today's code.
    cfg = RAFTConfig.full(
        compute_dtype="bfloat16",
        corr_impl=os.environ.get("BENCH_CORR_IMPL", "allpairs"))
    model = RAFT(cfg)
    rng = jax.random.PRNGKey(0)
    img = jax.random.uniform(rng, (1, H, W, 3), np.float32) * 255.0
    # Jitted tiny-shape init (conv params are size-independent; an
    # unjitted full-shape init dispatches and compiles op by op).
    small = jax.random.uniform(rng, (1, 64, 96, 3), np.float32)
    variables = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, small, small,
                             iters=2, train=False))(rng)

    # The real inference entry point (it pins scan_unroll=1 — the config
    # default tunes the training backward pass).
    from raft_tpu.evaluate import make_eval_fn

    fwd = make_eval_fn(cfg, iters)

    for _ in range(2):
        low, up = fwd(variables, img, img)
    jax.block_until_ready(up)
    n = 20
    t0 = time.perf_counter()
    for _ in range(n):
        low, up = fwd(variables, img, img)
    jax.block_until_ready(up)
    dt = time.perf_counter() - t0
    # Regression target: the round-3 figure at the DEFAULT config (32
    # iters, allpairs — BENCH_EVAL_r03.json; not measured on today's
    # code, the first benchmark PR replaces it); there
    # is no external eval baseline (the reference publishes none,
    # SURVEY §6), so our own best-known number is the bar and
    # vs_baseline < 1.0 means a regression (VERDICT r3 weak #7).  Only
    # meaningful at the pinned config: overrides (BENCH_EVAL_ITERS /
    # BENCH_CORR_IMPL) report 0.0 rather than a fake ratio.
    default_cfg = (iters == 32
                   and os.environ.get("BENCH_CORR_IMPL",
                                      "allpairs") == "allpairs")
    eval_target = 12.97 if default_cfg else None
    # Tuning-registry provenance of the eval arm (make_eval_fn consults
    # the 'eval' entries; this records whether one applied).
    from raft_tpu import tuning

    _, tinfo = tuning.resolve_config(cfg, ("eval",), (H, W), 1)
    # Work accounting off the already-compiled forward (the capture is
    # an AOT re-lower of the same jit — a cache hit, host-side only).
    cost = fwd.capture_cost(variables, img, img)
    frame_s = dt / n
    at = cost.achieved_tflops(frame_s)
    m = cost.mfu(frame_s)
    print(json.dumps({
        "metric": f"eval_forward_sintel_440x1024_bf16_iters{iters}",
        "value": round(n / dt, 3),
        "unit": "frames/sec/chip",
        "vs_baseline": (round(n / dt / eval_target, 3) if eval_target
                        else 0.0),
        "baseline_frames_per_sec": eval_target or "n/a (non-default cfg)",
        "config": dict(
            tinfo.stamp(),
            flops_per_pair=cost.flops_per_pair,
            achieved_tflops=round(at, 4) if at is not None else None,
            mfu=round(m, 4) if m is not None else None,
            bound_by=cost.bound_by, cost_source=cost.source),
    }))


def main():
    import os

    if os.environ.get("BENCH_MODE", "train") == "eval":
        bench_eval()
        return

    n_dev = jax.device_count()
    mesh = make_mesh(num_data=n_dev, num_spatial=1)

    # Default: chairs crop (train_standard.sh:3).  BENCH_IMAGE=400x720
    # benches the FlyingThings stage shape (BASELINE.json config 4).
    H, W = (int(x) for x in
            os.environ.get("BENCH_IMAGE", "368x496").split("x"))
    # Batch sweep (v5e, allpairs_pallas, unroll 3): 12 -> 17.5,
    # 16 -> 18.4; 24 regressed under the XLA path (HBM pressure).
    per_chip_batch = int(os.environ.get("BENCH_BATCH", 16))
    B = per_chip_batch * n_dev
    _defaults = RAFTConfig()
    # Bench-curated knob defaults (hand-tuned winners at the chairs
    # shape from before PR 1; their records were deleted in PR 22 and
    # none is measured on today's code): allpairs_pallas materialized
    # pyramid + fused Pallas sampling (pallas/chunked trade speed for
    # O((HW)^2) memory); remat/remat_upsample OFF win at this shape now
    # that the flat fused loss + query-minor pyramid freed the
    # activation memory (59.5 vs 55.8 round 2, 74.6 vs 73.9 round 3) —
    # the MODEL defaults stay remat-on, safe for big crops.
    knobs = {
        "corr_impl": "allpairs_pallas",
        "corr_precision": "highest",
        "corr_dtype": _defaults.corr_dtype,
        "remat": False,
        "remat_policy": _defaults.remat_policy,
        "scan_unroll": _defaults.scan_unroll,
        "lookup_block_q": _defaults.lookup_block_q,
        "remat_upsample": False,
        "upsample_group": _defaults.upsample_group,
        "upsample_unroll": _defaults.upsample_unroll,
        "upsample_dtype": _defaults.upsample_dtype,
        "fuse_upsample_in_scan": _defaults.fuse_upsample_in_scan,
        "upsample_loss_kernel": _defaults.upsample_loss_kernel,
    }
    # Knob resolution, highest precedence first: BENCH_* env (a hand-set
    # knob), then the per-hardware tuning registry (raft_tpu/tuning.py —
    # scripts/autotune.py winners for this (device, shape, batch)), then
    # the curated defaults above.  The emitted config says which
    # (tuned/tuning_key/tuning_registry_hash), so a series of records
    # is attributable to autotune vs hand-tuning.
    env_knobs = {
        "corr_impl": "BENCH_CORR_IMPL",
        "corr_precision": "BENCH_CORR_PRECISION",
        "corr_dtype": "BENCH_CORR_DTYPE",
        "remat": "BENCH_REMAT",
        "remat_policy": "BENCH_REMAT_POLICY",
        "scan_unroll": "BENCH_SCAN_UNROLL",
        "lookup_block_q": "BENCH_LOOKUP_BLOCK_Q",
        "remat_upsample": "BENCH_REMAT_UPSAMPLE",
        "upsample_group": "BENCH_UPSAMPLE_GROUP",
        "upsample_unroll": "BENCH_UPSAMPLE_UNROLL",
        "upsample_dtype": "BENCH_UPSAMPLE_DTYPE",
        "fuse_upsample_in_scan": "BENCH_FUSE_UPSAMPLE",
        "upsample_loss_kernel": "BENCH_UPSAMPLE_KERNEL",
    }
    _bools = {"remat", "remat_upsample", "fuse_upsample_in_scan"}
    _ints = {"scan_unroll", "lookup_block_q", "upsample_group",
             "upsample_unroll"}
    hand_set = {}
    for knob, env in env_knobs.items():
        if env in os.environ:
            raw = os.environ[env]
            hand_set[knob] = (raw == "1" if knob in _bools
                              else int(raw) if knob in _ints else raw)

    from raft_tpu import tuning

    tuning_stamp = {"tuned": False}
    if tuning.enabled():
        hit = tuning.lookup("train", (H, W), per_chip_batch)
        if hit is not None:
            key, entry, exact = hit
            for knob, value in entry.get("knobs", {}).items():
                if knob in knobs and knob not in hand_set:
                    knobs[knob] = value
            info = tuning.TuningInfo(
                tuned=True, key=key, exact=exact,
                registry_hash=tuning.registry_file_hash())
            tuning_stamp = info.stamp()
    knobs.update(hand_set)

    compute_dtype = os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16")
    model_cfg = RAFTConfig.full(compute_dtype=compute_dtype, **knobs)
    corr_impl, corr_precision = knobs["corr_impl"], knobs["corr_precision"]
    remat, remat_policy = knobs["remat"], knobs["remat_policy"]
    scan_unroll = knobs["scan_unroll"]
    cfg = TrainConfig(num_steps=1000, batch_size=B, image_size=(H, W),
                      iters=12)

    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    # Tiny-shape init: conv/GRU param shapes don't depend on image size,
    # and a full-shape init would trace the whole model a second time.
    state = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
    step_fn = make_train_step(model, tx, cfg, mesh)

    rng = np.random.default_rng(0)
    batch = shard_batch({
        "image1": rng.uniform(0, 255, (B, H, W, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (B, H, W, 3)).astype(np.float32),
        "flow": (8.0 * rng.standard_normal((B, H, W, 2))).astype(np.float32),
        "valid": np.ones((B, H, W), np.float32),
    }, mesh)
    key = jax.random.PRNGKey(1)

    # AOT-compile once: the SAME executable is timed below and queried
    # for compile-time FLOPs/bytes (raft_tpu/obs/cost.py) — work
    # accounting costs zero extra compiles and zero device syncs.
    from raft_tpu.train.step import step_cost

    compiled = step_fn.lower(state, batch, key).compile()
    cost = step_cost(compiled, B, n_dev)

    # Warmup + 2 steady-state steps, then a real device sync.
    for _ in range(3):
        state, metrics = compiled(state, batch, key)
    jax.block_until_ready(metrics["loss"])

    n_steps = 10
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = compiled(state, batch, key)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0

    pairs_per_sec_per_chip = n_steps * B / dt / n_dev
    # The 30 pairs/s/chip north star is defined for the chairs crop
    # (BASELINE.json); the ratio is meaningless for other shapes.
    vs = (pairs_per_sec_per_chip / BASELINE_PAIRS_PER_SEC_PER_CHIP
          if _stage_name(H, W) == "flyingchairs" else 0.0)
    # Hardware-normalized work figures: flops_per_pair is mesh-shape-
    # invariant (per-device flops over per-device pairs), MFU/bound_by
    # normalize throughput by the device peak (None on unknown peaks,
    # e.g. CPU — check_regression --min-mfu skips those records).
    step_s = dt / n_steps
    at = cost.achieved_tflops(step_s)
    m = cost.mfu(step_s)
    cost_fields = {
        "flops_per_pair": cost.flops_per_pair,
        "achieved_tflops": round(at, 4) if at is not None else None,
        "mfu": round(m, 4) if m is not None else None,
        "bound_by": cost.bound_by,
        "cost_source": cost.source,
    }
    print(json.dumps({
        "metric": _train_metric_name(H, W),
        "value": round(pairs_per_sec_per_chip, 3),
        "unit": "image-pairs/sec/chip",
        "vs_baseline": round(vs, 3),
        # Bench-config knobs that differ from the MODEL defaults (bench
        # defaults remat=0/remat_upsample=0, which won at this shape;
        # the model ships save_corr/remat_upsample=1 — safe for big
        # crops).  Recorded so BENCH_*.json A/Bs across rounds always
        # say what configuration they measured — including WHERE the
        # knobs came from: `tuned: true` + registry key + file hash
        # means autotune set them, `tuned: false` means hand-set/curated
        # defaults (scripts/check_regression.py --require-tuned gates
        # on this).
        "config": {"batch_per_chip": per_chip_batch, "corr_impl": corr_impl,
                   "corr_dtype": model_cfg.corr_dtype,
                   "remat": remat,
                   "remat_upsample": model_cfg.remat_upsample,
                   "scan_unroll": scan_unroll,
                   "fuse_upsample_in_scan": model_cfg.fuse_upsample_in_scan,
                   "upsample_loss_kernel": model_cfg.upsample_loss_kernel,
                   **cost_fields, **tuning_stamp},
    }))


if __name__ == "__main__":
    try:
        main()
    except RuntimeError as e:
        # JAX could not initialize the backend it was told to use (no
        # chip on this machine, or another process holds it): still
        # produce one parseable JSON line for the record instead of only
        # a traceback; exit nonzero so the failure is not mistaken for a
        # measurement.
        if "backend" not in str(e).lower():
            raise
        import os

        # Reconstruct the metric name of the series this run WOULD have
        # produced, so a driver aggregating BENCH_*.json can attach the
        # failure to the right series.
        if os.environ.get("BENCH_MODE", "train") == "eval":
            it = int(os.environ.get("BENCH_EVAL_ITERS", 32))
            metric = f"eval_forward_sintel_440x1024_bf16_iters{it}"
            unit = "frames/sec/chip"
        else:
            h, w = (int(x) for x in
                    os.environ.get("BENCH_IMAGE", "368x496").split("x"))
            metric = _train_metric_name(h, w)
            unit = "image-pairs/sec/chip"
        print(json.dumps({
            "metric": metric, "value": None, "unit": unit,
            "vs_baseline": None,
            "error": f"backend unavailable: {str(e)[:200]}",
        }))
        raise SystemExit(1)
