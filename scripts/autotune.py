"""Autotune the performance knob surface and persist winners per hardware.

The early rounds' fourfold gain on the chairs-crop train bench came from
HAND-tuning a handful of knobs (the records are gone — PR 22 — and
nothing is measured on today's code); this script makes that automatic and durable: it sweeps a seeded,
time-boxed cross-product of the ``RAFTConfig`` knob surface with
bench.py-style timing (synthetic batches, warmup + steady-state steps,
``perf_counter``) and writes the winner into the per-hardware tuning
registry (``raft_tpu/tuning.py``), keyed by ``(kind, device_kind,
bucket_hw, batch)``.  From then on every train/eval/serve entry point
that leaves its knobs at the defaults gets the tuned configuration on
this hardware with no human in the loop.

::

    python scripts/autotune.py                        # train, chairs crop
    python scripts/autotune.py --image 400x720 --batch-per-chip 8
    python scripts/autotune.py --kind eval            # test-mode forward
    python scripts/autotune.py --kind serve           # engine dispatcher
    python scripts/autotune.py --tiny                 # CPU smoke (tier-1)

``--kind serve`` sweeps the ServeConfig dispatcher surface (``batching``
mode, ``slots``, ``early_exit_threshold`` — raft_tpu/serve/engine.py)
through a real InferenceEngine on a closed-loop synthetic workload and
persists the winner as a ``kind='serve'`` entry the engine consumes via
``tuning.resolve_serve_config`` (request mode ignores the slot-mode
knobs, so those points collapse to one measurement).

A finished sweep records a ``sweep_id`` (hash of the grid + timing
parameters + code version); re-running the same sweep against the same
registry is a CACHE HIT and exits immediately (``--force`` re-measures).
``--tiny`` is the CI smoke: a 2-point sweep on a toy shape, registry
write, a second in-process invocation that must hit the cache, and a
tiny train step that must CONSUME the entry via ``make_train_step``'s
default registry consult — the full zero-hand-knobs loop in one run.

Quantized corr storage ('int8') is excluded from the default eval grid —
it trades accuracy bounded by the calibration scale, so gate it with
``python -m raft_tpu evaluate ... --epe_delta float32,int8`` first and
opt in with ``--allow-quantized`` (docs/PERFORMANCE.md).
"""

from __future__ import annotations

import argparse
import hashlib
import itertools
import json
import os
import os.path as osp
import random
import sys
import time

sys.path.insert(0, osp.dirname(osp.dirname(osp.abspath(__file__))))

_AUTOTUNE_VERSION = 1


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="sweep the RAFTConfig knob surface, persist the "
                    "winner in the per-hardware tuning registry")
    p.add_argument("--kind", default="train",
                   choices=["train", "eval", "serve"],
                   help="workload to tune: the jitted training step, "
                        "the test-mode eval forward, or the serving "
                        "engine's dispatcher knobs")
    p.add_argument("--image", default="368x496",
                   help="input HxW (the registry bucket key); default "
                        "is the chairs training crop")
    p.add_argument("--batch-per-chip", "--batch_per_chip", type=int,
                   default=16, help="per-device batch (registry key)")
    p.add_argument("--iters", type=int, default=None,
                   help="refinement iterations (default: 12 train / "
                        "32 eval)")
    p.add_argument("--steps", type=int, default=8,
                   help="timed steps per sweep point")
    p.add_argument("--warmup", type=int, default=2,
                   help="untimed warmup (compile) steps per point")
    p.add_argument("--time-box", type=float, default=900.0,
                   help="sweep wall-clock budget in seconds; points are "
                        "visited in seeded-shuffled order and the sweep "
                        "stops STARTING new points at the deadline (at "
                        "least one point always completes)")
    p.add_argument("--seed", type=int, default=0,
                   help="seed for the sweep-order shuffle and the "
                        "synthetic batch")
    p.add_argument("--out", default=None,
                   help="registry file (default: "
                        "$RAFT_TUNING_REGISTRY or "
                        "~/.cache/raft_tpu/tuning.json)")
    p.add_argument("--force", action="store_true",
                   help="re-measure even when the registry already "
                        "holds this exact sweep (same sweep_id)")
    p.add_argument("--allow-quantized", action="store_true",
                   help="include int8 corr storage in the eval grid "
                        "(run the EPE-delta gate first; "
                        "docs/PERFORMANCE.md)")
    p.add_argument("--tiny", action="store_true",
                   help="CPU smoke: 2-point sweep on a toy shape, "
                        "cache-hit re-invocation, and a tiny train "
                        "step consuming the written entry (tier-1)")
    p.add_argument("--seed-known", action="store_true",
                   help="no sweep: write the repo's hand-tuned winners "
                        "of rounds 1-4 (not measured on today's "
                        "code) into the registry for this "
                        "device, provenance-labeled as seeded — the "
                        "known-good starting table a real sweep later "
                        "re-measures (a seeded entry has no sweep_id, "
                        "so it is never a cache hit)")
    return p.parse_args(argv)


# The hand-tuned chairs-crop winners of rounds 1-4 (their record was
# deleted in PR 22).  `--seed-known` installs
# them as the registry's starting point on hardware nobody has swept
# yet; corr_impl 'allpairs_pallas' self-falls-back to 'allpairs' off-TPU
# (RAFTConfig.resolved_corr_impl), so one entry serves both backends.
_KNOWN_WINNERS = {
    ("train", (368, 496), 16): {
        "corr_impl": "allpairs_pallas",
        "corr_dtype": "auto",
        "corr_precision": "highest",
        "remat": False,
        "remat_upsample": False,
        "scan_unroll": 12,
        "fuse_upsample_in_scan": False,
        "upsample_loss_kernel": "xla",
    },
}


def seed_known(out=None):
    """Write the hand-tuned winners as seeded registry entries
    (provenance mode='seed-known')."""
    from raft_tpu import tuning

    keys = []
    for (kind, hw, batch), knobs in _KNOWN_WINNERS.items():
        keys.append(tuning.save_entry(
            kind, hw, batch, knobs,
            provenance={"tool": "scripts/autotune.py",
                        "mode": "seed-known",
                        "source": "hand-tuned winners of rounds 1-4; "
                                  "not measured on today's code"},
            path=out))
    return keys


def _grid(kind: str, tiny: bool, allow_quantized: bool):
    """The knob cross-product for one workload on this backend.

    Kept deliberately curated (not every RAFTConfig field): each axis
    here has MOVED a bench number in some round, which
    is what makes the cross-product worth its compile time."""
    import jax

    on_tpu = jax.default_backend() == "tpu"
    # The fused Pallas kernels (PR 13) are real sweep axes only where
    # they can dispatch; off-TPU they resolve to the unfused paths, so a
    # single-value axis keeps them visible in the grid (and the
    # sweep_id) without duplicating measurements.
    fused_axis = [False, True] if on_tpu else [False]
    if kind == "serve":
        if tiny:
            return {"batching": ["request", "slot"], "slots": [2]}
        return {
            "batching": ["request", "slot"],
            "slots": [4, 8, 16],
            "early_exit_threshold": [0.0, 0.05, 0.2],
            "fused_gru": fused_axis,
        }
    if tiny:
        # Keep the tiny sweep at 2 points: one fused knob rides the
        # sweep -> save_entry -> resolve_config loop (it resolves to
        # the unfused path on the CPU smoke backend — this is plumbing
        # coverage, not a kernel measurement).  fused_lookup_encoder is
        # a single-value axis: the second fused knob doubled the smoke's
        # compile count without adding coverage the fused_gru axis
        # doesn't already give.
        return {"scan_unroll": [1],
                "fused_lookup_encoder": [False],
                "fused_gru": [False, True]}
    if kind == "eval":
        grid = {
            "corr_impl": (["allpairs", "allpairs_pallas", "pallas"]
                          if on_tpu else ["allpairs", "chunked"]),
            "corr_dtype": ["auto", "float32"],
        }
        if allow_quantized:
            grid["corr_dtype"].append("int8")
        return grid
    grid = {
        "corr_impl": (["allpairs_pallas", "allpairs"] if on_tpu
                      else ["allpairs"]),
        "corr_dtype": ["auto", "float32"],
        "scan_unroll": [1, 6, 12],
        "remat": [False, True],
        "remat_upsample": [False, True],
        "fuse_upsample_in_scan": [False, True],
        "fused_lookup_encoder": fused_axis,
        "fused_gru": fused_axis,
    }
    if on_tpu:
        grid["upsample_loss_kernel"] = ["xla", "pallas"]
    return grid


def _points(grid: dict, seed: int):
    keys = sorted(grid)
    pts = [dict(zip(keys, vals))
           for vals in itertools.product(*(grid[k] for k in keys))]
    if "batching" in grid:
        # Request mode ignores the slot-mode dispatcher knobs: collapse
        # those axes for every batching=request cross-product point
        # instead of re-timing identical configs.  Model-level knobs
        # (fused_gru etc.) affect BOTH batching modes, so they survive
        # the collapse.
        slot_only = ("slots", "early_exit_threshold")
        seen, uniq = set(), []
        for p in pts:
            if p.get("batching") == "request":
                p = {k: v for k, v in p.items() if k not in slot_only}
            key = json.dumps(p, sort_keys=True)
            if key not in seen:
                seen.add(key)
                uniq.append(p)
        pts = uniq
    random.Random(seed).shuffle(pts)
    return pts


def _sweep_id(kind, grid, hw, batch, iters, steps, warmup, seed) -> str:
    blob = json.dumps({"v": _AUTOTUNE_VERSION, "kind": kind,
                       "grid": {k: list(v) for k, v in sorted(grid.items())},
                       "hw": list(hw), "batch": batch, "iters": iters,
                       "steps": steps, "warmup": warmup, "seed": seed},
                      sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def _synth_batch(hw, batch, seed):
    import numpy as np

    H, W = hw
    rng = np.random.default_rng(seed)
    return {
        "image1": rng.uniform(0, 255, (batch, H, W, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (batch, H, W, 3)).astype(np.float32),
        "flow": (8.0 * rng.standard_normal((batch, H, W, 2))
                 ).astype(np.float32),
        "valid": np.ones((batch, H, W), np.float32),
    }


def _time_train_point(knobs, hw, batch_global, iters, steps, warmup,
                      seed, tiny):
    """pairs/sec/chip of one knob point — bench.py's measurement shape:
    jitted train step on a synthetic sharded batch, warmup to absorb
    compile, a blocking float() sync closing each timed region."""
    import jax

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh, shard_batch
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    mk = RAFTConfig.small_model if tiny else RAFTConfig.full
    model_cfg = mk(compute_dtype="bfloat16", **knobs)
    cfg = TrainConfig(num_steps=max(steps * 4, 100),
                      batch_size=batch_global, image_size=tuple(hw),
                      iters=iters)
    mesh = make_mesh(num_data=jax.device_count(), num_spatial=1)
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    state = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
    step_fn = make_train_step(model, tx, cfg, mesh)
    batch = shard_batch(_synth_batch(hw, batch_global, seed), mesh)
    key = jax.random.PRNGKey(1)
    metrics = None
    for _ in range(max(warmup, 1)):
        state, metrics = step_fn(state, batch, key)
    float(metrics["loss"])
    t0 = time.perf_counter()
    for _ in range(steps):
        state, metrics = step_fn(state, batch, key)
    float(metrics["loss"])
    dt = time.perf_counter() - t0
    return steps * batch_global / dt / max(jax.device_count(), 1)


def _time_eval_point(knobs, hw, batch, iters, steps, warmup, seed, tiny):
    """frames/sec/chip of one knob point on the test-mode forward."""
    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.evaluate import make_eval_fn
    from raft_tpu.models.raft import RAFT

    mk = RAFTConfig.small_model if tiny else RAFTConfig.full
    model_cfg = mk(compute_dtype="bfloat16", **knobs)
    H, W = hw
    rng = np.random.default_rng(seed)
    img1 = (rng.uniform(0, 255, (batch, H, W, 3))).astype(np.float32)
    img2 = (rng.uniform(0, 255, (batch, H, W, 3))).astype(np.float32)
    model = RAFT(model_cfg)
    small = np.zeros((1, 64, 96, 3), np.float32)
    variables = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, small, small,
                             iters=2, train=False))(jax.random.PRNGKey(0))
    fwd = make_eval_fn(model_cfg, iters)
    up = None
    for _ in range(max(warmup, 1)):
        _, up = fwd(variables, img1, img2)
    float(up.sum())
    t0 = time.perf_counter()
    for _ in range(steps):
        _, up = fwd(variables, img1, img2)
    float(up.sum())
    dt = time.perf_counter() - t0
    return steps * batch / dt / max(jax.device_count(), 1)


def _time_serve_point(knobs, hw, batch, iters, steps, warmup, seed,
                      tiny):
    """pairs/sec/chip of one ServeConfig knob point through a real
    InferenceEngine on a closed-loop synthetic workload (``batch``
    concurrent requests per wave, ``steps`` timed waves)."""
    import jax
    import numpy as np

    from raft_tpu import tuning
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve.engine import InferenceEngine, ServeConfig

    mk = RAFTConfig.small_model if tiny else RAFTConfig.full
    # Model-level knobs in a serve sweep (fused_gru etc.) configure the
    # RAFTConfig the engine compiles; dispatcher knobs go to ServeConfig.
    model_cfg = mk(**{k: knobs[k] for k in tuning.TUNABLE_KNOBS
                      if k in knobs})
    H, W = hw
    serve_kw = {k: knobs[k] for k in ("batching", "slots",
                                      "early_exit_threshold")
                if k in knobs}
    cfg = ServeConfig(iters=int(knobs.get("iters", iters)),
                      max_batch=batch, batch_sizes=(batch,),
                      max_wait_ms=2.0, **serve_kw)
    rng = np.random.default_rng(seed)
    pairs = [(rng.uniform(0, 255, (H, W, 3)).astype(np.float32),
              rng.uniform(0, 255, (H, W, 3)).astype(np.float32))
             for _ in range(batch)]
    model = RAFT(model_cfg)
    small = np.zeros((1, 64, 96, 3), np.float32)
    variables = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, small, small,
                             iters=2, train=False))(jax.random.PRNGKey(0))
    eng = InferenceEngine(variables, model_cfg, cfg)
    with eng:
        eng.warmup([hw])

        def wave():
            futs = [eng.submit(a, b) for a, b in pairs]
            for f in futs:
                f.result(timeout=600)

        for _ in range(max(warmup, 1)):
            wave()
        t0 = time.perf_counter()
        for _ in range(steps):
            wave()
        dt = time.perf_counter() - t0
    return steps * len(pairs) / dt / max(jax.device_count(), 1)


def run_sweep(kind, hw, batch_per_chip, iters, steps, warmup, time_box,
              seed, out, force=False, tiny=False, allow_quantized=False):
    """Sweep -> persist winner.  Returns the result record (one JSON
    line, bench.py schema) without printing it."""
    import jax

    from raft_tpu import tuning

    grid = _grid(kind, tiny, allow_quantized)
    sweep_id = _sweep_id(kind, grid, hw, batch_per_chip, iters, steps,
                        warmup, seed)
    out = out or tuning.default_registry_path()
    existing = tuning.lookup(kind, tuple(hw), batch_per_chip, path=out)
    if (existing is not None and existing[2]
            and existing[1].get("provenance", {}).get("sweep_id")
            == sweep_id and not force):
        return {
            "metric": f"autotune_{kind}_{hw[0]}x{hw[1]}_b{batch_per_chip}",
            "value": existing[1]["provenance"].get("best_value"),
            "unit": existing[1]["provenance"].get("unit", ""),
            "vs_baseline": 0.0,
            "config": {"cache_hit": True, "key": existing[0],
                       "knobs": existing[1]["knobs"],
                       "sweep_id": sweep_id, "registry": out},
        }

    n_dev = max(jax.device_count(), 1)
    batch_global = batch_per_chip * n_dev
    timer = {"train": _time_train_point, "eval": _time_eval_point,
             "serve": _time_serve_point}[kind]
    unit = {"train": "image-pairs/sec/chip",
            "eval": "frames/sec/chip",
            "serve": "pairs/sec/chip"}[kind]
    # The sweep must measure each point's RAW knobs — a registry consult
    # inside make_train_step would overwrite the very values under test
    # with the previous winner (a tuning feedback loop).
    prev_disable = os.environ.get(tuning.ENV_DISABLE)
    os.environ[tuning.ENV_DISABLE] = "0"
    points = _points(grid, seed)
    results = []
    deadline = time.monotonic() + time_box
    t_start = time.monotonic()
    try:
        for i, knobs in enumerate(points):
            if results and time.monotonic() > deadline:
                print(f"time box hit after {len(results)}/{len(points)} "
                      "points", flush=True)
                break
            value = timer(knobs, hw, batch_global if kind == "train"
                          else batch_per_chip, iters, steps, warmup,
                          seed, tiny)
            results.append((value, knobs))
            print(f"[{i + 1}/{len(points)}] {json.dumps(knobs)} -> "
                  f"{value:.3f} {unit}", flush=True)
    finally:
        if prev_disable is None:
            os.environ.pop(tuning.ENV_DISABLE, None)
        else:
            os.environ[tuning.ENV_DISABLE] = prev_disable
    best_value, best_knobs = max(results, key=lambda r: r[0])
    key = tuning.save_entry(
        kind, tuple(hw), batch_per_chip, best_knobs,
        provenance={"tool": "scripts/autotune.py", "seed": seed,
                    "sweep_id": sweep_id, "points_tried": len(results),
                    "points_total": len(points), "steps": steps,
                    "warmup": warmup, "iters": iters,
                    "best_value": round(best_value, 3), "unit": unit,
                    "time_box_s": time_box,
                    "elapsed_s": round(time.monotonic() - t_start, 1)},
        path=out)
    return {
        "metric": f"autotune_{kind}_{hw[0]}x{hw[1]}_b{batch_per_chip}",
        "value": round(best_value, 3),
        "unit": unit,
        "vs_baseline": 0.0,
        "config": {"cache_hit": False, "key": key, "knobs": best_knobs,
                   "points_tried": len(results),
                   "points_total": len(points), "sweep_id": sweep_id,
                   "registry": out},
    }


def _tiny_main(args) -> int:
    """The tier-1 smoke: sweep -> cache hit -> consumption, one run."""
    import tempfile

    import jax
    import numpy as np

    from raft_tpu import tuning

    hw, batch, iters, steps, warmup = (48, 64), 2, 2, 2, 1
    out = args.out or osp.join(tempfile.mkdtemp(prefix="raft_autotune_"),
                               "tuning.json")
    common = dict(kind="train", hw=hw, batch_per_chip=batch, iters=iters,
                  steps=steps, warmup=warmup, time_box=args.time_box,
                  seed=args.seed, out=out, tiny=True)
    first = run_sweep(**common)
    second = run_sweep(**common)       # must be served from the registry
    ok = (not first["config"]["cache_hit"]
          and second["config"]["cache_hit"]
          and second["config"]["knobs"] == first["config"]["knobs"])

    # Consumption: a tiny train step with every knob left at its default
    # must pick the winner up through make_train_step's registry consult.
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    prev = os.environ.get(tuning.ENV_REGISTRY)
    os.environ[tuning.ENV_REGISTRY] = out
    try:
        model_cfg = RAFTConfig.small_model()
        resolved, info = tuning.resolve_config(
            model_cfg, "train", hw, batch)
        consumed = info.tuned and all(
            getattr(resolved, k) == v
            for k, v in first["config"]["knobs"].items())
        cfg = TrainConfig(num_steps=10, batch_size=batch, image_size=hw,
                          iters=iters)
        model = RAFT(model_cfg)   # knobs at defaults — the step resolves
        tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay,
                            cfg.epsilon, cfg.clip)
        state = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
        step_fn = make_train_step(model, tx, cfg, None)
        state, metrics = step_fn(state, _synth_batch(hw, batch, 0),
                                 jax.random.PRNGKey(1))
        step_ran = bool(np.isfinite(float(metrics["loss"])))
    finally:
        if prev is None:
            os.environ.pop(tuning.ENV_REGISTRY, None)
        else:
            os.environ[tuning.ENV_REGISTRY] = prev

    passed = ok and consumed and step_ran
    print(json.dumps({
        "metric": "autotune_tiny",
        "value": 1.0 if passed else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": {
            "registry": out,
            "winner": first["config"]["knobs"],
            "first_cache_hit": first["config"]["cache_hit"],
            "second_cache_hit": second["config"]["cache_hit"],
            "consumed_by_train_step": bool(consumed),
            "tiny_step_loss_finite": bool(step_ran),
            "registry_hash": tuning.registry_file_hash(out),
        },
    }))
    return 0 if passed else 1


def main(argv=None) -> int:
    args = parse_args(argv)

    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    # Every point is a fresh jit trace; across re-runs the persistent
    # cache turns repeat compiles into loads.
    enable_persistent_compile_cache()

    if args.seed_known:
        from raft_tpu import tuning

        keys = seed_known(args.out)
        print(json.dumps({
            "metric": "autotune_seed_known",
            "value": float(len(keys)),
            "unit": "entries",
            "vs_baseline": 0.0,
            "config": {"keys": keys,
                       "registry": args.out
                       or tuning.default_registry_path()},
        }))
        return 0

    if args.tiny:
        return _tiny_main(args)

    hw = tuple(int(x) for x in args.image.split("x"))
    iters = args.iters or (12 if args.kind == "train" else 32)
    rec = run_sweep(args.kind, hw, args.batch_per_chip, iters, args.steps,
                    args.warmup, args.time_box, args.seed, args.out,
                    force=args.force, allow_quantized=args.allow_quantized)
    print(json.dumps(rec))
    return 0


if __name__ == "__main__":
    sys.exit(main())
