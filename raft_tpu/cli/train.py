"""Training CLI (reference ``train.py:217-246`` flags).

Differences from the reference, by design:

- ``--gpus`` is gone: the job uses every device in the mesh
  (``jax.devices()``); ``--batch_size`` stays GLOBAL and is sharded over
  the ``data`` axis.
- ``--mixed_precision`` maps to bf16 compute (default ON — it is the right
  choice on TPU; pass ``--precision fp32`` to disable).  There is no
  GradScaler: bf16 keeps fp32 exponent range.
- ``--restore_ckpt`` takes an orbax checkpoint directory (a previous
  stage's ``ckpt_dir/name``) and seeds weights only, like the reference's
  ``strict=False`` load (train.py:141-142).
"""

from __future__ import annotations

import argparse
import functools
import os
import os.path as osp

# config.py is jax-free by design; validating the corr knobs at the
# argparse edge means a typo names the allowed set immediately instead
# of dying inside ``jnp.dtype(...)`` at trace time.
from raft_tpu.cli import (add_arch_argument, arch_from_args,
                          parse_with_arch)
from raft_tpu.config import validate_corr_dtype, validate_corr_precision


def _corr_dtype_arg(value: str) -> str:
    try:
        return validate_corr_dtype(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def _corr_precision_arg(value: str) -> str:
    try:
        return validate_corr_precision(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="RAFT-TPU training")
    p.add_argument("--name", default="raft", help="experiment name")
    p.add_argument("--stage", default="chairs",
                   choices=["chairs", "things", "sintel", "kitti"])
    p.add_argument("--restore_ckpt", default=None,
                   help="orbax ckpt dir of a previous stage")
    add_arch_argument(p)
    p.add_argument("--validation", nargs="+", default=[],
                   choices=["chairs", "sintel", "kitti"])
    p.add_argument("--lr", type=float, default=4e-4)
    p.add_argument("--num_steps", type=int, default=100000)
    p.add_argument("--batch_size", type=int, default=6,
                   help="GLOBAL batch size (sharded over devices).  When "
                        "it does not divide the device count it is rounded "
                        "UP to the next multiple and the LR is scaled "
                        "linearly (so the reference schedules run "
                        "unmodified on any pod slice; see --batch_per_chip "
                        "to pin the per-device batch instead)")
    p.add_argument("--batch_per_chip", type=int, default=None,
                   help="per-device batch size; overrides --batch_size "
                        "(global = per_chip * device_count, no LR "
                        "rescaling — tune --lr for the resulting global "
                        "batch yourself)")
    p.add_argument("--image_size", type=int, nargs=2, default=[384, 512])
    p.add_argument("--precision", default="bf16", choices=["bf16", "fp32"])
    p.add_argument("--iters", type=int, default=12)
    p.add_argument("--wdecay", type=float, default=1e-4)
    p.add_argument("--epsilon", type=float, default=1e-8)
    p.add_argument("--clip", type=float, default=1.0)
    p.add_argument("--dropout", type=float, default=0.0)
    p.add_argument("--gamma", type=float, default=0.8,
                   help="exponential loss weighting")
    p.add_argument("--add_noise", action="store_true")
    p.add_argument("--seed", type=int, default=1234)
    p.add_argument("--val_freq", type=int, default=5000,
                   help="checkpoint + validation cadence in steps "
                        "(reference VAL_FREQ, train.py:159)")
    p.add_argument("--remat", default="save_corr",
                   choices=["save_corr", "save_corr_upsample", "full",
                            "dots", "none"],
                   help="backward rematerialization of the refinement "
                        "scan. 'none' is fastest when the activations "
                        "fit (59.5 vs 55.8 pairs/s/chip at the chairs "
                        "crop, batch 16/chip, v5e round 2); 'save_corr' "
                        "(default) is the safe memory/speed trade for "
                        "large crops or batches")
    p.add_argument("--remat_upsample", type=int, default=1,
                   choices=[0, 1],
                   help="rematerialize the upsample/loss scan in "
                        "backward. 0 is faster when its residuals fit "
                        "(+11%% at the things crop batch 8/chip, v5e "
                        "round 3); 1 (default) is the safe choice")
    p.add_argument("--corr_levels", type=int, default=None,
                   help="correlation pyramid levels (default: the "
                        "config's 4).  Toy-scale runs (the curriculum "
                        "smoke) shrink this to cut CPU compile time")
    p.add_argument("--corr_radius", type=int, default=None,
                   help="correlation lookup radius (default: the "
                        "config's 4)")
    p.add_argument("--scan_unroll", type=int, default=None,
                   help="refinement-scan unroll factor (default: the "
                        "config's tuned 12). Use 1 at beyond-HBM "
                        "shapes — each iteration is O(100ms) of device "
                        "work so unroll buys nothing and the 12x graph "
                        "can crash the compiler (round-4 lesson) — or "
                        "on CPU where the unrolled compile is minutes")
    p.add_argument("--corr_dtype", default="auto", type=_corr_dtype_arg,
                   help="materialized corr-pyramid storage dtype; 'auto' "
                        "follows the compute dtype (bf16 storage under "
                        "bf16 compute), 'float32' pins fp32 like the "
                        "reference (core/corr.py:50); 'int8'/fp8 names "
                        "store the volume quantized with a calibrated "
                        "per-level scale — inference-focused, gate with "
                        "`evaluate --epe_delta float32,int8` "
                        "(docs/PERFORMANCE.md)")
    p.add_argument("--corr_precision", default="auto",
                   type=_corr_precision_arg,
                   help="MXU precision of the correlation einsums "
                        "(auto / default / high / highest; 'auto' = "
                        "'highest', the measured v5e winner)")
    p.add_argument("--corr_impl", default="auto",
                   choices=["auto", "allpairs", "allpairs_pallas",
                            "chunked", "pallas"],
                   help="'auto' = the materialized pyramid; which "
                        "lookup samples it (the Mosaic kernel on a TPU "
                        "where its block fits VMEM, XLA elsewhere) is "
                        "chosen from the platform and the crop when "
                        "the step traces (models/raft.py corr_impl_at)")
    p.add_argument("--data_root", default="datasets")
    p.add_argument("--chairs_split", default="chairs_split.txt")
    p.add_argument("--ckpt_dir", default="checkpoints")
    p.add_argument("--tensorboard_dir", default=None)
    p.add_argument("--profile_dir", default=None,
                   help="capture a jax.profiler trace of a few steps "
                        "into this directory (view with XProf/TB)")
    p.add_argument("--telemetry_dir", "--telemetry-dir", default=None,
                   help="write per-step JSONL telemetry (step_time_s, "
                        "queue_wait_s, h2d_s, pairs/sec/chip, compile + "
                        "hbm events; docs/OBSERVABILITY.md) into this "
                        "directory; defaults to $RAFT_TELEMETRY_DIR, "
                        "unset = disabled")
    p.add_argument("--num_workers", type=int, default=0,
                   help="loader prefetch threads; 0 = min(16, cpu_count) "
                        "(the native augmentation kernels release the "
                        "GIL, so threads scale on multi-core pod hosts)")
    p.add_argument("--accum_steps", "--accum-steps", type=int, default=1,
                   help="gradient-accumulation microbatches per step: "
                        "the per-host batch is split into this many "
                        "equal microbatches scanned with fp32 grad "
                        "accumulation before the single optimizer "
                        "update — keeps the paper's effective batch "
                        "when HBM bounds the per-step batch "
                        "(docs/PERFORMANCE.md); must divide the "
                        "per-host batch; 1 = off")
    p.add_argument("--prefetch_batches", "--prefetch-batches", type=int,
                   default=0,
                   help="loader decode window in BATCHES (decode "
                        "futures in flight ahead of the consumer); "
                        "0 = the legacy max(2*batch, 2*workers)-sample "
                        "default")
    p.add_argument("--device_prefetch", "--device-prefetch", type=int,
                   default=2,
                   help="device-prefetch buffer depth: batches host-"
                        "prepped and device_put ahead of the consuming "
                        "step on a background thread, so the H2D copy "
                        "of batch N+1 overlaps the step on batch N; "
                        "0 = the serial fetch->prep->put->step path "
                        "(A/B; the batch stream is bit-identical "
                        "either way)")
    p.add_argument("--ckpt_commit_window", "--ckpt-commit-window",
                   type=int, default=2,
                   help="bound on in-flight background checkpoint "
                        "commits: the step loop never waits on "
                        "checkpoint I/O unless this many saves are "
                        "still uncommitted (each holds one on-device "
                        "TrainState snapshot; docs/ROBUSTNESS.md)")
    p.add_argument("--nonfinite_guard", "--nonfinite-guard", type=int,
                   default=1, choices=[0, 1],
                   help="in-graph non-finite step guard: an isfinite "
                        "reduction over loss+grads gates the optimizer "
                        "update, so a poisoned step (bf16 overflow, "
                        "corrupt batch) leaves params untouched, bumps "
                        "the TrainState nonfinite_steps counter and "
                        "triggers a forensic bundle at log cadence "
                        "(docs/OBSERVABILITY.md); 0 = unguarded A/B")
    p.add_argument("--forensic_keep", "--forensic-keep", type=int,
                   default=8,
                   help="host batches kept in the forensics ring; a "
                        "non-finite step whose batch is still ringed "
                        "gets a fully replayable bundle "
                        "(scripts/replay_step.py).  Guaranteed capture "
                        "needs log_freq <= this; 0 disables batch "
                        "capture")
    p.add_argument("--watchdog_timeout", "--watchdog-timeout",
                   type=float, default=0.0, metavar="SECONDS",
                   help="stall watchdog: seconds without a training-"
                        "loop heartbeat before dumping all thread "
                        "stacks and emitting a `stall` telemetry event "
                        "(0 = off).  Pick ~20x the median step time "
                        "and above startup compile; paused around "
                        "save/validate")
    p.add_argument("--watchdog_exit", "--watchdog-exit",
                   action="store_true",
                   help="hard-exit (code 42) when the watchdog fires, "
                        "so a hung multi-host job fails fast instead "
                        "of burning the pod")
    p.add_argument("--trace_sample_rate", "--trace-sample-rate",
                   type=float, default=None, metavar="RATE",
                   help="distributed step tracing: fraction of steps "
                        "that emit a `train_step` trace tree "
                        "(queue_wait/prep/h2d/step_dispatch/ckpt_commit "
                        "spans as trace_span events; errors, retries "
                        "and non-finite steps always kept — "
                        "docs/OBSERVABILITY.md).  Default "
                        "$RAFT_TRACE_SAMPLE_RATE, unset = off; "
                        "reconstruct with scripts/trace_report.py")
    p.add_argument("--profile_steps", "--profile-steps", default=None,
                   metavar="A:B",
                   help="capture an XProf device profile for steps "
                        "[A, B) into <telemetry_dir>/xprof/ and link "
                        "the artifact dir from concurrently emitted "
                        "trace spans (e.g. --profile-steps 100:105)")
    p.add_argument("--shard_spatial", type=int, default=1, metavar="N",
                   help="shard activations (image height) over N mesh "
                        "devices in addition to data parallelism — for "
                        "inputs whose all-pairs correlation volume "
                        "exceeds one chip's HBM (720p+); device_count "
                        "must be divisible by N")
    p.add_argument("--distributed", action="store_true",
                   help="multi-host pod run: call "
                        "jax.distributed.initialize() (auto-detects the "
                        "coordinator on TPU pods) before touching devices")
    p.add_argument("--chaos", default=None,
                   help="deterministic fault-injection spec, e.g. "
                        "'corrupt_image@step=7;torn_ckpt@step=50' "
                        "(docs/ROBUSTNESS.md grammar) — exercises the "
                        "quarantine/fallback paths on purpose; default "
                        "$RAFT_CHAOS_SPEC, unset = no injection")
    p.add_argument("--chaos-seed", type=int, default=None,
                   help="seed for probabilistic chaos rules "
                        "(default $RAFT_CHAOS_SEED or 0)")
    return parse_with_arch(p, argv)


def resolve_batch(batch_size, batch_per_chip, num_devices, lr):
    """Map the requested batch onto the device grid.

    Returns ``(global_batch, lr)``.  ``batch_per_chip`` pins the
    per-device batch (no LR rescale — the caller owns the tuning).
    Otherwise a global ``batch_size`` that does not divide the mesh is
    rounded UP to the next multiple of ``num_devices`` and the LR is
    scaled linearly with the batch growth, so the reference's 2-GPU
    global batches (10/6/6/6, /root/reference/train_standard.sh:3-6)
    map onto any pod slice (e.g. v5e-64: 10 -> 64, lr x6.4) without
    editing the scripts.
    """
    if batch_per_chip is not None:
        if batch_per_chip <= 0:
            raise ValueError(f"--batch_per_chip must be > 0, got "
                             f"{batch_per_chip}")
        return batch_per_chip * num_devices, lr
    if batch_size <= 0:
        raise ValueError(f"--batch_size must be > 0, got {batch_size}")
    rounded = -(-batch_size // num_devices) * num_devices
    if rounded != batch_size:
        lr = lr * (rounded / batch_size)
    return rounded, lr


def default_corr_impl() -> str:
    """What ``--corr_impl auto`` hands the model: the materialized
    pyramid, as ``RAFTConfig`` defaults to it.  Which lookup samples it
    is not decided here: ``models.raft.corr_impl_at`` picks it from the
    platform and the crop when the step traces, for training as for
    serving."""
    from raft_tpu.config import RAFTConfig

    return RAFTConfig.corr_impl


def run(argv=None):
    """Parse flags, build the stage, and train; returns the final
    :class:`TrainState` (the curriculum driver consumes it — the
    ``main`` entry below keeps the plain int-returning CLI contract)."""
    args = parse_args(argv)

    # Export the telemetry dir before anything builds a default sink, so
    # event emitters without an explicit sink (chaos fires, library
    # spans) land in the same directory as the per-step stream.
    if args.telemetry_dir:
        os.environ.setdefault("RAFT_TELEMETRY_DIR", args.telemetry_dir)

    from raft_tpu import chaos

    if args.chaos:
        os.environ[chaos.ENV_SPEC] = args.chaos
    if args.chaos_seed is not None:
        os.environ[chaos.ENV_SEED] = str(args.chaos_seed)
    chaos.install_from_env()

    import jax

    if args.distributed:
        # Must run before any backend initialization; every host then sees
        # the same global device mesh and feeds its own batch stride
        # (ShardedLoader host_id below).
        if os.environ.get("JAX_PLATFORMS", "").startswith("cpu"):
            # Multi-process CPU "pods" (CI, local rehearsal of the pod
            # flow) need an explicit collectives backend — without it
            # jitted collectives die with "Multiprocess computations
            # aren't implemented on the CPU backend".  Gloo ships in the
            # jaxlib wheel.
            jax.config.update("jax_cpu_collectives_implementation",
                              "gloo")
        jax.distributed.initialize()

    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    from raft_tpu import evaluate
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.data.datasets import ShardedLoader, fetch_dataset
    from raft_tpu.models.raft import RAFT
    from raft_tpu.train.checkpoint import CheckpointManager
    from raft_tpu.train.loop import train
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state

    compute_dtype = "bfloat16" if args.precision == "bf16" else "float32"
    corr_impl = args.corr_impl
    if corr_impl == "auto":
        corr_impl = default_corr_impl()
    from raft_tpu.config import QUANTIZED_CORR_DTYPES

    if (args.corr_dtype in QUANTIZED_CORR_DTYPES
            and corr_impl in ("chunked", "pallas")):
        raise SystemExit(
            f"--corr_dtype {args.corr_dtype} requires a materialized "
            f"correlation pyramid (--corr_impl allpairs or "
            f"allpairs_pallas); the on-demand {corr_impl!r} path never "
            "stores the volume, so there is nothing to quantize")
    model_cfg = RAFTConfig.preset(
        arch_from_args(args), dropout=args.dropout, corr_impl=corr_impl,
        compute_dtype=compute_dtype,
        corr_dtype=args.corr_dtype,
        corr_precision=args.corr_precision,
        remat=args.remat != "none",
        remat_policy=args.remat if args.remat != "none"
        else "save_corr",
        remat_upsample=bool(args.remat_upsample),
        **{k: v for k, v in
           (("scan_unroll", args.scan_unroll),
            ("corr_levels", args.corr_levels),
            ("corr_radius", args.corr_radius))
           if v is not None})
    from raft_tpu.models.raft import corr_impl_at
    from raft_tpu.parallel.mesh import data_parallel_kernels

    h8, w8 = args.image_size[0] // 8, args.image_size[1] // 8
    with data_parallel_kernels(None, rows_split=args.shard_spatial > 1):
        print(f"corr_impl: {args.corr_impl} -> {corr_impl}, which at "
              f"{h8}x{w8} on {jax.default_backend()} runs "
              f"{corr_impl_at(model_cfg, h8, w8)!r}", flush=True)
    num_hosts = jax.process_count()
    num_devices = jax.device_count()
    batch_size, lr = resolve_batch(args.batch_size, args.batch_per_chip,
                                   num_devices, args.lr)
    if (batch_size, lr) != (args.batch_size, args.lr):
        print(f"batch {args.batch_size} -> {batch_size} over "
              f"{num_devices} devices"
              + (f", lr {args.lr:g} -> {lr:g} (linear scaling)"
                 if lr != args.lr else ""), flush=True)
    if args.shard_spatial > 1:
        if num_devices % args.shard_spatial:
            raise SystemExit(f"--shard_spatial {args.shard_spatial} must "
                             f"divide the {num_devices}-device mesh")
        if args.image_size[0] % (8 * args.shard_spatial):
            raise SystemExit(
                f"--shard_spatial {args.shard_spatial} needs image height "
                f"{args.image_size[0]} divisible by "
                f"{8 * args.shard_spatial} (1/8-res rows split evenly)")
    if args.accum_steps < 1:
        raise SystemExit(f"--accum_steps must be >= 1, got "
                         f"{args.accum_steps}")
    if args.prefetch_batches < 0 or args.device_prefetch < 0:
        raise SystemExit("--prefetch_batches / --device_prefetch must "
                         "be >= 0")
    trace_rate = (args.trace_sample_rate
                  if args.trace_sample_rate is not None
                  else float(os.environ.get("RAFT_TRACE_SAMPLE_RATE",
                                            "0") or 0))
    if not 0.0 <= trace_rate <= 1.0:
        raise SystemExit(f"--trace_sample_rate must be in [0, 1], got "
                         f"{trace_rate}")
    profile_steps = None
    if args.profile_steps:
        try:
            a, b = args.profile_steps.split(":")
            profile_steps = (int(a), int(b))
        except ValueError:
            raise SystemExit(f"--profile_steps expects A:B (step "
                             f"window), got {args.profile_steps!r}")
        if profile_steps[1] <= profile_steps[0]:
            raise SystemExit(f"--profile_steps window must be "
                             f"non-empty, got {args.profile_steps!r}")
    per_host_batch = batch_size // num_hosts
    if per_host_batch % args.accum_steps:
        raise SystemExit(
            f"--accum_steps {args.accum_steps} must divide the per-host "
            f"batch size {per_host_batch} (global {batch_size} over "
            f"{num_hosts} host(s)) evenly — pick a batch size that is a "
            f"multiple of accum_steps * num_hosts")
    cfg = TrainConfig(
        name=args.name, stage=args.stage, restore_ckpt=args.restore_ckpt,
        validation=tuple(args.validation), lr=lr,
        num_steps=args.num_steps, batch_size=batch_size,
        image_size=tuple(args.image_size), iters=args.iters,
        wdecay=args.wdecay, epsilon=args.epsilon, clip=args.clip,
        gamma=args.gamma, add_noise=args.add_noise, seed=args.seed,
        val_freq=args.val_freq,
        freeze_bn=args.stage != "chairs",  # reference train.py:147-148
        accum_steps=args.accum_steps,
        prefetch_batches=args.prefetch_batches,
        device_prefetch=args.device_prefetch,
        nonfinite_guard=bool(args.nonfinite_guard),
        forensic_keep=max(args.forensic_keep, 0),
        watchdog_timeout=max(args.watchdog_timeout, 0.0),
        watchdog_exit=args.watchdog_exit,
        ckpt_dir=args.ckpt_dir,
        ckpt_commit_window=max(args.ckpt_commit_window, 1),
        trace_sample_rate=trace_rate,
        profile_steps=profile_steps)
    dataset = fetch_dataset(args.stage, tuple(args.image_size),
                            root=args.data_root,
                            split_file=args.chairs_split)
    if args.num_workers < 0:
        raise SystemExit(f"--num_workers must be >= 0, got "
                         f"{args.num_workers}")
    try:  # respect CPU affinity / container quotas, not raw core count
        avail_cpus = len(os.sched_getaffinity(0))
    except AttributeError:  # non-Linux
        avail_cpus = os.cpu_count() or 4
    num_workers = args.num_workers or min(16, avail_cpus)
    loader = ShardedLoader(dataset, batch_size // num_hosts,
                           seed=args.seed, num_hosts=num_hosts,
                           host_id=jax.process_index(),
                           num_workers=num_workers,
                           prefetch_batches=args.prefetch_batches)

    from raft_tpu.parallel.mesh import make_mesh

    if args.shard_spatial > 1:
        mesh = make_mesh(num_data=num_devices // args.shard_spatial,
                         num_spatial=args.shard_spatial)
    else:
        mesh = make_mesh()

    restore = None
    if args.restore_ckpt:
        model = RAFT(model_cfg)
        tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                            cfg.clip)
        template = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
        rmgr = CheckpointManager(args.restore_ckpt)
        # mesh= reshards the seed weights onto THIS run's topology — a
        # previous stage trained on a different pod slice seeds cleanly
        # (docs/ROBUSTNESS.md "Elastic resume").
        restore = rmgr.restore_params(template, mesh=mesh)
        saved_on = rmgr.saved_topology(rmgr.latest_step())
        rmgr.close()
        assert restore is not None, f"no checkpoint in {args.restore_ckpt}"
        print(f"restored weights from {args.restore_ckpt}"
              + (f" (saved on {saved_on.get('mesh', saved_on)})"
                 if saved_on else ""), flush=True)

    roots = {
        "chairs": dict(root=osp.join(args.data_root,
                                     "FlyingChairs_release/data"),
                       split_file=args.chairs_split),
        "sintel": dict(root=osp.join(args.data_root, "Sintel")),
        "kitti": dict(root=osp.join(args.data_root, "KITTI")),
    }
    # Bind one jitted eval forward per validator so periodic validation
    # reuses the compilation across rounds (shapes are constant per split).
    val_iters = {"chairs": 24, "sintel": 32, "kitti": 24}
    validators = {
        name: functools.partial(
            evaluate.VALIDATORS[name], model_cfg=model_cfg,
            iters=val_iters[name],
            eval_fn=evaluate.make_eval_fn(model_cfg, val_iters[name]),
            **roots[name])
        for name in args.validation
    }

    # Pod preemption (SIGTERM) -> cooperative flag -> the train loop
    # exits at the next STEP BOUNDARY with an emergency checkpoint of
    # the last completed step (train/loop.py), so a preempted run
    # resumes with optimizer/LR state and mid-epoch shuffle position
    # intact.  (A flag, not an async exception: an exception could land
    # mid-orbax-save and abort a registered-but-uncommitted step.)
    # Single-host only — multi-host preemption goes through JAX's
    # coordination-service sync protocol (SIGTERM is its default
    # notice), polled by the loop, so all hosts exit at the SAME agreed
    # step; a python handler here would shadow it.
    if jax.process_count() == 1:
        import signal

        from raft_tpu.train.loop import request_preemption

        signal.signal(signal.SIGTERM,
                      lambda signum, frame: request_preemption())

    # On-demand "where is it stuck": SIGQUIT (kill -QUIT <pid>) appends
    # an all-thread faulthandler stack dump to the same per-process file
    # the stall watchdog writes (telemetry dir; stderr when telemetry is
    # off) — inspect a wedged run without killing it.
    from raft_tpu.obs.watchdog import install_sigquit_dump, stack_dump_path

    install_sigquit_dump(stack_dump_path(
        args.telemetry_dir or os.environ.get("RAFT_TELEMETRY_DIR")))

    return train(model_cfg, cfg, loader=loader,
                 validators=validators or None,
                 restore_params=restore,
                 tensorboard_dir=args.tensorboard_dir,
                 profile_dir=args.profile_dir,
                 telemetry_dir=args.telemetry_dir,
                 mesh=mesh, shard_spatial=args.shard_spatial > 1)


def main(argv=None):
    run(argv)
    return 0


if __name__ == "__main__":
    main()
