"""Evaluation CLI (reference ``evaluate.py:169-195`` flags).

``--model`` is an orbax checkpoint directory: either a bare variables tree
(``save_variables`` / the torch converter) or a training run's
``ckpt_dir/name`` (weights are extracted from the latest step).
"""

from __future__ import annotations

import argparse

# config.py is jax-free by design, so importing the validators here keeps
# `--help` (and argparse errors) instant.
from raft_tpu.cli import (add_arch_argument, arch_from_args,
                          parse_with_arch)
from raft_tpu.config import validate_corr_dtype, validate_corr_precision


def _corr_dtype_arg(value: str) -> str:
    """Validate at the CLI edge: a typo'd dtype fails HERE with the
    allowed set in the message, not minutes later inside
    ``jnp.dtype(...)`` at trace time."""
    try:
        return validate_corr_dtype(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def _corr_precision_arg(value: str) -> str:
    try:
        return validate_corr_precision(value)
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def _epe_delta_arg(value: str):
    dtypes = [d.strip() for d in value.split(",") if d.strip()]
    if len(dtypes) < 2:
        raise argparse.ArgumentTypeError(
            f"--epe_delta needs a comma list of >= 2 corr dtypes "
            f"(e.g. 'float32,int8'), got {value!r}")
    try:
        return [validate_corr_dtype(d, flag="--epe_delta")
                for d in dtypes]
    except ValueError as e:
        raise argparse.ArgumentTypeError(str(e))


def _early_exit_arg(value: str):
    parts = [t.strip() for t in value.split(",") if t.strip()]
    if not parts:
        raise argparse.ArgumentTypeError(
            f"--early_exit_threshold needs a comma list of >= 1 "
            f"float, got {value!r}")
    try:
        thrs = [float(t) for t in parts]
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--early_exit_threshold values must be floats, "
            f"got {value!r}")
    if any(t < 0 for t in thrs):
        raise argparse.ArgumentTypeError(
            f"--early_exit_threshold values must be >= 0, got {value!r}")
    return thrs


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="RAFT-TPU evaluation")
    p.add_argument("--model", required=True, help="checkpoint directory")
    p.add_argument("--dataset", required=True,
                   choices=["chairs", "sintel", "kitti"])
    add_arch_argument(p)
    p.add_argument("--precision", default="bf16", choices=["bf16", "fp32"])
    p.add_argument("--corr_dtype", default="auto", type=_corr_dtype_arg,
                   help="correlation-volume STORAGE dtype (auto / "
                        "float32 / bfloat16 / int8 / fp8 names); "
                        "quantized dtypes need a materialized corr_impl "
                        "and should be gated with --epe_delta "
                        "(docs/PERFORMANCE.md)")
    p.add_argument("--corr_precision", default="auto",
                   type=_corr_precision_arg,
                   help="MXU precision of the correlation einsums "
                        "(auto / default / high / highest)")
    p.add_argument("--epe_delta", default=None, type=_epe_delta_arg,
                   metavar="DTYPE,DTYPE[,...]",
                   help="accuracy-gate mode: run the SAME checkpoint "
                        "under each corr storage dtype and report "
                        "per-metric deltas against the first (e.g. "
                        "'float32,int8' gates int8 against fp32 "
                        "storage); overrides --corr_dtype")
    p.add_argument("--early_exit_threshold", default=None,
                   type=_early_exit_arg, metavar="T[,T...]",
                   help="accuracy-gate mode for adaptive early exit: "
                        "sweep each convergence threshold against the "
                        "full-iteration baseline (threshold 0) on the "
                        "SAME checkpoint and report per-arm EPE deltas "
                        "plus iters_used p50/p95 (the serve knob it "
                        "gates is ServeConfig.early_exit_threshold; "
                        "docs/SERVING.md)")
    p.add_argument("--quality-proxies", "--quality_proxies",
                   action="store_true", dest="quality_proxies",
                   help="calibration mode for the unsupervised quality "
                        "proxies (raft_tpu/obs/quality.py): score every "
                        "image with the label-free photometric / "
                        "retirement-residual proxies the serve sampler "
                        "emits and report each proxy's Spearman rank "
                        "correlation with true EPE "
                        "(docs/OBSERVABILITY.md)")
    p.add_argument("--quality-cycle", "--quality_cycle",
                   action="store_true", dest="quality_cycle",
                   help="with --quality-proxies: also score "
                        "forward-backward cycle consistency (second "
                        "inference pass on swapped frames — doubles the "
                        "forward cost)")
    p.add_argument("--alternate_corr", action="store_true",
                   help="memory-efficient on-demand correlation "
                        "(reference --alternate_corr)")
    p.add_argument("--iters", type=int, default=None,
                   help="refinement iterations (default: reference "
                        "per-dataset values: 24/32/24)")
    p.add_argument("--data_root", default="datasets")
    p.add_argument("--chairs_split", default="chairs_split.txt")
    p.add_argument("--eval_batch", type=int, default=4,
                   help="images per jitted forward (streamed through one "
                        "compiled bucket shape)")
    p.add_argument("--no_bucket", action="store_true",
                   help="KITTI: exact reference per-resolution padding "
                        "(one XLA compile per distinct image shape) "
                        "instead of one common bucket shape")
    p.add_argument("--telemetry_dir", "--telemetry-dir", default=None,
                   help="write JSONL telemetry events (per-batch forward "
                        "spans, final eval record) into this directory; "
                        "defaults to $RAFT_TELEMETRY_DIR, unset = "
                        "disabled")
    return parse_with_arch(p, argv)


def variables_arch(variables) -> str:
    """The architecture a variables tree is of, read off the tree itself
    (a checkpoint carries its model in its names): GMA alone has ``att``,
    SEA-RAFT alone ``init_conv``, GMFlow alone ``transformer``, the small
    model alone no convex-upsampling mask head; UniMatch's flow model is
    GMFlow's tree with a ``refine``."""
    params = variables["params"]
    if "transformer" in params:
        return "unimatch" if "refine" in params else "gmflow"
    if "att" in params:
        return "gma"
    if "init_conv" in params:
        return "searaft"
    return "full" if "upsampler" in params else "small"


def load_model_variables(path: str, arch=None):
    """Variables from a bare-pytree checkpoint dir (``save_variables`` /
    the torch converter), or from the latest step of a training-run
    checkpoint directory (orbax CheckpointManager layout:
    ``<dir>/<step>/default``).  ``arch``: the architecture the caller
    is about to run them through; a checkpoint of another one is refused
    here, by name, and not by a shape error deep in the first trace."""
    tree = _load_model_variables(path)
    held = variables_arch(tree)
    if arch is not None and held != arch:
        raise SystemExit(f"{path} holds a {held!r} model and --arch says "
                         f"{arch!r}; pass --arch {held}")
    return tree


def _load_model_variables(path: str):
    import os

    from raft_tpu.train import checkpoint as ckpt

    if os.path.exists(os.path.join(path, "_METADATA")):
        return ckpt.load_variables(path)
    steps = sorted(int(d) for d in os.listdir(path) if d.isdigit())
    assert steps, f"no checkpoint found under {path}"
    tree = ckpt.load_variables(os.path.join(path, str(steps[-1]),
                                            "default"))
    if "opt_state" in tree or "step" in tree:  # full TrainState pytree
        tree = {"params": tree["params"],
                "batch_stats": tree.get("batch_stats", {})}
    return tree


def main(argv=None):
    args = parse_args(argv)

    import os
    import os.path as osp

    if args.telemetry_dir:
        # The eval spans write through the process-default sink, which
        # binds to this env var on first use (raft_tpu/obs/events.py).
        os.environ["RAFT_TELEMETRY_DIR"] = args.telemetry_dir
        from raft_tpu.obs import reset_default_sink

        reset_default_sink()

    from raft_tpu import evaluate
    from raft_tpu.config import RAFTConfig
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    enable_persistent_compile_cache()

    compute_dtype = "bfloat16" if args.precision == "bf16" else "float32"
    model_cfg = RAFTConfig.preset(
        arch_from_args(args), compute_dtype=compute_dtype,
        corr_dtype=args.corr_dtype,
        corr_precision=args.corr_precision,
        corr_impl=evaluate.default_alternate_corr_impl()
        if args.alternate_corr else "allpairs")
    variables = load_model_variables(args.model, model_cfg.arch)
    if "batch_stats" not in variables:
        variables = dict(variables, batch_stats={})

    default_iters = {"chairs": 24, "sintel": 32, "kitti": 24}
    iters = args.iters or default_iters[args.dataset]

    roots = {
        "chairs": dict(root=osp.join(args.data_root,
                                     "FlyingChairs_release/data"),
                       split_file=args.chairs_split),
        "sintel": dict(root=osp.join(args.data_root, "Sintel")),
        "kitti": dict(root=osp.join(args.data_root, "KITTI")),
    }
    if args.early_exit_threshold:
        # The adaptive-early-exit accuracy gate: same checkpoint, N
        # convergence thresholds vs the full-iteration baseline.
        kwargs = dict(roots[args.dataset])
        if args.dataset == "kitti":
            kwargs["bucket"] = not args.no_bucket
        result = evaluate.evaluate_early_exit_delta(
            variables, model_cfg, args.early_exit_threshold,
            dataset=args.dataset, iters=iters,
            batch_size=args.eval_batch, **kwargs)
        # Bench-format record so the sweep rides the BENCH series:
        # check_regression.py --max-early-exit-epe-delta reads the raw
        # arm dict (config.early_exit_delta_vs_full) off this record.
        import json
        print(json.dumps({
            "metric": f"eval_early_exit_{args.dataset}_iters{iters}",
            "value": 1.0,
            "unit": "pass",
            "vs_baseline": 0.0,
            "config": {
                "early_exit_delta_vs_full": result["delta_vs_full"],
                "thresholds": result["thresholds"],
                "per_threshold": result["per_threshold"],
            },
        }))
        return

    if args.quality_proxies:
        # Proxy-calibration mode: Spearman(proxy, EPE) per dataset so
        # the label-free serve/fleet quality signals are calibrated
        # against ground truth, not vibes.
        kwargs = dict(roots[args.dataset])
        if args.dataset == "kitti":
            kwargs["bucket"] = not args.no_bucket
        result = evaluate.evaluate_quality_proxies(
            variables, model_cfg, dataset=args.dataset, iters=iters,
            batch_size=args.eval_batch, cycle=args.quality_cycle,
            **kwargs)
        # Bench-format record: check_regression.py reads
        # config.quality_spearman off this series.
        import json
        print(json.dumps({
            "metric": f"eval_quality_proxies_{args.dataset}",
            "value": 1.0,
            "unit": "pass",
            "vs_baseline": 0.0,
            "config": {
                "quality_spearman": result["spearman"],
                "proxy_means": result["proxy_means"],
                "epe_mean": result["epe_mean"],
                "n": result["n"],
            },
        }))
        return

    if args.epe_delta:
        # The quantization accuracy gate: same checkpoint, N corr
        # storage dtypes, per-metric deltas vs the first.
        kwargs = dict(roots[args.dataset])
        if args.dataset == "kitti":
            kwargs["bucket"] = not args.no_bucket
        evaluate.evaluate_epe_delta(
            variables, model_cfg, args.epe_delta, dataset=args.dataset,
            iters=iters, batch_size=args.eval_batch, **kwargs)
        return

    if args.dataset == "chairs":
        evaluate.validate_chairs(
            variables, model_cfg, iters=iters,
            root=osp.join(args.data_root, "FlyingChairs_release/data"),
            split_file=args.chairs_split, batch_size=args.eval_batch)
    elif args.dataset == "sintel":
        evaluate.validate_sintel(variables, model_cfg, iters=iters,
                                 root=osp.join(args.data_root, "Sintel"),
                                 batch_size=args.eval_batch)
    else:
        evaluate.validate_kitti(variables, model_cfg, iters=iters,
                                root=osp.join(args.data_root, "KITTI"),
                                batch_size=args.eval_batch,
                                bucket=not args.no_bucket)


if __name__ == "__main__":
    main()
