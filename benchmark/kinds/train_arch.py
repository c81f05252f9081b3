"""Traffic of kind ``train_arch``: what kind ``train`` does (one call of
``raft_tpu.train.loop.train`` under the same probe, the same window and the
same comparison), for a configuration that names its own architecture.

Where ``kinds/train.py`` knows two presets, one reference and one count of
operations, this kind reads them from the configuration file:

- ``preset``: the ``RAFTConfig.preset`` name the program builds;
- ``reference``, ``operations``, ``weights``: modules under ``benchmark/``
  with the functions of ``reference.py`` (``train_steps``, ``QUANTS``),
  ``flops.py`` (``train_ops``; optionally ``aggregate_cost``) and
  ``weights.py`` (``make_variables``).

So the next architecture adds data and its three modules, not a kind.  The
probe, the comparison and its units are ``kinds/train.py``'s own, imported.
``--fault no_aggregate`` (where the reference takes ``drop_aggregate``) puts
the reference with ``gamma * (A v)`` left out in the program's place: what a
program that dropped the block would read.
"""

from __future__ import annotations

import importlib
import os
import time

from benchmark.kinds.train import (WARM_STEPS, Probe, _read_events, compare,
                                   gradient_unit, half_batches)


def run(ctx):
    import jax

    from raft_tpu.cli import train as cli
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.data.datasets import ShardedLoader, fetch_dataset
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.train import loop
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    from benchmark import traffic

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    reference, flops, weights = (
        importlib.import_module(f"benchmark.{cfg[k]}")
        for k in ("reference", "operations", "weights"))
    enable_persistent_compile_cache()
    crop, batch = tuple(tr["crop"]), int(tr["batch_per_chip"]) * ctx["chips"]
    iters = int(tr.get("iters", cfg["train_iters"]))
    prog_seed = int(seed) % (2 ** 31 - 1)
    # the CLI's own defaults, then the traffic file's flags on top
    args = cli.parse_args(
        ["--stage", "chairs", "--image_size", str(crop[0]), str(crop[1]),
         "--batch_size", str(batch), "--iters", str(iters),
         "--num_workers", str(tr["num_workers"]), "--seed", str(prog_seed)]
        + [str(a) for a in tr.get("flags", [])])
    corr_impl = (cli.default_corr_impl() if args.corr_impl == "auto"
                 else args.corr_impl)
    # the rehearsal's XLA:CPU compile is minutes shorter without unrolling
    overrides = {"scan_unroll": 1} if ctx["tiny"] else {}
    model_cfg = RAFTConfig.preset(cfg["preset"], **{**dict(
        dropout=args.dropout, corr_impl=corr_impl,
        compute_dtype="bfloat16" if args.precision == "bf16" else "float32",
        corr_dtype=args.corr_dtype, corr_precision=args.corr_precision,
        remat=args.remat != "none",
        remat_policy=args.remat if args.remat != "none" else "save_corr",
        remat_upsample=bool(args.remat_upsample)), **overrides})
    for key in ("hidden_dim", "context_dim", "corr_levels", "corr_radius"):
        if getattr(model_cfg, key) != cfg[key]:
            raise SystemExit(f"{key}: program {getattr(model_cfg, key)} != "
                             f"configuration file {cfg[key]}")
    if model_cfg.compute_dtype != cfg["compute_dtype"] and not ctx["tiny"]:
        raise SystemExit("compute_dtype differs from the configuration")
    work = ctx["workdir"]
    tcfg = TrainConfig(
        name="bench", stage=args.stage, lr=args.lr, num_steps=args.num_steps,
        batch_size=batch, image_size=crop, iters=args.iters,
        wdecay=args.wdecay, epsilon=args.epsilon, clip=args.clip,
        gamma=args.gamma, add_noise=args.add_noise, seed=prog_seed,
        val_freq=args.val_freq, freeze_bn=args.stage != "chairs",
        accum_steps=args.accum_steps, prefetch_batches=args.prefetch_batches,
        device_prefetch=args.device_prefetch,
        nonfinite_guard=bool(args.nonfinite_guard),
        forensic_keep=max(args.forensic_keep, 0),
        ckpt_dir=os.path.join(work, "ckpt"),
        ckpt_commit_window=max(args.ckpt_commit_window, 1))
    variables = weights.make_variables(RAFT(model_cfg), seed)
    host_vars = jax.device_get(variables)    # the step donates its state
    data_root, split = traffic.write_chairs_tree(
        os.path.join(work, "data"), seed, tuple(tr["image"]),
        int(tr["pairs"]))
    dataset = fetch_dataset(args.stage, crop, root=data_root,
                            split_file=split)
    loader = ShardedLoader(dataset, batch, seed=prog_seed,
                           num_workers=args.num_workers,
                           prefetch_batches=args.prefetch_batches)
    mesh = make_mesh(num_data=ctx["chips"],
                     devices=jax.devices()[:ctx["chips"]])
    telemetry = os.path.join(work, "telemetry") if ctx["trace"] else None
    trace_dir = os.path.join(work, "xplane") if ctx["trace"] else None
    probes = []
    real_make = loop.make_train_step

    def make_probe(*a, **kw):
        probes.append(Probe(real_make(*a, **kw), ctx["seconds"], batch,
                            trace_dir, float(tr.get("trace_seconds", 3.0)),
                            loop.request_preemption,
                            None if ctx.get("fault") == "no_aggregate"
                            else ctx.get("fault")))
        return probes[-1]

    loop.make_train_step = make_probe
    os.environ["RAFT_TELEMETRY_HBM"] = "0"
    os.environ["RAFT_TELEMETRY_COST"] = "0"
    try:
        loop.train(model_cfg, tcfg, loader=loader, restore_params=variables,
                   telemetry_dir=telemetry, mesh=mesh)
    except SystemExit as e:      # the loop's answer to its preemption flag
        if e.code != 143:
            raise
    finally:
        loop.make_train_step = real_make
    ctx["mark"]("loop_returned")
    probe = probes[0]
    fault = ctx.get("fault")
    if probe.t1 is None:
        raise SystemExit("the loop ended before the window closed")
    window = probe.t1 - probe.t0
    pairs_per_s = probe.steps * batch / window
    peak_bytes = ctx["memory_peak"]()
    result = {
        "attempted": probe.steps, "failed": 0,
        "setup_s": probe.t0 - ctx["t_start"],
        "e2e": {"train_pairs_per_s_per_chip": pairs_per_s / ctx["chips"]},
        "memory_peak_bytes": peak_bytes,
        "facts": {"window_s": window, "steps": probe.steps, "batch": batch,
                  "pairs_per_s": pairs_per_s, "chips": ctx["chips"],
                  "ops_per_pair": flops.train_ops(cfg, crop[0], crop[1],
                                                  iters),
                  "lookup": {"h": crop[0] // 8, "w": crop[1] // 8,
                             "pairs_per_call": batch // ctx["chips"]},
                  # one A v an iteration over the chip's pairs, forward
                  # and backward; A stored in the compute type
                  "aggregate": {"n": (crop[0] // 8) * (crop[1] // 8),
                                "pairs_per_call": batch // ctx["chips"],
                                "bytes": 2 if model_cfg.compute_dtype
                                == "bfloat16" else 4},
                  "trace_dir": trace_dir},
    }
    # loop telemetry of the window's steps (traced run only)
    ev = [e for e in _read_events(telemetry)
          if e.get("event") == "train_step" or "queue_wait_s" in e]
    ev = [e for e in ev if WARM_STEPS <= int(e.get("step", -1))
          < WARM_STEPS + probe.steps]
    if ev:
        result["facts"]["loop"] = {
            "queue_wait_s": sum(float(e["queue_wait_s"]) for e in ev),
            "h2d_s": sum(float(e.get("h2d_s", 0.0)) for e in ev)}

    # ---- correctness: the reference follows the first three steps -------
    prog = ([float(x) for x in probe.losses],
            jax.device_get(jax.tree_util.tree_map(lambda m: m / 0.1,
                                                  probe.mu1)),
            jax.device_get(probe.params3))
    batches = probe.batches
    del probe.mu1, probe.params3, probe.real, probes[:], variables
    ctx["mark"]("reference_starts")
    t = time.perf_counter()
    step_seconds = []

    def follow(b, variables=host_vars, quant=None, **kw):
        return reference.train_steps(cfg, variables, b, iters, args.lr,
                                     args.num_steps, quant=quant,
                                     seconds=step_seconds, **kw)

    ref = jax.device_get(follow(batches))
    unit, touchy = gradient_unit(host_vars, ref, follow, batches)
    info = {"reference_s": time.perf_counter() - t,
            "reference_step_s": list(step_seconds),
            "unit": unit, "touchy_leaves": touchy}
    numbers = compare(host_vars, ref, prog, info, unit, touchy)
    if ctx.get("reference_quant"):
        # The control: the reference in the program's place, one precision
        # below the configuration's; it is what gets judged.  The same
        # process reads the program (a lower reading on this seed) and the
        # fault "half of the batch left out" planted in the reference.
        info["program"] = numbers
        info["half_batch"] = compare(
            host_vars, ref, jax.device_get(follow(half_batches(batches))),
            {}, unit, touchy)
        info["controls"] = {
            q: compare(host_vars, ref, jax.device_get(follow(
                batches, quant=reference.QUANTS[q])), {}, unit, touchy)
            for q in ctx["reference_quant"].split(",")}
        numbers = dict(next(iter(info["controls"].values())))
    if fault == "no_aggregate":
        info.setdefault("program", numbers)
        numbers = compare(host_vars, ref, jax.device_get(follow(
            batches, drop_aggregate=True)), {}, unit, touchy)
    numbers["_info"] = info
    result["numbers"] = numbers
    return result
