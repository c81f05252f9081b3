"""Traffic of kind ``train``: one call of ``raft_tpu.train.loop.train``.

Set-up builds what ``cli/train.py`` builds (the flags come from its own
``parse_args`` defaults), hands the loop weights made from the seed and a
loader over a tree generated from the seed, and lets the loop run.  The loop
has no per-step hook, so the benchmark wraps the one step function the loop
asks ``make_train_step`` for (the probe below): it sees every call the loop
makes, copies what the comparison needs from the first three, fences the
device at both ends of the window, and asks the loop to stop through its own
preemption flag.  Same loop, same compiled step, same state from the first
step to the last of the window.
"""

from __future__ import annotations

import collections
import json
import os
import time

import numpy as np

from benchmark import check

WARM_STEPS = 4      # 3 the reference follows + 1 that keeps their state


class Probe:
    """Stands where the loop's ``step_fn`` stands."""

    def __init__(self, real, seconds, batch_size, trace_dir, trace_seconds,
                 stop, fault=None):
        self.real, self.seconds, self.batch_size = real, seconds, batch_size
        self.trace_dir, self.trace_seconds = trace_dir, trace_seconds
        self.stop, self.fault = stop, fault
        self.calls = 0
        self.batches, self.losses = [], []
        self.mu1 = self.params3 = None
        self.t0 = self.t1 = None
        self.steps = 0
        self.pending = collections.deque()
        self.completed = 0
        self.tracing = False
        self.closed = False

    def _call(self, state, batch, key):
        import jax
        import jax.numpy as jnp

        if self.fault == "state_unchanged":
            keep = jax.tree_util.tree_map(jnp.copy, state)
            _, metrics = self.real(state, batch, key)
            return keep, metrics
        if self.fault == "half_batch":
            batch = jax.tree_util.tree_map(
                lambda x: jnp.concatenate([x[:x.shape[0] // 2]] * 2), batch)
        return self.real(state, batch, key)

    def __call__(self, state, batch, key):
        import jax
        import jax.numpy as jnp

        k = self.calls
        self.calls += 1
        if self.closed:                      # a straggler after the close
            return self.real(state, batch, key)
        if k < 3:
            self.batches.append(jax.device_get(batch))
        if k == WARM_STEPS:
            jax.block_until_ready(state.params)
            self.t0 = time.perf_counter()
        new_state, metrics = self._call(state, batch, key)
        if k < 3:
            self.losses.append(metrics["loss"])
        if k == 0:
            adam = [s for s in jax.tree_util.tree_leaves(
                new_state.opt_state, is_leaf=lambda s: hasattr(s, "mu"))
                if hasattr(s, "mu")][0]
            self.mu1 = jax.tree_util.tree_map(jnp.copy, adam.mu)
        if k == 2:
            self.params3 = jax.tree_util.tree_map(jnp.copy, new_state.params)
        if k < WARM_STEPS:
            return new_state, metrics
        self.pending.append(metrics["loss"])
        while self.pending and self.pending[0].is_ready():
            self.pending.popleft()
            self.completed += 1
        elapsed = time.perf_counter() - self.t0
        drain = (len(self.pending) * elapsed / self.completed
                 if self.completed else 0.0)
        if (self.trace_dir and not self.tracing
                and elapsed + drain >= self.seconds - self.trace_seconds):
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.trace_dir, profiler_options=opts)
            self.tracing = True
        if elapsed + drain >= self.seconds:
            jax.block_until_ready(new_state.params)
            self.t1 = time.perf_counter()
            self.steps = k - WARM_STEPS + 1
            if self.tracing:
                jax.profiler.stop_trace()
            self.closed = True
            self.stop()
        return new_state, metrics


def _read_events(directory):
    out = []
    if not directory or not os.path.isdir(directory):
        return out
    for name in sorted(os.listdir(directory)):
        if name.endswith(".jsonl"):
            with open(os.path.join(directory, name)) as f:
                out += [json.loads(line) for line in f if line.strip()]
    return out


def run(ctx):
    import jax

    from raft_tpu.cli import train as cli
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.data.datasets import ShardedLoader, fetch_dataset
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.train import loop
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    from benchmark import flops, reference, traffic, weights

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
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
    mk = RAFTConfig.small_model if cfg["small"] else RAFTConfig.full
    model_cfg = mk(**{**dict(
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
                            loop.request_preemption, ctx.get("fault")))
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

    def follow(b, variables=host_vars, quant=None):
        return reference.train_steps(cfg, variables, b, iters, args.lr,
                                     args.num_steps, quant=quant,
                                     seconds=step_seconds)

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
    numbers["_info"] = info
    result["numbers"] = numbers
    return result


def half_batches(batches):
    """The planted fault "half of the batch left out, the mean taken over
    the rest": every batch's first half, twice."""
    return [{k: np.concatenate([v[:len(v) // 2]] * 2) for k, v in b.items()}
            for b in batches]


def gradient_unit(variables, ref, follow, batches):
    """The unit the first gradient's gaps are also read in: what rounding
    the *weights* to bfloat16 does to the reference's own first gradient
    (the same compiled reference, other inputs).  Twelve iterations of a
    random-weight GRU amplify any rounding by a factor that differs from
    seed to seed; this reads that factor on the seed's own weights.

    The same run names the leaves that the worst-leaf numbers leave out
    (``check.touchy_leaves``): those whose norm that rounding alone moves by
    more than a tenth.  -> (unit, {leaf: gap})."""
    import jax
    import jax.numpy as jnp

    rounded = dict(variables, params=jax.tree_util.tree_map(
        lambda x: np.asarray(jnp.asarray(x).astype(jnp.bfloat16)
                             .astype(jnp.float32)), variables["params"]))
    _, g1, _ = jax.device_get(follow(batches[:1], variables=rounded))
    touchy = check.touchy_leaves(check.leaf_norms(g1),
                                 check.leaf_norms(ref[1]))
    return {"grad_diff": check.tree_diff(g1, ref[1]),
            "grad_leaf_diff_median": check.median_leaf_diff(g1, ref[1])
            }, touchy


def compare(variables, ref, prog, info, unit=None, touchy=()):
    """The training comparison of ``prog`` = (losses, first gradient as the
    optimiser got it, parameters after three steps) with the reference's
    same three: each step's loss, and the other two by the worst leaf
    (``check.worst_leaf_gap``) of those that are not ``touchy``
    (``gradient_unit``); ``*_all`` is the worst of every leaf."""
    (ref_losses, ref_g1, ref_p3), (losses, g1, p3) = ref, prog
    p0 = variables["params"]

    def delta(a):
        import jax

        return jax.tree_util.tree_map(
            lambda x, y: np.asarray(x, np.float64) - np.asarray(y, np.float64),
            a, p0)

    gn_ref, gn = check.leaf_norms(ref_g1), check.leaf_norms(g1)
    dn_ref, dn = check.leaf_norms(delta(ref_p3)), check.leaf_norms(delta(p3))
    dead = check.dead_leaves(gn_ref)
    touchy = set(touchy)
    g_gap, g_leaf = check.worst_leaf_gap(gn, gn_ref, skip=touchy)
    d_gap, d_leaf = check.worst_leaf_gap(dn, dn_ref, skip=dead | touchy)
    out = {f"loss{i + 1}_gap": check.rel_gap(losses[i], ref_losses[i])
           for i in range(len(ref_losses))}
    out.update(grad_gap=g_gap, change_gap=d_gap,
               grad_gap_all=check.worst_leaf_gap(gn, gn_ref)[0],
               change_gap_all=check.worst_leaf_gap(dn, dn_ref, skip=dead)[0],
               grad_diff=check.tree_diff(g1, ref_g1),
               grad_gap_median=check.median_leaf_gap(gn, gn_ref),
               change_gap_median=check.median_leaf_gap(dn, dn_ref,
                                                       skip=dead),
               grad_leaf_diff_median=check.median_leaf_diff(g1, ref_g1))
    if unit:
        for k, u in unit.items():
            out[k + "_vs_unit"] = out[k] / u if u else None
    # the worst leaves' norms [program, reference, reference's median leaf]:
    # a small leaf under the median is read against the median
    info.update(grad_leaf=g_leaf, change_leaf=d_leaf, dead_leaves=len(dead),
                grad_leaf_norms=[gn[g_leaf], gn_ref[g_leaf],
                                 float(np.median(list(gn_ref.values())))],
                change_leaf_norms=[dn[d_leaf], dn_ref[d_leaf],
                                   float(np.median(list(dn_ref.values())))],
                losses=list(losses), ref_losses=list(ref_losses))
    return out
