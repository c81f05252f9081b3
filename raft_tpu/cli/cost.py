"""``python -m raft_tpu cost`` — per-program FLOPs/bytes/roofline table.

Compiles the stack's jitted programs (train step, inference forward,
and the serving engine's ``enc``/``iter`` slot pair) at one
configuration and prints each program's compile-time work accounting
from ``raft_tpu/obs/cost.py``: FLOPs, HBM bytes, arithmetic intensity,
the compute-vs-memory roofline verdict against the device's peak
specs, and the mesh-invariant ``flops_per_pair``.  Everything is
host-side metadata off the ``Compiled`` objects — the programs are
never executed, so the table is safe to produce on a busy machine.

Typical loops::

    python -m raft_tpu cost --tiny            # CPU smoke (small model)
    python -m raft_tpu cost                   # chairs-stage shapes
    python -m raft_tpu cost --image-size 368x768 --batch 4 --json

Use it to answer "what is this program bound by" before reaching for a
profiler (docs/PERFORMANCE.md triage); ``scripts/profile_step.py``
gives the measured-time complement, ``scripts/trace_report.py
--roofline`` the per-span view of a traced run.
"""

from __future__ import annotations

import argparse
import json


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m raft_tpu cost",
        description="compile-time FLOPs/bytes/roofline per jitted "
                    "program (docs/OBSERVABILITY.md, 'Cost model & "
                    "roofline')")
    p.add_argument("--tiny", action="store_true",
                   help="small model at test shapes — seconds on the "
                        "CPU backend (the test-suite smoke config)")
    p.add_argument("--image-size", default=None, metavar="HxW",
                   help="train/inference image size "
                        "(default 368x496; --tiny: 48x64)")
    p.add_argument("--batch", type=int, default=None,
                   help="global train batch size "
                        "(default 8; --tiny: 2)")
    p.add_argument("--iters", type=int, default=None,
                   help="refinement iterations for the train step "
                        "(default 12; --tiny: 2) — inference and the "
                        "serve iter program are per-iteration anyway")
    p.add_argument("--serve-bucket", default=None, metavar="HxW",
                   help="serve program bucket shape "
                        "(default 440x1024; --tiny: 40x56)")
    p.add_argument("--lanes", type=int, default=None,
                   help="serve slot lanes (default 4; --tiny: 2)")
    p.add_argument("--json", action="store_true",
                   help="emit the table as one JSON object instead of "
                        "the human layout")
    return p.parse_args(argv)


def _parse_hw(s, default):
    if s is None:
        return default
    h, w = s.lower().split("x")
    return int(h), int(w)


def _fmt(v, unit=1.0, digits=3):
    if v is None:
        return "-"
    if unit != 1.0:
        return f"{v / unit:.{digits}f}"
    return f"{v:.{digits}f}" if isinstance(v, float) else str(v)


def collect_costs(model_cfg, train_hw, batch, iters, bucket, lanes,
                  num_data=None):
    """The table rows: one :class:`~raft_tpu.obs.cost.ProgramCost` per
    compiled program.  Pure AOT ``lower().compile()`` — cheap under
    the persistent compile cache, never dispatches to the device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu.config import TrainConfig
    from raft_tpu.evaluate import make_eval_fn
    from raft_tpu.models.raft import RAFT
    from raft_tpu.obs import cost as cost_mod
    from raft_tpu.parallel.mesh import make_mesh, shard_batch
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step, step_cost

    H, W = train_hw
    # num_data=1 (the tiny preset) keeps the train-step compile off the
    # SPMD partitioning pass — every derived metric is mesh-invariant
    # by design (per-device flops over per-device pairs), and the
    # test-suite smoke runs under a conftest exposing 8 virtual CPU
    # devices.
    mesh = make_mesh(num_data=num_data)
    n_dev = mesh.devices.size
    B = max(batch, n_dev)
    model = RAFT(model_cfg)
    rng = jax.random.PRNGKey(0)
    costs = []

    # --- train step (forward + backward + optimizer update) ----------
    # Everything is lowered from jax.eval_shape specs — params and
    # optimizer state are never materialized, so the only real work
    # here is the four AOT compiles.
    tcfg = TrainConfig(num_steps=100, batch_size=B,
                       image_size=(H, W), iters=iters)
    tx = make_optimizer(tcfg.lr, tcfg.num_steps, tcfg.wdecay,
                        tcfg.epsilon, tcfg.clip)
    state = jax.eval_shape(
        lambda r: init_state(model, tx, r, (48, 64)), rng)
    step_fn = make_train_step(model, tx, tcfg, mesh)
    arr = np.zeros((B, H, W, 3), np.float32)
    batch_spec = shard_batch({
        "image1": arr, "image2": arr,
        "flow": np.zeros((B, H, W, 2), np.float32),
        "valid": np.zeros((B, H, W), np.float32)}, mesh)
    compiled = step_fn.lower(state, batch_spec, rng).compile()
    costs.append(step_cost(compiled, B, n_dev))

    # --- inference forward (test-mode, the eval/demo/serve math) -----
    small = jax.ShapeDtypeStruct((1, 48, 64, 3), jnp.float32)
    variables = jax.eval_shape(
        lambda k, im: model.init({"params": k, "dropout": k}, im, im,
                                 iters=1, train=False), rng, small)
    fwd = make_eval_fn(model_cfg, iters)
    img = jax.ShapeDtypeStruct((1, H, W, 3), jnp.float32)
    costs.append(fwd.capture_cost(variables, img, img))

    costs.extend(serve_costs(model_cfg, variables, bucket, lanes))
    return costs


def serve_costs(model_cfg, variables, bucket, lanes):
    """The engine's ``enc``/``iter`` compile ledger at ``(bucket,
    lanes)``, lowered as ``serve/engine.py _get_programs`` lowers it.
    ``iter`` is a device loop with a runtime step count, which XLA
    counts as ONE pass of its body: a pair served with ``n`` iterations
    costs ``enc + n x iter``."""
    import jax
    import jax.numpy as jnp

    from raft_tpu.obs import cost as cost_mod
    from raft_tpu.serve import slots as slots_mod

    bh, bw = bucket
    template = slots_mod.state_template(model_cfg, variables, lanes,
                                        (bh, bw))
    state_spec = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), template)
    im = jax.ShapeDtypeStruct((lanes, bh, bw, 3), jnp.float32)
    mask = jax.ShapeDtypeStruct((lanes,), jnp.bool_)
    budg = jax.ShapeDtypeStruct((lanes,), jnp.int32)
    thr = jax.ShapeDtypeStruct((), jnp.float32)
    steps = jax.ShapeDtypeStruct((), jnp.int32)
    enc = jax.jit(slots_mod.make_encode_fn(model_cfg)).lower(
        variables, im, im, state_spec, mask, budg).compile()
    it = jax.jit(slots_mod.make_iter_fn(model_cfg)).lower(
        variables, state_spec, thr, steps).compile()
    return [cost_mod.program_cost(
        exe, program=f"serve_{prog}_{bh}x{bw}_b{lanes}",
        pairs_per_call=lanes) for prog, exe in (("enc", enc), ("iter", it))]


def main(argv=None) -> int:
    args = parse_args(argv)
    from raft_tpu.config import RAFTConfig
    from raft_tpu.obs import cost as cost_mod

    if args.tiny:
        # The reduced corr pyramid (the test_loop/chaos smoke config)
        # roughly halves each AOT compile; cost numbers stay nonzero
        # and mesh-invariant, which is all the smoke asserts.
        model_cfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
        train_hw = _parse_hw(args.image_size, (48, 64))
        batch = args.batch or 2
        iters = args.iters or 2
        bucket = _parse_hw(args.serve_bucket, (40, 56))
        lanes = args.lanes or 2
    else:
        model_cfg = RAFTConfig.full()
        train_hw = _parse_hw(args.image_size, (368, 496))
        batch = args.batch or 8
        iters = args.iters or 12
        bucket = _parse_hw(args.serve_bucket, (440, 1024))
        lanes = args.lanes or 4

    costs = collect_costs(model_cfg, train_hw, batch, iters, bucket,
                          lanes, num_data=1 if args.tiny else None)
    spec = cost_mod.peak_spec()
    if args.json:
        print(json.dumps({
            "device_kind": costs[0].device_kind,
            "peak_tflops": spec.tflops,
            "peak_hbm_gbps": spec.hbm_gbps,
            "ridge_flops_per_byte": spec.ridge,
            "programs": [c.as_record() for c in costs]}))
        return 0

    print(f"device_kind: {costs[0].device_kind}   "
          f"peak: {_fmt(spec.tflops)} bf16 TFLOP/s, "
          f"{_fmt(spec.hbm_gbps)} GB/s HBM   "
          f"ridge: {_fmt(spec.ridge, digits=1)} flop/byte")
    hdr = (f"{'program':<24} {'GFLOPs':>10} {'MB':>10} "
           f"{'flop/byte':>10} {'bound_by':>9} {'flops/pair':>12} "
           f"{'source':>8}")
    print(hdr)
    print("-" * len(hdr))
    for c in costs:
        print(f"{c.program:<24} {_fmt(c.flops, 1e9):>10} "
              f"{_fmt(c.bytes, 1e6):>10} "
              f"{_fmt(c.arithmetic_intensity):>10} {c.bound_by:>9} "
              f"{_fmt(c.flops_per_pair, 1e0, 0):>12} {c.source:>8}")
    if spec.tflops is None:
        print("(unknown device peak — MFU/BW utilization are only "
              "derivable on known hardware, e.g. v5e/v4)")
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main())
