"""Chaos smoke: prove the self-healing paths actually heal (tier-1).

Runs, on CPU with a tiny model and a synthetic on-disk FlyingChairs
tree, the three recovery paths docs/ROBUSTNESS.md promises — under a canned
deterministic :class:`raft_tpu.chaos.FaultPlan` — and asserts each run
COMPLETES with exactly the expected telemetry:

1. **Train under a corrupt sample** (``corrupt_image``): the loader
   quarantines the poisoned read (one ``sample_quarantine`` event, one
   deterministic replacement draw) and training reaches the target step
   with batch shapes unchanged.
2. **Resume past a torn checkpoint** (``torn_ckpt``): the newest saved
   step is torn post-commit; a second ``train()`` walks the fallback
   chain (one ``ckpt_fallback`` event), restores the newest VALID step,
   and trains on to the new target.
3. **Serve through a transient device error** (``device_err``): the
   first device batch fails with a retryable error; the engine
   re-dispatches once (one ``serve_retry`` event) and every co-batched
   request still succeeds.

Finally the telemetry log is folded through
``scripts/telemetry_summary.py`` to assert the run's
``quarantined_total`` / ``ckpt_fallback_total`` reach the
``check_regression.py`` gate fields.

Prints one check_regression-format JSON line (``metric: chaos_smoke``,
``value`` 1.0 = all scenarios healed); exit 0/1.

::

    python scripts/chaos_smoke.py --tiny     # the tier-1 CPU smoke
    python scripts/chaos_smoke.py            # same flow, bigger shapes
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="fault-injection smoke test")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/steps (the tier-1 CPU smoke)")
    p.add_argument("--seed", type=int, default=0,
                   help="chaos plan seed (the plan here is fully "
                        "deterministic; the seed only matters for "
                        "p= rules)")
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (telemetry + checkpoints) "
                        "under DIR instead of a deleted temp dir")
    return p.parse_args(argv)


def _make_dataset(root, n, hw):
    """``n`` FlyingChairs-layout pairs of ``hw`` on disk (a random frame,
    the frame moved a pixel right, flow (1, 0)), read unaugmented by the
    real ``FlyingChairs`` reader: the corrupt-sample fault then takes the
    loader's own read path, in its worker processes."""
    import numpy as np
    from PIL import Image

    from raft_tpu.data.datasets import FlyingChairs
    from raft_tpu.data.frame_utils import write_flo

    data = os.path.join(root, "chairs")
    os.makedirs(data, exist_ok=True)
    flow = np.zeros(hw + (2,), np.float32)
    flow[..., 0] = 1.0
    for i in range(n):
        img1 = np.random.default_rng(i).integers(0, 256, hw + (3,),
                                                 dtype=np.uint8)
        Image.fromarray(img1).save(os.path.join(data, f"{i:05d}_img1.ppm"))
        Image.fromarray(np.roll(img1, 1, axis=1)).save(
            os.path.join(data, f"{i:05d}_img2.ppm"))
        write_flo(os.path.join(data, f"{i:05d}_flow.flo"), flow)
    split = os.path.join(root, "chairs_split.txt")
    with open(split, "w") as f:
        f.write("1\n" * n)
    return FlyingChairs(None, root=data, split_file=split)


def _count_events(tdir):
    import glob

    counts = {}
    for path in sorted(glob.glob(os.path.join(tdir, "*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    ev = json.loads(line).get("event")
                except ValueError:
                    continue
                counts[ev] = counts.get(ev, 0) + 1
    return counts


def main(argv=None) -> int:
    args = parse_args(argv)

    root = args.keep or tempfile.mkdtemp(prefix="chaos-smoke-")
    tdir = os.path.join(root, "telemetry")
    ckpt_root = os.path.join(root, "checkpoints")
    os.makedirs(tdir, exist_ok=True)

    env_backup = {k: os.environ.get(k)
                  for k in ("RAFT_TELEMETRY_DIR", "RAFT_TELEMETRY_HBM",
                            "RAFT_TELEMETRY_COST")}
    os.environ["RAFT_TELEMETRY_DIR"] = tdir
    # hbm + cost share one extra startup lower().compile() — skip it
    os.environ["RAFT_TELEMETRY_HBM"] = "0"
    os.environ["RAFT_TELEMETRY_COST"] = "0"

    from raft_tpu import chaos
    from raft_tpu.obs.events import reset_default_sink

    reset_default_sink()

    hw = (32, 48) if args.tiny else (48, 64)
    steps1, steps2 = (4, 6) if args.tiny else (6, 9)
    cfg_detail = {}
    try:
        import jax
        import numpy as np

        from raft_tpu.config import RAFTConfig, TrainConfig
        from raft_tpu.data.datasets import ShardedLoader
        from raft_tpu.models.raft import RAFT
        from raft_tpu.obs.events import EventSink
        from raft_tpu.serve import InferenceEngine, ServeConfig
        from raft_tpu.train.loop import train

        model_cfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2,
                                           scan_unroll=1)

        # ---- scenario 1+2: train under corrupt sample + torn newest
        # checkpoint, then resume through the fallback chain ----------
        # The loader draws each sample's chaos decision in this process,
        # in stream order: the call-ordinal trigger is deterministic.
        chaos.install(chaos.FaultPlan.parse(
            f"corrupt_image@call=3;torn_ckpt@step={steps1}",
            seed=args.seed))
        # The batch shards over the data mesh, so it must divide the
        # device count (8 virtual CPU devices under the test harness,
        # 1 standalone).
        bs = max(2, jax.device_count())
        n_samples = 4 * bs

        def make_cfg(num_steps, val_freq):
            return TrainConfig(
                name="chaos-smoke", num_steps=num_steps, batch_size=bs,
                image_size=hw, iters=2, val_freq=val_freq, log_freq=2,
                seed=11, ckpt_dir=ckpt_root, device_prefetch=2)

        # sample_retries=0: a one-shot injected corruption would
        # otherwise be healed by the same-file retry (the rule is
        # exhausted by the time the retry re-reads) — this smoke wants
        # to see the QUARANTINE path, not the retry path.
        dataset = _make_dataset(root, n_samples, hw)
        loader = ShardedLoader(dataset,
                               batch_size=bs, seed=7, num_workers=1,
                               sample_retries=0)
        state = train(model_cfg, make_cfg(steps1, val_freq=2),
                      loader=loader, telemetry_dir=tdir)
        assert int(state.step) == steps1, \
            f"train under chaos stopped at {int(state.step)} != {steps1}"
        assert loader.quarantined_total == 1, \
            f"expected exactly 1 quarantine, got " \
            f"{loader.quarantined_total}"

        # Resume: newest step is torn; fallback must restore an older
        # one and still reach the new target.  val_freq=3 keeps the
        # resumed run's saves off the torn step number.
        loader2 = ShardedLoader(dataset,
                                batch_size=bs, seed=7, num_workers=1)
        state2 = train(model_cfg, make_cfg(steps2, val_freq=3),
                       loader=loader2, telemetry_dir=tdir)
        assert int(state2.step) == steps2, \
            f"resume stopped at {int(state2.step)} != {steps2}"
        cfg_detail["train_final_step"] = int(state2.step)

        # ---- scenario 3: serve retries one transient device error ----
        chaos.install(chaos.FaultPlan.parse("device_err@batch=1",
                                            seed=args.seed))
        rng = jax.random.PRNGKey(0)
        img = jax.numpy.zeros((1,) + hw + (3,))
        variables = RAFT(model_cfg).init({"params": rng, "dropout": rng},
                                         img, img, iters=1)
        sink = EventSink(tdir)
        eng = InferenceEngine(
            variables, model_cfg,
            ServeConfig(iters=2, max_batch=2, batch_sizes=(2,),
                        max_wait_ms=20, device_retries=1,
                        retry_backoff_s=0.01),
            sink=sink)
        eng.start()
        try:
            r = np.random.default_rng(3)
            ims = [r.uniform(0, 255, hw + (3,)).astype(np.float32)
                   for _ in range(4)]
            futs = [eng.submit(ims[0], ims[1]),
                    eng.submit(ims[2], ims[3])]
            flows = [f.result(timeout=600) for f in futs]
            for flow in flows:
                assert flow.shape == hw + (2,), flow.shape
            stats = eng.stats()
            assert stats["retries"] == 1, stats
            assert stats["completed"] == 2, stats
            cfg_detail["serve_retries"] = stats["retries"]
        finally:
            eng.stop()
            sink.close()

        # ---- telemetry contract ----
        counts = _count_events(tdir)
        expected = {"sample_quarantine": 1, "ckpt_fallback": 1,
                    "serve_retry": 1, "chaos_inject": 3}
        for ev, want in expected.items():
            got = counts.get(ev, 0)
            assert got == want, \
                f"event {ev}: expected {want}, got {got} ({counts})"
        cfg_detail["events"] = {k: counts.get(k, 0) for k in expected}

        # The gate fields reach the bench series: fold the log through
        # telemetry_summary and check the check_regression inputs.
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "telemetry_summary",
            os.path.join(REPO, "scripts", "telemetry_summary.py"))
        ts = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(ts)
        summary = ts.summarize(*ts.last_run(ts.iter_records(tdir)),
                               skip=0)
        assert summary["config"]["quarantined_total"] == 1, summary
        assert summary["config"]["ckpt_fallback_total"] == 1, summary
        cfg_detail["summary_gates"] = {
            "quarantined_total": summary["config"]["quarantined_total"],
            "ckpt_fallback_total":
                summary["config"]["ckpt_fallback_total"],
        }
        ok = True
    except AssertionError as e:
        print(f"chaos_smoke FAILED: {e}", file=sys.stderr, flush=True)
        ok = False
    finally:
        chaos.uninstall()
        for k, v in env_backup.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        reset_default_sink()
        if args.keep is None:
            shutil.rmtree(root, ignore_errors=True)

    print(json.dumps({
        "metric": "chaos_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": dict(cfg_detail, tiny=bool(args.tiny),
                       image_size=list(hw)),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
