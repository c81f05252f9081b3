"""Curriculum smoke: prove kill-anywhere resume converges (tier-1).

Drives the REAL curriculum driver (``raft_tpu.curriculum``) over a
micro on-disk FlyingChairs corpus with a two-stage manifest, twice:

- **Run A** — uninterrupted: both stages train to completion; its
  normalized stage ledger is the reference.  (Executed in full mode;
  ``--tiny`` substitutes the analytically-known result — every stage
  ``complete`` at ``final_step = steps`` — to keep the tier-1 CPU
  budget: each train invocation costs a fresh ~25 s XLA:CPU step-fn
  compile, and run A is two of them.)
- **Run B** — chaos-killed at BOTH kill classes docs/ROBUSTNESS.md
  promises resume across, then resumed by re-running the same command:

  1. ``preempt@step=3;torn_ckpt@step=3`` — a SIGTERM lands mid-stage 1
     (the cooperative flag fires at the step boundary where the last
     COMPLETED step is 3 — odd, so unsaved by the val_freq=2 cadence);
     the emergency checkpoint of step 3 is torn post-commit.  The
     driver exits 143 with stage 1 ``running``.
  2. ``stage_kill@step=1`` — the resume restores stage 1 past the torn
     step (exactly one ``ckpt_fallback``), finishes it, then dies at
     the stage BOUNDARY — after stage 1's ledger commit, before stage 2
     starts.  Exits 143 with stage 2 still ``pending``.
  3. no plan — stage 1 is skipped as complete, stage 2 trains to the
     end.

The final assertion is convergence: run B's normalized ledger (status +
per-stage final_step) equals run A's, with exactly the expected
telemetry (``chaos_inject`` = 3, ``ckpt_fallback`` = 1, every
``ckpt_commit`` ok).  ``verify-ckpt`` is then run over run B's stage-1
directory to check the torn step is reported CORRUPT alongside its
saved-topology stamp.

Prints one check_regression-format JSON line (``metric: curriculum_smoke``,
``value`` 1.0 = converged); exit 0/1.

::

    python scripts/curriculum_smoke.py --tiny     # the tier-1 CPU smoke
    python scripts/curriculum_smoke.py            # same flow, bigger shapes
"""

from __future__ import annotations

import argparse
import io
import json
import os
import os.path as osp
import shutil
import sys
import tempfile
from contextlib import redirect_stdout

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="chaos-killed curriculum resume smoke test")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/steps (the tier-1 CPU smoke)")
    p.add_argument("--seed", type=int, default=0,
                   help="chaos plan seed (the plans here are fully "
                        "deterministic; the seed only matters for "
                        "p= rules)")
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (corpus + workdirs + telemetry) "
                        "under DIR instead of a deleted temp dir")
    return p.parse_args(argv)


def build_chairs(root, n=10, n_val=2, hw=(64, 96), seed=0):
    """Micro FlyingChairs corpus in the reference layout: rigid integer
    translations of blocky random textures (exactly representable
    flow), ppm pairs + .flo + split file."""
    import numpy as np
    from PIL import Image

    from raft_tpu.data import frame_utils

    data = osp.join(root, "datasets", "FlyingChairs_release", "data")
    os.makedirs(data, exist_ok=True)
    H, W = hw
    rng = np.random.default_rng(seed)
    for i in range(n):
        coarse = rng.uniform(0, 255, (H // 8 + 3, W // 8 + 3, 3))
        big = np.kron(coarse, np.ones((8, 8, 1)))
        u = int(rng.integers(-4, 5))
        v = int(rng.integers(-4, 5))
        img1 = big[8:8 + H, 8:8 + W].astype(np.uint8)
        img2 = big[8 - v:8 - v + H, 8 - u:8 - u + W].astype(np.uint8)
        flow = np.zeros((H, W, 2), np.float32)
        flow[..., 0], flow[..., 1] = u, v
        Image.fromarray(img1).save(osp.join(data, f"{i:05d}_img1.ppm"))
        Image.fromarray(img2).save(osp.join(data, f"{i:05d}_img2.ppm"))
        frame_utils.write_flo(osp.join(data, f"{i:05d}_flow.flo"), flow)
    split = osp.join(root, "chairs_split.txt")
    with open(split, "w") as f:
        f.write("1\n" * (n - n_val) + "2\n" * n_val)
    return osp.join(root, "datasets"), split


def _read_events(tdir):
    import glob

    events = []
    for path in sorted(glob.glob(osp.join(tdir, "*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    events.append(json.loads(line))
                except ValueError:
                    continue
    return events


def _counts(events):
    counts = {}
    for ev in events:
        name = ev.get("event")
        counts[name] = counts.get(name, 0) + 1
    return counts


def main(argv=None) -> int:
    args = parse_args(argv)

    root = args.keep or tempfile.mkdtemp(prefix="curriculum-smoke-")
    os.makedirs(root, exist_ok=True)

    env_backup = {k: os.environ.get(k)
                  for k in ("RAFT_TELEMETRY_DIR", "RAFT_TELEMETRY_HBM",
                            "RAFT_TELEMETRY_COST", "RAFT_CHAOS_SPEC")}
    # hbm + cost share one extra startup lower().compile() per train()
    # — this smoke re-enters the loop ~6 times, skip it.
    os.environ["RAFT_TELEMETRY_HBM"] = "0"
    os.environ["RAFT_TELEMETRY_COST"] = "0"
    os.environ.pop("RAFT_CHAOS_SPEC", None)  # plans installed directly

    from raft_tpu import chaos
    from raft_tpu.obs.events import reset_default_sink
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    # Six train invocations share one step program.  On TPU/GPU the
    # persistent cache dedupes their compiles; on the CPU test backend
    # the call is a guarded no-op (cached XLA:CPU executables abort on
    # deserialization — see enable_persistent_compile_cache).
    enable_persistent_compile_cache()

    # steps per stage (even: val_freq=2 saves land on even steps; the
    # preempt below fires at the boundary where the last completed step
    # is the odd `steps - 1` — UNSAVED, forcing the emergency
    # checkpoint that torn_ckpt then tears).
    steps = 4 if args.tiny else 6
    crop = (32, 48) if args.tiny else (48, 64)
    detail = {}
    try:
        import jax

        from raft_tpu.curriculum import (LEDGER_FILE, Manifest, StageLedger,
                                         StageSpec, run_curriculum)

        data_root, split = build_chairs(root, hw=(64, 96))
        # --tiny shrinks the model knobs to the floor (1 refinement
        # iteration, 1 corr level/radius): the smoke asserts ledger /
        # chaos / recovery semantics, which never look inside the
        # update operator — the extra compile time bought nothing.
        it = 1 if args.tiny else 2
        cl = 1 if args.tiny else 2
        manifest = Manifest(base={
            "small": True, "iters": it, "scan_unroll": 1,
            "corr_levels": cl, "corr_radius": cl, "precision": "fp32",
            "image_size": list(crop), "num_steps": steps, "val_freq": 2,
            "batch_per_chip": 1, "num_workers": 1, "device_prefetch": 2,
            "data_root": data_root, "chairs_split": split, "seed": 11,
        }, stages=[StageSpec("s1", "chairs", {}),
                   StageSpec("s2", "chairs", {})])

        def run_phase(workdir, tdir, plan, expect_exit):
            os.makedirs(tdir, exist_ok=True)
            os.environ["RAFT_TELEMETRY_DIR"] = tdir
            reset_default_sink()
            if plan is not None:
                chaos.install(chaos.FaultPlan.parse(plan, seed=args.seed))
            else:
                chaos.uninstall()
            code = None
            try:
                run_curriculum(manifest, workdir,
                               extra_argv=["--telemetry_dir", tdir])
            except SystemExit as e:
                code = e.code
            assert code == expect_exit, \
                f"plan {plan!r}: exited {code}, expected {expect_exit}"

        def ledger(workdir):
            led = StageLedger(osp.join(workdir, LEDGER_FILE))
            led.load()
            return led

        # ---- run A: uninterrupted reference -------------------------
        if args.tiny:
            # The uninterrupted ledger is deterministic; its known
            # value stands in for executing run A (full mode runs it).
            ref = {"status": "complete",
                   "stages": {s.name: {"status": "complete",
                                       "final_step": steps}
                              for s in manifest.stages}}
        else:
            wa, ta = osp.join(root, "run_a"), osp.join(root,
                                                       "telemetry_a")
            run_phase(wa, ta, plan=None, expect_exit=None)
            ref = ledger(wa).normalized()
            assert ref["status"] == "complete", ref
            assert all(s["final_step"] == steps
                       for s in ref["stages"].values()), ref
            ca = _counts(_read_events(ta))
            assert ca.get("chaos_inject", 0) == 0, ca
            assert ca.get("ckpt_fallback", 0) == 0, ca
        detail["reference"] = ref

        # ---- run B: killed mid-stage, killed at the boundary, resumed
        wb, tb = osp.join(root, "run_b"), osp.join(root, "telemetry_b")
        # phase 1: SIGTERM mid-stage 1 + torn emergency checkpoint (the
        # preempt seam's step context is the last COMPLETED step).
        run_phase(wb, tb, plan=f"preempt@step={steps - 1};"
                               f"torn_ckpt@step={steps - 1}",
                  expect_exit=143)
        assert ledger(wb).normalized()["stages"]["s1"]["status"] \
            == "running"
        # phase 2: resume past the torn step, die at the stage boundary.
        run_phase(wb, tb, plan="stage_kill@step=1", expect_exit=143)
        mid = ledger(wb).normalized()
        assert mid["stages"]["s1"] == {"status": "complete",
                                       "final_step": steps}, mid
        assert mid["stages"]["s2"]["status"] == "pending", mid
        # phase 3: resume to completion.
        run_phase(wb, tb, plan=None, expect_exit=None)

        led_b = ledger(wb)
        assert led_b.normalized() == ref, \
            f"resumed ledger diverged:\n{led_b.normalized()}\nvs\n{ref}"
        assert led_b.stage("s1")["runs"] == 2, led_b.stage("s1")
        assert led_b.stage("s2")["runs"] == 1, led_b.stage("s2")
        detail["converged"] = led_b.normalized()

        # ---- telemetry contract -------------------------------------
        ev_b = _read_events(tb)
        cb = _counts(ev_b)
        # preempt + torn_ckpt (phase 1) + stage_kill (phase 2).
        assert cb.get("chaos_inject", 0) == 3, cb
        # exactly one fallback: the phase-2 resume walking past torn
        # step `steps - 1` to the last val_freq save.
        assert cb.get("ckpt_fallback", 0) == 1, cb
        # background commits: steps 2 and `steps` per completed stage
        # pass (phase 1 commits only step 2; the torn emergency save is
        # synchronous), every one probed ok.
        commits = [ev for ev in ev_b if ev.get("event") == "ckpt_commit"]
        assert len(commits) == 4, [c.get("step") for c in commits]
        assert all(c.get("ok") for c in commits), commits
        assert all(c.get("commit_latency_s", -1) >= 0 for c in commits)
        detail["events"] = {k: cb.get(k, 0)
                            for k in ("chaos_inject", "ckpt_fallback",
                                      "ckpt_commit", "curriculum_stage")}

        # ---- verify-ckpt sees the torn step + the topology stamp ----
        from raft_tpu.cli import verify_ckpt

        buf = io.StringIO()
        with redirect_stdout(buf):
            rc = verify_ckpt.main(
                [osp.join(wb, "checkpoints", "s1"), "--json"])
        report = json.loads(buf.getvalue())
        assert rc == 1, (rc, report)  # torn step present, but resumable
        by_step = {r["step"]: r for r in report["steps"]}
        assert not by_step[steps - 1]["ok"], report
        assert report["latest_valid"] == steps, report
        assert by_step[steps]["topology"]["mesh"] \
            == {"data": jax.device_count(), "spatial": 1}, report
        detail["verify_ckpt"] = {"latest_valid": report["latest_valid"],
                                 "topology": by_step[steps]["topology"]}
        ok = True
    except AssertionError as e:
        print(f"curriculum_smoke FAILED: {e}", file=sys.stderr, flush=True)
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
        "metric": "curriculum_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": dict(detail, tiny=bool(args.tiny),
                       steps_per_stage=steps, image_size=list(crop)),
    }))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
