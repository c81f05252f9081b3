"""Flow-quality drill: prove the quality observability loop closes
(tier-1, CPU).

Brings up a 1-replica :class:`raft_tpu.serve.ReplicaFleet` with sampled
quality scoring on (``ServeConfig.quality_sample_rate=1``,
``raft_tpu/obs/quality.py``) over a procedural demo-frames-style
workload (smooth low-motion pairs, the bundled ``demo-frames/`` look —
see ``scripts/make_demo_frames.py``) and walks the two promises
docs/OBSERVABILITY.md's "Flow quality" section makes:

1. **The front door refuses degraded weights**: a finite-but-scrambled
   weight set (every param scaled x25 — passes the shape+finiteness
   canary that used to be the only gate) is pushed through
   ``update_weights`` and REFUSED at the golden-batch proxy gate
   (``FleetConfig.canary_proxy_budget``); the fleet keeps serving its
   current weights and emits ``fleet_canary_proxy`` /
   ``fleet_weight_update ok=false``.
2. **Drift catches what sneaks past the door**: the same scrambled
   weights are then hot-swapped directly into the live replica's
   engine — gated behind a deterministic ``weights_scramble`` chaos
   rule (:mod:`raft_tpu.chaos`), so the injection is telemetry-marked
   — and continued traffic makes the windowed PSI drift detector fire
   ``quality_drift``, which the fleet supervisor surfaces as
   ``fleet_quality_drift``.

Prints one check_regression-format JSON line (``metric: quality_smoke``,
``value`` 1.0 = both promises held) whose config block carries the
``quality_drift_score`` / ``canary_proxy_delta_pct`` figures that
``scripts/check_regression.py --max-quality-drift`` /
``--max-canary-proxy-delta`` gate on; exit 0, or an assertion failure.

::

    JAX_PLATFORMS=cpu python scripts/quality_smoke.py --tiny
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

#: Drift-detector sizing for the drill: under no drift the smoothed PSI
#: fluctuates around (bins-1)/window (see DriftDetector), so the tiny
#: window=8 needs a threshold well above the serve default 0.5 —
#: measured stable-noise peak ~0.9, fully-shifted ~1.7; 1.25 sits in
#: the gap.
DRIFT_WINDOW = 8
DRIFT_REFERENCE = 16
DRIFT_THRESHOLD = 1.25


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="flow-quality drill")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/counts (the tier-1 CPU drill)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (telemetry) under DIR instead "
                        "of a temp dir")
    p.add_argument("--aot-dir", default=None, metavar="DIR",
                   help="import pre-exported AOT executables (see "
                        "InferenceEngine.export_aot) instead of "
                        "compiling at fleet warmup; the fingerprint "
                        "gate still applies, so a mismatched export "
                        "falls back to compilation")
    return p.parse_args(argv)


def _wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out after {timeout_s}s waiting for "
                         f"{what}")


def _events(tdir, name):
    """All telemetry events called ``name`` in the JSONL dir."""
    out = []
    for path in sorted(glob.glob(os.path.join(tdir, "*.jsonl"))):
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except ValueError:
                    continue
                if rec.get("event") == name:
                    out.append(rec)
    return out


def _demo_pairs(rng, shape, n):
    """Procedural demo-frames-style workload: a smooth textured scene
    panning 2 px per pair plus mild sensor noise — low-motion traffic
    with a STATIONARY quality distribution, so the drill's drift
    reference freezes on an honest baseline."""
    import numpy as np

    h, w = shape
    pad = 8
    base = rng.uniform(0.0, 255.0, (h + 2 * pad, w + 2 * pad, 3))
    kernel = np.ones(9) / 9.0
    for axis in (0, 1):
        base = np.apply_along_axis(
            lambda v: np.convolve(v, kernel, mode="same"), axis, base)
    base -= base.min()
    base *= 255.0 / max(base.max(), 1e-6)
    pairs = []
    for _ in range(n):
        im1 = base[pad:pad + h, pad:pad + w]
        im2 = base[pad:pad + h, pad - 2:pad - 2 + w]  # 2 px pan
        noise = rng.normal(0.0, 1.0, im1.shape)
        pairs.append(
            (np.clip(im1 + noise, 0, 255).astype(np.float32),
             np.clip(im2 + rng.normal(0.0, 1.0, im1.shape), 0,
                     255).astype(np.float32)))
    return pairs


def main(argv=None) -> int:
    args = parse_args(argv)
    workdir = args.keep or tempfile.mkdtemp(prefix="raft-quality-smoke-")
    os.makedirs(workdir, exist_ok=True)
    tdir = os.path.join(workdir, "telemetry")
    os.environ["RAFT_TELEMETRY_DIR"] = tdir

    import jax
    import numpy as np

    from raft_tpu import chaos
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.obs import reset_default_sink
    from raft_tpu.serve import (FleetConfig, FlowRouter, ReplicaFleet,
                                RouterConfig, ServeConfig,
                                WeightUpdateError)

    reset_default_sink()  # bind the JSONL sink to this drill's dir

    model_cfg = RAFTConfig.small_model()  # fp32: CPU-friendly
    # --tiny: the tier-1 CPU sizing; the default exercises a bigger
    # bucket and a real iteration budget (for a run on the chip; none
    # has been made — chip_smoke.py is the on-chip entry point).
    shape = (36, 52) if args.tiny else (64, 96)
    serve_iters = 2 if args.tiny else 8
    model_img = jax.numpy.zeros((1, 40, 56, 3))
    key = jax.random.PRNGKey(args.seed)
    variables = RAFT(model_cfg).init({"params": key, "dropout": key},
                                     model_img, model_img, iters=1)
    # Finite but useless: every parameter scaled far out of its trained
    # regime.  Passes the finiteness canary; the flow it produces is
    # garbage — exactly the failure mode the proxy gate exists for.
    scrambled = jax.device_get(jax.tree_util.tree_map(
        lambda x: np.asarray(x) * 25.0, jax.device_get(variables)))

    serve_cfg = ServeConfig(
        iters=serve_iters, max_batch=2, batch_sizes=(2,), max_wait_ms=5,
        max_queue=64, batching="slot", slots=2,  # scoring = slot path
        quality_sample_rate=1.0,
        quality_drift_reference=DRIFT_REFERENCE,
        quality_drift_window=DRIFT_WINDOW,
        quality_drift_threshold=DRIFT_THRESHOLD)
    fleet = ReplicaFleet(
        variables, model_cfg, serve_cfg,
        FleetConfig(replicas=1, warmup_shapes=(shape,),
                    restart_backoff_s=0.05, health_poll_s=0.05,
                    aot_dir=args.aot_dir or os.path.join(workdir,
                                                         "aot")))
    fleet.start()
    router = FlowRouter(fleet, RouterConfig())
    rng = np.random.default_rng(args.seed)
    n_good = DRIFT_REFERENCE + DRIFT_WINDOW  # freeze ref + fill window
    n_bad = 2 * DRIFT_WINDOW
    checks = {}
    try:
        # -- 1. healthy traffic: reference freezes, no drift ----------
        for im1, im2 in _demo_pairs(rng, shape, n_good):
            flow = router.infer(im1, im2, timeout=120)
            assert flow.shape == shape + (2,)
        eng = fleet.replicas[0].engine
        drift0 = eng.quality_drift()
        assert drift0 is not None, "quality scoring is off"
        _wait_for(lambda: all(
            d["observed"] >= n_good - 1
            for d in eng.quality_drift().values()),
            10, "quality scores to land")
        drift0 = eng.quality_drift()
        assert not any(d["drifted"] for d in drift0.values()), drift0
        assert sum(d["events"] for d in drift0.values()) == 0, drift0
        checks["baseline"] = {
            "requests": n_good,
            "scores": {k: round(d["score"], 3)
                       for k, d in drift0.items()}}

        # -- 2. scrambled weights REFUSED at the proxy gate -----------
        version0 = fleet.weights_version
        try:
            fleet.update_weights(scrambled)
            raise AssertionError(
                "finite-but-scrambled weights were NOT refused — the "
                "golden-batch proxy gate is broken")
        except WeightUpdateError as e:
            refusal = str(e)
        assert "proxy" in refusal, refusal
        assert fleet.weights_version == version0
        flow = router.infer(*_demo_pairs(rng, shape, 1)[0], timeout=120)
        assert flow.shape == shape + (2,)  # fleet kept serving
        proxy_events = _events(tdir, "fleet_canary_proxy")
        assert proxy_events and proxy_events[-1]["ok"] is False, \
            proxy_events
        delta_pct = float(proxy_events[-1]["delta_pct"])
        refusals = [e for e in _events(tdir, "fleet_weight_update")
                    if e.get("ok") is False]
        assert refusals, "no fleet_weight_update ok=false event"
        checks["proxy_refusal"] = {
            "refused": refusal[:140],
            "delta_pct": round(delta_pct, 1),
            "old": proxy_events[-1]["old"],
            "new": proxy_events[-1]["new"]}

        # -- 3. hot-swap past the gate: drift fires -------------------
        chaos.install(chaos.FaultPlan.parse("weights_scramble@call=0",
                                            seed=args.seed))
        assert chaos.should_inject("weights_scramble",
                                   point="serve.quality_drill"), \
            "chaos plan did not arm the scramble injection"
        eng._variables = jax.device_put(scrambled)
        for im1, im2 in _demo_pairs(rng, shape, n_bad):
            flow = router.infer(im1, im2, timeout=120)
            assert flow.shape == shape + (2,)
        _wait_for(lambda: any(d["events"] >= 1
                              for d in eng.quality_drift().values()),
                  10, "the PSI drift detector to fire")
        drift1 = eng.quality_drift()
        drift_score = max(d["score"] for d in drift1.values())
        assert drift_score > DRIFT_THRESHOLD, drift1
        drift_events = _events(tdir, "quality_drift")
        assert drift_events, "no quality_drift event reached telemetry"
        # The supervisor polls engine drift state and re-emits it
        # fleet-labeled for fleet-level alerting.
        _wait_for(lambda: _events(tdir, "fleet_quality_drift"),
                  10, "the fleet supervisor to surface the drift")
        assert _events(tdir, "chaos_inject"), \
            "the injected scramble left no chaos_inject marker"
        checks["drift"] = {
            "requests": n_bad,
            "drift_score": round(drift_score, 3),
            "events": sum(d["events"] for d in drift1.values()),
            "per_proxy": {k: round(d["score"], 3)
                          for k, d in drift1.items()}}
        ok = True
    finally:
        chaos.uninstall()
        fleet.stop()

    print(json.dumps({
        "metric": "quality_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": {
            **checks,
            # The literal gate fields (scripts/check_regression.py
            # --max-quality-drift / --max-canary-proxy-delta).
            "quality_drift_score": round(drift_score, 6),
            "canary_proxy_delta_pct": round(delta_pct, 3),
            "drift_window": DRIFT_WINDOW,
            "drift_threshold": DRIFT_THRESHOLD,
            "workdir": workdir if args.keep else None},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
