"""Fleet chaos drill: prove the self-healing serving fleet heals
(tier-1, CPU).

Brings up a 2-replica :class:`raft_tpu.serve.ReplicaFleet` behind the
health-gated :class:`raft_tpu.serve.FlowRouter` with a tiny model and
walks the three promises docs/SERVING.md's fleet section makes:

1. **AOT warm-start**: replica 0 compiles the warmup ladder and exports
   it; replica 1 imports and serves with ZERO JIT compiles
   (``CompileCounter``-asserted).
2. **Kill drill**: a deterministic ``replica_kill`` chaos fault takes a
   replica down mid-batch under open-loop load.  Every accepted request
   still resolves (failover, ``raft_fleet_dropped_total == 0``), the
   supervisor restarts the dead replica with backoff, and the restarted
   replica ALSO comes up with zero compiles (AOT import again).
3. **Rolling weight update**: new weights land in an orbax run-layout
   checkpoint; ``update_weights`` verifies the newest step (an actual
   restore), canaries the warming engine, then flips both replicas with
   zero downtime.  A TORN copy of the same checkpoint is refused at the
   verify gate — the fleet keeps serving the good version.  A
   NaN-poisoned weight set is refused at the canary gate.

Prints one check_regression-format JSON line (``metric: serve_fleet_smoke``,
``value`` 1.0 = every promise held); exit 0, or an assertion failure.

::

    JAX_PLATFORMS=cpu python scripts/serve_fleet_smoke.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="serving-fleet chaos drill")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/counts (the tier-1 CPU drill)")
    p.add_argument("--requests", type=int, default=None,
                   help="open-loop requests through the kill drill")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (AOT dir, checkpoints, "
                        "telemetry) under DIR instead of a temp dir")
    return p.parse_args(argv)


def _wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out after {timeout_s}s waiting for "
                         f"{what}")


def main(argv=None) -> int:
    args = parse_args(argv)
    n_requests = args.requests or (16 if args.tiny else 64)
    workdir = args.keep or tempfile.mkdtemp(prefix="raft-fleet-smoke-")
    os.makedirs(workdir, exist_ok=True)
    os.environ.setdefault("RAFT_TELEMETRY_DIR",
                          os.path.join(workdir, "telemetry"))

    import jax
    import numpy as np

    from raft_tpu import chaos
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve import (FleetConfig, FlowRouter, ReplicaFleet,
                                RouterConfig, ServeConfig,
                                WeightUpdateError)
    from raft_tpu.train.checkpoint import CheckpointManager

    model_cfg = RAFTConfig.small_model()  # fp32: CPU-friendly
    shape = (36, 52)  # -> bucket (40, 56)
    model_img = jax.numpy.zeros((1, 40, 56, 3))

    def init_vars(seed):
        k = jax.random.PRNGKey(seed)
        return RAFT(model_cfg).init({"params": k, "dropout": k},
                                    model_img, model_img, iters=1)

    variables = init_vars(args.seed)
    serve_cfg = ServeConfig(iters=2, max_batch=2, batch_sizes=(2,),
                            max_wait_ms=5, max_queue=64,
                            stall_timeout_s=30.0)
    fleet = ReplicaFleet(
        variables, model_cfg, serve_cfg,
        FleetConfig(replicas=2, warmup_shapes=(shape,),
                    restart_backoff_s=0.05, restart_backoff_max_s=0.5,
                    health_poll_s=0.05,
                    aot_dir=os.path.join(workdir, "aot")))
    t0 = time.perf_counter()
    fleet.start()
    router = FlowRouter(fleet, RouterConfig())
    checks = {}
    rng = np.random.default_rng(args.seed)

    def frame():
        return rng.uniform(0, 255, shape + (3,)).astype(np.float32)

    try:
        # -- 1. AOT warm-start ----------------------------------------
        r0, r1 = fleet.replicas
        assert r1.engine.aot_info["ok"] is True, r1.engine.aot_info
        assert r1.engine.compile_counter.counts() == {}, \
            "replica 1 compiled despite AOT import"
        flow = router.infer(frame(), frame(), timeout=120)
        assert flow.shape == shape + (2,)
        assert r1.engine.compile_counter.counts() == {}, \
            "first fleet request triggered a JIT compile on replica 1"
        checks["aot_warm_start"] = {
            "imported": r1.engine.aot_info["imported"],
            "startup_s": round(time.perf_counter() - t0, 2)}

        # -- 2. kill drill under open-loop load -----------------------
        chaos.install(chaos.FaultPlan.parse("replica_kill@batch=3",
                                            seed=args.seed))
        futures = []
        for _ in range(n_requests):
            futures.append(router.submit(frame(), frame()))
            time.sleep(0.01)  # open loop: arrivals keep coming
        results = [f.result(timeout=120) for f in futures]
        chaos.uninstall()
        assert all(r.shape == shape + (2,) for r in results), \
            "a request accepted before the kill never produced flow"
        rstats = router.router_stats()
        assert rstats["dropped_total"] == 0, rstats
        assert rstats["failovers_total"] >= 1, \
            f"kill fired but no failover recorded: {rstats}"
        _wait_for(lambda: sum(r.restarts for r in fleet.replicas) >= 1
                  and all(r.state == "ready" for r in fleet.replicas),
                  30, "supervised restart of the killed replica")
        restarted = next(r for r in fleet.replicas if r.restarts)
        assert restarted.engine.aot_info["ok"] is True
        assert restarted.engine.compile_counter.counts() == {}, \
            "restarted replica had to JIT-compile (AOT import failed)"
        flow = router.infer(frame(), frame(), timeout=120)
        assert flow.shape == shape + (2,)
        assert restarted.engine.compile_counter.counts() == {}, \
            "restarted replica compiled on its first request"
        checks["kill_drill"] = {
            "requests": n_requests,
            "failovers": rstats["failovers_total"],
            "dropped": rstats["dropped_total"],
            "restarts": {r.name: r.restarts for r in fleet.replicas}}

        # -- 3. rolling weight update (verify + canary gated) ---------
        new_vars = jax.device_get(init_vars(args.seed + 1))
        ckpt_dir = os.path.join(workdir, "ckpt-good")
        mgr = CheckpointManager(ckpt_dir, async_save=False)
        mgr.save(1, new_vars)
        mgr.wait()
        mgr.close()
        report = fleet.update_weights(ckpt_dir)
        assert report["ok"] and report["provenance"]["verified"], report
        assert sorted(report["flipped"]) == ["r0", "r1"], report
        assert fleet.weights_version == 2
        flow = router.infer(frame(), frame(), timeout=120)
        assert flow.shape == shape + (2,)

        # torn checkpoint: refused at the verify gate, version holds
        torn_dir = os.path.join(workdir, "ckpt-torn")
        mgr = CheckpointManager(torn_dir, async_save=False)
        mgr.save(1, new_vars)
        mgr.wait()
        mgr.close()
        chaos.tear_files(os.path.join(torn_dir, "1"))
        try:
            fleet.update_weights(torn_dir)
            raise AssertionError("torn checkpoint was NOT refused")
        except WeightUpdateError as e:
            torn_msg = str(e)
        assert fleet.weights_version == 2

        # NaN-poisoned weights: refused at the canary gate
        poisoned = jax.tree_util.tree_map(
            lambda x: np.full_like(x, np.nan), new_vars)
        try:
            fleet.update_weights(jax.device_get(poisoned))
            raise AssertionError("NaN weights were NOT refused")
        except WeightUpdateError as e:
            assert "canary" in str(e), e
        assert fleet.weights_version == 2
        flow = router.infer(frame(), frame(), timeout=120)
        assert flow.shape == shape + (2,)
        checks["rolling_update"] = {
            "version": fleet.weights_version,
            "flipped": report["flipped"],
            "torn_refused": torn_msg[:120],
            "update_s": report["seconds"]}

        # -- fleet-wide invariants ------------------------------------
        mt = fleet.metrics_text()
        assert 'replica="r0"' in mt and 'replica="r1"' in mt
        assert "raft_fleet_restarts_total" in mt
        health = fleet.health()
        assert health["ready"], health
        ok = True
    finally:
        chaos.uninstall()
        fleet.stop()

    print(json.dumps({
        "metric": "serve_fleet_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": dict(checks, requests=n_requests, replicas=2,
                       workdir=workdir if args.keep else None),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
