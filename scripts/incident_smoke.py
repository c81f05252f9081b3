"""Incident-engine chaos drill: one cascade, ONE correlated incident
(tier-1, CPU).

Brings up a 2-replica fleet with SLO tracking and the incident engine
on, then walks the two promises docs/OBSERVABILITY.md's "Incidents &
SLOs" section makes:

1. **Quiet baseline is free**: under healthy load the incident manager
   opens NOTHING, and the SLO/flight-recorder layers add zero JIT
   compiles and zero device syncs (``CompileCounter``-pinned — the
   serve path stays byte-identical to the un-instrumented one).
2. **Cascade correlation**: a ``replica_kill`` plus a ``device_err``
   burst injected under open-loop load produce exactly ONE incident —
   the co-occurring signals (``serve_retry`` retries from the device
   burst, the ``replica_crash``/``fleet_restart`` arc from the kill)
   fold into it instead of opening one incident each.  Its forensic
   bundle under ``<telemetry_dir>/incidents/<id>/`` is self-contained:
   the event window, at least one captured trace tree, the metric
   snapshot, and the engine/fleet stats + resolved configs.

Prints one check_regression-format JSON line (``metric: incident_smoke``,
``value`` 1.0 = both promises held); exit 0, or an assertion failure.

::

    JAX_PLATFORMS=cpu python scripts/incident_smoke.py --tiny
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
    p = argparse.ArgumentParser(description="incident-engine chaos drill")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/counts (the tier-1 CPU drill)")
    p.add_argument("--requests", type=int, default=None,
                   help="open-loop requests through the cascade")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (telemetry + incident bundles) "
                        "under DIR instead of a temp dir")
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
    n_requests = args.requests or (12 if args.tiny else 48)
    workdir = args.keep or tempfile.mkdtemp(prefix="raft-incident-smoke-")
    os.makedirs(workdir, exist_ok=True)
    telem_dir = os.path.join(workdir, "telemetry")
    env_prev = os.environ.get("RAFT_TELEMETRY_DIR")
    os.environ["RAFT_TELEMETRY_DIR"] = telem_dir

    import jax
    import numpy as np

    from raft_tpu import chaos
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.obs import events, trace
    from raft_tpu.serve import (FleetConfig, FlowRouter, ReplicaFleet,
                                RouterConfig, ServeConfig)

    events.reset_default_sink()   # re-bind to the drill's telemetry dir
    sink = events.default_sink()
    # Full-rate tracing so the cascade's request trees land in the
    # stream (and therefore in the bundle's traces.jsonl).
    trace.configure(sample_rate=1.0, seed=args.seed, sink=sink)

    model_cfg = RAFTConfig.small_model()  # fp32: CPU-friendly
    shape = (36, 52)  # -> bucket (40, 56)
    model_img = jax.numpy.zeros((1, 40, 56, 3))
    k = jax.random.PRNGKey(args.seed)
    variables = RAFT(model_cfg).init({"params": k, "dropout": k},
                                     model_img, model_img, iters=1)

    quiet_s = 1.5 if args.tiny else 3.0
    serve_cfg = ServeConfig(
        iters=2, max_batch=2, batch_sizes=(2,), max_wait_ms=5,
        max_queue=64, stall_timeout_s=30.0,
        # enough headroom for the 3-error device_err burst
        device_retries=5, retry_backoff_s=0.01, retry_backoff_max_s=0.05,
        # SLOs at seconds scale (scaled_policy inside the tracker)
        slo_availability_target=0.99, slo_latency_target_ms=5000.0,
        slo_window_s=30.0,
        # the tentpole under test
        incidents=True, incident_window_s=10.0, incident_quiet_s=quiet_s,
        incident_cooldown_s=60.0)
    fleet = ReplicaFleet(
        variables, model_cfg, serve_cfg,
        FleetConfig(replicas=2, warmup_shapes=(shape,),
                    restart_backoff_s=0.05, restart_backoff_max_s=0.5,
                    health_poll_s=0.05,
                    aot_dir=os.path.join(workdir, "aot")),
        sink=sink)  # one stream: fleet + engines + tracer + chaos
    fleet.start()
    router = FlowRouter(fleet, RouterConfig())
    checks = {}
    rng = np.random.default_rng(args.seed)

    def frame():
        return rng.uniform(0, 255, shape + (3,)).astype(np.float32)

    def incidents_snap():
        return fleet.stats()["fleet"]["incidents"]

    try:
        # -- 1. quiet baseline: zero incidents, zero added compiles ---
        r0, r1 = fleet.replicas
        for _ in range(4):
            flow = router.infer(frame(), frame(), timeout=120)
            assert flow.shape == shape + (2,)
        counts0 = dict(r0.engine.compile_counter.counts())
        assert r1.engine.compile_counter.counts() == {}, \
            "replica 1 compiled despite AOT import (SLO/incident " \
            "layers must not add compiles)"
        for _ in range(4):
            router.infer(frame(), frame(), timeout=120)
        assert dict(r0.engine.compile_counter.counts()) == counts0, \
            "steady-state request compiled with incident engine on"
        snap = incidents_snap()
        assert snap["opened"] == 0 and snap["open"] is None, \
            f"quiet baseline opened an incident: {snap}"
        assert not os.path.isdir(os.path.join(telem_dir, "incidents")), \
            "bundle directory created with no incident"
        checks["quiet_baseline"] = {"requests": 8, "incidents": 0}

        # -- 2. the cascade: kill + device-error burst under load -----
        # p-based one-shot kill (the baseline already advanced the
        # batch counters, so a batch=N trigger would never match).
        chaos.install(chaos.FaultPlan.parse(
            "replica_kill@p=1,times=1;device_err@p=1,times=3",
            seed=args.seed))
        futures = []
        for _ in range(n_requests):
            futures.append(router.submit(frame(), frame()))
            time.sleep(0.01)  # open loop: arrivals keep coming
        results = [f.result(timeout=120) for f in futures]
        chaos.uninstall()
        assert all(r.shape == shape + (2,) for r in results), \
            "a request accepted before the cascade never produced flow"
        rstats = router.router_stats()
        assert rstats["dropped_total"] == 0, rstats
        _wait_for(lambda: sum(r.restarts for r in fleet.replicas) >= 1
                  and all(r.state == "ready" for r in fleet.replicas),
                  30, "supervised restart of the killed replica")

        # -- 3. exactly ONE correlated incident, then quiet close -----
        _wait_for(lambda: incidents_snap()["opened"] >= 1
                  and incidents_snap()["open"] is None,
                  30, "the incident to open and quiet-close")
        snap = incidents_snap()
        assert snap["opened"] == 1, \
            f"cascade must correlate into ONE incident, got {snap}"

        inc_root = os.path.join(telem_dir, "incidents")
        bundles = sorted(os.listdir(inc_root))
        assert len(bundles) == 1, f"expected 1 bundle, got {bundles}"
        bdir = os.path.join(inc_root, bundles[0])

        def bundle_written():
            # the manager's state closes before its writer thread has
            # rewritten the bundle; stats.json is the last file of the
            # final pass, so once it parses every file is whole
            try:
                with open(os.path.join(bdir, "stats.json")) as f:
                    json.load(f)
                return True
            except (OSError, ValueError):
                return False

        _wait_for(bundle_written, 30, "the closed incident's bundle")
        with open(os.path.join(bdir, "incident.json")) as f:
            inc = json.load(f)
        assert inc["status"] == "closed", inc
        signals = {s["event"] for s in inc["signals"]}
        assert "serve_retry" in signals, \
            f"device_err burst missing from signals: {sorted(signals)}"
        assert signals & {"replica_crash", "fleet_restart"}, \
            f"replica_kill arc missing from signals: {sorted(signals)}"
        with open(os.path.join(bdir, "events.jsonl")) as f:
            window = [json.loads(l) for l in f]
        assert window, "bundle event window is empty"
        with open(os.path.join(bdir, "traces.jsonl")) as f:
            spans = [json.loads(l) for l in f]
        assert spans, "bundle captured no trace tree"
        with open(os.path.join(bdir, "stats.json")) as f:
            stats = json.load(f)
        assert "fleet_stats" in stats and "fleet_config" in stats, \
            f"stats snapshot incomplete: {sorted(stats)}"
        checks["cascade"] = {
            "incident": inc["id"], "severity": inc["severity"],
            "signals": [s["event"] for s in inc["signals"]],
            "window_events": len(window), "trace_spans": len(spans)}

        # -- 4. post-cascade: still serving, still zero compiles ------
        restarted = next(r for r in fleet.replicas if r.restarts)
        flow = router.infer(frame(), frame(), timeout=120)
        assert flow.shape == shape + (2,)
        assert restarted.engine.compile_counter.counts() == {}, \
            "restarted replica compiled (AOT import must still hold)"
        slo = fleet.replicas[0].engine.stats()["slo"]
        assert "availability" in slo, slo
        checks["post_cascade"] = {
            "restarts": {r.name: r.restarts for r in fleet.replicas},
            "availability_budget": slo["availability"][
                "budget_remaining"]}
        # Gate producers: check_regression.py --max-incidents SEV:N
        # reads config.incidents (severity -> count) and --max-slo-burn
        # NAME:RATE reads config.slo_burn_rates (name -> burn rate).
        checks["incidents"] = {inc["severity"]: 1}
        checks["slo_burn_rates"] = {
            name: entry["burn_rate"] for name, entry in slo.items()}
        ok = True
    finally:
        chaos.uninstall()
        fleet.stop()
        trace.reset_default_tracer()
        events.reset_default_sink()
        if env_prev is None:
            os.environ.pop("RAFT_TELEMETRY_DIR", None)
        else:
            os.environ["RAFT_TELEMETRY_DIR"] = env_prev

    print(json.dumps({
        "metric": "incident_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": dict(checks, requests=n_requests, replicas=2,
                       workdir=workdir if args.keep else None),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
