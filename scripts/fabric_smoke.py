"""Multi-host fabric drill: partition -> failover -> heal -> rejoin,
queue pressure -> scale-up, idle -> graceful scale-down (tier-1, CPU).

Brings up a hybrid fleet — one local replica plus one REMOTE replica
(a second engine in this process behind a real loopback HTTP server,
fronted by :class:`raft_tpu.serve.RemoteEngine`) — behind the
health-gated :class:`raft_tpu.serve.FlowRouter`, with signal-driven
elastic autoscaling on, and walks the four promises docs/SERVING.md's
"Multi-host fabric" section makes:

1. **Partition tolerance**: a deterministic ``net_partition`` chaos
   fault (``serve.remote`` seam) makes every wire operation to the
   remote time out.  Every request accepted during the partition still
   resolves (failover to the local replica,
   ``raft_fleet_dropped_total == 0``) and the whole cascade correlates
   into ONE incident (obs/incident.py).
2. **Heal -> rejoin**: when the fault plan's ``heal=`` ordinal passes,
   the supervisor observes the down->up health transition and REJOINS
   the remote — generation bump + breaker reset
   (``fleet_remote_rejoin``), after which bucket-affine traffic routes
   to it again.
3. **Elastic scale-up**: sustained queue pressure past
   ``autoscale_up_queue_frac`` for ``autoscale_up_consecutive`` ticks
   grows the fleet by exactly ONE local replica (hysteresis + cooldown:
   no flapping).
4. **Graceful scale-down**: when the fleet goes idle the autoscaler
   drains the newest local replica — its streaming session is migrated
   to a sibling first (``stream_restart reason=scale_down`` replay),
   in-flight work drains, and the stream continues with monotone frame
   numbering.  Zero dropped requests across the whole drill.

Prints one check_regression-format JSON line (``metric: fabric_smoke``,
``value`` 1.0 = every promise held) whose config carries the
``scale_flaps`` / ``net_retry_rate`` keys the
``check_regression.py --max-scale-flaps / --max-net-retry-rate`` gates
read; exit 0, or an assertion failure.

::

    JAX_PLATFORMS=cpu python scripts/fabric_smoke.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time
import zlib

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="multi-host fabric drill")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/counts (the tier-1 CPU drill)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (AOT dir, telemetry) under DIR "
                        "instead of a temp dir")
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
    # --tiny is the tier-1 CPU profile: the smallest burst/backlog
    # that still drives every phase transition.  The default profile
    # doubles the load for a longer soak on real hosts.
    burst_n = 6 if args.tiny else 12
    press_n = 4 if args.tiny else 8
    backlog_cap = 24 if args.tiny else 48
    workdir = args.keep or tempfile.mkdtemp(prefix="raft-fabric-smoke-")
    os.makedirs(workdir, exist_ok=True)
    os.environ.setdefault("RAFT_TELEMETRY_DIR",
                          os.path.join(workdir, "telemetry"))

    import jax
    import numpy as np

    from raft_tpu import chaos
    from raft_tpu.cli.serve import make_server
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.obs import EventSink
    from raft_tpu.serve import (FleetConfig, FlowRouter, InferenceEngine,
                                QueueFullError, RemoteConfig,
                                ReplicaFleet, RouterConfig, ServeConfig)

    model_cfg = RAFTConfig.small_model()  # fp32: CPU-friendly
    # Bucket (56, 40) is the drill's keystone: crc32 % 2 == 1 routes it
    # to the REMOTE replica (index 1) in the 2-replica fleet, and
    # crc32 % 3 == 2 pins the phase-4 stream to the SCALED-UP replica
    # (index 2) in the 3-replica fleet.
    shape = (52, 36)  # -> bucket (56, 40)
    bucket = (56, 40)
    assert zlib.crc32(repr(bucket).encode()) % 2 == 1
    assert zlib.crc32(repr(bucket).encode()) % 3 == 2
    model_img = jax.numpy.zeros((1,) + bucket + (3,))

    k = jax.random.PRNGKey(args.seed)
    variables = RAFT(model_cfg).init({"params": k, "dropout": k},
                                     model_img, model_img, iters=1)

    # ---- the "other host": a real engine behind a loopback server ----
    # Deliberately heterogeneous: max_queue=8 vs the locals' 32, so the
    # router's spill math must read THIS replica's capacity through the
    # queue_capacity() facade rather than the shared ServeConfig.
    remote_serve_cfg = ServeConfig(
        iters=2, batching="slot", slots=2, max_wait_ms=5, max_queue=8,
        stall_timeout_s=30.0, chaos_slow_s=0.05)
    server_engine = InferenceEngine(variables, model_cfg,
                                    remote_serve_cfg)
    server_engine.start()
    server_engine.warmup([shape])
    server = make_server(server_engine, "127.0.0.1", 0)
    port = server.server_address[1]
    threading.Thread(target=server.serve_forever, daemon=True).start()

    # ---- the fleet: 1 local + 1 remote, autoscaling 1..2 locals ------
    serve_cfg = ServeConfig(
        iters=2, batching="slot", slots=2, max_wait_ms=5, max_queue=32,
        stall_timeout_s=30.0, chaos_slow_s=0.05,
        # ONE incident must span the whole drill: generous correlation
        # window, and a quiet-close threshold longer than the drill.
        incidents=True, incident_window_s=60.0, incident_quiet_s=120.0)
    rcfg = RemoteConfig(connect_timeout_s=0.5, request_timeout_s=30.0,
                        health_timeout_s=0.5, health_cache_s=0.25,
                        max_queue=8, workers=8)
    sink = EventSink.from_env()
    seen: list = []
    sink.add_observer(seen.append)

    def events(name):
        return [r for r in seen if r.get("event") == name]

    fleet = ReplicaFleet(
        variables, model_cfg, serve_cfg,
        FleetConfig(replicas=1, remote=(f"127.0.0.1:{port}",),
                    remote_cfg=rcfg, warmup_shapes=(shape,),
                    restart_backoff_s=0.05, restart_backoff_max_s=0.5,
                    health_poll_s=0.05,
                    autoscale_min=1, autoscale_max=2,
                    autoscale_interval_s=0.2,
                    autoscale_up_queue_frac=0.25,
                    autoscale_down_queue_frac=0.02,
                    autoscale_up_consecutive=2,
                    autoscale_down_consecutive=3,
                    autoscale_cooldown_s=2.0,
                    aot_dir=os.path.join(workdir, "aot")),
        sink=sink)
    fleet.start()
    router = FlowRouter(fleet, RouterConfig(breaker_threshold=2,
                                            breaker_cooldown_s=0.5),
                        sink=sink)
    checks = {}
    rng = np.random.default_rng(args.seed)

    def frame():
        return rng.uniform(0, 255, shape + (3,)).astype(np.float32)

    def autoscale():
        return fleet.stats()["fleet"]["autoscale"]

    try:
        r0, r1 = fleet.replicas
        assert getattr(r1, "is_remote", False)

        # -- 1a. pre-partition: the remote serves affine traffic ------
        for _ in range(3):
            flow = router.infer(frame(), frame(), timeout=120)
            assert flow.shape == shape + (2,)
        rstats = router.router_stats()
        assert rstats["requests_by_replica"].get("r1", 0) >= 1, \
            f"bucket {bucket} never routed to the remote: {rstats}"
        assert r1.queue_capacity() == 8, \
            "heterogeneous remote capacity not visible via the facade"
        gen0 = r1.generation

        # -- 1b. partition: failover, zero drops ----------------------
        # The partition starts one wire operation after it is installed.
        # The router reads r1's health (cached health_cache_s) before
        # every pick; on a loaded host the cache has run out by now, and
        # a refresh that met the partition would sideline r1 before any
        # request reached it: nothing dropped, and no failover to count.
        # This way whoever goes first gets through — a refresh leaves the
        # cache fresh for the burst's picks, a request is served — and
        # the next request to r1 meets the partition.
        heal_at = 17
        chaos.install(chaos.FaultPlan.parse(
            f"net_partition@step=1,heal={heal_at}", seed=args.seed))
        futures = [router.submit(frame(), frame())
                   for _ in range(burst_n)]
        results = [f.result(timeout=120) for f in futures]
        assert all(r.shape == shape + (2,) for r in results), \
            "a request accepted during the partition never resolved"
        rstats = router.router_stats()
        assert rstats["dropped_total"] == 0, rstats
        assert rstats["failovers_total"] >= 1, \
            f"partition fired but no failover recorded: {rstats}"
        assert len(events("net_retry")) >= 1, \
            "no net_retry event emitted during the partition"

        # -- 2. heal -> rejoin (generation-guarded breaker reset) -----
        _wait_for(lambda: r1.generation > gen0, 30,
                  "the healed remote to rejoin the fleet")
        rejoins = events("fleet_remote_rejoin")
        assert rejoins and rejoins[-1]["replica"] == "r1", rejoins
        chaos.uninstall()
        _wait_for(r1.eligible, 10, "the rejoined remote to pass the "
                                   "health gate")
        before = router.router_stats()["requests_by_replica"].get(
            "r1", 0)
        for _ in range(2):
            router.infer(frame(), frame(), timeout=120)
        after = router.router_stats()["requests_by_replica"].get(
            "r1", 0)
        assert after > before, \
            "affine traffic did not return to the healed remote"
        checks["partition"] = {
            "failovers": rstats["failovers_total"],
            "net_retries": len(events("net_retry")),
            "rejoin_generation": r1.generation}

        # -- 3. queue pressure -> exactly one scale-up ----------------
        # Every device batch is made a (short) straggler while the
        # pressure is on, so the backlog builds on ANY host: left to
        # itself the tiny model drains its queue as fast as one thread
        # can submit on a many-core machine, and the drill then measures
        # the host, not the autoscaler.
        chaos.install(chaos.FaultPlan.parse("replica_slow@p=1.0",
                                            seed=args.seed))
        futures = []
        deadline = time.time() + 20
        while autoscale()["ups"] < 1:
            assert time.time() < deadline, \
                f"no scale-up under sustained load: {autoscale()}"
            for _ in range(press_n):
                try:
                    futures.append(router.submit(frame(), frame()))
                except QueueFullError:
                    time.sleep(0.01)  # shed, not dropped: retry later
            if sum(not f.done() for f in futures) > backlog_cap:
                time.sleep(0.005)
        chaos.uninstall()
        results = [f.result(timeout=120) for f in futures]
        assert all(r.shape == shape + (2,) for r in results)
        scales = events("fleet_scale")
        assert [e["direction"] for e in scales] == ["up"], scales
        assert len(fleet.replicas) == 3, \
            [r.name for r in fleet.replicas]
        r2 = fleet.replicas[-1]
        assert r2.name == "r2" and r2.state == "ready"
        assert router.router_stats()["dropped_total"] == 0
        checks["scale_up"] = {
            "requests": len(futures),
            "signals": scales[0]["signals"],
            "seconds": scales[0]["seconds"]}

        # -- 4. stream + idle -> graceful scale-down ------------------
        out = router.stream_ingest("cam0", frame(), timeout=120)
        assert out["frame"] == 0 and out["flow"] is None
        assert router._streams["cam0"].replica == "r2", \
            "stream did not open on the scale-up replica"
        out = router.stream_ingest("cam0", frame(), timeout=120)
        assert out["frame"] == 1 and out["flow"] is not None
        _wait_for(lambda: autoscale()["downs"] >= 1, 30,
                  "the idle fleet to scale down")
        scales = events("fleet_scale")
        assert [e["direction"] for e in scales] == ["up", "down"], \
            scales
        down = scales[-1]
        assert down["replica"] == "r2" and down["moved"] == 1, down
        rst = events("stream_restart")
        assert rst and rst[-1]["reason"] == "scale_down", rst
        assert rst[-1]["from_replica"] == "r2", rst
        assert len(fleet.replicas) == 2, \
            [r.name for r in fleet.replicas]
        # The migrated stream keeps going: next frame is the cold pair
        # on the new owner, monotone frame numbering intact.
        out = router.stream_ingest("cam0", frame(), timeout=120)
        assert out["frame"] == 2 and out["flow"] is not None
        summary = router.stream_close("cam0")
        assert summary["restarts"] >= 1, summary
        auto = autoscale()
        assert auto["ups"] == 1 and auto["downs"] == 1, auto
        assert auto["flaps"] <= 1, auto
        checks["scale_down"] = {
            "victim": down["replica"], "streams_moved": down["moved"],
            "stream_restarts": summary["restarts"]}

        # -- fleet-wide invariants ------------------------------------
        rstats = router.router_stats()
        assert rstats["dropped_total"] == 0, rstats
        incidents = fleet.stats()["fleet"]["incidents"]
        assert incidents["opened"] == 1, \
            f"the drill must correlate into ONE incident: {incidents}"
        signals = set((incidents.get("open") or {}).get("signals", ()))
        assert "net_retry" in signals and "stream_restart" in signals, \
            f"fabric signals did not correlate: {sorted(signals)}"
        mt = fleet.metrics_text()
        assert "raft_fleet_scale_events_total" in mt
        assert 'raft_remote_net_errors_total' in mt
        net_retries = len(events("net_retry"))
        requests = rstats["requests_total"]
        ok = True
    finally:
        chaos.uninstall()
        fleet.stop(drain=False)
        server.shutdown()
        server_engine.stop(drain=False)

    net_retry_rate = round(100.0 * net_retries / max(requests, 1), 2)
    print(json.dumps({
        "metric": "fabric_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": {
            **checks,
            "requests": requests,
            "failovers": rstats["failovers_total"],
            "dropped": rstats["dropped_total"],
            "fleet_scale": {"ups": auto["ups"], "downs": auto["downs"],
                            "flaps": auto["flaps"]},
            "scale_flaps": auto["flaps"],
            "net_retry_total": net_retries,
            "net_retry_rate": net_retry_rate,
            "incidents_opened": incidents["opened"],
            "workdir": workdir if args.keep else None},
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
