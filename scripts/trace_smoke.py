"""Distributed-tracing chaos drill: prove a hedged request yields ONE
trace tree (tier-1, CPU).

Brings up a 2-replica :class:`raft_tpu.serve.ReplicaFleet` behind the
hedging :class:`raft_tpu.serve.FlowRouter` with tracing at sample rate
1.0, makes the primary replica a straggler with the ``replica_slow``
chaos fault, and walks the promises docs/OBSERVABILITY.md's tracing
section makes:

1. **One tree per request**: the straggler fires the router's hedge —
   the request runs on BOTH replicas — yet the telemetry stream
   reconstructs to a single trace tree: one ``route`` root with two
   ``attempt`` subtrees (``hedge=false`` loser, ``hedge=true`` winner),
   each carrying its replica's ``queue``/``pad``/``device`` spans.
   The loser's spans land AFTER the root flushed (the straggler batch
   ends seconds later) — the late-span path must stitch them in.
2. **Critical path attribution**: scripts/trace_report.py's backward
   walk bottoms out in the WINNER's ``device`` span; the loser (which
   ends after the root) is excluded.
3. **Exports hold**: the tree round-trips through the Perfetto
   ``trace_event`` export and the bench-record fold
   (``critical_path_ms`` + full ``queue``/``pad``/``device`` span
   coverage, the shape scripts/check_regression.py gates on).

Prints one check_regression-format JSON line (``metric: trace_smoke``,
``value`` 1.0 = every promise held); exit 0, or an assertion failure.

::

    JAX_PLATFORMS=cpu python scripts/trace_smoke.py --tiny
"""

from __future__ import annotations

import argparse
import importlib.util
import json
import os
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description="distributed-tracing drill")
    p.add_argument("--tiny", action="store_true",
                   help="smallest shapes/counts (the tier-1 CPU drill)")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--keep", default=None, metavar="DIR",
                   help="keep artifacts (telemetry, AOT dir, Perfetto "
                        "export) under DIR instead of a temp dir")
    return p.parse_args(argv)


def _load_report():
    spec = importlib.util.spec_from_file_location(
        "trace_report", os.path.join(REPO, "scripts", "trace_report.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


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
    workdir = args.keep or tempfile.mkdtemp(prefix="raft-trace-smoke-")
    telem_dir = os.path.join(workdir, "telemetry")
    os.makedirs(telem_dir, exist_ok=True)

    import jax
    import numpy as np

    from raft_tpu import chaos
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.obs import EventSink, trace
    from raft_tpu.serve import (FleetConfig, FlowRouter, ReplicaFleet,
                                RouterConfig, ServeConfig)

    model_cfg = RAFTConfig.small_model()  # fp32: CPU-friendly
    if args.tiny:
        shape = (36, 52)      # -> bucket (40, 56): the tier-1 drill
        n_followups = 2
    else:
        shape = (68, 100)     # -> bucket (72, 104): heavier soak
        n_followups = 6
    bucket = tuple(-(-s // 8) * 8 for s in shape)
    model_img = jax.numpy.zeros((1,) + bucket + (3,))
    k = jax.random.PRNGKey(args.seed)
    variables = RAFT(model_cfg).init({"params": k, "dropout": k},
                                     model_img, model_img, iters=1)

    sink = EventSink(telem_dir)
    trace.configure(sample_rate=1.0, sink=sink)

    # The straggler sleep (3 s) dwarfs the hedge timer (0.25 s): the
    # hedge fires onto the sibling replica, which answers long before
    # the straggler — same proven geometry as test_fleet.py's drill.
    serve_cfg = ServeConfig(iters=2, max_batch=2, batch_sizes=(2,),
                            max_wait_ms=5, max_queue=64,
                            stall_timeout_s=30.0, chaos_slow_s=3.0)
    fleet = ReplicaFleet(
        variables, model_cfg, serve_cfg,
        FleetConfig(replicas=2, warmup_shapes=(shape,),
                    restart_backoff_s=0.05, restart_backoff_max_s=0.5,
                    health_poll_s=0.05,
                    aot_dir=os.path.join(workdir, "aot")))
    fleet.start()
    router = FlowRouter(fleet, RouterConfig(hedge_timeout_s=0.25))
    checks = {}
    rng = np.random.default_rng(args.seed)

    def frame():
        return rng.uniform(0, 255, shape + (3,)).astype(np.float32)

    report = _load_report()

    def span_count(name):
        sink.flush()
        try:
            return sum(1 for s in report.load_spans(telem_dir)
                       if s.get("name") == name)
        except SystemExit:  # no .jsonl file yet
            return 0

    try:
        # -- the hedged request ---------------------------------------
        chaos.install(chaos.FaultPlan.parse("replica_slow@batch=1",
                                            seed=args.seed))
        t0 = time.perf_counter()
        flow = router.infer(frame(), frame(), timeout=60)
        dt = time.perf_counter() - t0
        chaos.uninstall()
        assert flow.shape == shape + (2,)
        assert dt < 2.5, f"hedge did not cover the {dt:.1f}s straggler"
        # The router settles the caller's future FIRST and bumps its
        # win/latency counters right after, on the replica's callback
        # thread — infer() can return before the counter moves.
        _wait_for(lambda: router.router_stats()["hedge_wins_total"] == 1,
                  5, "hedge win never counted")
        rstats = router.router_stats()
        assert rstats["hedges_total"] == 1, rstats
        assert rstats["hedge_wins_total"] == 1, rstats

        # The loser attempt (and its queue/pad/device spans) only ends
        # when the straggler batch wakes up — wait for BOTH attempt
        # subtrees to reach the stream before reconstructing.
        _wait_for(lambda: span_count("attempt") >= 2, 30,
                  "both attempt spans (incl. the straggler's late one)")
        # a few untraced-path-free normal requests for stats depth
        for _ in range(n_followups):
            router.infer(frame(), frame(), timeout=60)
        _wait_for(lambda: span_count("route") >= 1 + n_followups, 30,
                  "the follow-up request roots")
        sink.flush()

        # -- 1. one tree, two attempts --------------------------------
        traces = report.build_traces(report.load_spans(telem_dir))
        hedged = [t for t in traces.values()
                  if report.root_of(t) is not None
                  and report.root_of(t).get("hedged")]
        assert len(hedged) == 1, \
            f"expected exactly one hedged trace, got {len(hedged)} " \
            f"of {len(traces)} total"
        tree = hedged[0]
        root = report.root_of(tree)
        assert root["name"] == "route", root
        attempts = [s for s in tree["spans"].values()
                    if s["name"] == "attempt"]
        assert len(attempts) == 2, attempts
        assert {a.get("hedge") for a in attempts} \
            == {True, False}, attempts
        assert {a.get("replica") for a in attempts} \
            == {"r0", "r1"}, attempts
        for a in attempts:  # each subtree carries its engine spans
            kids = {c["name"]
                    for c in tree["children"].get(a["span_id"], [])}
            assert {"queue", "pad", "device"} <= kids, (a, kids)
            dev = next(c for c in tree["children"][a["span_id"]]
                       if c["name"] == "device")
            assert {c["name"] for c in tree["children"].get(
                dev["span_id"], [])} == {"h2d", "launch", "drain"}, dev
        winner = next(a for a in attempts if a.get("won"))
        loser = next(a for a in attempts if not a.get("won"))
        assert winner["hedge"] is True
        checks["one_tree"] = {
            "trace_id": root["trace_id"], "spans": len(tree["spans"]),
            "winner_replica": winner["replica"],
            "loser_dur_s": round(loser["dur_s"], 2)}

        # -- 2. critical path bottoms out in the winner's device ------
        path = report.critical_path(tree)
        names = [rec["name"] for rec, _ in path]
        # device's last child (the wait for the chip + copy back) ends it
        assert names[0] == "route" \
            and names[-2:] == ["device", "drain"], names
        assert winner["span_id"] in [rec["span_id"] for rec, _ in path], \
            f"critical path skipped the hedge winner: {names}"
        assert loser["span_id"] not in [rec["span_id"] for rec, _ in
                                        path], \
            "the straggler (ends after the root) is on the critical path"
        report.print_waterfall(tree, out=sys.stderr)
        checks["critical_path"] = [
            f"{rec['name']}:{ms:.1f}ms" for rec, ms in path]

        # -- 3. exports: Perfetto + gateable bench record -------------
        events = report.perfetto_events(traces)
        out_json = os.path.join(workdir, "trace.perfetto.json")
        with open(out_json, "w") as f:
            json.dump(events, f)
        with open(out_json) as f:
            loaded = json.load(f)
        assert any(e.get("ph") == "X" for e in loaded["traceEvents"])
        rec = report.bench_record(traces)
        cov = set(rec["config"]["serve_span_names"])
        assert {"queue", "pad", "device"} <= cov, cov
        assert rec["config"]["critical_path_ms"].get("device", 0) > 0
        checks["exports"] = {
            "perfetto_events": len(loaded["traceEvents"]),
            "traces_total": rec["value"],
            "critical_path_ms": rec["config"]["critical_path_ms"]}
        ok = True
    finally:
        chaos.uninstall()
        fleet.stop(drain=False)  # never wait out a chaos straggler
        trace.reset_default_tracer()
        sink.close()

    print(json.dumps({
        "metric": "trace_smoke",
        "value": 1.0 if ok else 0.0,
        "unit": "pass",
        "vs_baseline": 0.0,
        "config": dict(checks, replicas=2,
                       workdir=workdir if args.keep else None),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
