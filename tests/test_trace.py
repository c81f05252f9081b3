"""Distributed-tracing tests (tier-1): span-tree round-trip through an
:class:`EventSink`-shaped sink, deterministic head sampling, tail-based
keep (error status / device retries / late non-finite verdicts), wire
header round-trip, ID propagation across the engine's dispatcher and
device threads, the router's hedge+failover single-tree invariant, the
zero-overhead contract at ``sample_rate=0``, and the
``scripts/trace_report.py`` / ``scripts/trace_smoke.py`` ``--tiny``
round-trips.

Budget discipline mirrors test_fleet.py: ONE engine compiles the single
``(40, 56) x b2`` program (module-scoped ``aot_dir``); every engine and
fleet in the file imports that artifact."""

import importlib.util
import json
import os.path as osp
import random
import time

import numpy as np
import pytest

from raft_tpu import chaos
from raft_tpu.config import RAFTConfig
from raft_tpu.obs import stages, trace
from raft_tpu.serve import (FleetConfig, FlowRouter, InferenceEngine,
                            ReplicaFleet, RouterConfig, ServeConfig)

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))

CFG = RAFTConfig.small_model()  # fp32: CPU-friendly
ITERS = 2
SHAPE = (36, 52)                # -> bucket (40, 56)


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _serve_cfg(**kw):
    base = dict(iters=ITERS, max_batch=2, batch_sizes=(2,),
                max_wait_ms=5, max_queue=64)
    base.update(kw)
    return ServeConfig(**base)


def _images(rng, h=SHAPE[0], w=SHAPE[1]):
    return (rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            rng.uniform(0, 255, (h, w, 3)).astype(np.float32))


def _wait_for(pred, timeout_s, what):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        if pred():
            return
        time.sleep(0.02)
    raise AssertionError(f"timed out waiting for {what}")


class _ListSink:
    """EventSink-shaped sink capturing records in-process."""

    def __init__(self):
        self.records = []

    def emit(self, event, **fields):
        self.records.append(dict(event=event, **fields))

    def spans(self, name=None):
        return [r for r in self.records
                if r["event"] == trace.EVENT
                and (name is None or r["name"] == name)]

    def flush(self):
        pass

    def close(self):
        pass


@pytest.fixture(autouse=True)
def _clean_process_state():
    chaos.uninstall()
    trace.reset_default_tracer()
    yield
    chaos.uninstall()
    trace.reset_default_tracer()
    trace.set_active_profile(None)


@pytest.fixture(scope="module")
def variables():
    import jax

    from raft_tpu.models.raft import RAFT

    model_img = jax.numpy.zeros((1, 40, 56, 3))
    rng = jax.random.PRNGKey(0)
    return RAFT(CFG).init({"params": rng, "dropout": rng},
                          model_img, model_img, iters=1)


@pytest.fixture(scope="module")
def aot_dir(variables, tmp_path_factory):
    """The file's ONE compile: warm a throwaway engine and export."""
    d = str(tmp_path_factory.mktemp("aot"))
    eng = InferenceEngine(variables, CFG, _serve_cfg())
    eng.start()
    try:
        eng.warmup([SHAPE])
        eng.export_aot(d)
    finally:
        eng.stop()
    return d


def _mk_engine(variables, aot_dir, **scfg_kw):
    return InferenceEngine(variables, CFG,
                           _serve_cfg(aot_dir=aot_dir, **scfg_kw))


def _mk_fleet(variables, aot_dir, *, scfg=None, **fcfg_kw):
    kw = dict(replicas=2, aot_dir=aot_dir, warmup_shapes=(SHAPE,),
              auto_export_aot=False, restart_backoff_s=0.05,
              restart_backoff_max_s=0.4, health_poll_s=0.05)
    kw.update(fcfg_kw)
    return ReplicaFleet(variables, CFG, scfg or _serve_cfg(),
                        FleetConfig(**kw))


# ---------------------------------------------------------------------------
# core API: tree round-trip, sampling, tail-keep, wire header
# ---------------------------------------------------------------------------


def test_span_tree_round_trip():
    sink = _ListSink()
    tracer = trace.Tracer(sink=sink, sample_rate=1.0)
    root = tracer.start_trace("req", bucket="40x56")
    child = root.child("queue")
    child.end()
    with trace.use_context(root):
        with trace.trace_span("pad", real=2) as pad:
            assert trace.current() is pad
    assert not sink.spans(), "nothing may emit before the root closes"
    root.end(hedged=False)
    recs = sink.spans()
    assert [r["name"] for r in recs] == ["queue", "pad", "req"]
    assert len({r["trace_id"] for r in recs}) == 1
    by_name = {r["name"]: r for r in recs}
    assert by_name["queue"]["parent_id"] == by_name["req"]["span_id"]
    assert by_name["pad"]["parent_id"] == by_name["req"]["span_id"]
    assert by_name["req"]["parent_id"] is None
    assert by_name["pad"]["real"] == 2        # attrs flatten into the
    assert by_name["req"]["hedged"] is False  # record (end() kwargs too)
    assert all(r["dur_s"] >= 0 for r in recs)


def test_sampling_deterministic_at_fixed_seed():
    def verdicts(n=32):
        sink = _ListSink()
        tracer = trace.Tracer(sink=sink, sample_rate=0.3, seed=42)
        out = []
        for i in range(n):
            before = len(sink.spans())
            tracer.start_trace("t", i=i).end()
            out.append(len(sink.spans()) > before)
        return out

    a, b = verdicts(), verdicts()
    assert a == b, "same seed must sample the same traces"
    assert True in a and False in a, "0.3 over 32 coins hits both ways"
    # and the coin IS the seeded PRNG stream — pinned, not incidental
    rnd = random.Random(42)
    assert a == [rnd.random() < 0.3 for _ in range(32)]


def test_tail_keep_error_and_late_recovery():
    sink = _ListSink()
    # seed 0's first coins all miss a 0.001 rate: heads-dropped traces
    tracer = trace.Tracer(sink=sink, sample_rate=0.001, seed=0)

    # an error status forces the trace out despite the dropped coin
    root = tracer.start_trace("req")
    root.child("device").end(status="error", error="boom")
    root.end(status="error", error="boom")
    assert [r["name"] for r in sink.spans()] == ["device", "req"]

    # a clean dropped trace parks in the ring ...
    sink.records.clear()
    tracer.start_trace("train_step", step=7).end()
    tracer.start_trace("train_step", step=8).end()
    assert not sink.spans()
    # ... until a late verdict (non-finite at step 8) recovers it
    assert tracer.emit_recent_dropped(steps=[8]) == 1
    recs = sink.spans("train_step")
    assert len(recs) == 1 and recs[0]["step"] == 8


def test_wire_header_round_trip():
    tracer = trace.Tracer(sink=_ListSink(), sample_rate=1.0)
    span = tracer.start_trace("route")
    hdr = trace.format_header(span)
    tid, parent, sampled = trace.parse_header(hdr)
    assert (tid, parent, sampled) == (span.trace_id, span.span_id, True)
    for bad in (None, "", "x", "a-b", "a-b-c-d", "zz-yy-s",
                f"{span.trace_id}-{span.span_id}-q"):
        assert trace.parse_header(bad) is None
    assert trace.format_header(None) is None
    assert trace.format_header(trace.NOOP_SPAN) is None
    # continuation: a downstream tracer with tracing OFF still records
    # because the upstream sampling decision rides the header
    sink2 = _ListSink()
    downstream = trace.Tracer(sink=sink2, sample_rate=0.0)
    cont = downstream.start_trace("serve_http", trace_id=tid,
                                  parent_id=parent, sampled=sampled)
    cont.end()
    recs = sink2.spans()
    assert len(recs) == 1
    assert recs[0]["trace_id"] == span.trace_id
    assert recs[0]["parent_id"] == span.span_id


def test_noop_singleton_when_disabled():
    tracer = trace.Tracer(sample_rate=0.0)
    assert not tracer.enabled
    assert tracer.start_trace("x") is trace.NOOP_SPAN
    assert tracer.begin("x") is trace.NOOP_SPAN
    assert trace.trace_span("x") is trace.NOOP_SPAN  # no context
    assert not trace.NOOP_SPAN  # falsy: `if span` guards all skip
    # the no-op absorbs the whole Span surface without allocating
    trace.NOOP_SPAN.child("y").annotate(z=1)
    trace.NOOP_SPAN.mark_keep()
    trace.NOOP_SPAN.end(status="error")
    with trace.use_context(trace.NOOP_SPAN):
        assert trace.current() is None


# ---------------------------------------------------------------------------
# engine: dispatcher -> device-thread propagation; tail-keep on chaos
# ---------------------------------------------------------------------------


def test_engine_propagates_ids_across_threads(variables, aot_dir):
    """The submitting thread's context rides the request through the
    dispatcher to the device worker: queue/pad/device land in the SAME
    trace, parented to the submitting span."""
    sink = _ListSink()
    tracer = trace.Tracer(sink=sink, sample_rate=1.0)
    eng = _mk_engine(variables, aot_dir).start()
    try:
        rng = np.random.default_rng(1)
        root = tracer.start_trace("req")
        with trace.use_context(root):
            fut = eng.submit(*_images(rng))
        flow = fut.result(timeout=60)
        assert flow.shape == SHAPE + (2,)
        root.end()
        _wait_for(lambda: len(sink.spans("device")) == 1, 10,
                  "the device worker's spans")
        by_name = {r["name"]: r for r in sink.spans()}
        assert {"queue", "pad", "device"} <= set(by_name)
        assert {r["trace_id"] for r in sink.spans()} \
            == {root.trace_id}
        for name in ("queue", "pad", "device"):
            assert by_name[name]["parent_id"] == root.span_id, name
        assert by_name["device"]["retries"] == 0
    finally:
        eng.stop()


def test_device_err_tail_keeps_trace(variables, aot_dir):
    """An injected transient ``device_err`` makes the engine retry; the
    retried batch tail-keeps the trace even though the head-sampling
    coin DROPPED it."""
    sink = _ListSink()
    tracer = trace.Tracer(sink=sink, sample_rate=0.001, seed=0)
    eng = _mk_engine(variables, aot_dir).start()
    try:
        chaos.install(chaos.FaultPlan.parse("device_err@batch=1",
                                            seed=0))
        rng = np.random.default_rng(2)
        root = tracer.start_trace("req")
        assert not root.sampled, "rate=0.001/seed=0 must drop the coin"
        with trace.use_context(root):
            fut = eng.submit(*_images(rng))
        flow = fut.result(timeout=60)
        assert flow.shape == SHAPE + (2,)
        root.end()
        _wait_for(lambda: len(sink.spans("device")) == 1, 10,
                  "the tail-kept device span")
        dev = sink.spans("device")[0]
        assert dev["retries"] >= 1, dev
        assert sink.spans("req"), "tail-keep must flush the whole tree"
    finally:
        eng.stop()


def test_zero_overhead_when_disabled(variables, aot_dir):
    """``sample_rate=0`` serves with NO span machinery: requests carry
    ``trace=None``, the default tracer hands out the no-op singleton,
    and not one trace_span event reaches the sink."""
    sink = _ListSink()
    trace.configure(sample_rate=0.0, sink=sink)
    assert trace.default_tracer().begin("route") is trace.NOOP_SPAN
    eng = _mk_engine(variables, aot_dir).start()
    try:
        rng = np.random.default_rng(3)
        before = len(stages.recent("serve"))
        fut = eng.submit(*_images(rng))
        assert fut.result(timeout=60).shape == SHAPE + (2,)
        assert not sink.spans()
        assert trace.current() is None
        # the stage clock is not the span machinery: it timed the batch
        # all the same, with no sink, context or sample rate
        _wait_for(lambda: len(stages.recent("serve")) == before + 1, 10,
                  "the batch's stage record")
        assert stages.recent("serve")[-1]["real"] == 1
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# router: hedge + failover keep ONE tree per request
# ---------------------------------------------------------------------------


def test_router_failover_one_tree(variables, aot_dir):
    """``replica_kill`` fails the first attempt; the router fails over.
    The trace reconstructs as ONE tree: a ``route`` root with TWO
    attempt subtrees — the error loser and the winner — and the error
    status tail-keeps it past the dropped sampling coin."""
    sink = _ListSink()
    trace.configure(sample_rate=0.001, seed=0, sink=sink)
    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig())
        chaos.install(chaos.FaultPlan.parse("replica_kill@batch=1",
                                            seed=0))
        rng = np.random.default_rng(4)
        flow = router.infer(*_images(rng), timeout=60)
        assert flow.shape == SHAPE + (2,)
        assert router.router_stats()["failovers_total"] >= 1
        _wait_for(lambda: len(sink.spans("attempt")) >= 2, 10,
                  "both attempt spans")
        roots = [r for r in sink.spans("route")
                 if r["parent_id"] is None]
        assert len(roots) == 1, roots
        tid = roots[0]["trace_id"]
        attempts = sink.spans("attempt")
        assert all(a["trace_id"] == tid for a in attempts)
        assert all(a["parent_id"] == roots[0]["span_id"]
                   for a in attempts)
        statuses = sorted(a["status"] for a in attempts)
        assert statuses == ["error", "ok"], attempts
        assert {a["replica"] for a in attempts} == {"r0", "r1"}
        assert roots[0]["replicas_tried"] == 2
    finally:
        fleet.stop(drain=False)


def test_router_hedge_one_tree(variables, aot_dir):
    """``replica_slow`` fires the bounded hedge: two attempts on two
    replicas, first result wins — still ONE tree, with the winner
    marked ``won=True``/``hedge=True`` and the straggler's spans
    stitched in late (it ends after the root flushed)."""
    sink = _ListSink()
    trace.configure(sample_rate=1.0, sink=sink)
    fleet = _mk_fleet(variables, aot_dir,
                      scfg=_serve_cfg(aot_dir=aot_dir, chaos_slow_s=3.0))
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig(hedge_timeout_s=0.25))
        chaos.install(chaos.FaultPlan.parse("replica_slow@batch=1",
                                            seed=0))
        rng = np.random.default_rng(5)
        t0 = time.perf_counter()
        flow = router.infer(*_images(rng), timeout=60)
        dt = time.perf_counter() - t0
        assert flow.shape == SHAPE + (2,)
        assert dt < 2.5, f"hedge did not cover the {dt:.1f}s straggler"
        _wait_for(lambda: len(sink.spans("attempt")) >= 2, 30,
                  "the straggler's late attempt span")
        roots = [r for r in sink.spans("route")
                 if r["parent_id"] is None]
        assert len(roots) == 1 and roots[0]["hedged"] is True
        attempts = sink.spans("attempt")
        assert len(attempts) == 2
        assert {a["trace_id"] for a in attempts} \
            == {roots[0]["trace_id"]}
        winner = next(a for a in attempts if a["won"])
        loser = next(a for a in attempts if not a["won"])
        assert winner["hedge"] is True and loser["hedge"] is False
        assert loser["dur_s"] > winner["dur_s"]
        # each attempt subtree carries its replica's device span
        devices = sink.spans("device")
        assert {d["parent_id"] for d in devices} \
            == {a["span_id"] for a in attempts}
    finally:
        fleet.stop(drain=False)


# ---------------------------------------------------------------------------
# tooling round-trips (tier-1 wiring of the analysis surface)
# ---------------------------------------------------------------------------


def test_trace_report_tiny(capsys):
    mod = _load_script("trace_report")
    assert mod.main(["--tiny"]) == 0
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rec["metric"] == "trace_report"
    assert rec["config"]["traces_total"] == 2
    assert {"queue", "pad", "device"} <= set(
        rec["config"]["serve_span_names"])
    assert rec["config"]["critical_path_ms"]["device"] > 0


def test_trace_smoke_tiny(capsys):
    """The end-to-end drill: 2-replica fleet under ``replica_slow``,
    hedged request -> one reconstructed tree, critical path through the
    winner, Perfetto + bench-record exports (the tier-1 acceptance
    wiring for docs/OBSERVABILITY.md's tracing section)."""
    mod = _load_script("trace_smoke")
    rc = mod.main(["--tiny"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert rc == 0, rec
    assert rec["metric"] == "trace_smoke" and rec["value"] == 1.0
    cfg = rec["config"]
    # route + 2x(attempt + queue/pad/device + device's h2d/launch/drain)
    assert cfg["one_tree"]["spans"] == 15
    assert cfg["critical_path"][-2].startswith("device:")
    assert cfg["critical_path"][-1].startswith("drain:")
    assert cfg["exports"]["traces_total"] == 3
