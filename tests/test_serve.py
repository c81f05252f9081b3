"""Serving-engine tests (tier-1): mixed-resolution concurrent requests
return correctly unpadded flows matching the offline jitted forward;
compile count equals the number of distinct ``(bucket, batch)`` programs
under mixed-shape load; bounded-queue backpressure rejects past
``max_queue``; the HTTP front end round-trips the npz protocol.

Small model, fp32, 2 iters, tiny shapes — each AOT compile is ~2-3 s on
the CPU backend, so the whole file stays inside the fast tier."""

import io
import json
import re
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from raft_tpu.config import RAFTConfig
from raft_tpu.serve import InferenceEngine, QueueFullError, ServeConfig
from raft_tpu.serve.stats import Counters, LatencyRecorder

CFG = RAFTConfig.small_model()  # fp32 compute: bit-comparable to eval
ITERS = 2
# (36, 52) -> bucket (40, 56); (64, 96) -> bucket (64, 96): two distinct
# compile buckets from mixed traffic.
SHAPES = [(36, 52), (64, 96)]


def _images(rng, h, w):
    return (rng.uniform(0, 255, (h, w, 3)).astype(np.float32),
            rng.uniform(0, 255, (h, w, 3)).astype(np.float32))


@pytest.fixture(scope="module")
def variables():
    import jax

    model_img = jax.numpy.zeros((1, 40, 56, 3))
    rng = jax.random.PRNGKey(0)
    from raft_tpu.models.raft import RAFT

    return RAFT(CFG).init({"params": rng, "dropout": rng},
                          model_img, model_img, iters=1)


@pytest.fixture(scope="module")
def engine(variables):
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=4, batch_sizes=(4,), max_wait_ms=15,
        max_queue=64))
    eng.start()
    yield eng
    eng.stop()


def test_mixed_load_matches_eval_and_compiles_once(engine, variables):
    """Two waves of concurrent mixed-resolution requests: every flow
    comes back unpadded at its own resolution and matches the offline
    ``evaluate.make_eval_fn`` batch-1 forward; the compile ledger shows
    EXACTLY one encode + one iter_step compile per (bucket, batch) —
    wave 2 reuses wave 1's programs."""
    from raft_tpu import evaluate

    rng = np.random.default_rng(1)
    reqs = [(h, w) + _images(rng, h, w)
            for _ in range(4) for (h, w) in SHAPES]

    for wave in range(2):
        futs = [(h, w, im1, im2, engine.submit(im1, im2))
                for (h, w, im1, im2) in reqs]
        for h, w, _, _, f in futs:
            assert f.result(timeout=120).shape == (h, w, 2)

    counts = engine.compile_counter.counts()
    assert counts == {((40, 56), 4, "enc"): 1, ((40, 56), 4, "iter"): 1,
                      ((64, 96), 4, "enc"): 1,
                      ((64, 96), 4, "iter"): 1}, counts
    stats = engine.stats()
    assert stats["num_buckets"] == len(SHAPES)
    assert stats["completed"] == 2 * len(reqs)
    assert stats["latency_ms"]["p99_ms"] >= stats["latency_ms"]["p50_ms"]

    # Outputs match the offline eval path (same inference overrides, same
    # /8 bucket + sintel pad placement, batch-1 per image).
    eval_fn = evaluate.make_eval_fn(CFG, ITERS)
    from raft_tpu.ops.pad import InputPadder

    for h, w, im1, im2 in reqs[:2]:
        padder = InputPadder((h, w), mode="sintel")
        p1, p2 = padder.pad_np(im1)[None], padder.pad_np(im2)[None]
        _, ref_up = eval_fn(variables, p1, p2)
        ref = np.asarray(padder.unpad(np.asarray(ref_up))[0])
        got = engine.infer(im1, im2, timeout=120)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_healthz_readiness_reports_device_stall(variables):
    """GET /v1/healthz is readiness, not liveness: with a request
    pending and no device batch completed within ``stall_timeout_s``
    the route turns 503 with the stall detail, and recovers to 200
    ``ok`` once the device worker completes the batch."""
    import time

    from raft_tpu.cli.serve import make_server

    # A long max_wait holds the first request pending (the batch waits
    # to fill), modelling a device worker not completing batches; the
    # tiny stall threshold trips inside that window.
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=4, batch_sizes=(4,), max_wait_ms=2500,
        max_queue=8, stall_timeout_s=0.2))
    eng.start()
    server = make_server(eng, "127.0.0.1", 0)
    host, port = server.server_address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    base = f"http://{host}:{port}"
    try:
        # idle engine: no pending work -> ready even with no batch ever
        with urllib.request.urlopen(base + "/v1/healthz",
                                    timeout=30) as r:
            assert r.status == 200 and r.read() == b"ok"

        rng = np.random.default_rng(4)
        im1, im2 = _images(rng, 36, 52)
        fut = eng.submit(im1, im2)
        time.sleep(0.6)  # pending > 0, no batch done, past threshold
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/v1/healthz", timeout=30)
        assert ei.value.code == 503
        detail = json.loads(ei.value.read())
        assert detail["ready"] is False and detail["stalled"] is True
        assert detail["pending"] == 1

        assert fut.result(timeout=120).shape == (36, 52, 2)
        h = eng.health()
        assert h["ready"] and h["seconds_since_last_batch"] is not None
        with urllib.request.urlopen(base + "/v1/healthz",
                                    timeout=30) as r:
            assert r.status == 200 and r.read() == b"ok"
    finally:
        server.shutdown()
        server.server_close()
        eng.stop()


def test_stop_drain_timeout_with_wedged_device_call(variables):
    """stop(drain=True) while a device call is wedged: the drain times
    out instead of spinning forever, requests still queued in the
    dispatcher fail with 'engine stopped', the wedged batch's requests
    get the device error, and the whole shutdown (loop thread joined,
    device pool drained) completes inside the 10 s join bound."""
    import time

    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=2, batch_sizes=(2,), max_wait_ms=4000,
        max_queue=8, device_retries=0))
    eng.start()

    def wedged_exe(v, a1, a2):
        time.sleep(1.5)  # wedged, but finite: the pool must join
        raise RuntimeError("device wedged")

    eng._get_executable = lambda bucket, bs: wedged_exe
    rng = np.random.default_rng(5)
    im1, im2 = _images(rng, 36, 52)
    f1 = eng.submit(im1, im2)
    f2 = eng.submit(im1, im2)   # fills the batch of 2 -> device, wedged
    time.sleep(0.3)             # let the batch reach the worker
    f3 = eng.submit(im1, im2)   # held open by the dispatcher (max_wait)

    t0 = time.perf_counter()
    eng.stop(drain=True, timeout=0.4)   # drain cannot finish: times out
    elapsed = time.perf_counter() - t0
    assert elapsed < 10.0, elapsed
    assert eng._thread is None  # loop thread joined

    with pytest.raises(RuntimeError, match="engine stopped"):
        f3.result(timeout=1)
    for f in (f1, f2):          # the wedged batch fails with its error
        with pytest.raises(RuntimeError, match="device wedged"):
            f.result(timeout=1)
    stats = eng.stats()
    assert stats["errors"] == 1 and stats["pending"] == 0
    with pytest.raises(RuntimeError):  # no accepting after stop
        eng.submit(im1, im2)


def test_backpressure_rejects_past_max_queue(variables):
    """With the dispatcher holding batches open (long max_wait_ms), the
    ``max_queue``+1-th submit is rejected immediately — the queue is
    bounded by construction, not by luck."""
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=4, batch_sizes=(4,), max_wait_ms=2000,
        max_queue=3))
    eng.start()
    try:
        rng = np.random.default_rng(2)
        im1, im2 = _images(rng, 36, 52)
        futs = [eng.submit(im1, im2) for _ in range(3)]
        with pytest.raises(QueueFullError):
            eng.submit(im1, im2)
        for f in futs:  # batch of 3 pads to the compiled batch of 4
            assert f.result(timeout=120).shape == (36, 52, 2)
        stats = eng.stats()
        assert stats["rejected"] == 1 and stats["completed"] == 3
        # 3 real lanes + 1 ballast lane in the one executed batch
        assert stats["occupancy"] == 0.75
    finally:
        eng.stop()


def test_http_round_trip(engine):
    """The stdlib HTTP front end: POST /v1/flow npz -> flow npz at the
    original resolution; /v1/stats and /healthz respond; concurrent
    posts coalesce through the same engine."""
    from raft_tpu.cli.serve import make_server

    server = make_server(engine, "127.0.0.1", 0)
    host, port = server.server_address[:2]
    t = threading.Thread(target=server.serve_forever, daemon=True)
    t.start()
    base = f"http://{host}:{port}"
    try:
        rng = np.random.default_rng(3)
        im1, im2 = _images(rng, 36, 52)
        buf = io.BytesIO()
        np.savez(buf, image1=im1, image2=im2)
        req = urllib.request.Request(base + "/v1/flow",
                                     data=buf.getvalue(), method="POST")
        with urllib.request.urlopen(req, timeout=120) as r:
            assert r.status == 200
            flow = np.load(io.BytesIO(r.read()))["flow"]
        assert flow.shape == (36, 52, 2)

        with urllib.request.urlopen(base + "/healthz", timeout=30) as r:
            assert r.read() == b"ok"
        with urllib.request.urlopen(base + "/v1/healthz", timeout=30) as r:
            assert r.read() == b"ok"
        with urllib.request.urlopen(base + "/v1/stats", timeout=30) as r:
            stats = json.loads(r.read())
        assert stats["completed"] >= 1 and "latency_ms" in stats
        assert stats["latency_ms"]["count"] \
            == stats["latency_ms"]["count_total"]

        # /metrics: valid Prometheus text exposition, rendered from the
        # SAME registry /v1/stats reads — request/latency counters agree.
        with urllib.request.urlopen(base + "/metrics", timeout=30) as r:
            assert r.status == 200
            assert r.headers["Content-Type"].startswith("text/plain")
            text = r.read().decode()
        metrics = {}
        for line in text.splitlines():
            assert line.startswith("#") or re.match(
                r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^}\n]*\})? -?[0-9.eE+-]+$",
                line), f"unparseable exposition line: {line!r}"
            if not line.startswith("#") and "{" not in line:
                name, val = line.rsplit(" ", 1)
                metrics[name] = float(val)
        # stable metric names (the scrape-config contract)
        for name in ("raft_serve_pairs_completed_total",
                     "raft_serve_requests_rejected_total",
                     "raft_serve_batches_total",
                     "raft_serve_uptime_seconds",
                     "raft_serve_pending_requests",
                     "raft_serve_request_latency_seconds_count"):
            assert name in metrics, (name, sorted(metrics))
        stats2 = json.loads(urllib.request.urlopen(
            base + "/v1/stats", timeout=30).read())
        assert metrics["raft_serve_pairs_completed_total"] \
            == stats2["completed"]
        assert metrics["raft_serve_request_latency_seconds_count"] \
            == stats2["latency_ms"]["count_total"]

        bad = urllib.request.Request(base + "/v1/flow", data=b"junk",
                                     method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(bad, timeout=30)
        assert ei.value.code == 400
    finally:
        server.shutdown()
        server.server_close()


def test_multitool_entry_point(capsys):
    """``python -m raft_tpu`` usage text + unknown-subcommand exit."""
    from raft_tpu.__main__ import main

    assert main([]) == 0
    out = capsys.readouterr().out
    assert "serve" in out and "train" in out
    assert main(["bogus"]) == 2


def test_serve_cli_flag_parsing():
    from raft_tpu.cli.serve import parse_args

    args = parse_args(["--random-init", "--small", "--port", "0",
                       "--buckets", "440x1024,720x1280",
                       "--batch-sizes", "1,4"])
    assert args.random_init and args.small and args.port == 0
    with pytest.raises(SystemExit):  # --model XOR --random-init
        from raft_tpu.cli.serve import main as serve_main

        serve_main(["--small"])


def test_counters_failed_batch_keeps_lanes():
    """A failed batch's real lanes stay in every lane denominator (as
    ``failed_lanes``) — errors can no longer make ``occupancy`` and
    ``mean_batch_fill`` read *healthier*."""
    c = Counters()
    c.mark_started()
    c.add_batch(real=3, padded=1, failed=False)
    snap_ok = c.snapshot(num_chips=1)
    assert snap_ok["occupancy"] == 0.75
    c.add_batch(real=2, padded=2, failed=True)
    snap = c.snapshot(num_chips=1)
    assert snap["completed"] == 3          # successes only
    assert snap["failed_lanes"] == 2 and snap["errors"] == 1
    # (3 + 2) real lanes over (3 + 2 + 1 + 2) total lanes
    assert snap["occupancy"] == round(5 / 8, 3)
    assert snap["mean_batch_fill"] == 2.5  # (3 + 2) real lanes / 2
    # the old accounting (real lanes vanish) would have REPORTED better:
    assert snap["occupancy"] < snap_ok["occupancy"]


def test_latency_recorder_window_vs_lifetime():
    lr = LatencyRecorder(window=4)
    assert lr.snapshot() == {"count": 0, "count_total": 0,
                             "window_count": 0, "p50_ms": 0.0,
                             "p95_ms": 0.0, "p99_ms": 0.0, "mean_ms": 0.0}
    for i in range(6):
        lr.record(1.0 if i < 2 else 0.01)  # slow samples age out
    s = lr.snapshot()
    assert s["count_total"] == 6 and s["count"] == 6  # lifetime (alias)
    assert s["window_count"] == 4                     # bounded window
    assert s["p99_ms"] < 100                          # window-only stats


def test_serve_config_validation():
    with pytest.raises(ValueError):
        ServeConfig(buckets=((441, 1024),))  # not /8-aligned
    with pytest.raises(ValueError):
        ServeConfig(max_batch=0)
    assert ServeConfig(max_batch=8).resolved_batch_sizes() == (1, 2, 4, 8)
    assert ServeConfig(max_batch=6).resolved_batch_sizes() == (1, 2, 4, 6)
    assert ServeConfig(batch_sizes=(4, 2)).resolved_batch_sizes() == (2, 4)


# ---------------------------------------------------------------------------
# lifecycle edges, retry backoff ladder, structured 429
# ---------------------------------------------------------------------------


class _RecordingSink:
    """EventSink stand-in: collects (event, fields) for assertions."""

    def __init__(self):
        self.events = []

    def emit(self, event, step=None, **fields):
        self.events.append((event, fields))

    def of(self, event):
        return [f for e, f in self.events if e == event]


class _FlakyDeviceError(RuntimeError):
    transient = True  # is_transient_error honors the explicit flag


def test_submit_after_stop_fails_fast_and_engine_is_single_use(
        variables):
    """Engines are single-use: after ``stop()`` a submit fails
    IMMEDIATELY with an unambiguous error (not the generic not-started
    one, and never a hang on a dead loop), ``start()`` refuses to
    resurrect the carcass, and a second ``stop()`` is a no-op.  The
    fleet supervisor leans on exactly these semantics when it swaps a
    restarted engine in."""
    rng = np.random.default_rng(7)
    im1, im2 = _images(rng, 36, 52)

    # never-started engine: stop() is legal and marks it used up
    eng = InferenceEngine(variables, CFG, ServeConfig(iters=ITERS))
    with pytest.raises(RuntimeError, match="not started"):
        eng.submit(im1, im2)
    eng.stop()
    eng.stop()  # idempotent
    with pytest.raises(RuntimeError, match="single-use"):
        eng.submit(im1, im2)
    with pytest.raises(RuntimeError, match="single-use"):
        eng.start()
    assert eng.health()["ready"] is False

    # started-then-stopped engine: same contract after a real lifecycle
    eng2 = InferenceEngine(variables, CFG, ServeConfig(iters=ITERS))
    eng2.start()
    eng2.stop(drain=True, timeout=5)
    with pytest.raises(RuntimeError, match="single-use"):
        eng2.submit(im1, im2)
    with pytest.raises(RuntimeError, match="single-use"):
        eng2.start()


def test_queue_full_error_carries_backoff_hints():
    e = QueueFullError("full", queue_depth=7, retry_after_s=2.0)
    assert e.queue_depth == 7 and e.retry_after_s == 2.0
    assert isinstance(e, RuntimeError)
    d = QueueFullError("bare")  # defaults keep old call sites valid
    assert d.queue_depth == 0 and d.retry_after_s == 1.0


def test_call_device_exponential_backoff_schedule(variables):
    """The retry ladder doubles from ``retry_backoff_s`` and caps at
    ``retry_backoff_max_s``; with jitter off the ``serve_retry`` events
    record the exact schedule (chaos drills replay these)."""
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, device_retries=3, retry_backoff_s=0.01,
        retry_backoff_max_s=0.02, retry_jitter=0.0,
        retry_deadline_s=10.0), sink=sink)
    calls = {"n": 0}

    def flaky_exe(v, a1, a2):
        calls["n"] += 1
        if calls["n"] <= 3:
            raise _FlakyDeviceError(f"flaky dispatch #{calls['n']}")
        return None, np.zeros((1, 40, 56, 2), np.float32)

    out = eng._call_device(flaky_exe, np.zeros((1, 40, 56, 3)),
                          np.zeros((1, 40, 56, 3)), (40, 56), 1)
    assert out.shape == (1, 40, 56, 2) and calls["n"] == 4
    retries = sink.of("serve_retry")
    # 0.01 -> 0.02 -> 0.04 capped at 0.02; attempts numbered from 1
    assert [r["backoff_s"] for r in retries] == [0.01, 0.02, 0.02]
    assert [r["attempt"] for r in retries] == [1, 2, 3]
    assert all(r["elapsed_s"] >= 0 for r in retries)
    assert eng.stats()["retries"] == 3


def test_call_device_jitter_stays_within_band(variables):
    """With jitter on, each recorded backoff lands inside the
    ±``retry_jitter`` band around the deterministic ladder value."""
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, device_retries=2, retry_backoff_s=0.01,
        retry_backoff_max_s=0.02, retry_jitter=0.25,
        retry_deadline_s=10.0), sink=sink)

    def always_flaky(v, a1, a2):
        raise _FlakyDeviceError("flaky dispatch")

    with pytest.raises(_FlakyDeviceError):
        eng._call_device(always_flaky, np.zeros((1, 40, 56, 3)),
                         np.zeros((1, 40, 56, 3)), (40, 56), 1)
    bands = [(0.01, 1), (0.02, 2)]  # (ladder base, attempt)
    retries = sink.of("serve_retry")
    assert len(retries) == 2
    for rec, (base, attempt) in zip(retries, bands):
        assert rec["attempt"] == attempt
        assert 0.75 * base <= rec["backoff_s"] <= 1.25 * base


def test_call_device_retry_deadline_caps_the_ladder(variables):
    """When the next sleep would cross ``retry_deadline_s`` the engine
    gives up with the ORIGINAL error and records the abandonment as a
    ``serve_retry_deadline`` event instead of a ``serve_retry``."""
    sink = _RecordingSink()
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, device_retries=10, retry_backoff_s=0.4,
        retry_jitter=0.0, retry_deadline_s=0.01), sink=sink)

    def always_flaky(v, a1, a2):
        raise _FlakyDeviceError("still flaky")

    with pytest.raises(_FlakyDeviceError, match="still flaky"):
        eng._call_device(always_flaky, np.zeros((1, 40, 56, 3)),
                         np.zeros((1, 40, 56, 3)), (40, 56), 1)
    assert sink.of("serve_retry") == []  # never slept once
    deadline = sink.of("serve_retry_deadline")
    assert len(deadline) == 1 and deadline[0]["attempt"] == 1
    assert deadline[0]["deadline_s"] == 0.01


def test_http_429_is_structured(variables):
    """The shed-load response is machine-readable: standard
    ``Retry-After`` header (delta-seconds, ceiled) plus a JSON body
    with the queue depth and the raw float hint.  Exercised through the
    real handler with a facade whose queue is 'full'."""
    from raft_tpu.cli.serve import make_server

    class _FullService:
        def infer(self, im1, im2, timeout=None):
            raise QueueFullError("queue full: 7 in flight",
                                 queue_depth=7, retry_after_s=1.5)

    server = make_server(_FullService(), "127.0.0.1", 0)
    host, port = server.server_address[:2]
    threading.Thread(target=server.serve_forever, daemon=True).start()
    try:
        rng = np.random.default_rng(8)
        im1, im2 = _images(rng, 36, 52)
        buf = io.BytesIO()
        np.savez(buf, image1=im1, image2=im2)
        req = urllib.request.Request(
            f"http://{host}:{port}/v1/flow", data=buf.getvalue(),
            method="POST")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 429
        assert ei.value.headers["Retry-After"] == "2"  # ceil(1.5)
        body = json.loads(ei.value.read())
        assert body["queue_depth"] == 7
        assert body["retry_after_s"] == 1.5
        assert "queue full" in body["error"]
    finally:
        server.shutdown()
        server.server_close()


# ---------------------------------------------------------------------
# Request mode issues batch n+1 while batch n is on the device, and a
# second thread answers in the order of issue.  tests/fake_device.py
# stands in for the compiled pipeline, so "on the device" lasts as long
# as a test wants (no compile); one real engine of single-request
# batches holds the answers to their serial values.
# ---------------------------------------------------------------------

def _ahead_engine(variables, dev, **cfg_kw):
    from raft_tpu.obs import stages

    kw = dict(iters=ITERS, max_batch=1, batch_sizes=(1,), max_wait_ms=1,
              max_queue=64)
    kw.update(cfg_kw)
    eng = InferenceEngine(variables, CFG, ServeConfig(**kw))
    dev.install(eng)
    eng.start()
    return eng, len(stages.recent("serve"))


def _settled(eng, n0, n):
    """The engine's ``n`` newest stage records, once its batches have
    closed them (a future resolves inside ``reply``, the record closes
    just after; the module's engines run one after another, so the
    ring's newest records are this engine's)."""
    import time

    from raft_tpu.obs import stages

    deadline = time.perf_counter() + 10
    while eng.stats()["pending"] and time.perf_counter() < deadline:
        time.sleep(0.002)
    recs = stages.recent("serve")[n0:]
    assert len(recs) == n, (len(recs), n)
    return recs


def test_next_batch_is_issued_while_the_last_is_on_the_device(variables):
    from tests.fake_device import FakeDevice

    dev = FakeDevice(gated=True)
    eng, n0 = _ahead_engine(variables, dev)
    try:
        im1, im2 = _images(np.random.default_rng(11), 36, 52)
        f0 = eng.submit(im1, im2)
        assert dev.wait_launched(1)
        f1 = eng.submit(im1, im2)
        # batch 1 is uploaded and launched while batch 0's result is
        # still on the device: nothing of it has been read back
        assert dev.wait_launched(2)
        assert dev.drained == [] and not f0.done() and not f1.done()
        dev.gate.set()
        assert f0.result(timeout=30)[0, 0, 0] == 0.0
        assert f1.result(timeout=30)[0, 0, 0] == 1.0
        r0, r1 = _settled(eng, n0, 2)
        assert (r0["ahead"], r1["ahead"]) == (0, 1)
        assert r1["spans"]["h2d"][1] <= r1["spans"]["launch"][1] \
            < r0["spans"]["drain"][1]
        stats = eng.stats()
        assert stats["issued_ahead"] == 1 and stats["batches"] == 2
        assert "raft_serve_batches_issued_ahead_total 1" \
            in eng.metrics_text()
    finally:
        dev.gate.set()
        eng.stop()


def test_a_lone_request_is_answered_with_nobody_behind_it(engine):
    """One caller: the batch is drained as soon as the device is done,
    its record says so (``ahead`` 0, no ``hold``, stages inside its own
    cycle), and nothing counts as issued ahead."""
    from raft_tpu.obs import stages

    before = engine.stats()["issued_ahead"]
    n0 = len(stages.recent("serve"))
    im1, im2 = _images(np.random.default_rng(12), 36, 52)
    for _ in range(2):
        assert engine.infer(im1, im2, timeout=120).shape == (36, 52, 2)
    r0, r1 = _settled(engine, n0, 2)
    for r in (r0, r1):
        assert r["ahead"] == 0 and "hold" not in r["stages"]
        assert set(r["stages"]) == {"wait", "pad", "h2d", "launch",
                                    "drain", "reply"}
    assert r1["t_start"] == r0["t_end"] == r1["spans"]["wait"][0]
    assert abs(sum(r1["stages"].values())
               - (r1["t_end"] - r1["t_start"])) < 5e-3
    assert engine.stats()["issued_ahead"] == before


def test_burst_keeps_two_in_flight_and_answers_in_order(variables):
    """8 single-request batches at once: never more than two issued and
    unanswered, answers in the order of issue, and each flow equal, bit
    for bit, to the same pair's served alone."""
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=ITERS, max_batch=1, batch_sizes=(1,), max_wait_ms=1,
        max_queue=64))
    eng.start()
    try:
        eng.warmup([(36, 52)])
        rng = np.random.default_rng(13)
        pairs = [_images(rng, 36, 52) for _ in range(8)]
        in_flight, issue = [], eng._issue

        def watched(item):
            in_flight.append(eng._in_flight)
            return issue(item)

        eng._issue = watched
        order = []
        futs = [eng.submit(a, b) for a, b in pairs]
        for i, f in enumerate(futs):
            f.add_done_callback(lambda _f, i=i: order.append(i))
        burst = [f.result(timeout=120) for f in futs]
        assert len(in_flight) == 8 and max(in_flight) == 2
        assert order == list(range(8))
        assert eng.stats()["issued_ahead"] >= 1
        alone = [eng.infer(a, b, timeout=120) for a, b in pairs]
        for got, want in zip(burst, alone):
            assert np.array_equal(got, want)
    finally:
        eng.stop()


@pytest.mark.parametrize("drain", [True, False])
def test_stop_with_two_batches_in_flight(variables, drain):
    """Two batches on the device, a third held behind them, a fourth
    cut and waiting.  ``stop(drain=True)`` answers all four;
    ``stop(drain=False)`` answers what has been issued and fails the
    rest; either way no future stays pending and no thread alive."""
    import time

    from tests.fake_device import FakeDevice

    dev = FakeDevice(gated=True)
    eng, _ = _ahead_engine(variables, dev)
    im1, im2 = _images(np.random.default_rng(14), 36, 52)
    futs = [eng.submit(im1, im2) for _ in range(4)]
    assert dev.wait_launched(2)
    time.sleep(0.1)       # the third pads and is held; the fourth waits
    assert len(dev.launched) == 2 and eng._in_flight == 2
    threads = [eng._thread, eng._completer]
    stopper = threading.Thread(target=eng.stop,
                               kwargs=dict(drain=drain, timeout=30))
    stopper.start()
    time.sleep(0.1)
    dev.gate.set()        # the device finishes what it was given
    stopper.join(timeout=30)
    threads += list(eng._device_pool._threads)
    assert not stopper.is_alive()
    assert len(threads) == 3 and not any(t.is_alive() for t in threads)
    assert all(f.done() for f in futs) and eng.stats()["pending"] == 0
    assert [f.result()[0, 0, 0] for f in futs[:3]] == [0.0, 1.0, 2.0]
    if drain:
        assert futs[3].result()[0, 0, 0] == 3.0
    else:
        with pytest.raises(RuntimeError, match="engine stopped"):
            futs[3].result()
        assert len(dev.launched) == 3


def test_stage_records_tile_and_wait_leaves_hold_out(variables):
    """A burst of 6 over a device that takes 30 ms a batch: one record a
    batch, each starting where the one before ended; the time the
    issuing side is held behind two unanswered batches is ``hold``, not
    ``wait``; ``pad`` / ``h2d`` / ``launch`` of a batch issued ahead lie
    before its record starts."""
    from tests.fake_device import FakeDevice

    dev = FakeDevice(work_s=0.03)
    eng, n0 = _ahead_engine(variables, dev)
    try:
        im1, im2 = _images(np.random.default_rng(15), 36, 52)
        futs = [eng.submit(im1, im2) for _ in range(6)]
        assert [f.result(timeout=30)[0, 0, 0] for f in futs] == [
            0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
        recs = _settled(eng, n0, 6)
    finally:
        eng.stop()
    assert [r["batch"] for r in recs] == [1, 2, 3, 4, 5, 6]
    for prev, cur in zip(recs, recs[1:]):
        assert cur["t_start"] == prev["t_end"]
    assert [r["ahead"] for r in recs] == [0, 1, 1, 1, 1, 1]
    held = [r for r in recs if "hold" in r["stages"]]
    assert len(held) >= 3           # batches 3.. find two in flight
    for r in held:
        sp = r["spans"]
        assert sp["wait"][1] <= sp["pad"][0] <= sp["pad"][1] \
            <= sp["hold"][0] <= sp["hold"][1] <= sp["h2d"][0]
        assert r["stages"]["hold"] > 0.01
        assert r["stages"]["hold"] > r["stages"]["wait"]
        assert sp["launch"][1] < r["t_start"] < sp["drain"][1]
    # the cycle is the device's 30 ms, not 30 ms + the host's work
    cycles = [r["t_end"] - r["t_start"] for r in recs[2:]]
    assert 0.025 < sorted(cycles)[len(cycles) // 2] < 0.08
