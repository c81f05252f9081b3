"""The stage clock (``raft_tpu/obs/stages.py``), tier-1, CPU: the
primitive's arithmetic, ring and thread safety; the four places it sits
(serve worker, train loop, input feed, compiler) leaving the records the
benchmark's readers expect with no telemetry directory and no trace
context; the ``raft/<loop>/<stage>`` annotations reaching a profiler
capture; and the six new readers on a hand-built ring.

Budget: ONE engine compile (module-scoped, the ``(40, 56) x b2`` program
test_trace.py uses); the train loop is stubbed as in test_obs.py."""

import glob
import os
import sys
import threading
import time

import numpy as np
import pytest

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.data.datasets import ShardedLoader
from raft_tpu.obs import stages, trace
from raft_tpu.serve import InferenceEngine, ServeConfig

from loader_datasets import SynthDataset

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)       # the readers import as benchmark.*

CFG = RAFTConfig.small_model()
SHAPE = (36, 52)                   # -> bucket (40, 56)
SERVE_STAGES = {"wait", "pad", "h2d", "launch", "drain", "reply"}


@pytest.fixture(autouse=True)
def _fresh_rings():
    stages.reset()
    yield
    stages.reset()


# ------------------------------------------------------------ (a) primitive

def test_a_units_stage_seconds_sum_to_its_span():
    unit = stages.begin("t")
    for name, s in (("one", 0.01), ("two", 0.02), ("one", 0.005)):
        with stages.stage("t", name):
            time.sleep(s)
    rec = stages.end("t", k=3)
    assert rec["loop"] == "t" and rec["k"] == 3 and rec["n"] == 1
    assert rec["stages"]["one"] >= 0.015 and rec["stages"]["two"] >= 0.02
    assert abs(sum(rec["stages"].values())
               - (rec["t_end"] - rec["t_start"])) < 1e-3
    # a repeated stage: seconds add up, the span runs first start -> last end
    assert rec["spans"]["one"][0] == pytest.approx(unit.t_start, abs=1e-3)
    assert rec["spans"]["one"][1] > rec["spans"]["two"][1]
    assert stages.total("t", "one") == rec["stages"]["one"]
    # no unit open: the block still runs, nothing is recorded
    with stages.stage("t", "stray"):
        pass
    assert stages.end("t") is None and len(stages.recent("t")) == 1


def test_a_ring_is_bounded_and_safe_under_two_writers(monkeypatch):
    monkeypatch.setattr(stages, "RING", 64)
    per_thread, errors = 400, []

    def writer():
        try:
            for _ in range(per_thread):
                stages.begin("w")
                with stages.stage("w", "s"):
                    pass
                stages.end("w")
        except Exception as e:              # pragma: no cover
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=writer) for _ in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads) and not errors
    finally:
        sys.setswitchinterval(old)
    ring = stages.recent("w")
    assert len(ring) == 64                        # bounded
    # no lost update: the newest record is number 800, numbers are dense
    assert [r["n"] for r in ring] == list(range(2 * per_thread - 63,
                                                2 * per_thread + 1))
    # the cumulative total outlives the ring
    assert stages.total("w", "s") >= sum(r["stages"]["s"] for r in ring)


def test_a_recent_trails_the_stream_not_the_wall_clock():
    for _ in range(3):
        stages.begin("r")
        stages.end("r")
        time.sleep(0.03)
    ring = stages.recent("r")
    assert [r["n"] for r in ring] == [1, 2, 3]
    # long after the last record the window still ends at the newest one
    assert [r["n"] for r in stages.recent("r", 0.045)] == [2, 3]
    assert [r["n"] for r in stages.recent("r", 0.0)] == [3]
    assert stages.recent("never") == []


def test_a_registry_gets_stage_seconds_by_loop_and_stage():
    from raft_tpu.obs import MetricRegistry

    reg = MetricRegistry()
    stages.begin("serve")
    with stages.stage("serve", "pad"):
        pass
    rec = stages.end("serve", registry=reg)
    assert reg.counter("raft_stage_seconds_total").value(
        loop="serve", stage="pad") == rec["stages"]["pad"]


# ------------------------------------------------- (b, d) the serve worker

def _images(rng):
    return (rng.uniform(0, 255, SHAPE + (3,)).astype(np.float32),
            rng.uniform(0, 255, SHAPE + (3,)).astype(np.float32))


class _ListSink:
    def __init__(self):
        self.records = []

    def emit(self, event, **fields):
        self.records.append(dict(event=event, **fields))

    def flush(self):
        pass

    def close(self):
        pass


@pytest.fixture(scope="module")
def engine():
    import jax

    from raft_tpu.models.raft import RAFT

    img = jax.numpy.zeros((1, 40, 56, 3))
    key = jax.random.PRNGKey(0)
    variables = RAFT(CFG).init({"params": key, "dropout": key}, img, img,
                               iters=1)
    eng = InferenceEngine(variables, CFG, ServeConfig(
        iters=2, max_batch=2, batch_sizes=(2,), max_wait_ms=1,
        max_queue=64))
    eng.start()
    eng.warmup([SHAPE])
    yield eng
    eng.stop()


def test_b_request_mode_engine_leaves_one_record_a_batch(engine):
    """8 requests, one at a time, no telemetry directory: 8 batch records
    with all six stages; a traced request's queue/pad/device spans are the
    record's own stamps."""
    sink = _ListSink()
    tracer = trace.Tracer(sink=sink, sample_rate=1.0)
    rng = np.random.default_rng(0)
    root = None
    for i in range(8):
        if i == 5:
            root = tracer.start_trace("req")
            with trace.use_context(root):
                fut = engine.submit(*_images(rng))
        else:
            fut = engine.submit(*_images(rng))
        assert fut.result(timeout=120).shape == SHAPE + (2,)
    root.end()
    deadline = time.time() + 10
    while len(stages.recent("serve")) < 8 and time.time() < deadline:
        time.sleep(0.01)
    recs = stages.recent("serve")
    assert len(recs) == 8
    seqs = [r["batch"] for r in recs]
    assert seqs == list(range(seqs[0], seqs[0] + 8))
    for r in recs:
        assert set(r["stages"]) == SERVE_STAGES
        assert (r["real"], r["ballast"], r["retries"]) == (1, 1, 0)
        assert r["error"] is None and r["bucket"] == "40x56"
        assert len(r["queue_s"]) == 1 and r["queue_s"][0] >= 0
        assert abs(sum(r["stages"].values())
                   - (r["t_end"] - r["t_start"])) < 5e-3
    # a batch's cycle starts where the worker's previous batch ended
    for prev, cur in zip(recs, recs[1:]):
        assert cur["t_start"] == prev["t_end"]
    # the engine's own registry has the seconds: /metrics and stats()
    assert engine.registry.counter("raft_stage_seconds_total").value(
        loop="serve", stage="drain") >= sum(
            r["stages"]["drain"] for r in recs)
    assert set(engine.stats()["stage_seconds"]) == SERVE_STAGES
    assert 'raft_stage_seconds_total{loop="serve",stage="h2d"}' \
        in engine.metrics_text()
    # the traced request (the 6th batch): spans == the record's stamps
    rec = recs[5]
    deadline = time.time() + 10
    while (sum(r["event"] == trace.EVENT for r in sink.records) < 7
           and time.time() < deadline):
        time.sleep(0.01)
    spans = {r["name"]: r for r in sink.records
             if r["event"] == trace.EVENT}
    assert set(spans) == {"req", "queue", "pad", "device", "h2d",
                          "launch", "drain"}

    def stamps(name):
        s = spans[name]
        return s["t_start_mono"], s["t_start_mono"] + s["dur_s"]

    entered = rec["spans"]["wait"][1]
    assert stamps("queue")[1] == pytest.approx(entered, abs=2e-6)
    assert stamps("queue")[0] == pytest.approx(
        entered - rec["queue_s"][0], abs=2e-6)
    assert stamps("pad") == pytest.approx(rec["spans"]["pad"], abs=2e-6)
    assert stamps("device") == pytest.approx(
        (rec["spans"]["h2d"][0], rec["spans"]["drain"][1]), abs=2e-6)
    for name in ("h2d", "launch", "drain"):
        assert spans[name]["parent_id"] == spans["device"]["span_id"]
        assert stamps(name) == pytest.approx(rec["spans"][name], abs=2e-6)


def test_d_a_profiler_capture_holds_the_stage_annotations(engine,
                                                          tmp_path):
    import jax
    from jax.profiler import ProfileData

    rng = np.random.default_rng(1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(2):
            engine.infer(*_images(rng), timeout=120)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                        recursive=True)
    names = [e.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for e in line.events
             if e.name.startswith("raft/")]
    for stage in ("pad", "h2d", "launch", "drain", "reply"):
        assert names.count(f"raft/serve/{stage}") == 2, (stage, names)


# ----------------------------------------- (c) the train loop and the feed

def test_c_six_steps_leave_six_records_without_telemetry(tmp_path,
                                                         monkeypatch):
    from test_obs import _stub_loop

    from raft_tpu.train import loop as loop_mod

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    cfg = TrainConfig(name="t", num_steps=6, batch_size=8,
                      image_size=(32, 32), iters=2, val_freq=100,
                      log_freq=3, ckpt_dir=str(tmp_path / "ck"),
                      device_prefetch=2)
    loader = ShardedLoader(SynthDataset(n=16, hw=(32, 32), sleep_s=0.002),
                           batch_size=8, seed=1,
                           num_workers=2)
    state = loop_mod.train(
        RAFTConfig.small_model(corr_levels=2, corr_radius=2), cfg,
        loader=loader, telemetry_dir=None)
    assert int(state.step) == 6
    assert not list(tmp_path.glob("**/telemetry-*.jsonl"))
    steps = stages.recent("train")
    assert [r["step"] for r in steps] == [0, 1, 2, 3, 4, 5]
    for r in steps:
        assert set(r["stages"]) == {"input_wait", "dispatch", "host"}
        assert abs(sum(r["stages"].values())
                   - (r["t_end"] - r["t_start"])) < 5e-3
    feed = stages.recent("input")
    assert len(feed) >= 6
    for r in feed:
        assert set(r["stages"]) == {"slot_wait", "source", "prep", "h2d"}
    totals = [r["sample_seconds_total"] for r in feed]
    assert totals == sorted(totals) and totals[-1] > totals[0] > 0
    # 8 samples a batch of at least 2 ms each (the workers run ahead of
    # the producer, so a record's totals may hold later batches' too)
    assert feed[-1]["samples_total"] >= 8 * len(feed)
    assert stages.total("data_sample_seconds") \
        >= 0.002 * stages.total("data_samples")


# -------------------------------------------------- (e) the compile listener

def test_e_compile_listener_records_a_fresh_jit_once(monkeypatch):
    import jax
    import jax.numpy as jnp

    from raft_tpu.obs import MetricRegistry
    from raft_tpu.utils import profiling

    # the route every CLI and both benchmark drivers take (on the CPU
    # backend it turns no cache on, and still registers the listener)
    assert profiling.enable_persistent_compile_cache() == ""
    profiling.enable_persistent_compile_cache()           # registered once
    # trace and lowering records under 50 ms are dropped (a process makes
    # thousands); this test wants to see the lambda's
    monkeypatch.setattr(profiling, "_MIN_BUILD_STEP_S", 0.0)
    fresh = jax.jit(lambda x: jnp.tanh(x) * 3.0 + 0.125)
    x = jnp.arange(7.0)
    x.block_until_ready()
    before = len(stages.recent("compile"))
    fresh(x)
    mine = stages.recent("compile")[before:]
    # ONE line for the program XLA built, after its trace and lowering
    # (jnp.tanh, jitted itself, is traced inside and reports too)
    built = [r for r in mine if r["kind"] in ("compile", "cache_load")]
    assert len(built) == 1 and built[0] is mine[-1], mine
    rec = built[0]
    assert rec["kind"] == "compile" and rec["seconds"] > 0
    assert rec["t_end"] - rec["t_start"] == pytest.approx(rec["seconds"])
    assert {r["kind"] for r in mine if "lambda" in r["name"]} \
        == {"trace", "lower", "compile"}
    fresh(x)                                  # second call: nothing built
    assert len(stages.recent("compile")) == before + len(mine)
    # the pull-style mirror a registry's owner installs
    reg = MetricRegistry()
    reg.add_collect_hook(stages.compile_seconds_hook())
    reg.collect()
    reg.collect()                             # idempotent between compiles
    assert reg.counter("raft_compile_seconds_total").value(
        kind="compile") == pytest.approx(stages.total("compile",
                                                      "compile"))


# ------------------------------------------------------- (f) the six readers

def _hand_built_ring():
    """Three serve batches (cycles 100, 100, 160 ms; the third stalled in
    ``wait``), three producer batches a second apart whose workers loaded
    1.2 s of samples between first and last and a fourth after a stall of
    the consumer, two compiles before the window and one (the
    reference's) after it."""
    from raft_tpu.obs.stages import _append_locked, _lock

    def put(loop, **rec):
        with _lock:
            _append_locked(loop, dict(loop=loop, **rec))

    put("compile", t_start=0.0, t_end=1.0, seconds=9.0, kind="trace")
    put("compile", t_start=1.0, t_end=31.0, seconds=30.0, kind="compile")
    put("compile", t_start=31.0, t_end=71.0, seconds=40.0,
        kind="cache_load")
    t = 100.0
    for wait, real in ((0.001, 1), (0.001, 1), (0.061, 2)):
        st = {"wait": wait, "pad": 0.004, "h2d": 0.030, "launch": 0.010,
              "drain": 0.050, "reply": 0.005}
        put("serve", t_start=t, t_end=t + sum(st.values()), stages=st,
            real=real)
        t += sum(st.values())
    for i, total in enumerate((5.0, 5.5, 6.2)):
        put("input", t_start=100.0 + i, t_end=100.5 + i,
            stages={"source": 0.4}, sample_seconds_total=total,
            samples_total=16.0 * i)
    # the consumer stood still for ten seconds (a profiler being stopped):
    # that interval counts for nothing
    put("input", t_start=102.5, t_end=112.5, stages={"slot_wait": 9.9},
        sample_seconds_total=6.3, samples_total=48.0)
    put("train", t_start=100.0, t_end=100.3, stages={"dispatch": 0.3})
    put("compile", t_start=200.0, t_end=205.0, seconds=5.0, kind="compile")


READERS = {
    "worker_host_share.serve":
        100.0 * (0.063 + 3 * (0.004 + 0.030 + 0.005)) / 0.36,
    "h2d_ms_per_pair.serve": 1e3 * 0.090 / 4,
    "worker_stall_ms.serve": 60.0,
    "loader_busy_share.train": 100.0 * 1.2 / (4 * 2.0),
    "compile_s.train": 70.0,
    "compile_s.serve": 70.0,
}


def _read(name):
    import importlib
    import json

    with open(os.path.join(REPO, "benchmark", "metrics",
                           name + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    ctx = {"facts": {"window_s": 30.0}, "trace": None, "peaks": None,
           "config": {}, "traffic": {"num_workers": 4, "clients": 16,
                                     "trace_seconds": 0.0}}
    return reader.read(ctx, **spec.get("args", {}))


@pytest.mark.parametrize("name", sorted(READERS))
def test_f_reader_on_a_hand_built_ring(name):
    _hand_built_ring()
    assert _read(name) == pytest.approx(READERS[name], rel=1e-9)


def test_f_stall_reader_leaves_the_profilers_own_stall_out():
    """Per-layer metrics are read in the traced run, and starting the
    profiler stalls the worker: units that ended after the capture began
    (``trace_seconds`` before the close, ``clients`` cycles of drain after
    it, half a second of room) do not count."""
    from benchmark.readers import stage_stall
    from raft_tpu.obs.stages import _append_locked, _lock

    t = 100.0
    for i in range(100):            # 10 s of 100 ms batches
        cycle = {30: 0.130, 80: 0.190}.get(i, 0.100)
        with _lock:
            _append_locked("serve", dict(loop="serve", t_start=t,
                                         t_end=t + cycle, stages={}))
        t += cycle
    ctx = {"facts": {"window_s": 30.0},
           "traffic": {"clients": 10, "trace_seconds": 1.0}}
    # the last 1 + 10 x 0.1 + 0.5 = 2.5 s are the capture's: batch 80's
    # 90 ms do not count, batch 30's 30 ms do
    assert stage_stall.read(ctx, "serve") == pytest.approx(30.0)
    ctx["traffic"]["trace_seconds"] = 0.0
    assert stage_stall.read(ctx, "serve") == pytest.approx(90.0)


@pytest.mark.parametrize("name", sorted(READERS))
def test_f_reader_on_an_empty_ring_reads_nothing(name):
    assert _read(name) is None          # never 0
