"""Telemetry-layer tests: registry semantics + thread safety, Prometheus
exposition validity, JSONL event sink, span timing, and the instrumented
train loop — including the contract that telemetry adds NO per-step
device sync (the Logger's once-per-interval transfer stays the only
one).

The loop tests stub ``make_train_step``/``init_state`` (monkeypatched on
``raft_tpu.train.loop``): what they pin — iterator-wait measurement,
flush cadence, event-stream shape — is independent of the real jitted
step, and the stub keeps the whole file in the fast tier."""

import importlib.util
import json
import os.path as osp
import re
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.obs import EventSink, MetricRegistry, span
from raft_tpu.obs.train import TrainTelemetry

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


# ---------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------

def test_registry_basics_and_labels():
    r = MetricRegistry()
    c = r.counter("raft_x_total", "help")
    c.inc()
    c.inc(2, kind="a")
    assert c.value() == 1 and c.value(kind="a") == 2
    assert r.counter("raft_x_total") is c  # get-or-create idempotent
    with pytest.raises(TypeError):  # same name, different kind
        r.gauge("raft_x_total")
    with pytest.raises(ValueError):
        r.counter("bad name")
    with pytest.raises(ValueError):
        c.inc(1, **{"0bad": "v"})
    g = r.gauge("raft_g")
    g.set(2.5)
    assert g.value() == 2.5 and g.value(kind="none") is None
    h = r.histogram("raft_h_seconds", reservoir=4)
    for i in range(10):
        h.observe(float(i))
    count, total, window = h.collect()
    assert count == 10 and total == 45.0
    assert window == [6.0, 7.0, 8.0, 9.0]  # bounded reservoir


def test_registry_disabled_is_noop():
    r = MetricRegistry(enabled=False)
    c = r.counter("raft_x_total")
    c.inc(5)
    r.gauge("raft_g").set(1)
    r.histogram("raft_h").observe(1.0)
    assert c.value() == 0
    assert r.snapshot()["raft_g"]["values"] == {}


def test_label_cardinality_guard(monkeypatch):
    """Past the cap, UNSEEN label sets fold into ``overflow="true"``
    (one RuntimeWarning, once): the series count stays bounded but the
    totals stay honest, and already-seen sets keep updating in place."""
    monkeypatch.setenv("RAFT_METRIC_MAX_LABELSETS", "4")
    r = MetricRegistry()
    c = r.counter("raft_capped_total")
    for i in range(4):
        c.inc(1, replica=f"r{i}")
    with pytest.warns(RuntimeWarning, match="cardinality cap"):
        c.inc(1, replica="r4")
        c.inc(1, replica="r5")  # ... and only ONE warning for both
    assert c.value(replica="r0") == 1      # existing series intact
    assert c.value(replica="r4") == 0      # unseen set never created
    assert c.value(overflow="true") == 2   # folded, not dropped
    c.inc(1, replica="r0")                 # seen sets update past cap
    assert c.value(replica="r0") == 2
    assert len(c.items()) == 5             # 4 sets + overflow, bounded
    assert 'overflow="true"' in r.render_prometheus()
    # gauges and histograms run the same guard
    g = r.gauge("raft_capped_g")
    with pytest.warns(RuntimeWarning):
        for i in range(6):
            g.set(float(i), shard=f"s{i}")
    assert g.value(overflow="true") == 5.0
    h = r.histogram("raft_capped_seconds")
    with pytest.warns(RuntimeWarning):
        for i in range(6):
            h.observe(1.0, bucket=f"b{i}")
    count, total, _ = h.collect(overflow="true")
    assert (count, total) == (2, 2.0)


def test_cardinality_cap_env_default(monkeypatch):
    """Unset / garbage env falls back to the shipped default; a
    zero-or-negative override clamps to 1 (always at least one
    real series)."""
    from raft_tpu.obs import registry as regmod

    monkeypatch.delenv("RAFT_METRIC_MAX_LABELSETS", raising=False)
    assert regmod._max_labelsets() == regmod.DEFAULT_MAX_LABELSETS
    monkeypatch.setenv("RAFT_METRIC_MAX_LABELSETS", "not-a-number")
    assert regmod._max_labelsets() == regmod.DEFAULT_MAX_LABELSETS
    monkeypatch.setenv("RAFT_METRIC_MAX_LABELSETS", "-3")
    assert regmod._max_labelsets() == 1


def test_registry_thread_safety():
    """Concurrent record + snapshot/render: no exceptions, no lost
    increments."""
    r = MetricRegistry()
    c = r.counter("raft_conc_total")
    h = r.histogram("raft_conc_seconds", reservoir=128)
    n_threads, n_iter = 8, 300
    stop = threading.Event()

    def worker():
        for i in range(n_iter):
            c.inc()
            h.observe(i * 1e-3, worker="w")

    def reader():
        while not stop.is_set():
            r.snapshot()
            r.render_prometheus()

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    rd = threading.Thread(target=reader)
    rd.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    rd.join(timeout=10)
    assert not rd.is_alive()
    assert c.value() == n_threads * n_iter
    count, _, _ = h.collect(worker="w")
    assert count == n_threads * n_iter


def test_collect_hook_failure_is_contained():
    r = MetricRegistry()
    r.counter("raft_ok_total").inc()
    r.add_collect_hook(lambda reg: 1 / 0)
    text = r.render_prometheus()  # must not raise
    assert "raft_ok_total 1" in text
    assert r.counter("raft_obs_collect_errors_total").value() >= 1


# ---------------------------------------------------------------------
# Prometheus exposition
# ---------------------------------------------------------------------

# One sample line: name{labels} value  (value: int/float/scientific)
_SAMPLE_RE = re.compile(
    r"^[a-zA-Z_:][a-zA-Z0-9_:]*"
    r"(\{[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\""
    r"(,[a-zA-Z_][a-zA-Z0-9_]*=\"[^\"\n]*\")*\})?"
    r" -?[0-9.eE+-]+$")


def test_prometheus_exposition_parses():
    r = MetricRegistry()
    r.counter("raft_req_total", 'with "quotes" and\nnewline').inc(3)
    r.counter("raft_req_total").inc(1, bucket="440x1024", batch="8")
    r.gauge("raft_pending").set(0.0)
    h = r.histogram("raft_lat_seconds", "latency")
    for i in range(20):
        h.observe(i * 1e-3)
    text = r.render_prometheus()
    assert text.endswith("\n")
    types = {}
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, name, kind = line.split()
            types[name] = kind
        elif not line.startswith("#"):
            assert _SAMPLE_RE.match(line), f"unparseable line: {line!r}"
    # stable public names + correct types (histogram -> summary)
    assert types == {"raft_req_total": "counter",
                     "raft_pending": "gauge",
                     "raft_lat_seconds": "summary"}
    assert 'raft_req_total{batch="8",bucket="440x1024"} 1' in text
    assert "raft_lat_seconds_count 20" in text
    assert 'quantile="0.5"' in text


# ---------------------------------------------------------------------
# event sink
# ---------------------------------------------------------------------

def test_event_sink_jsonl(tmp_path):
    sink = EventSink(str(tmp_path))
    sink.emit("alpha", step=7, foo="bar", value=1.5)
    sink.emit("beta")
    sink.close()
    files = list(tmp_path.glob("telemetry-p*.jsonl"))
    assert len(files) == 1
    recs = [json.loads(line) for line in files[0].read_text().splitlines()]
    assert [r["event"] for r in recs] == ["alpha", "beta"]
    a = recs[0]
    assert a["step"] == 7 and a["foo"] == "bar" and a["value"] == 1.5
    assert a["process"] == jax.process_index()
    assert isinstance(a["t_wall"], float) and isinstance(a["t_mono"], float)
    assert recs[1]["t_mono"] >= a["t_mono"]  # monotonic within a process
    assert "step" not in recs[1]


def test_event_sink_disabled_and_unjsonable(tmp_path):
    off = EventSink(None)
    assert not off.enabled
    off.emit("x", anything=object())  # no-op, no error, no file
    on = EventSink(str(tmp_path))
    on.emit("x", arr=np.float32(1.25))  # default=str keeps this alive
    on.close()
    (f,) = tmp_path.glob("*.jsonl")
    assert json.loads(f.read_text())["arr"] in (1.25, "1.25")


def test_span_records_histogram_and_event(tmp_path):
    r = MetricRegistry()
    sink = EventSink(str(tmp_path))
    with span("raft_eval_forward", registry=r, sink=sink, dataset="x"):
        pass
    count, total, _ = r.histogram(
        "raft_eval_forward_seconds").collect(dataset="x")
    assert count == 1 and total >= 0
    sink.close()
    (f,) = tmp_path.glob("*.jsonl")
    rec = json.loads(f.read_text())
    assert rec["event"] == "span" and rec["name"] == "raft_eval_forward"
    assert rec["dataset"] == "x" and rec["seconds"] >= 0


# ---------------------------------------------------------------------
# train telemetry
# ---------------------------------------------------------------------

def test_train_telemetry_stream(tmp_path, monkeypatch):
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    t = TrainTelemetry(str(tmp_path), batch_size=16, num_devices=4,
                       image_size=(368, 496))
    assert t.enabled
    t.start(start_step=0, num_steps=100)
    t.record_compile(0, 12.5, key=("train_step", (368, 496), 16))
    # a closed `train` unit of the stage clock + the `input` unit of the
    # batch it consumed (obs/stages.py): all record_step reads
    t.record_step({"step": 0, "t_start": 10.0, "t_end": 10.5,
                   "stages": {"input_wait": 0.01, "dispatch": 0.4}},
                  {"stages": {"h2d": 0.002, "prep": 0.001}})
    t.record_hbm({"peak_hbm_gb": 3.5})
    t.close()
    (f,) = tmp_path.glob("*.jsonl")
    recs = [json.loads(line) for line in f.read_text().splitlines()]
    by_event = {r["event"]: r for r in recs}
    assert set(by_event) == {"run_config", "compile", "train_step",
                             "hbm_usage", "metrics_summary"}
    rc = by_event["run_config"]
    assert rc["batch_size"] == 16 and rc["image_size"] == [368, 496]
    ts = by_event["train_step"]
    assert ts["step_time_s"] == 0.5 and ts["queue_wait_s"] == 0.01
    assert ts["h2d_s"] == 0.002 and ts["prep_s"] == 0.001
    assert ts["pairs_per_sec_per_chip"] == 8.0  # 16 / 0.5 / 4
    assert by_event["hbm_usage"]["peak_hbm_gb"] == 3.5
    summary = by_event["metrics_summary"]["metrics"]
    assert summary["raft_train_step_seconds"]["values"][""]["count"] == 1
    # the compile listener's seconds, pulled by the registry's hook
    assert summary["raft_compile_seconds_total"]["type"] == "counter"


def test_train_telemetry_disabled_writes_nothing(tmp_path, monkeypatch):
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    t = TrainTelemetry(None, batch_size=8, num_devices=1,
                       image_size=(32, 32))
    assert not t.enabled and not t.hbm_enabled
    t.start(0, 10)
    t.record_step({"step": 0, "t_start": 0.0, "t_end": 0.1,
                   "stages": {}})
    t.close()
    assert list(tmp_path.iterdir()) == []


# ---------------------------------------------------------------------
# the instrumented loop (stubbed step: fast tier)
# ---------------------------------------------------------------------

class _SyncSpy:
    """Device-array stand-in that counts host transfers."""

    calls = 0

    def __init__(self, v):
        self._v = v

    def __array__(self, dtype=None, copy=None):
        _SyncSpy.calls += 1
        return np.asarray(self._v, dtype or np.float32)


# The numerics-telemetry metric surface the real step emits
# (train/step.py): every key a transfer-counting spy, so the no-sync
# contract below covers the training-health path too — the health
# monitor must feed off the Logger's converted arrays, never pull its
# own.
_STUB_METRIC_KEYS = ("loss", "param_norm", "update_ratio", "nonfinite",
                     "epe_iter")


def _stub_loop(monkeypatch, loop_mod):
    """Stub init_state/make_train_step on the loop module: a 'step' just
    bumps the counter and returns transfer-counting metrics."""
    from raft_tpu.train.state import TrainState

    def fake_init_state(model, tx, rng, size):
        params = {"w": np.zeros((2, 2), np.float32)}
        return TrainState(step=jnp.asarray(0, jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params))

    def fake_make_train_step(model, tx, cfg, mesh, shard_spatial=False):
        def step_fn(state, batch, key):
            metrics = {k: _SyncSpy([0.5, 0.25] if k == "epe_iter"
                                   else 1.0)
                       for k in _STUB_METRIC_KEYS}
            metrics["nonfinite"] = _SyncSpy(0.0)
            return state.replace(step=state.step + 1), metrics

        return step_fn

    monkeypatch.setattr(loop_mod, "init_state", fake_init_state)
    monkeypatch.setattr(loop_mod, "make_train_step", fake_make_train_step)


def _slow_batches(n, batch_size, hw, slow_steps=(), delay=0.06):
    import time

    H, W = hw
    rng = np.random.default_rng(0)
    for i in range(n):
        if i in slow_steps:
            time.sleep(delay)  # an input-bound step
        yield {"image1": rng.uniform(0, 255, (batch_size, H, W, 3)
                                     ).astype(np.float32),
               "image2": rng.uniform(0, 255, (batch_size, H, W, 3)
                                     ).astype(np.float32),
               "flow": np.zeros((batch_size, H, W, 2), np.float32),
               "valid": np.ones((batch_size, H, W), np.float32)}


def test_loop_data_wait_and_no_per_step_sync(tmp_path, monkeypatch,
                                             capsys):
    """The acceptance contract in one run: the telemetry JSONL carries
    per-step ``step_time_s``/``queue_wait_s``/``h2d_s``; an
    artificially slow iterator shows up in ``queue_wait_s``; the ONLY
    host transfers are the Logger's once-per-interval flushes
    (telemetry adds zero, and the flush cadence is unchanged); and
    scripts/telemetry_summary.py folds the log into one JSON line.
    Serial pipeline (device_prefetch=0) so the slow fetch lands on a
    deterministic step; the overlapped attribution is covered in
    tests/test_prefetch.py."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.train import loop as loop_mod

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    tdir = tmp_path / "telemetry"
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)

    def run(name, telemetry_dir):
        cfg = TrainConfig(name=name, num_steps=4, batch_size=8,
                          image_size=(32, 32), iters=2, val_freq=100,
                          log_freq=2, ckpt_dir=str(tmp_path / name),
                          device_prefetch=0)
        _SyncSpy.calls = 0
        loop_mod.train(mcfg, cfg,
                       _slow_batches(10, 8, (32, 32), slow_steps=(2,)),
                       telemetry_dir=telemetry_dir)
        flushes = sum(1 for line in capsys.readouterr().out.splitlines()
                      if line.startswith("["))  # Logger interval lines
        return _SyncSpy.calls, flushes

    transfers_off, flushes_off = run("off", None)
    transfers_on, flushes_on = run("on", str(tdir))
    # Telemetry — including the training-health path (HealthMonitor +
    # registry gauges + train_health events, active on the "on" run) —
    # adds ZERO host transfers, and the Logger still flushes once per
    # log_freq interval (4 steps / 2 = 2 flushes), pulling each
    # buffered step value exactly once — never per step as it happens,
    # and never a second time for the health observer.
    expected = 4 * len(_STUB_METRIC_KEYS)  # num_steps * metric keys
    assert transfers_on == transfers_off == expected
    assert flushes_on == flushes_off == 2
    # ... and both counts are with the always-on stage clock
    # (obs/stages.py) timing every step, telemetry or not.
    from raft_tpu.obs import stages

    assert [r["step"] for r in stages.recent("train")][-8:] \
        == [0, 1, 2, 3] * 2

    (f,) = tdir.glob("telemetry-p*.jsonl")
    recs = [json.loads(line) for line in f.read_text().splitlines()]
    events = [r["event"] for r in recs]
    assert events[0] == "run_config" and events[-1] == "metrics_summary"
    assert "compile" in events and "hbm_usage" in events
    steps = {r["step"]: r for r in recs if r["event"] == "train_step"}
    assert sorted(steps) == [0, 1, 2, 3]
    for r in steps.values():
        assert r["step_time_s"] >= r["queue_wait_s"] >= 0
        assert r["h2d_s"] >= 0 and r["prep_s"] >= 0
        assert r["pairs_per_sec_per_chip"] > 0
    # the slow fetch before step 2 is caught by the input-bound detector
    assert steps[2]["queue_wait_s"] >= 0.04
    assert steps[3]["queue_wait_s"] < 0.04

    # JSONL -> one JSON line (schema + metric-name mapping).
    spec = importlib.util.spec_from_file_location(
        "telemetry_summary", osp.join(REPO, "scripts",
                                      "telemetry_summary.py"))
    ts = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ts)
    out = ts.summarize(*ts.last_run(ts.iter_records(str(tdir))), skip=2)
    assert out["metric"] == "train_throughput_custom_32x32_bf16_iters12"
    assert out["unit"] == "image-pairs/sec/chip" and out["value"] > 0
    assert out["config"]["steps_measured"] == 2
    assert 0 <= out["config"]["queue_wait_frac"] <= 1
    assert 0 <= out["config"]["h2d_frac"] <= 1


def test_loop_telemetry_disabled_by_default(tmp_path, monkeypatch):
    """No telemetry dir, no env var -> no telemetry files anywhere, and
    the loop still runs (the layer is a no-op when disabled)."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.train import loop as loop_mod

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    cfg = TrainConfig(name="t", num_steps=2, batch_size=8,
                      image_size=(32, 32), iters=2, val_freq=100,
                      log_freq=2, ckpt_dir=str(tmp_path / "ck"))
    state = loop_mod.train(
        RAFTConfig.small_model(corr_levels=2, corr_radius=2), cfg,
        _slow_batches(4, 8, (32, 32)))
    assert int(state.step) == 2
    assert not list(tmp_path.glob("**/telemetry-*.jsonl"))
