"""Training-health tests: the in-graph non-finite guard, the numerics
telemetry path (Logger flush -> HealthMonitor -> registry/JSONL), the
forensic-bundle -> replay_step round trip, the stall watchdog, the
SIGQUIT stack dump, the legacy-checkpoint counter fallback, and the
check_regression gate.

The two jit-compiling tests (guard step, loop e2e + replay) use the
tiniest viable model/shapes; everything else is stubbed or pure host
code so the file stays in the fast tier."""

import importlib.util
import json
import os.path as osp
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.obs import EventSink
from raft_tpu.obs.health import (HealthMonitor, load_forensic_bundle,
                                 tree_all_finite, tree_select,
                                 write_forensic_bundle)
from raft_tpu.obs.train import TrainTelemetry
from raft_tpu.obs.watchdog import StallWatchdog, install_sigquit_dump
from raft_tpu.train.logger import Logger

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, osp.join(REPO, "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------
# in-graph helpers
# ---------------------------------------------------------------------

def test_tree_all_finite_and_select():
    tree = {"a": jnp.ones((2, 2)), "b": jnp.zeros((), jnp.int32),
            "c": [jnp.asarray(1.5)]}
    assert bool(tree_all_finite(tree))
    bad = dict(tree, a=jnp.asarray([[1.0, np.inf], [0.0, 0.0]]))
    assert not bool(tree_all_finite(bad))
    assert not bool(tree_all_finite({"x": jnp.asarray(np.nan)}))
    assert bool(tree_all_finite({"ints": jnp.arange(3)}))  # skipped kinds

    sel = tree_select(jnp.asarray(False), tree, bad)
    np.testing.assert_array_equal(np.asarray(sel["a"]),
                                  np.asarray(bad["a"]))
    sel = tree_select(jnp.asarray(True), tree, bad)
    np.testing.assert_array_equal(np.asarray(sel["a"]),
                                  np.asarray(tree["a"]))
    assert sel["b"].dtype == jnp.int32  # int leaves survive the select


# ---------------------------------------------------------------------
# the guarded train step (one tiny jit compile)
# ---------------------------------------------------------------------

def test_guard_skips_poisoned_update_bit_identical():
    """NaN-injection at the step level: a poisoned batch must leave
    params AND opt_state bit-identical, bump the TrainState counter,
    flag the metrics — and a following clean step must train again.
    Also pins the numerics-metric surface: param_norm / update_ratio
    scalars, (iters,)-shaped loss_iter / epe_iter curves."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    tcfg = TrainConfig(num_steps=10, batch_size=2, image_size=(24, 32),
                       iters=2)
    model = RAFT(mcfg)
    tx = make_optimizer(tcfg.lr, tcfg.num_steps, tcfg.wdecay,
                        tcfg.epsilon, tcfg.clip)
    state = init_state(model, tx, jax.random.PRNGKey(0), (24, 32))
    assert int(state.nonfinite_steps) == 0

    rng = np.random.default_rng(0)
    batch = {"image1": rng.uniform(0, 255, (2, 24, 32, 3))
             .astype(np.float32),
             "image2": rng.uniform(0, 255, (2, 24, 32, 3))
             .astype(np.float32),
             "flow": np.zeros((2, 24, 32, 2), np.float32),
             "valid": np.ones((2, 24, 32), np.float32)}
    poisoned = dict(batch, image1=batch["image1"].copy())
    poisoned["image1"][0, 0, 0, 0] = np.inf

    step_fn = make_train_step(model, tx, tcfg, donate=False)
    key = jax.random.PRNGKey(1)
    s1, m1 = step_fn(state, batch, key)
    assert float(m1["nonfinite"]) == 0.0
    assert int(s1.nonfinite_steps) == 0
    assert float(m1["param_norm"]) > 0
    assert 0 < float(m1["update_ratio"]) < 1
    assert np.asarray(m1["loss_iter"]).shape == (2,)
    assert np.asarray(m1["epe_iter"]).shape == (2,)
    assert np.isfinite(np.asarray(m1["epe_iter"])).all()
    # clean update actually moved the params
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), state.params, s1.params)
    assert not all(jax.tree_util.tree_leaves(moved))

    s2, m2 = step_fn(s1, poisoned, key)
    assert float(m2["nonfinite"]) == 1.0
    assert int(s2.nonfinite_steps) == 1
    assert int(s2.step) == int(s1.step) + 1  # schedule moves on
    same = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), s1.params, s2.params)
    assert all(jax.tree_util.tree_leaves(same)), "guard leaked an update"
    same_opt = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), s1.opt_state,
        s2.opt_state)
    assert all(jax.tree_util.tree_leaves(same_opt))
    same_bs = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), s1.batch_stats,
        s2.batch_stats)
    assert all(jax.tree_util.tree_leaves(same_bs))

    s3, m3 = step_fn(s2, batch, key)  # recovery: training continues
    assert float(m3["nonfinite"]) == 0.0
    assert int(s3.nonfinite_steps) == 1
    moved = jax.tree_util.tree_map(
        lambda a, b: bool(jnp.array_equal(a, b)), s2.params, s3.params)
    assert not all(jax.tree_util.tree_leaves(moved))


# ---------------------------------------------------------------------
# loop e2e: poison -> counter + JSONL + bundle -> replay reproduces
# ---------------------------------------------------------------------

def test_nonfinite_e2e_forensics_and_replay(tmp_path, monkeypatch):
    """The acceptance path end-to-end: a real tiny training run hits an
    inf pixel at step 2 — the run finishes (guard), the JSONL carries
    the flag, a forensic bundle lands under telemetry/forensics, and
    scripts/replay_step.py reproduces the non-finite gradients from the
    bundle + the run's checkpoint."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.train.loop import train

    monkeypatch.setenv("RAFT_TELEMETRY_HBM", "0")
    monkeypatch.setenv("RAFT_TELEMETRY_COST", "0")
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    tcfg = TrainConfig(name="t", num_steps=4, batch_size=8,
                       image_size=(24, 32), iters=2, val_freq=100,
                       log_freq=2, ckpt_dir=str(tmp_path / "ck"))

    def batches(n, poison_at=2):
        rng = np.random.default_rng(0)
        for i in range(n):
            b = {"image1": rng.uniform(0, 255, (8, 24, 32, 3))
                 .astype(np.float32),
                 "image2": rng.uniform(0, 255, (8, 24, 32, 3))
                 .astype(np.float32),
                 "flow": np.zeros((8, 24, 32, 2), np.float32),
                 "valid": np.ones((8, 24, 32), np.float32)}
            if i == poison_at:
                b["image1"][0, 0, 0, 0] = np.inf
            yield b

    tdir = tmp_path / "telemetry"
    state = train(mcfg, tcfg, batches(8), telemetry_dir=str(tdir))
    assert int(state.step) == 4          # the run survived the poison
    assert int(state.nonfinite_steps) == 1

    (f,) = tdir.glob("telemetry-p*.jsonl")
    recs = [json.loads(line) for line in f.read_text().splitlines()]
    health = [r for r in recs if r["event"] == "train_health"]
    assert health and health[-1]["nonfinite_steps_total"] == 1
    assert len(health[-1]["epe_iter"]) == 2
    flagged = [r for r in recs if r["event"] == "nonfinite_step"]
    assert len(flagged) == 1 and flagged[0]["step"] == 2
    assert flagged[0]["batch_captured"]
    bundle = flagged[0]["bundle"]
    assert osp.exists(bundle)

    # metrics_summary carries the counter + health gauges
    summary = recs[-1]
    assert summary["event"] == "metrics_summary"
    reg = summary["metrics"]
    assert reg["raft_train_nonfinite_steps_total"]["values"][""] == 1
    assert "iter=01" in reg["raft_train_epe_iter"]["values"]

    # telemetry_summary surfaces the health fields (and old-log parsing
    # is covered by test_obs, which has no train_health events)
    ts = _load_script("telemetry_summary")
    out = ts.summarize(*ts.last_run(ts.iter_records(str(tdir))), skip=0)
    assert out["config"]["nonfinite_steps_total"] == 1
    assert len(out["config"]["final_epe_iter"]) == 2
    assert "final_update_ratio" in out["config"]

    # replay: the bundle + the run's checkpoint reproduce the blow-up
    rs = _load_script("replay_step")
    report = rs.replay(bundle, ckpt=str(tmp_path / "ck" / "t"))
    assert report["reproduced"], report
    assert report["step"] == 2
    assert report["batch_nonfinite_elements"]["image1"] == 1
    assert report["nonfinite_grad_leaves"], "no poisoned grads found"


# ---------------------------------------------------------------------
# host-side pieces (no jit): monitor, bundles, logger hook
# ---------------------------------------------------------------------

def test_forensic_bundle_roundtrip(tmp_path):
    batch = {"image1": np.full((1, 4, 4, 3), np.inf, np.float32),
             "flow": np.zeros((1, 4, 4, 2), np.float32)}
    p = write_forensic_bundle(str(tmp_path), 7, batch,
                              {"seed": 3, "metrics": {"loss": 1.0}})
    got, meta = load_forensic_bundle(p)
    assert meta["step"] == 7 and meta["seed"] == 3
    assert meta["batch_captured"]
    np.testing.assert_array_equal(got["image1"], batch["image1"])

    p2 = write_forensic_bundle(str(tmp_path), 8, None, {"seed": 3})
    got2, meta2 = load_forensic_bundle(p2)
    assert got2 is None and not meta2["batch_captured"]


def test_health_monitor_capture_and_ring_eviction(tmp_path):
    telem = TrainTelemetry(str(tmp_path), batch_size=4, num_devices=1,
                           image_size=(8, 8))
    mon = HealthMonitor(telem, forensics_dir=str(tmp_path / "forensics"),
                        seed=5, keep=2, run_meta={"train_cfg": {}})
    batches = {s: {"image1": np.full((1, 2, 2, 3), s, np.float32)}
               for s in range(4)}
    for s in range(4):
        mon.note_batch(s, batches[s])          # ring keeps steps 2, 3
    per_step = [{"loss": np.float32(np.nan), "nonfinite": np.float32(1.0),
                 "param_norm": np.float32(3.0),
                 "update_ratio": np.float32(1e-3),
                 "epe_iter": np.asarray([2.0, 1.0], np.float32)}
                if s in (1, 3) else
                {"loss": np.float32(0.5), "nonfinite": np.float32(0.0)}
                for s in range(4)]
    mon.observe_flush(0, {}, per_step)
    assert mon.nonfinite_total == 2
    assert len(mon.bundles) == 2
    b1, m1 = load_forensic_bundle(mon.bundles[0])   # step 1: evicted
    assert b1 is None and m1["step"] == 1
    b3, m3 = load_forensic_bundle(mon.bundles[1])   # step 3: ringed
    assert m3["step"] == 3 and b3["image1"][0, 0, 0, 0] == 3.0
    assert m3["rng"] == {"kind": "fold_in(PRNGKey(seed), step)",
                         "seed": 5, "step": 3}
    assert telem.registry.counter(
        "raft_train_nonfinite_steps_total").value() == 2
    telem.close()
    recs = [json.loads(line) for line in
            next(tmp_path.glob("*.jsonl")).read_text().splitlines()]
    events = [r["event"] for r in recs]
    assert events.count("nonfinite_step") == 2
    th = [r for r in recs if r["event"] == "train_health"][0]
    assert th["nonfinite_in_interval"] == 2
    assert th["param_norm"] == 3.0 and th["epe_iter"] == [2.0, 1.0]


def test_logger_vector_metrics_and_flush_hook(capsys):
    calls = []
    log = Logger(log_freq=2, on_flush=lambda s, means, per_step:
                 calls.append((s, means, per_step)))
    for i in range(4):
        log.push(i, {"loss": np.float32(i),
                     "epe_iter": np.asarray([i, i + 1.0], np.float32)})
    log.close()
    out = capsys.readouterr().out
    lines = [l for l in out.splitlines() if l.startswith("[")]
    assert len(lines) == 2                       # cadence unchanged
    assert "loss" in lines[0] and "epe_iter" not in lines[0]
    assert len(calls) == 2
    first_step, means, per_step = calls[0]
    assert first_step == 0 and len(per_step) == 2
    assert float(means["loss"]) == 0.5
    np.testing.assert_allclose(means["epe_iter"], [0.5, 1.5])
    np.testing.assert_allclose(per_step[1]["epe_iter"], [1.0, 2.0])


def test_logger_hook_failure_is_contained(capsys):
    log = Logger(log_freq=1,
                 on_flush=lambda *a: (_ for _ in ()).throw(OSError("x")))
    log.push(0, {"loss": np.float32(1.0)})
    log.close()
    out = capsys.readouterr().out
    assert "WARNING: logger flush hook failed" in out
    assert any(l.startswith("[") for l in out.splitlines())


# ---------------------------------------------------------------------
# watchdog
# ---------------------------------------------------------------------

def test_watchdog_fires_dumps_and_rearms(tmp_path):
    sink = EventSink(str(tmp_path))
    dump = str(tmp_path / "stacks.txt")
    wd = StallWatchdog(0.15, sink=sink, dump_path=dump,
                       recent_records=lambda: [{"step": 9}],
                       poll_s=0.02)
    wd.start()
    try:
        for _ in range(5):                     # healthy heartbeats
            wd.beat(1)
            time.sleep(0.03)
        assert wd.stall_count == 0
        time.sleep(0.4)                        # stall
        assert wd.stall_count == 1             # fired exactly once
        wd.beat(2)                             # re-arm
        time.sleep(0.4)
        assert wd.stall_count == 2
    finally:
        wd.stop()
    sink.close()
    with open(dump) as f:
        text = f.read()
    assert "stall watchdog" in text and "Thread" in text
    recs = [json.loads(line) for line in
            next(tmp_path.glob("*.jsonl")).read_text().splitlines()]
    stalls = [r for r in recs if r["event"] == "stall"]
    assert len(stalls) == 2
    assert stalls[0]["step"] == 1 and stalls[0]["stacks"] == dump
    assert stalls[0]["seconds_since_heartbeat"] >= 0.15
    assert stalls[0]["recent"] == [{"step": 9}]


def test_watchdog_pause_resume(tmp_path):
    wd = StallWatchdog(0.1, poll_s=0.02)
    wd.start()
    try:
        wd.beat(0)
        wd.pause()
        time.sleep(0.3)                        # "validation"
        assert wd.stall_count == 0
        wd.resume()
        time.sleep(0.05)
        assert wd.stall_count == 0             # resume reset the clock
        time.sleep(0.3)
        assert wd.stall_count == 1
    finally:
        wd.stop()


def _loop_cfg(tmp_path, name, **kw):
    from raft_tpu.config import TrainConfig

    return TrainConfig(name=name, num_steps=4, batch_size=8,
                       image_size=(32, 32), iters=2, val_freq=100,
                       log_freq=2, ckpt_dir=str(tmp_path / name),
                       device_prefetch=0, **kw)


def test_watchdog_fires_on_blocked_iterator(tmp_path, monkeypatch):
    """A stalled input iterator (the classic wedged-loader hang) trips
    the watchdog mid-run: `stall` JSONL event with thread stacks and the
    last telemetry records; the run itself still completes."""
    from raft_tpu.config import RAFTConfig
    from raft_tpu.train import loop as loop_mod
    from tests.test_obs import _slow_batches, _stub_loop

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    tdir = tmp_path / "telemetry"
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    cfg = _loop_cfg(tmp_path, "wd", watchdog_timeout=0.3)
    state = loop_mod.train(
        mcfg, cfg, _slow_batches(8, 8, (32, 32), slow_steps=(2,),
                                 delay=1.0),
        telemetry_dir=str(tdir))
    assert int(state.step) == 4
    recs = [json.loads(line) for line in
            next(tdir.glob("telemetry-p*.jsonl")).read_text().splitlines()]
    stalls = [r for r in recs if r["event"] == "stall"]
    assert len(stalls) == 1
    assert stalls[0]["seconds_since_heartbeat"] >= 0.3
    assert stalls[0]["recent"], "stall event lost the recent records"
    with open(stalls[0]["stacks"]) as f:
        assert "Thread" in f.read()


def test_watchdog_quiet_on_healthy_run(tmp_path, monkeypatch):
    from raft_tpu.config import RAFTConfig
    from raft_tpu.train import loop as loop_mod
    from tests.test_obs import _slow_batches, _stub_loop

    _stub_loop(monkeypatch, loop_mod)
    monkeypatch.delenv("RAFT_TELEMETRY_DIR", raising=False)
    tdir = tmp_path / "telemetry"
    cfg = _loop_cfg(tmp_path, "ok", watchdog_timeout=30.0)
    loop_mod.train(RAFTConfig.small_model(corr_levels=2, corr_radius=2),
                   cfg, _slow_batches(8, 8, (32, 32)),
                   telemetry_dir=str(tdir))
    recs = [json.loads(line) for line in
            next(tdir.glob("telemetry-p*.jsonl")).read_text().splitlines()]
    assert not [r for r in recs if r["event"] == "stall"]
    assert not (tdir / "stacks-p0.txt").exists()


@pytest.mark.skipif(not hasattr(signal, "SIGQUIT"),
                    reason="platform has no SIGQUIT")
def test_sigquit_stack_dump(tmp_path):
    import faulthandler
    import os

    dump = str(tmp_path / "stacks.txt")
    try:
        assert install_sigquit_dump(dump) == dump
        os.kill(os.getpid(), signal.SIGQUIT)
        deadline = time.time() + 5
        marker = "most recent call first"  # faulthandler dump format
        while time.time() < deadline:
            with open(dump) as f:
                if marker in f.read():
                    break
            time.sleep(0.05)
        with open(dump) as f:
            assert marker in f.read()
    finally:
        faulthandler.unregister(signal.SIGQUIT)


# ---------------------------------------------------------------------
# legacy checkpoint fallback
# ---------------------------------------------------------------------

def test_restore_legacy_checkpoint_without_counter(tmp_path):
    """A checkpoint saved by pre-guard code (no nonfinite_steps leaf)
    must restore into the new TrainState with the counter re-attached
    at zero."""
    import optax

    from raft_tpu.train.checkpoint import CheckpointManager
    from raft_tpu.train.state import TrainState

    params = {"w": jnp.ones((2, 2), jnp.float32)}
    tx = optax.sgd(1e-2)
    legacy = TrainState(step=jnp.asarray(3, jnp.int32), params=params,
                        batch_stats={}, opt_state=tx.init(params))
    assert legacy.nonfinite_steps is None     # the old pytree structure
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(3, legacy)
    mgr.wait()

    template = TrainState(step=jnp.zeros((), jnp.int32), params=params,
                          batch_stats={}, opt_state=tx.init(params),
                          nonfinite_steps=jnp.zeros((), jnp.int32))
    restored = mgr.restore_latest(template)
    mgr.close()
    assert int(restored.step) == 3
    assert int(restored.nonfinite_steps) == 0


# ---------------------------------------------------------------------
# check_regression gate
# ---------------------------------------------------------------------

def test_check_regression_gate(tmp_path, capsys):
    cr = _load_script("check_regression")

    def write(i, value, nonfinite=None, wrap=False):
        rec = {"metric": "train_throughput_x", "value": value,
               "unit": "u", "vs_baseline": 0.0,
               "config": ({} if nonfinite is None
                          else {"nonfinite_steps_total": nonfinite})}
        if wrap:
            rec = {"parsed": rec, "rc": 0}
        p = tmp_path / f"BENCH_r{i:02d}.json"
        p.write_text(json.dumps(rec))
        return str(p)

    flat = [write(0, 30.0), write(1, 31.0, wrap=True), write(2, 30.5)]
    assert cr.main(flat) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["ok"] and out["checked"][0]["n_records"] == 3

    dropped = flat[:2] + [write(3, 20.0)]
    assert cr.main(dropped) == 1
    capsys.readouterr()

    poisoned = flat[:2] + [write(4, 30.4, nonfinite=2)]
    assert cr.main(poisoned) == 1
    capsys.readouterr()

    # tolerance knob: the 33% drop passes at --max-drop-pct 50
    assert cr.main(dropped + ["--max-drop-pct", "50"]) == 0
    capsys.readouterr()


def test_check_regression_tiny_selftest(capsys):
    cr = _load_script("check_regression")
    assert cr.main(["--tiny"]) == 0
    out = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert out["metric"] == "check_regression_selftest"
    assert out["value"] == 1.0


def test_early_exit_gate():
    cr = _load_script("check_regression")
    rec = {"metric": "m", "value": 30.0,
           "config": {"early_exit_epe_delta": 0.02}}
    failures, _ = cr.check({"m": [rec]}, max_early_exit_epe_delta=0.05)
    assert not failures
    rec2 = {"metric": "m", "value": 30.0,
            "config": {"early_exit_epe_delta": 0.2}}
    failures, _ = cr.check({"m": [rec2]}, max_early_exit_epe_delta=0.05)
    assert failures and "early-exit" in failures[0]
    # the gate refuses to pass vacuously
    failures, _ = cr.check({"m": [{"metric": "m", "value": 1.0}]},
                           max_early_exit_epe_delta=0.05)
    assert failures and "did not run" in failures[0]
