"""Fleet-serving tests (tier-1): AOT executable export/import and the
zero-compile warm start; router affinity/health-gating/failover/
hedging; supervised restart of crashed and wedged replicas; rolling
weight updates behind the verify + canary gates; the end-to-end chaos
drill (``scripts/serve_fleet_smoke.py --tiny``).

Budget discipline: ONE engine compiles the single ``(40, 56) x b2``
program and exports it (module-scoped ``aot_dir``); every fleet in the
file imports that artifact, so fleets construct in well under a second
and no test but the fixture pays a JIT compile."""

import importlib.util
import json
import os.path as osp
import threading
import time

import numpy as np
import pytest

from raft_tpu import chaos
from raft_tpu.config import RAFTConfig
from raft_tpu.serve import (FleetConfig, FlowRouter, InferenceEngine,
                            ReplicaFleet, RouterConfig, ServeConfig,
                            WeightUpdateError)
from raft_tpu.serve import aot as aot_mod
from raft_tpu.serve.router import is_failover_error

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))

CFG = RAFTConfig.small_model()  # fp32: CPU-friendly, matches test_serve
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


@pytest.fixture(autouse=True)
def _no_leaked_plan():
    chaos.uninstall()
    yield
    chaos.uninstall()


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


def _mk_fleet(variables, aot_dir, *, replicas=2, scfg=None, **fcfg_kw):
    kw = dict(replicas=replicas, aot_dir=aot_dir,
              warmup_shapes=(SHAPE,), auto_export_aot=False,
              restart_backoff_s=0.05, restart_backoff_max_s=0.4,
              health_poll_s=0.05)
    kw.update(fcfg_kw)
    return ReplicaFleet(variables, CFG, scfg or _serve_cfg(),
                        FleetConfig(**kw))


# ---------------------------------------------------------------------------
# AOT export/import
# ---------------------------------------------------------------------------


def test_model_fingerprint_sensitivity(variables):
    """The fingerprint must move with anything that changes the traced
    program: iters, leaf shapes/dtypes, and the tree STRUCTURE (an
    empty added collection changes the input pytree without changing a
    single leaf — the smoke drill's original failure mode)."""
    fp = aot_mod.model_fingerprint(CFG, variables, ITERS)
    assert fp == aot_mod.model_fingerprint(CFG, variables, ITERS)
    assert fp != aot_mod.model_fingerprint(CFG, variables, ITERS + 1)
    restructured = dict(variables, batch_stats={})
    assert fp != aot_mod.model_fingerprint(CFG, restructured, ITERS)


def test_aot_import_gates_and_corruption(variables, aot_dir, tmp_path):
    """A good artifact round-trips; a wrong fingerprint, a truncated
    blob, and a missing directory are each refused with
    ``AOTImportError`` (all-or-nothing: no partial import)."""
    import shutil

    fp = aot_mod.model_fingerprint(CFG, variables, ITERS)
    exes = aot_mod.import_executables(aot_dir, fingerprint=fp)
    assert set(exes) == {((40, 56), 2, "enc"), ((40, 56), 2, "iter")}

    with pytest.raises(aot_mod.AOTImportError, match="fingerprint"):
        aot_mod.import_executables(aot_dir, fingerprint="deadbeef")
    with pytest.raises(aot_mod.AOTImportError, match="manifest"):
        aot_mod.import_executables(str(tmp_path / "nope"),
                                   fingerprint=fp)

    torn = tmp_path / "torn"
    shutil.copytree(aot_dir, torn)
    blob = next(p for p in torn.iterdir()
                if p.name.startswith("exe-"))
    blob.write_bytes(blob.read_bytes()[:100])
    with pytest.raises(aot_mod.AOTImportError, match="checksum"):
        aot_mod.import_executables(str(torn), fingerprint=fp)


def test_aot_roundtrip_runs_with_8_devices_present(tmp_path):
    """Regression for the loader spreading a ONE-device executable over
    every local device: with 8 virtual devices present, an exported and
    re-imported executable must run on the one device it was compiled
    for, whichever of the eight that is ("Expected args to
    execute_sharded_on_local_devices to have 8 shards" before PR 22).
    The device assignment is baked into the artifact: it loads on the
    device it was built for and no other."""
    import jax
    import jax.numpy as jnp

    assert jax.local_device_count() == 8  # tests/conftest.py
    key = ((8, 8), 1, "enc")
    x = np.arange(12.0, dtype=np.float32).reshape(3, 4)
    for device in (jax.local_devices()[0], jax.local_devices()[5]):
        args = jax.device_put(({"w": jnp.float32(2.0)}, x), device)
        compiled = jax.jit(lambda v, a: v["w"] * a + 1.0).lower(
            *args).compile()
        path = str(tmp_path / f"dev{device.id}")
        aot_mod.export_executables({key: compiled}, path,
                                   fingerprint="fp")
        exe = aot_mod.import_executables(
            path, fingerprint="fp", execution_devices=[device])[key]
        out = exe(*args)
        assert out.devices() == {device}
        np.testing.assert_array_equal(out, 2.0 * x + 1.0)
    # Default: where a bare device_put lands (the engine's placement).
    exe = aot_mod.import_executables(str(tmp_path / "dev0"),
                                     fingerprint="fp")[key]
    np.testing.assert_array_equal(
        exe({"w": jnp.float32(3.0)}, x), 3.0 * x + 1.0)
    other = jax.local_devices()[5:6]
    with pytest.raises(aot_mod.AOTImportError, match="deserialize"):
        aot_mod.import_executables(str(tmp_path / "dev0"),
                                   fingerprint="fp",
                                   execution_devices=other)


def test_engine_aot_preload_zero_compiles(variables, aot_dir):
    """An engine built with ``aot_dir`` serves its first request with
    CompileCounter == 0 — the fleet's warm-start contract."""
    eng = InferenceEngine(variables, CFG,
                          _serve_cfg(aot_dir=aot_dir))
    assert eng.aot_info["ok"] is True and eng.aot_info["imported"] == 2
    eng.start()
    try:
        im1, im2 = _images(np.random.default_rng(1))
        flow = eng.infer(im1, im2, timeout=120)
        assert flow.shape == SHAPE + (2,)
        assert np.isfinite(flow).all()
        assert eng.compile_counter.counts() == {}
        assert eng.stats()["aot"]["imported"] == 2
    finally:
        eng.stop()


def test_engine_aot_miss_falls_back_to_lazy_jit(variables, tmp_path):
    """An unusable artifact dir is a warm-start MISS, not a serve
    failure: the engine logs it and compiles lazily."""
    eng = InferenceEngine(variables, CFG,
                          _serve_cfg(aot_dir=str(tmp_path / "empty")))
    assert eng.aot_info["ok"] is False
    eng.start()
    try:
        im1, im2 = _images(np.random.default_rng(1))
        assert eng.infer(im1, im2, timeout=120).shape == SHAPE + (2,)
        assert eng.compile_counter.counts() == {
            ((40, 56), 2, "enc"): 1, ((40, 56), 2, "iter"): 1}
    finally:
        eng.stop()


# ---------------------------------------------------------------------------
# router
# ---------------------------------------------------------------------------


def test_failover_error_classification():
    from raft_tpu.chaos import InjectedReplicaKill, ReplicaWedgedInterrupt
    from raft_tpu.serve import QueueFullError

    assert is_failover_error(InjectedReplicaKill("kill"))
    assert is_failover_error(ReplicaWedgedInterrupt("wedge"))
    assert is_failover_error(RuntimeError("engine stopped — ..."))
    assert is_failover_error(RuntimeError("engine crashed: reason"))
    assert not is_failover_error(ValueError("bad shapes"))
    assert not is_failover_error(QueueFullError("full"))


def test_router_affinity_fallback_and_breaker(variables, aot_dir):
    """Placement policy: the bucket's affine replica gets the traffic;
    exclusion or an open breaker reroutes to the sibling; the breaker
    closes again after its cooldown."""
    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig(breaker_threshold=1,
                                                breaker_cooldown_s=0.3))
        bucket = (40, 56)
        affine = router._pick(bucket, set())
        other = next(r for r in fleet.replicas if r is not affine)
        assert router._pick(bucket, set()) is affine  # deterministic
        assert router._pick(bucket, {affine.name}) is other
        affine.note_failure(1, 0.3)          # breaker opens
        assert affine.breaker_open()
        assert router._pick(bucket, set()) is other
        time.sleep(0.35)                     # cooldown passes
        assert router._pick(bucket, set()) is affine
        assert router._pick(bucket, {affine.name, other.name}) is None

        # live traffic actually lands on the affine replica
        rng = np.random.default_rng(2)
        for _ in range(3):
            router.infer(*_images(rng), timeout=120)
        by_rep = router.router_stats()["requests_by_replica"]
        assert by_rep == {affine.name: 3}
    finally:
        fleet.stop()


def test_kill_failover_no_dropped_requests(variables, aot_dir):
    """The acceptance drill in unit form: a chaos ``replica_kill``
    mid-load fails the victim's in-flight batch over to the sibling;
    every accepted future resolves, the dropped tripwire stays 0, and
    the supervisor restarts the victim with ZERO compiles (AOT)."""
    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig())
        chaos.install(chaos.FaultPlan.parse("replica_kill@batch=2",
                                            seed=0))
        rng = np.random.default_rng(3)
        futs = []
        for _ in range(8):
            futs.append(router.submit(*_images(rng)))
            time.sleep(0.01)
        results = [f.result(timeout=120) for f in futs]
        assert all(r.shape == SHAPE + (2,) for r in results)
        rstats = router.router_stats()
        assert rstats["dropped_total"] == 0
        assert rstats["failovers_total"] >= 1
        _wait_for(lambda: sum(r.restarts for r in fleet.replicas) == 1
                  and all(r.state == "ready" for r in fleet.replicas),
                  30, "supervised restart")
        victim = next(r for r in fleet.replicas if r.restarts)
        assert victim.engine.aot_info["ok"] is True
        assert victim.engine.compile_counter.counts() == {}
        assert router.infer(*_images(rng),
                            timeout=120).shape == SHAPE + (2,)
        assert victim.engine.compile_counter.counts() == {}
        assert 'reason="crash"' in fleet.metrics_text()
    finally:
        fleet.stop()


def test_hang_detected_as_stall_and_restarted(variables, aot_dir):
    """A wedged device worker (``replica_hang``) never raises on its
    own — the stall watchdog turns health not-ready, the supervisor
    restarts the replica, the interrupted batch fails over, and the
    requests still resolve."""
    scfg = _serve_cfg(stall_timeout_s=0.3, chaos_hang_max_s=20.0)
    fleet = _mk_fleet(variables, aot_dir, scfg=scfg)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig())
        chaos.install(chaos.FaultPlan.parse("replica_hang@batch=1",
                                            seed=0))
        rng = np.random.default_rng(4)
        futs = [router.submit(*_images(rng)) for _ in range(2)]
        results = [f.result(timeout=60) for f in futs]
        assert all(r.shape == SHAPE + (2,) for r in results)
        _wait_for(lambda: sum(r.restarts for r in fleet.replicas) == 1
                  and all(r.state == "ready" for r in fleet.replicas),
                  30, "stall-triggered restart")
        assert 'reason="stall"' in fleet.metrics_text()
    finally:
        fleet.stop()


def test_hedge_covers_straggler(variables, aot_dir):
    """``replica_slow`` makes the primary's batch a straggler; the
    router's bounded hedge duplicates the request onto the sibling,
    which answers first (hedge win) long before the straggler."""
    scfg = _serve_cfg(chaos_slow_s=3.0)
    fleet = _mk_fleet(variables, aot_dir, scfg=scfg)
    fleet.start()
    try:
        router = FlowRouter(fleet,
                            RouterConfig(hedge_timeout_s=0.25))
        chaos.install(chaos.FaultPlan.parse("replica_slow@batch=1",
                                            seed=0))
        rng = np.random.default_rng(5)
        t0 = time.perf_counter()
        flow = router.infer(*_images(rng), timeout=60)
        dt = time.perf_counter() - t0
        assert flow.shape == SHAPE + (2,)
        assert dt < 2.5, f"hedge did not cover the {dt:.1f}s straggler"
        rstats = router.router_stats()
        assert rstats["hedges_total"] == 1
        assert rstats["hedge_wins_total"] == 1
        assert rstats["dropped_total"] == 0
    finally:
        fleet.stop(drain=False)


# ---------------------------------------------------------------------------
# rolling weight updates + fleet lifecycle
# ---------------------------------------------------------------------------


def test_rolling_update_flips_and_gates(variables, aot_dir):
    """An in-memory weight update flips every replica (zero compiles —
    the AOT artifact is weight-independent) and changes what the fleet
    serves; NaN weights and a missing checkpoint dir are refused with
    the version unchanged."""
    import jax

    from raft_tpu.models.raft import RAFT

    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig())
        rng = np.random.default_rng(6)
        im1, im2 = _images(rng)
        before = router.infer(im1, im2, timeout=120)

        k = jax.random.PRNGKey(9)
        model_img = jax.numpy.zeros((1, 40, 56, 3))
        new_vars = jax.device_get(RAFT(CFG).init(
            {"params": k, "dropout": k}, model_img, model_img, iters=1))
        report = fleet.update_weights(new_vars)
        assert report["ok"] and sorted(report["flipped"]) == ["r0", "r1"]
        assert fleet.weights_version == 2
        for r in fleet.replicas:  # flip kept the zero-compile start
            assert r.engine.compile_counter.counts() == {}
            assert r.generation >= 2
        after = router.infer(im1, im2, timeout=120)
        assert after.shape == before.shape
        assert not np.allclose(after, before), \
            "new weights served identical flow — flip did not take"

        poisoned = jax.tree_util.tree_map(
            lambda x: np.full_like(x, np.nan), new_vars)
        with pytest.raises(WeightUpdateError, match="canary"):
            fleet.update_weights(poisoned)
        assert fleet.weights_version == 2
        with pytest.raises(WeightUpdateError, match="not found"):
            fleet.update_weights("/nonexistent/ckpt-dir")
        assert fleet.weights_version == 2
        assert fleet.health()["ready"]
    finally:
        fleet.stop()


def test_scrambled_weights_refused_by_proxy_canary(variables, aot_dir):
    """Finite-but-garbage weights (every param scaled x25) sail through
    the shape+finiteness canary — the flow is the right shape and all
    finite, just wild — and are refused at the golden-batch quality
    proxy gate instead (``FleetConfig.canary_proxy_budget``).  The
    version stays put and the fleet keeps serving the old weights."""
    import jax

    scrambled = jax.tree_util.tree_map(
        lambda x: np.asarray(x) * 25.0, jax.device_get(variables))
    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    try:
        router = FlowRouter(fleet, RouterConfig())
        rng = np.random.default_rng(11)
        im1, im2 = _images(rng)
        before = router.infer(im1, im2, timeout=120)
        version0 = fleet.weights_version
        with pytest.raises(WeightUpdateError, match="proxy"):
            fleet.update_weights(scrambled)
        assert fleet.weights_version == version0
        after = router.infer(im1, im2, timeout=120)
        assert np.allclose(after, before), \
            "refused update changed what the fleet serves"
        assert fleet.health()["ready"]
    finally:
        fleet.stop()


def test_fleet_stop_during_update_warmup_joins_cleanly(variables,
                                                      aot_dir):
    """``fleet.stop(drain=True)`` racing a rolling update's warmup must
    join cleanly: the warming engine is stopped, the update fails with
    ``WeightUpdateError`` instead of hanging, and no replica flips."""
    fleet = _mk_fleet(variables, aot_dir)
    fleet.start()
    gate = threading.Event()
    entered = threading.Event()
    real_canary = fleet._canary

    def blocking_canary(warming):
        entered.set()
        gate.wait(timeout=30)
        return real_canary(warming)

    fleet._canary = blocking_canary
    outcome = {}

    def update():
        try:
            outcome["report"] = fleet.update_weights(
                {k: v for k, v in variables.items()})
        except BaseException as e:  # noqa: BLE001 — recorded for asserts
            outcome["error"] = e

    t = threading.Thread(target=update)
    t.start()
    assert entered.wait(timeout=30), "update never reached the canary"
    warming = fleet._warming
    assert warming is not None
    t0 = time.perf_counter()
    fleet.stop(drain=True)
    assert time.perf_counter() - t0 < 30
    gate.set()
    t.join(timeout=30)
    assert not t.is_alive(), "update thread hung after fleet.stop()"
    assert isinstance(outcome.get("error"), WeightUpdateError), outcome
    assert warming._stopped
    assert fleet.weights_version == 1
    assert all(r.state == "stopped" for r in fleet.replicas)


# ---------------------------------------------------------------------------
# the end-to-end drill
# ---------------------------------------------------------------------------


def test_serve_fleet_smoke_tiny(capsys):
    """The chaos drill the PR promises: AOT warm start, replica kill
    under open-loop load with zero dropped accepted requests, restart
    with zero compiles, verify+canary-gated rolling update."""
    mod = _load_script("serve_fleet_smoke")
    rc = mod.main(["--tiny", "--requests", "10"])
    out = capsys.readouterr().out.strip().splitlines()
    rec = json.loads(out[-1])
    assert rc == 0
    assert rec["metric"] == "serve_fleet_smoke" and rec["value"] == 1.0
    drill = rec["config"]["kill_drill"]
    assert drill["dropped"] == 0 and drill["failovers"] >= 1
    assert sum(drill["restarts"].values()) >= 1
    assert rec["config"]["rolling_update"]["version"] == 2
