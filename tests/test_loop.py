"""End-to-end loop test: synthetic in-memory batches, checkpoint/resume."""

import numpy as np
import jax
import pytest

from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.models.raft import RAFT
from raft_tpu.train import init_state, make_optimizer
from raft_tpu.train.checkpoint import CheckpointManager
from raft_tpu.train.loop import add_image_noise, train

pytestmark = pytest.mark.slow


def _batches(n, tcfg, seed=0):
    rng = np.random.default_rng(seed)
    H, W = tcfg.image_size
    for _ in range(n):
        img1 = rng.uniform(0, 255, size=(tcfg.batch_size, H, W, 3)
                           ).astype(np.float32)
        img2 = np.roll(img1, 1, axis=2)
        flow = np.zeros((tcfg.batch_size, H, W, 2), np.float32)
        flow[..., 0] = 1.0
        yield {"image1": img1, "image2": img2, "flow": flow,
               "valid": np.ones((tcfg.batch_size, H, W), np.float32)}


def test_add_image_noise_bounds():
    tcfg = TrainConfig(batch_size=2, image_size=(16, 16))
    b = next(_batches(1, tcfg))
    out = add_image_noise(np.random.default_rng(0), b)
    assert out["image1"].min() >= 0 and out["image1"].max() <= 255
    assert not np.array_equal(out["image1"], b["image1"])
    np.testing.assert_array_equal(out["flow"], b["flow"])


def test_train_loop_checkpoint_and_resume(tmp_path, monkeypatch):
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    tcfg = TrainConfig(name="t", lr=1e-4, num_steps=4, batch_size=8,
                       image_size=(32, 32), iters=2, val_freq=2,
                       log_freq=2, ckpt_dir=str(tmp_path))
    calls = []

    def fake_validator(variables):
        calls.append(1)
        return {"val/metric": 1.0}

    # hbm/cost snapshots would lower+compile the real step a second
    # time; the fast tier covers the events, this test the stream.
    monkeypatch.setenv("RAFT_TELEMETRY_HBM", "0")
    monkeypatch.setenv("RAFT_TELEMETRY_COST", "0")
    tdir = tmp_path / "telemetry"
    state = train(mcfg, tcfg, _batches(10, tcfg),
                  validators={"fake": fake_validator},
                  telemetry_dir=str(tdir))
    assert int(state.step) == 4
    assert len(calls) == 2  # steps 2 and 4

    # Real-model telemetry end-to-end: per-step JSONL with the
    # input-bound detector fields, plus one compile event.
    import json

    (f,) = tdir.glob("telemetry-p*.jsonl")
    recs = [json.loads(line) for line in f.read_text().splitlines()]
    steps = [r for r in recs if r["event"] == "train_step"]
    assert [r["step"] for r in steps] == [0, 1, 2, 3]
    assert all(r["step_time_s"] >= r["queue_wait_s"] >= 0 for r in steps)
    assert all(r["h2d_s"] >= 0 for r in steps)
    compiles = [r for r in recs if r["event"] == "compile"]
    assert len(compiles) == 1 and compiles[0]["step"] == 0

    # Resume: a fresh call with the same ckpt_dir restores step 4 and
    # trains on to step 6.
    import dataclasses
    state2 = train(mcfg, dataclasses.replace(tcfg, num_steps=6),
                   _batches(10, tcfg))
    assert int(state2.step) == 6


def test_checkpoint_manager_roundtrip(tmp_path):
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    model = RAFT(mcfg)
    tx = make_optimizer(1e-4, 10)
    state = init_state(model, tx, jax.random.PRNGKey(0), (32, 32))
    mgr = CheckpointManager(str(tmp_path / "ck"), async_save=False)
    mgr.save(3, state, force=True)
    mgr.wait()
    assert mgr.latest_step() == 3
    restored = mgr.restore_latest(state)
    leaves0 = jax.tree_util.tree_leaves(state.params)
    leaves1 = jax.tree_util.tree_leaves(restored.params)
    for a, b in zip(leaves0, leaves1):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    p = mgr.restore_params(state)
    assert "params" in p and "batch_stats" in p
    mgr.close()


def test_single_host_request_preemption_saves_and_resumes(tmp_path):
    """The cooperative single-host SIGTERM path (the one the CLI wires):
    request_preemption() mid-stream must exit SystemExit(143) at the
    next step boundary, flush the emergency checkpoint, and a fresh
    train() must resume from it.  This is the only coverage of the
    _PREEMPT flag path — the multihost child deliberately uses the
    agreed-step exit instead (the flag is gated to process_count()==1)."""
    from raft_tpu.train import loop as loop_mod

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    # Serial pipeline: with background prefetch the producer races ahead
    # of the consumer, so WHICH boundary observes the flag depends on
    # thread timing — the exact-step assertion below needs depth 0 (the
    # cooperative-save semantics are the same either way).
    tcfg = TrainConfig(name="p", lr=1e-4, num_steps=6, batch_size=8,
                       image_size=(32, 32), iters=2, val_freq=4,
                       log_freq=2, ckpt_dir=str(tmp_path),
                       device_prefetch=0)

    def preempting_batches():
        for n, b in enumerate(_batches(10, tcfg)):
            if n == 3:  # past the step-boundary check for step 3
                loop_mod.request_preemption()
            yield b

    with pytest.raises(SystemExit) as ex:
        train(mcfg, tcfg, preempting_batches())
    assert ex.value.code == 143
    # Emergency save flushed the last completed step (3: flag was set
    # while fetching batch 3, observed at that step's boundary check).
    mgr = CheckpointManager(str(tmp_path / "p"))
    assert mgr.latest_step() == 3
    mgr.close()

    state = train(mcfg, tcfg, _batches(10, tcfg))
    assert int(state.step) == 6


# ------------------------------------------------ one build of the step

def _step_builds_of(run):
    """Run ``run()`` (a train() call) -> the compile ring's records of the
    mesh train step it left: kinds of ``mesh_step_fn`` (traces) and of
    ``jit(mesh_step_fn)`` (lowering, compile or cache load)."""
    from raft_tpu.obs import stages
    from raft_tpu.utils.profiling import listen_for_compiles

    listen_for_compiles()
    before = len(stages.recent("compile"))
    state = run()
    mine = stages.recent("compile")[before:]
    return state, [r["kind"] for r in mine
                   if r["name"] in ("mesh_step_fn", "jit(mesh_step_fn)")]


def _tiny(tmp_path, name, num_steps, **kw):
    return TrainConfig(name=name, lr=1e-4, num_steps=num_steps,
                       batch_size=2, image_size=(32, 32), iters=2,
                       val_freq=100, log_freq=100, ckpt_dir=str(tmp_path),
                       **kw)


ONE_BUILD = ["trace", "lower", "compile"]


@pytest.mark.parametrize("n_dev", [1, 2])
def test_fresh_train_builds_step_once(tmp_path, n_dev):
    """A fresh train() of 3 steps traces, lowers and compiles the real
    step once: the state enters step 0 with the mesh-typed sharding the
    step gives back, so all calls share one jit cache key."""
    from raft_tpu.parallel import make_mesh

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    tcfg = _tiny(tmp_path, "f", 3)
    mesh = make_mesh(num_data=n_dev, devices=jax.devices()[:n_dev])
    state, kinds = _step_builds_of(
        lambda: train(mcfg, tcfg, _batches(3, tcfg), mesh=mesh))
    assert int(state.step) == 3
    assert kinds == ONE_BUILD


def test_resumed_and_warm_started_train_build_step_once(tmp_path):
    """The resumed path (restore_latest) and the warm-started one
    (restore_params, a curriculum stage's seed) build the step once too."""
    import dataclasses

    from raft_tpu.parallel import make_mesh

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    mesh = make_mesh(num_data=2, devices=jax.devices()[:2])
    tcfg = _tiny(tmp_path, "r", 2)
    first = train(mcfg, tcfg, _batches(2, tcfg), mesh=mesh)
    seed_params = {"params": jax.device_get(first.params),
                   "batch_stats": jax.device_get(first.batch_stats)}

    more = dataclasses.replace(tcfg, num_steps=5)
    state, kinds = _step_builds_of(
        lambda: train(mcfg, more, _batches(3, tcfg, seed=1), mesh=mesh))
    assert int(state.step) == 5      # resumed at 2, three steps on
    assert kinds == ONE_BUILD

    warm = _tiny(tmp_path, "w", 3)
    state, kinds = _step_builds_of(
        lambda: train(mcfg, warm, _batches(3, tcfg), mesh=mesh,
                      restore_params=seed_params))
    assert int(state.step) == 3
    assert kinds == ONE_BUILD


def test_step_builds_reads_one(tmp_path, monkeypatch):
    """``step_builds`` — the loop's own count of the step's lowerings,
    read once after the third dispatch — is 1 on the third step's
    `train` record and after, and on the `compile` event."""
    import json

    from raft_tpu.obs import stages
    from raft_tpu.parallel import make_mesh

    monkeypatch.setenv("RAFT_TELEMETRY_HBM", "0")
    monkeypatch.setenv("RAFT_TELEMETRY_COST", "0")
    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)
    tcfg = _tiny(tmp_path, "s", 4)
    tdir = tmp_path / "telemetry"
    train(mcfg, tcfg, _batches(4, tcfg), telemetry_dir=str(tdir),
          mesh=make_mesh(num_data=1, devices=jax.devices()[:1]))
    assert [r["step_builds"] for r in stages.recent("train")[-4:]] \
        == [None, None, 1, 1]
    (f,) = tdir.glob("telemetry-p*.jsonl")
    recs = [json.loads(line) for line in f.read_text().splitlines()]
    (compile_event,) = [r for r in recs if r["event"] == "compile"]
    assert compile_event["step"] == 0 and compile_event["step_builds"] == 1
