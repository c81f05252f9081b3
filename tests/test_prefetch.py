"""Overlapped input pipeline (`raft_tpu/data/prefetch.py`) + gradient
accumulation (`train/step.py accum_steps`) tests.

Fast tier: synthetic in-memory datasets, stubbed or tiny jitted steps.
The contracts pinned here are the PR-3 acceptance criteria: prefetch
on/off batch streams bit-identical (including mid-epoch resume and the
resume-keyed noise RNG), buffer boundedness, steady-state queue wait
< 10% of step time under overlap, accum grads == full-batch grads, and
the bench_input --tiny smoke.
"""

import gc
import json
import os.path as osp
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from raft_tpu.data.datasets import ShardedLoader
from raft_tpu.data.prefetch import DevicePipeline

from loader_datasets import SynthDataset, ledger

REPO = osp.dirname(osp.dirname(osp.abspath(__file__)))


def _noise_fn(seed, start_step):
    """The loop's producer-side prep: resume-keyed noise RNG
    (train/loop.py builds exactly this)."""
    from raft_tpu.train.loop import add_image_noise

    rng = np.random.default_rng(
        np.random.SeedSequence([seed + 1, start_step]))
    return lambda b: add_image_noise(rng, b)


def _take(pipe, n):
    try:
        return [next(pipe) for _ in range(n)]
    finally:
        pipe.close()


# ---------------------------------------------------------------------
# stream identity: prefetch on/off, resume, noise
# ---------------------------------------------------------------------

def test_prefetch_on_off_identical_streams_and_resume():
    """Acceptance: prefetch-on and prefetch-off batch streams are
    bit-identical, including mid-epoch resume via batches_from_step and
    the stateful resume-keyed noise RNG applied in the producer."""
    ds = SynthDataset(n=13)  # batch 2, drop_last -> 6 steps/epoch

    def stream(depth, start_step):
        loader = ShardedLoader(ds, batch_size=2, seed=7, num_workers=2)
        pipe = DevicePipeline(loader.batches_from_step(start_step),
                              prep_fn=_noise_fn(7, start_step),
                              depth=depth)
        return _take(pipe, 8)  # crosses the epoch boundary

    for start in (0, 5):  # fresh run + mid-epoch resume
        serial = stream(0, start)
        overlapped = stream(3, start)
        assert len(serial) == len(overlapped) == 8
        for a, b in zip(serial, overlapped):
            for k in a:
                np.testing.assert_array_equal(a[k], b[k])


def test_prefetch_device_put_parity_and_sharding():
    """With the real sharder, the overlapped arm yields committed
    jax.Arrays with values identical to the serial arm's."""
    from raft_tpu.parallel import make_batch_sharder, make_mesh

    put = make_batch_sharder(make_mesh())
    ds = SynthDataset(n=20)

    def stream(depth):
        loader = ShardedLoader(ds, batch_size=8, seed=3, num_workers=2)
        return _take(DevicePipeline(loader.batches(), put_fn=put,
                                    depth=depth), 3)

    serial, overlapped = stream(0), stream(3)
    for a, b in zip(serial, overlapped):
        for k in a:
            assert isinstance(b[k], jax.Array)
            np.testing.assert_array_equal(np.asarray(a[k]),
                                          np.asarray(b[k]))


def test_loader_prefetch_batches_stream_invariant_and_window(tmp_path):
    """The decode-window knob changes HOW FAR the workers run ahead, never
    the stream; the window actually bounds load-call runahead."""
    def batches(pb, ds):
        loader = ShardedLoader(ds, batch_size=2, seed=5, num_workers=2,
                               prefetch_batches=pb)
        it = loader.batches()
        return [next(it) for _ in range(7)]

    a = batches(0, SynthDataset(n=13))
    b = batches(5, SynthDataset(n=13))
    for x, y in zip(a, b):
        for k in x:
            np.testing.assert_array_equal(x[k], y[k])

    # window = prefetch_batches * batch_size = 2 samples: after pulling
    # 2 batches (4 samples), at most 4 + 2 loads may have been submitted.
    calls = str(tmp_path / "calls")
    ds = SynthDataset(n=13, ledger=calls)
    loader = ShardedLoader(ds, batch_size=2, seed=5, num_workers=2,
                           prefetch_batches=1)
    it = loader.batches()
    next(it), next(it)
    time.sleep(0.5)  # give the workers every chance to overrun
    assert len(ledger(calls)) <= 2 * 2 + 1 * 2, ledger(calls)
    it.close()


# ---------------------------------------------------------------------
# boundedness + lifecycle
# ---------------------------------------------------------------------

def test_prefetch_buffer_bounded():
    """The producer never pulls more than `depth` batches beyond what
    the consumer has taken (slot acquired BEFORE the source is pulled)."""
    pulled = [0]

    def src():
        while True:
            pulled[0] += 1
            yield {"x": np.zeros((4,), np.float32)}

    depth = 3
    pipe = DevicePipeline(src(), depth=depth)
    time.sleep(0.3)  # producer free-runs against an instant source
    assert pulled[0] <= depth
    for i in range(5):
        next(pipe)
        time.sleep(0.05)
        assert pulled[0] <= i + 1 + depth
    pipe.close()
    assert not pipe._thread.is_alive()


def test_prefetch_close_frees_buffered_batches():
    """Weakref/alloc check: close() drops every buffered batch — a
    leaked queue would pin device memory across runs."""
    refs = []

    def src():
        while True:
            a = np.zeros((64,), np.float32)
            refs.append(weakref.ref(a))
            yield {"x": a}

    pipe = DevicePipeline(src(), depth=4)
    first = next(pipe)
    time.sleep(0.2)  # let the buffer fill
    thread = pipe._thread
    pipe.close()
    assert len(refs) >= 3  # the buffer did fill before close
    del first, pipe  # the source generator's frame holds the last yield
    gc.collect()
    assert sum(r() is not None for r in refs) == 0
    assert not thread.is_alive()


def test_prefetch_producer_error_propagates():
    def src():
        yield {"x": np.zeros(2, np.float32)}
        raise RuntimeError("decode failed")

    for depth in (0, 2):
        pipe = DevicePipeline(src(), depth=depth)
        next(pipe)
        with pytest.raises(RuntimeError, match="decode failed"):
            for _ in range(3):
                next(pipe)
        if depth:  # after the error the pipeline is closed
            with pytest.raises(StopIteration):
                next(pipe)
        pipe.close()

    with pytest.raises(ValueError, match="depth"):
        DevicePipeline(iter(()), depth=-1)


def test_prefetch_producer_crash_preserves_type_and_close_joins():
    """Producer-crash semantics (the prefetch.py error-relay path): a
    producer that raises mid-stream re-raises the ORIGINAL exception
    object in the consumer's next(), and close() afterwards returns
    promptly with the thread joined — no hang, no leaked thread,
    idempotent."""

    class BoomError(Exception):
        pass

    boom = BoomError("mid-stream decode crash")

    def src():
        yield {"x": np.zeros((4,), np.float32)}
        yield {"x": np.ones((4,), np.float32)}
        raise boom

    pipe = DevicePipeline(src(), depth=2)
    next(pipe)
    next(pipe)
    with pytest.raises(BoomError) as ei:
        next(pipe)
    assert ei.value is boom  # the original object, not a re-wrap
    # after the error the pipeline is closed and stays closed
    with pytest.raises(StopIteration):
        next(pipe)
    t0 = time.perf_counter()
    pipe.close()
    pipe.close()  # idempotent
    assert time.perf_counter() - t0 < 5.0
    assert not pipe._thread.is_alive()


# ---------------------------------------------------------------------
# the overlap acceptance criterion
# ---------------------------------------------------------------------

def test_queue_wait_under_overlap_acceptance():
    """Synthetic slow-step + fast-loader: steady-state consumer queue
    wait is < 10% of step time with device prefetch on, vs ~ the serial
    fetch cost with it off (the PR-3 acceptance criterion)."""
    step_s, fetch_s, n = 0.05, 0.015, 10

    def src():
        while True:
            time.sleep(fetch_s)
            yield {"x": np.zeros((8,), np.float32)}

    def waits(depth):
        pipe = DevicePipeline(src(), depth=depth)
        ws = []
        try:
            for _ in range(n):
                t = time.perf_counter()
                next(pipe)
                ws.append(time.perf_counter() - t)
                time.sleep(step_s)  # the synthetic "device step"
        finally:
            pipe.close()
        return ws[2:]  # steady state: past the pipeline fill

    overlapped = waits(2)
    serial = waits(0)
    assert float(np.median(overlapped)) < 0.1 * step_s, overlapped
    assert float(np.median(serial)) >= 0.5 * fetch_s, serial


def test_loop_noise_identical_prefetch_on_off(tmp_path, monkeypatch):
    """End-to-end through train(): the batches the step consumes —
    including add_noise applied in the pipeline producer — are
    bit-identical at device_prefetch 0 vs 3 (determinism satellite)."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.train import loop as loop_mod
    from raft_tpu.train.state import TrainState

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2)

    def batches(n=8, bs=8, hw=(8, 10)):
        rng = np.random.default_rng(0)
        H, W = hw
        for _ in range(n):
            yield {"image1": rng.uniform(0, 255, (bs, H, W, 3)
                                         ).astype(np.float32),
                   "image2": rng.uniform(0, 255, (bs, H, W, 3)
                                         ).astype(np.float32),
                   "flow": np.zeros((bs, H, W, 2), np.float32),
                   "valid": np.ones((bs, H, W), np.float32)}

    def run(depth, name):
        captured = []

        def fake_init_state(model, tx, rng, size):
            params = {"w": np.zeros((2, 2), np.float32)}
            return TrainState(step=jnp.asarray(0, jnp.int32),
                              params=params, batch_stats={},
                              opt_state=tx.init(params))

        def fake_make_train_step(model, tx, cfg, mesh,
                                 shard_spatial=False):
            def step_fn(state, batch, key):
                captured.append(np.asarray(batch["image1"]))
                return (state.replace(step=state.step + 1),
                        {"loss": jnp.zeros(())})
            return step_fn

        monkeypatch.setattr(loop_mod, "init_state", fake_init_state)
        monkeypatch.setattr(loop_mod, "make_train_step",
                            fake_make_train_step)
        cfg = TrainConfig(name=name, num_steps=5, batch_size=8,
                          image_size=(8, 10), iters=2, val_freq=100,
                          log_freq=100, add_noise=True, seed=11,
                          ckpt_dir=str(tmp_path / name),
                          device_prefetch=depth)
        loop_mod.train(mcfg, cfg, batches())
        return captured

    serial = run(0, "off")
    overlapped = run(3, "on")
    assert len(serial) == len(overlapped) == 5
    for a, b in zip(serial, overlapped):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------
# gradient accumulation
# ---------------------------------------------------------------------

def _make_batch(bs, hw, seed=0):
    H, W = hw
    rng = np.random.default_rng(seed)
    return {
        "image1": rng.uniform(0, 255, (bs, H, W, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (bs, H, W, 3)).astype(np.float32),
        "flow": (4 * rng.standard_normal((bs, H, W, 2))
                 ).astype(np.float32),
        "valid": np.ones((bs, H, W), np.float32),
    }


def _tiny_step(accum, batch_size, hw=(16, 24), tx=None):
    import optax

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.train.step import init_state, make_train_step

    mcfg = RAFTConfig.small_model(corr_levels=2, corr_radius=2,
                                  scan_unroll=1)
    tcfg = TrainConfig(lr=1e-4, num_steps=10, batch_size=batch_size,
                       image_size=hw, iters=2, accum_steps=accum,
                       freeze_bn=True)
    model = RAFT(mcfg)
    # SGD(1.0) makes the update EQUAL the (negated) gradient, so the
    # param comparison below is a direct fp32 gradient comparison —
    # adam's sign-like first step would amplify noise on near-zero
    # gradient entries into full +/-lr flips.
    tx = tx or optax.sgd(1.0)
    state = init_state(model, tx, jax.random.PRNGKey(0), hw)
    return state, make_train_step(model, tx, tcfg, mesh=None,
                                  donate=False)


def test_accum_steps_matches_full_batch():
    """accum_steps=4 == accum_steps=1 at equal effective batch, within
    fp32 reduction-order tolerance (the acceptance criterion)."""
    batch = _make_batch(4, (16, 24))
    key = jax.random.PRNGKey(1)
    s1, f1 = _tiny_step(1, 4)
    s4, f4 = _tiny_step(4, 4)
    ns1, m1 = f1(s1, batch, key)
    ns4, m4 = f4(s4, batch, key)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=1e-5)
    np.testing.assert_allclose(float(m1["grad_norm"]),
                               float(m4["grad_norm"]), rtol=1e-4)
    flat1 = jax.tree_util.tree_leaves(ns1.params)
    flat4 = jax.tree_util.tree_leaves(ns4.params)
    for a, b in zip(flat1, flat4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-5)


def test_accum_steps_non_divisible_raises():
    s, f = _tiny_step(3, 4)
    with pytest.raises(ValueError, match="accum_steps=3 must divide"):
        f(s, _make_batch(4, (16, 24)), jax.random.PRNGKey(0))


def test_accum_peak_memory_scales_down():
    """The point of microbatching: peak live batch memory of the
    compiled step scales down with accum_steps (asserted via the
    existing hbm_usage / XLA memory-analysis path on CPU)."""
    from raft_tpu.utils.profiling import hbm_usage

    bs, hw = 8, (64, 96)
    batch = _make_batch(bs, hw)
    key = jax.random.PRNGKey(0)
    s1, f1 = _tiny_step(1, bs, hw=hw)
    s4, f4 = _tiny_step(4, bs, hw=hw)
    h1 = hbm_usage(f1, s1, batch, key)
    h4 = hbm_usage(f4, s4, batch, key)
    if "peak_hbm_gb" not in h1 or "peak_hbm_gb" not in h4:
        pytest.skip(f"XLA memory analysis unavailable: {h1} / {h4}")
    assert h4["peak_hbm_gb"] < h1["peak_hbm_gb"], (h1, h4)


# ---------------------------------------------------------------------
# bench + CLI wiring
# ---------------------------------------------------------------------

def test_bench_input_tiny_smoke(capsys):
    """scripts/bench_input.py --tiny: the tier-1 CPU smoke — runs both
    arms and prints one JSON line on the registered input-pipeline
    metric series."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_input", osp.join(REPO, "scripts", "bench_input.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.main(["--tiny"])
    rec = json.loads(capsys.readouterr().out.strip().splitlines()[-1])

    assert rec["metric"] == "input_pipeline_custom_32x48"
    assert rec["unit"] == "image-pairs/sec" and rec["value"] > 0
    assert rec["config"]["overlapped"]["pairs_per_sec"] > 0
    assert rec["config"]["serial"]["pairs_per_sec"] > 0
    assert rec["config"]["overlap_speedup"] > 0


def test_cli_train_pipeline_flags_parse():
    from raft_tpu.cli.train import parse_args

    a = parse_args(["--accum-steps", "2", "--prefetch-batches", "4",
                    "--device-prefetch", "3"])
    assert (a.accum_steps, a.prefetch_batches, a.device_prefetch) \
        == (2, 4, 3)
    # underscore spellings stay accepted (repo CLI convention)
    b = parse_args(["--accum_steps", "2", "--prefetch_batches", "4",
                    "--device_prefetch", "0"])
    assert (b.accum_steps, b.prefetch_batches, b.device_prefetch) \
        == (2, 4, 0)


def test_interrupt_predicate_unblocks_waiting_consumer():
    """Satellite (PR 7): a preemption flag set while the consumer is
    blocked in ``next()`` on an EMPTY buffer is observed within the
    poll interval — ``PipelineInterrupted`` — instead of going unseen
    until a batch arrives (the old SIGTERM-during-input-stall caveat).
    The pipeline stays usable afterwards: not a stream error."""
    import threading

    from raft_tpu.data.prefetch import PipelineInterrupted

    flag = threading.Event()
    release = threading.Event()

    def src():
        yield {"x": np.zeros((2,), np.float32)}
        release.wait(30.0)  # stall the producer: buffer stays empty
        yield {"x": np.ones((2,), np.float32)}

    pipe = DevicePipeline(src(), depth=2, interrupt=flag.is_set,
                          interrupt_poll_s=0.02)
    assert next(pipe)["x"][0] == 0.0
    timer = threading.Timer(0.05, flag.set)
    timer.start()
    t0 = time.perf_counter()
    with pytest.raises(PipelineInterrupted):
        next(pipe)
    # observed within ~poll interval of the flag flip, nowhere near the
    # 30 s the blocked source would have held the old blocking get
    assert time.perf_counter() - t0 < 5.0
    timer.cancel()

    flag.clear()
    release.set()  # input resumes -> the same pipeline delivers
    assert next(pipe)["x"][0] == 1.0
    pipe.close()


def test_interrupt_predicate_ignored_while_batches_buffered():
    """The poll is backpressure-free: with batches in the buffer the
    flag is never even consulted — delivery wins (the train loop's
    preempt seam handles the flag between steps)."""
    def src():
        for i in range(3):
            yield {"x": np.full((2,), float(i), np.float32)}

    pipe = DevicePipeline(src(), depth=2, interrupt=lambda: True,
                          interrupt_poll_s=0.02)
    deadline = time.time() + 10.0
    while pipe.buffered() < 1 and time.time() < deadline:
        time.sleep(0.005)
    assert pipe.buffered() >= 1
    assert next(pipe)["x"][0] == 0.0  # delivered despite the true flag
    pipe.close()
