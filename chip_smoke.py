#!/usr/bin/env python3
"""The quickest proof that raft-tpu still starts on the chip.

One process, three phases, the entry points a user would call, at
RAFT-full's published width (hidden 128 / context 128 / 4 levels /
radius 4, bf16 compute) on data made from ``--seed``:

1. **train**    ``raft_tpu.cli.train`` — chairs stage, 368x496 crops, 12
   iterations, ``--corr_impl auto`` (the Mosaic lookup on TPU), a few steps,
   a checkpoint;
2. **validate** ``raft_tpu.cli.evaluate`` on that checkpoint over the
   generated chairs validation split, then one test-mode forward at the
   Sintel shape (436x1024 padded to 440x1024, 32 iterations);
3. **serve**    ``raft_tpu.cli.serve`` — engine + HTTP server exactly as
   ``python -m raft_tpu serve`` builds them, warmed at the Sintel shape,
   answering ``POST /v1/flow`` from a client THREAD over loopback; first
   ``--batching request`` (the CLI default), then ``--batching slot``.

Each phase prints one JSON line; a failed check raises and the script exits
non-zero at once.  The LAST line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

``--chips 4`` runs none of that: it runs data-parallel train steps of the
same configuration on a ``(data=4, spatial=1)`` mesh against the same global
batch on one device of the four, and imports + runs one AOT serving
artifact on the four-device host.

Without an accelerator the script exits non-zero before any phase and
prints no result.  ``--tiny`` exists so the control flow can be walked on
the CPU (``JAX_PLATFORMS=cpu python chip_smoke.py --tiny``; add
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` for ``--chips 4``):
it shrinks the sizes and skips the TPU-only proofs, nothing else, and its
last line reports the platform it really ran on.

Every figure this prints is a smoke reading, not a benchmark.
"""

from __future__ import annotations

import argparse
import faulthandler
import io
import json
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

# Real sizes: the chairs images and crop of the reference schedule, the
# Sintel eval shape, the serving CLI's own defaults.
REAL = dict(image=(384, 512), crop=(368, 496), n_train=24, n_val=8,
            batch=8, steps=6, iters=12, eval_iters=24, eval_batch=4,
            sintel=(436, 1024), serve_iters=32, slots=8, requests=4,
            dp_batch_per_chip=2, dp_steps=3, aot_shape=(184, 320),
            aot_iters=4)
# Rehearsal sizes (CPU, interpret mode): same code path, toy shapes.
TINY = dict(image=(96, 128), crop=(64, 96), n_train=4, n_val=2,
            batch=2, steps=2, iters=2, eval_iters=2, eval_batch=2,
            sintel=(60, 124), serve_iters=2, slots=2, requests=2,
            dp_batch_per_chip=1, dp_steps=2, aot_shape=(40, 56),
            aot_iters=2)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=0,
                   help="fixes the generated data and the init")
    p.add_argument("--chips", type=int, default=1, choices=[1, 4],
                   help="4: only the data-parallel + AOT-import path")
    p.add_argument("--tiny", action="store_true",
                   help="CPU rehearsal of the control flow (see above)")
    return p.parse_args(argv)


# ---------------------------------------------------------------------------
# data from the seed
# ---------------------------------------------------------------------------

def make_pair(rng, hw):
    """A textured frame, the same frame translated, and the (constant)
    ground-truth flow — something a few steps can start to fit."""
    import numpy as np

    h, w = hw
    coarse = rng.integers(0, 255, size=(h // 8 + 1, w // 8 + 1, 3))
    img1 = np.kron(coarse, np.ones((8, 8, 1)))[:h, :w]
    img1 = np.clip(img1 + rng.normal(0, 6, img1.shape), 0, 255)
    dx, dy = (int(v) for v in rng.integers(-6, 7, size=2))
    img2 = np.roll(img1, (dy, dx), axis=(0, 1))
    flow = np.broadcast_to(np.float32([dx, dy]), (h, w, 2))
    return img1.astype(np.uint8), img2.astype(np.uint8), flow.copy()


def write_chairs_tree(root, seed, size):
    """FlyingChairs layout: ``<root>/datasets/FlyingChairs_release/data/
    NNNNN_img{1,2}.ppm`` + ``NNNNN_flow.flo`` and a split file (1 = train,
    2 = validation)."""
    import numpy as np
    from PIL import Image

    from raft_tpu.data import frame_utils

    rng = np.random.default_rng(seed)
    data = os.path.join(root, "datasets", "FlyingChairs_release", "data")
    os.makedirs(data)
    n = size["n_train"] + size["n_val"]
    for i in range(n):
        img1, img2, flow = make_pair(rng, size["image"])
        Image.fromarray(img1).save(
            os.path.join(data, f"{i:05d}_img1.ppm"), format="PPM")
        Image.fromarray(img2).save(
            os.path.join(data, f"{i:05d}_img2.ppm"), format="PPM")
        frame_utils.write_flo(os.path.join(data, f"{i:05d}_flow.flo"), flow)
    split = os.path.join(root, "chairs_split.txt")
    with open(split, "w") as f:
        f.write("1\n" * size["n_train"] + "2\n" * size["n_val"])
    return os.path.join(root, "datasets"), split


def read_events(directory):
    out = []
    for name in sorted(os.listdir(directory)):
        if name.endswith(".jsonl"):
            with open(os.path.join(directory, name)) as f:
                out += [json.loads(line) for line in f if line.strip()]
    return out


# ---------------------------------------------------------------------------
# reporting
# ---------------------------------------------------------------------------

def device_record():
    import jax

    d = jax.devices()
    return {"platform": d[0].platform, "kind": d[0].device_kind,
            "count": len(d)}


def peak_hbm_gb():
    import jax

    stats = jax.local_devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    return None if peak is None else round(peak / 2 ** 30, 3)


def report(phase, t0, compile_seconds, checked, **extra):
    import jax

    from raft_tpu.native import build as native_build

    print(json.dumps(dict(
        phase=phase, ok=True,
        seconds=round(time.perf_counter() - t0, 2),
        compile_seconds=round(compile_seconds, 2),
        checked=checked, jax=jax.__version__,
        device_kind=jax.devices()[0].device_kind,
        # False = the C build failed and the input path fell back to
        # NumPy (raft_tpu/native/build.py swallows the failure).
        native_aug_loaded=native_build.load() is not None,
        peak_hbm_gb=peak_hbm_gb(), **extra)), flush=True)


def finite(x) -> bool:
    import numpy as np

    return bool(np.isfinite(np.asarray(x, dtype=np.float64)).all())


# ---------------------------------------------------------------------------
# phase 1: train
# ---------------------------------------------------------------------------

def phase_train(work, data_root, split, size, seed, on_tpu):
    import jax

    from raft_tpu.cli import train as train_cli

    t0 = time.perf_counter()
    tdir = os.path.join(work, "telemetry-train")
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import corr_impl_at

    picked = train_cli.default_corr_impl()
    if on_tpu:
        # the code's own choice at this crop, nothing asked for by name
        at_crop = corr_impl_at(
            RAFTConfig.full(corr_impl=picked, compute_dtype="bfloat16"),
            size["crop"][0] // 8, size["crop"][1] // 8)
        assert at_crop == "allpairs_pallas", (picked, at_crop)
    state = train_cli.run([
        "--name", "smoke", "--stage", "chairs",
        "--image_size", *map(str, size["crop"]),
        "--precision", "bf16", "--iters", str(size["iters"]),
        "--batch_size", str(size["batch"]),
        "--num_steps", str(size["steps"]), "--seed", str(seed),
        "--data_root", data_root, "--chairs_split", split,
        "--ckpt_dir", os.path.join(work, "ckpts"),
        "--telemetry_dir", tdir, "--num_workers", "4"])
    # A real sync: both counters come off the device.  The in-graph guard
    # (train/step.py) counts every step whose loss or gradients were not
    # finite, so 0 here is "finite on every step".
    steps_done = int(jax.device_get(state.step))
    nonfinite = int(jax.device_get(state.nonfinite_steps))
    assert steps_done == size["steps"], steps_done
    assert nonfinite == 0, f"{nonfinite} non-finite step(s)"

    by = {}
    for rec in read_events(tdir):
        by.setdefault(rec["event"], []).append(rec)
    compile_s = by["compile"][0]["seconds"]
    step_s = [r["step_time_s"] for r in by["train_step"]][1:]
    health = by["train_health"][-1]
    assert finite(health["loss_iter"]) and finite(health["epe_iter"]), health
    hbm = by["hbm_usage"][0]
    cost = by["cost_report"][0]
    if on_tpu:
        # The kernel really ran: Mosaic custom calls in the compiled step
        # (not the XLA fallback, not the interpreter) ...
        assert hbm.get("tpu_custom_calls", 0) > 0, hbm
        # ... and the cost layer resolved this chip to its datasheet row,
        # so MFU is a number and not None.
        assert cost["peak_tflops"] == 197.0, cost
    ckpt = os.path.join(work, "ckpts", "smoke")
    assert os.path.isdir(ckpt), ckpt
    report("train", t0, compile_s,
           ["steps == num_steps", "nonfinite_steps == 0 (device read)",
            "last step loss/epe per iteration finite", "checkpoint written"]
           + (["tpu_custom_call in compiled step",
               "peak_spec resolves to v5e"] if on_tpu else []),
           corr_impl=f"auto -> {picked}", batch_per_chip=size["batch"],
           image_size=size["crop"], iters=size["iters"],
           steps=steps_done, step_seconds_after_first=step_s,
           loss_iter_last_step=health["loss_iter"],
           epe_iter_last_step=health["epe_iter"],
           compiled_step={k: v for k, v in hbm.items()
                          if k.endswith("_gb") or k == "tpu_custom_calls"},
           device_kind_cost_layer=cost["device_kind"])
    return ckpt


# ---------------------------------------------------------------------------
# phase 2: validate
# ---------------------------------------------------------------------------

def phase_validate(work, ckpt, data_root, split, size, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from raft_tpu import evaluate
    from raft_tpu.cli import evaluate as evaluate_cli
    from raft_tpu.config import RAFTConfig
    from raft_tpu.ops.pad import InputPadder

    t0 = time.perf_counter()
    tdir = os.path.join(work, "telemetry-eval")
    evaluate_cli.main([
        "--model", ckpt, "--dataset", "chairs",
        "--iters", str(size["eval_iters"]),
        "--eval_batch", str(size["eval_batch"]),
        "--data_root", data_root, "--chairs_split", split,
        "--telemetry_dir", tdir])
    events = read_events(tdir)
    epe = [r for r in events if r["event"] == "eval"][-1]["chairs"]
    assert finite(epe) and epe >= 0, epe
    fwd = [r["seconds"] for r in events
           if r["event"] == "span" and r["name"] == "raft_eval_forward"]
    compile_s = fwd[0] - min(fwd[1:]) if len(fwd) > 1 else fwd[0]

    # One test-mode forward at the Sintel shape through make_eval_fn, the
    # function every validator and the train loop's periodic validation
    # share.
    variables = evaluate_cli.load_model_variables(ckpt)
    eval_fn = evaluate.make_eval_fn(
        RAFTConfig.full(compute_dtype="bfloat16"), size["serve_iters"])
    rng = np.random.default_rng(seed + 1)
    img1, img2, _ = make_pair(rng, size["sintel"])
    padder = InputPadder((1,) + img1.shape, mode="sintel")
    a, b = padder.pad(jnp.asarray(img1[None], jnp.float32),
                      jnp.asarray(img2[None], jnp.float32))
    t1 = time.perf_counter()
    _, flow_up = eval_fn(variables, a, b)
    flow_up = np.asarray(flow_up)
    first_s = time.perf_counter() - t1
    # Second call: no compile.  Timed in two parts to show whether
    # block_until_ready is a real sync on this runtime — if it is, the
    # host copy after it is a few milliseconds of transfer, not the
    # forward pass.
    t1 = time.perf_counter()
    out = jax.block_until_ready(eval_fn(variables, a, b))
    block_s = time.perf_counter() - t1
    again = np.asarray(out[1])
    second_s = time.perf_counter() - t1
    h, w = size["sintel"]
    assert flow_up.shape == (1, -(-h // 8) * 8, -(-w // 8) * 8, 2), \
        flow_up.shape
    flow = np.asarray(padder.unpad(flow_up))
    assert flow.shape == (1, h, w, 2) and finite(flow), flow.shape
    assert np.array_equal(flow_up, again), "forward is not deterministic"
    report("validate", t0, compile_s + first_s - second_s,
           ["chairs validation EPE finite",
            "Sintel-shape flow finite, padded and unpadded shapes right",
            "same input twice -> same flow"],
           chairs_epe=epe, chairs_pairs=size["n_val"],
           alternate_corr_would_pick=evaluate.default_alternate_corr_impl(),
           sintel_padded_shape=list(flow_up.shape),
           sintel_forward_seconds=[round(first_s, 3), round(second_s, 3)],
           sync_check={"block_until_ready_s": round(block_s, 4),
                       "then_host_copy_s": round(second_s - block_s, 4)})


# ---------------------------------------------------------------------------
# phase 3: serve
# ---------------------------------------------------------------------------

def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _http(url, data=None, timeout=600):
    req = urllib.request.Request(url, data=data,
                                 method="POST" if data else "GET")
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.read()


def _client(port, pairs, result):
    """Runs in a thread: wait for the server, send the requests (the first
    two at once, the rest one after the other), read /v1/stats, then stop
    the server the way an operator does — SIGINT to the main thread."""
    import _thread

    import numpy as np

    base = f"http://127.0.0.1:{port}"
    try:
        t0 = time.perf_counter()
        while True:   # serve main() listens only once warm-up is done
            try:
                _http(base + "/v1/healthz", timeout=5)
                break
            except (urllib.error.URLError, ConnectionError, OSError):
                if time.perf_counter() - t0 > 900:
                    raise TimeoutError("server never came up")
                time.sleep(0.5)
        result["startup_seconds"] = time.perf_counter() - t0

        def ask(i):
            buf = io.BytesIO()
            np.savez(buf, image1=pairs[i][0], image2=pairs[i][1])
            t = time.perf_counter()
            body = _http(base + "/v1/flow", data=buf.getvalue())
            flows[i] = np.load(io.BytesIO(body))["flow"]
            lat[i] = round(time.perf_counter() - t, 3)

        flows, lat = [None] * len(pairs), [None] * len(pairs)
        first = [threading.Thread(target=ask, args=(i,))
                 for i in range(min(2, len(pairs)))]
        for t in first:
            t.start()
        for t in first:
            t.join()
        for i in range(len(first), len(pairs)):
            ask(i)
        result["flows"], result["latency_s"] = flows, lat
        result["stats"] = json.loads(_http(base + "/v1/stats"))
    except BaseException as e:  # re-raised by the main thread
        result["error"] = e
    finally:
        _thread.interrupt_main()


def phase_serve(ckpt, size, seed, batching):
    import numpy as np

    from raft_tpu.cli import serve as serve_cli

    t0 = time.perf_counter()
    rng = np.random.default_rng(seed + 2)
    pairs = [make_pair(rng, size["sintel"])[:2]
             for _ in range(size["requests"])]
    port, result = _free_port(), {}
    client = threading.Thread(target=_client, args=(port, pairs, result),
                              daemon=True)
    client.start()
    h, w = size["sintel"]
    # Returns when the client thread interrupts the main thread: main()
    # treats KeyboardInterrupt as shutdown, stops the engine and prints its
    # final stats line.
    serve_cli.main([
        "--model", ckpt, "--iters", str(size["serve_iters"]),
        "--port", str(port), "--batching", batching,
        "--slots", str(size["slots"]), "--batch-sizes", "1,2",
        "--max-batch", "2", "--warmup", f"{h}x{w}"])
    client.join(timeout=60)
    if "error" in result:
        raise result["error"]
    for (img1, _), flow in zip(pairs, result["flows"]):
        assert flow.shape == img1.shape[:2] + (2,), flow.shape
        assert flow.dtype == np.float32 and finite(flow)
    stats = result["stats"]
    assert stats["completed"] == len(pairs), stats
    assert stats["errors"] == 0 and stats["failed_lanes"] == 0, stats
    assert stats["batching"] == batching, stats
    # every program pair samples its pyramid with the lookup the code picks
    # for this platform: the Mosaic kernel on the chip, XLA elsewhere
    import jax

    lookup = "mosaic" if jax.default_backend() == "tpu" else "xla"
    assert stats["lookup"] and set(stats["lookup"].values()) == {lookup}, \
        stats["lookup"]
    report(f"serve[{batching}]", t0, result["startup_seconds"],
           ["every answer finite, float32, shaped like its request",
            "/v1/stats: completed == requests, 0 errors, 0 failed lanes",
            f"/v1/stats: every program's lookup is {lookup}"],
           compile_seconds_is="checkpoint load + warm-up, until "
                              "/v1/healthz answered",
           requests=len(pairs), latency_s=result["latency_s"],
           warmed=f"{h}x{w}", compiles=stats["compiles"],
           slot_steps=stats["slot_steps"])


# ---------------------------------------------------------------------------
# --chips 4: data parallel over the mesh, and an AOT import on 4 devices
# ---------------------------------------------------------------------------

def dp_setup(devices, size, corr_impl):
    """The train step of phase 1's configuration (what ``cli/train.py``
    resolves with its defaults) over ``devices`` as a ``(data=n,
    spatial=1)`` mesh, at global batch ``4 x dp_batch_per_chip``.
    Returns ``(step_fn, mesh, model, tx, cfg)``.  Also what the compile
    rehearsal for a described 2x2 topology drives."""
    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import make_train_step

    model = RAFT(RAFTConfig.full(corr_impl=corr_impl,
                                 compute_dtype="bfloat16"))
    cfg = TrainConfig(stage="chairs", num_steps=100,
                      batch_size=4 * size["dp_batch_per_chip"],
                      image_size=size["crop"], iters=size["iters"])
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    mesh = make_mesh(num_data=len(devices), num_spatial=1, devices=devices)
    return (make_train_step(model, tx, cfg, mesh, donate=False), mesh,
            model, tx, cfg)


def phase_data_parallel(size, seed, on_tpu):
    import jax
    import numpy as np

    from raft_tpu.cli.train import default_corr_impl
    from raft_tpu.parallel.mesh import replicated_sharding, shard_batch
    from raft_tpu.train.step import init_state

    t0 = time.perf_counter()
    devices = jax.devices()[:4]
    picked = default_corr_impl()
    rng = np.random.default_rng(seed)
    n = 4 * size["dp_batch_per_chip"]
    samples = [make_pair(rng, size["crop"]) for _ in range(n)]
    batch = {
        "image1": np.stack([s[0] for s in samples]).astype(np.float32),
        "image2": np.stack([s[1] for s in samples]).astype(np.float32),
        "flow": np.stack([s[2] for s in samples]),
        "valid": np.ones((n,) + tuple(size["crop"]), np.float32)}
    key = jax.random.PRNGKey(seed)

    losses, placement, compile_s = {}, {}, {}
    step_s = {"mesh4": [], "one_device": []}
    # The same global batch and the same init on the four-device mesh and
    # on ONE device of the four (the last, so nothing rides on device 0
    # being the default).
    for name, devs in (("mesh4", devices), ("one_device", devices[-1:])):
        step_fn, mesh, model, tx, cfg = dp_setup(devs, size, picked)
        state = jax.device_put(
            init_state(model, tx, jax.random.PRNGKey(seed), (48, 64)),
            replicated_sharding(mesh))
        sharded = shard_batch(batch, mesh)
        # ONE compile per program: the steps below call this executable.
        t1 = time.perf_counter()
        compiled = step_fn.lower(state, sharded, key).compile()
        compile_s[name] = time.perf_counter() - t1
        if name == "mesh4" and on_tpu:
            text = compiled.as_text()
            assert "tpu_custom_call" in text and "all-reduce" in text
        placement[name] = {
            "batch_shards": [f"{s.device} rows {s.index[0].start}:"
                             f"{s.index[0].stop}" for s in
                             sharded["image1"].addressable_shards],
            "param_devices": sorted(str(d) for d in jax.tree_util
                                    .tree_leaves(state.params)[0]
                                    .sharding.device_set),
            "param_sharding": str(jax.tree_util.tree_leaves(
                state.params)[0].sharding.spec)}
        losses[name] = []
        for _ in range(size["dp_steps"] if name == "mesh4" else 1):
            t1 = time.perf_counter()
            state, metrics = compiled(state, sharded, key)
            losses[name].append(float(jax.device_get(metrics["loss"])))
            step_s[name].append(round(time.perf_counter() - t1, 3))
            assert float(jax.device_get(metrics["nonfinite"])) == 0.0
    assert finite(losses["mesh4"]) and finite(losses["one_device"]), losses
    np.testing.assert_allclose(losses["mesh4"][0], losses["one_device"][0],
                               rtol=2e-2)
    shard_devs = {s.split(" ")[0] for s in
                  placement["mesh4"]["batch_shards"]}
    assert len(shard_devs) == 4, placement
    assert len(placement["mesh4"]["param_devices"]) == 4, placement
    assert len(placement["one_device"]["param_devices"]) == 1, placement
    report("data_parallel", t0, sum(compile_s.values()),
           ["first-step loss on the 4-device mesh == on one device "
            "(rtol 2e-2, bf16)", "every step finite",
            "batch shards on 4 distinct devices, params replicated on 4"]
           + (["tpu_custom_call and all-reduce in the compiled mesh step"]
              if on_tpu else []),
           corr_impl=f"auto -> {picked}", global_batch=n,
           batch_per_chip=size["dp_batch_per_chip"],
           image_size=size["crop"], iters=size["iters"], losses=losses,
           compile_seconds_each={k: round(v, 2)
                                 for k, v in compile_s.items()},
           step_seconds=step_s,
           placement=placement)


def phase_aot_import(work, size, seed):
    """Finding 1 of the issue: an exported serving executable must load on
    a host with more than one device.  Export from one engine, import in a
    second, answer a request with zero compiles."""
    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.serve import InferenceEngine, ServeConfig

    t0 = time.perf_counter()
    model_cfg = RAFTConfig.full(compute_dtype="bfloat16")
    rng = jax.random.PRNGKey(seed)
    img = jax.numpy.zeros((1, 64, 96, 3))
    variables = RAFT(model_cfg).init({"params": rng, "dropout": rng},
                                     img, img, iters=1)
    aot_dir = os.path.join(work, "aot")
    scfg = dict(iters=size["aot_iters"], batch_sizes=(1,), max_batch=1)
    t1 = time.perf_counter()
    exporter = InferenceEngine(variables, model_cfg, ServeConfig(**scfg))
    exporter.warmup([size["aot_shape"]])
    manifest = exporter.export_aot(aot_dir)
    exporter.stop()
    compile_s = time.perf_counter() - t1

    importer = InferenceEngine(variables, model_cfg,
                               ServeConfig(aot_dir=aot_dir, **scfg))
    assert importer.aot_info["ok"] is True, importer.aot_info
    assert importer.aot_info["imported"] == len(manifest["keys"])
    importer.start()
    try:
        im1, im2, _ = make_pair(np.random.default_rng(seed),
                                size["aot_shape"])
        flow = importer.infer(im1, im2, timeout=300)
    finally:
        importer.stop()
    assert flow.shape == tuple(size["aot_shape"]) + (2,) and finite(flow)
    assert importer.compile_counter.total() == 0, \
        importer.compile_counter.counts()
    report("aot_import", t0, compile_s,
           ["artifact imported on a host with "
            f"{jax.local_device_count()} devices",
            "first request answered finite with 0 compiles"],
           local_devices=jax.local_device_count(),
           imported=importer.aot_info["imported"],
           shape=size["aot_shape"])


# ---------------------------------------------------------------------------

def main(argv=None):
    args = parse_args(argv)
    # Never outlive the caller's patience: a hang becomes a stack dump and
    # a non-zero exit well inside the 1200 s contract.
    faulthandler.dump_traceback_later(1150, exit=True)

    import jax

    device = device_record()
    on_tpu = device["platform"] == "tpu"
    if not on_tpu and not args.tiny:
        print(f"chip_smoke: no TPU — JAX reports {device}; refusing to "
              "run (use --tiny for the CPU rehearsal)", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs that many devices, "
              f"JAX reports {device}", file=sys.stderr)
        return 1
    size = TINY if args.tiny else REAL

    from raft_tpu.obs.cost import peak_spec
    from raft_tpu.ops.pallas_util import auto_interpret
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    cache_dir = enable_persistent_compile_cache()
    if on_tpu:
        # No fallback may hide the device on this path: an interpreted
        # kernel or an unknown chip is a failure, not a slow success.
        assert auto_interpret() is False
        assert peak_spec().kind == "v5e", \
            f"device_kind {device['kind']!r} not in obs/cost.py PEAK_SPECS"
        assert cache_dir, "persistent compile cache refused on a TPU"
    print(json.dumps(dict(
        phase="start", device=device, jax=jax.__version__,
        tiny=args.tiny, chips=args.chips, seed=args.seed,
        compile_cache_dir=cache_dir,
        compile_cache_entries=(len(os.listdir(cache_dir))
                               if cache_dir and os.path.isdir(cache_dir)
                               else 0),
        peak_spec=peak_spec().kind)), flush=True)

    work = tempfile.mkdtemp(prefix="raft-chip-smoke-")
    try:
        if args.chips == 4:
            phase_data_parallel(size, args.seed, on_tpu)
            phase_aot_import(work, size, args.seed)
        else:
            data_root, split = write_chairs_tree(work, args.seed, size)
            ckpt = phase_train(work, data_root, split, size, args.seed,
                               on_tpu)
            phase_validate(work, ckpt, data_root, split, size, args.seed)
            phase_serve(ckpt, size, args.seed, "request")
            phase_serve(ckpt, size, args.seed, "slot")
    finally:
        shutil.rmtree(work, ignore_errors=True)
    faulthandler.cancel_dump_traceback_later()
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
