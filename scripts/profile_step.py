"""Capture an op-level XProf profile of the bench training step.

Round-1 tuning worked from whole-step ablations only; this script closes
that gap: it runs the chairs-crop training step (batch 16, bf16) under a
``jax.profiler`` trace and converts the captured xplane with the local
``xprof`` package into per-HLO-op statistics (no TensorBoard UI needed —
this box is headless).

Usage:
    python scripts/profile_step.py [outdir]
Env: BENCH_BATCH, BENCH_IMAGE, BENCH_CORR_IMPL... (read in ``main``).

Outputs in <outdir> (default ``$RAFT_TELEMETRY_DIR/xprof/bench-<ts>``
when telemetry is configured, else ``/tmp/raft_prof``) — the same
``xprof/`` layout the serve ``POST /debug/profile`` endpoint and the
train ``--profile-steps`` flag write, so every capture lands where
trace spans link to (``docs/OBSERVABILITY.md``).  An ``xprof_capture``
event is emitted into the telemetry stream, and any trace span
recorded during the capture carries ``xprof=<outdir>``:
    hlo_stats.json      per-op table (category, self time, FLOP rate)
    op_profile.json     xprof op_profile tree
    summary.txt         top self-time ops + per-category rollup

Before profiling, ask what the step is *bound by*: ``python -m
raft_tpu cost`` prints the compiled programs' FLOPs/bytes/roofline
verdict from compile-time metadata alone (``raft_tpu/obs/cost.py``;
``docs/PERFORMANCE.md`` has the triage table) — a memory-bound
verdict changes what to look for in the capture, and the measured
FLOP rates here are what validate the cost model's analytic kernel
formulas on hardware (not yet done: no trace of today's code on the
chip exists — PERF.md "Open questions").
"""

from __future__ import annotations

import glob
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def capture(outdir: str) -> str:
    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig, TrainConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.parallel.mesh import make_mesh, shard_batch
    from raft_tpu.train.optim import make_optimizer
    from raft_tpu.train.step import init_state, make_train_step

    n_dev = jax.device_count()
    mesh = make_mesh(num_data=n_dev, num_spatial=1)
    H, W = (int(x) for x in
            os.environ.get("BENCH_IMAGE", "368x496").split("x"))
    B = int(os.environ.get("BENCH_BATCH", 16)) * n_dev
    _d = RAFTConfig()
    model_cfg = RAFTConfig.full(
        compute_dtype=os.environ.get("BENCH_COMPUTE_DTYPE", "bfloat16"),
        corr_impl=os.environ.get("BENCH_CORR_IMPL", "allpairs_pallas"),
        corr_precision=os.environ.get("BENCH_CORR_PRECISION", "highest"),
        remat=os.environ.get("BENCH_REMAT", "1") == "1",
        remat_policy=os.environ.get("BENCH_REMAT_POLICY", _d.remat_policy),
        scan_unroll=int(os.environ.get("BENCH_SCAN_UNROLL", _d.scan_unroll)),
        remat_upsample=os.environ.get("BENCH_REMAT_UPSAMPLE", "1") == "1")
    cfg = TrainConfig(num_steps=1000, batch_size=B, image_size=(H, W),
                      iters=12)

    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    state = init_state(model, tx, jax.random.PRNGKey(0), (48, 64))
    step_fn = make_train_step(model, tx, cfg, mesh)

    rng = np.random.default_rng(0)
    batch = shard_batch({
        "image1": rng.uniform(0, 255, (B, H, W, 3)).astype(np.float32),
        "image2": rng.uniform(0, 255, (B, H, W, 3)).astype(np.float32),
        "flow": (8.0 * rng.standard_normal((B, H, W, 2))).astype(np.float32),
        "valid": np.ones((B, H, W), np.float32),
    }, mesh)
    key = jax.random.PRNGKey(1)

    for _ in range(3):
        state, metrics = step_fn(state, batch, key)
    float(metrics["loss"])

    # Link the capture into the tracing layer: spans recorded during
    # the profiled window carry xprof=<outdir> (raft_tpu/obs/trace.py).
    from raft_tpu.obs import trace

    jax.profiler.start_trace(outdir)
    trace.set_active_profile(outdir)
    try:
        for _ in range(3):
            state, metrics = step_fn(state, batch, key)
        float(metrics["loss"])  # hard sync before stopping the trace
    finally:
        trace.set_active_profile(None)
        jax.profiler.stop_trace()

    paths = glob.glob(os.path.join(outdir, "plugins/profile/*/*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"no xplane.pb under {outdir}")
    return max(paths, key=os.path.getmtime)


def convert(xplane: str, outdir: str) -> None:
    from xprof.convert import raw_to_tool_data as rtd

    for tool in ("hlo_stats", "op_profile"):
        try:
            data = rtd.xspace_to_tool_data([xplane], tool, {})
            if isinstance(data, tuple):
                data = data[0]
            out = os.path.join(outdir, f"{tool}.json")
            mode = "wb" if isinstance(data, bytes) else "w"
            with open(out, mode) as f:
                f.write(data)
            print(f"wrote {out}")
        except Exception as e:  # tool coverage varies by xprof version
            print(f"{tool} conversion failed: {e!r}")


def summarize(outdir: str) -> None:
    path = os.path.join(outdir, "hlo_stats.json")
    if not os.path.exists(path):
        return
    with open(path) as f:
        raw = f.read()
    data = json.loads(raw)
    # hlo_stats is a GViz table: {cols: [...], rows: [{c: [{v: ...}]}]}
    if isinstance(data, list):
        data = data[0]
    cols = [c.get("label") or c.get("id") for c in data["cols"]]
    rows = [[cell.get("v") if isinstance(cell, dict) else cell
             for cell in r["c"]] for r in data["rows"]]

    def col(name_frag):
        for i, c in enumerate(cols):
            if c and name_frag.lower() in str(c).lower():
                return i
        return None

    i_cat = col("category")
    i_name = col("HLO op name") or col("op name")
    i_self = col("Total self time (us)") or col("self time")
    i_prog = col("program")
    lines = [f"columns: {cols}", ""]

    by_cat = {}
    for r in rows:
        cat = r[i_cat] if i_cat is not None else "?"
        t = float(r[i_self] or 0) if i_self is not None else 0.0
        by_cat[cat] = by_cat.get(cat, 0.0) + t
    total = sum(by_cat.values())
    lines.append(f"== per-category self time (total {total/1e3:.1f} ms "
                 "across traced steps) ==")
    for cat, t in sorted(by_cat.items(), key=lambda kv: -kv[1]):
        lines.append(f"  {t/1e3:9.2f} ms  {100*t/max(total,1e-9):5.1f}%  {cat}")

    lines.append("")
    lines.append("== top 60 ops by self time ==")
    rows.sort(key=lambda r: -(float(r[i_self] or 0)
                              if i_self is not None else 0))
    for r in rows[:60]:
        t = float(r[i_self] or 0) / 1e3
        name = str(r[i_name])[:140] if i_name is not None else "?"
        cat = r[i_cat] if i_cat is not None else "?"
        prog = (str(r[i_prog])[:20] if i_prog is not None else "")
        lines.append(f"  {t:9.2f} ms  [{cat}] {prog} {name}")

    out = "\n".join(lines)
    with open(os.path.join(outdir, "summary.txt"), "w") as f:
        f.write(out + "\n")
    print(out)


def default_outdir() -> str:
    """Trace-linked layout when telemetry is configured, /tmp otherwise.

    ``$RAFT_TELEMETRY_DIR/xprof/bench-<ts>`` is the same directory the
    serve ``/debug/profile`` endpoint and the train ``--profile-steps``
    flag use, so one telemetry dir holds traces AND their profiles."""
    telem = os.environ.get("RAFT_TELEMETRY_DIR")
    if telem:
        return os.path.join(telem, "xprof",
                            time.strftime("bench-%Y%m%d-%H%M%S"))
    return "/tmp/raft_prof"


def _emit_capture_event(outdir: str) -> None:
    """Stamp the capture into the telemetry stream so trace_report /
    telemetry_summary readers can find the artifacts later."""
    try:
        from raft_tpu.obs.events import default_sink

        sink = default_sink()
        if sink is not None:
            sink.emit("xprof_capture", source="profile_step", dir=outdir)
    except Exception:
        pass  # telemetry must never fail a capture


if __name__ == "__main__":
    outdir = sys.argv[1] if len(sys.argv) > 1 else default_outdir()
    os.makedirs(outdir, exist_ok=True)
    t0 = time.time()
    xplane = capture(outdir)
    print(f"captured {xplane} in {time.time()-t0:.0f}s")
    _emit_capture_event(outdir)
    convert(xplane, outdir)
    summarize(outdir)
