"""Streaming-session benchmark: warm-start video flow vs independent
pairs (docs/SERVING.md "Streaming sessions").

Drives ``raft_tpu.serve.InferenceEngine`` in-process over a synthetic
clip with exactly-known motion (``scripts/make_demo_frames.make_clip``)
in two arms over the SAME frames:

- **stream**: one session per simulated camera; every frame after the
  first pair takes the warm path (carried fmap/ctx + forward-warped
  ``flow_init``), with the per-frame budget ``--stream-warm-iters``
  and the in-graph early-exit predicate compounding.
- **independent**: every consecutive pair submitted as a stateless
  request at the full budget — the arm serving today's API.

Prints ONE check_regression-format JSON line.  The headline value
is the stream arm's frames/sec/chip; the record also carries the
cold-vs-warm ``iters_used`` histograms (separable because retirements
are ``warm``-tagged), the two figures the regression gates consume
(``config.warm_iters_saved_frac`` for ``--min-warm-iters-saved-frac``,
``config.stream_epe_delta`` for ``--max-stream-epe-delta``), and
``encoder_flops_saved_frac`` from the cost ledger (``wenc`` vs ``enc``
``flops_per_pair`` — the fmap-reuse saving, stamped at compile time).

EPE is measured against the clip's analytic ground truth on an
interior crop (the rolled texture wraps at the border).  With random
weights (``--tiny``) the absolute EPE is meaningless; the DELTA
between arms on identical frames is still exactly the cost of warm
start, which is what the gate bounds.

``--tiny``: CPU smoke preset::

    JAX_PLATFORMS=cpu python scripts/bench_stream.py --tiny
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def parse_args(argv=None):
    p = argparse.ArgumentParser(
        description="RAFT-TPU streaming-session benchmark")
    p.add_argument("--tiny", action="store_true",
                   help="CPU smoke preset (small model, fp32, tiny "
                        "clip)")
    p.add_argument("--hw", default="384x512",
                   help="HxW clip resolution")
    p.add_argument("--frames", type=int, default=24,
                   help="frames per clip (pairs = frames - 1)")
    p.add_argument("--sessions", type=int, default=4,
                   help="concurrent streaming sessions (simulated "
                        "cameras; each pins one slot lane)")
    p.add_argument("--small", action="store_true")
    p.add_argument("--precision", default="bf16",
                   choices=["bf16", "fp32"])
    p.add_argument("--iters", type=int, default=32,
                   help="cold / independent-pair refinement budget")
    p.add_argument("--stream-warm-iters", type=int, default=None,
                   help="warm-frame budget (default: same as --iters; "
                        "the warm saving then comes from early exit "
                        "alone)")
    p.add_argument("--early-exit-threshold", type=float, default=0.0)
    p.add_argument("--slots", type=int, default=8)
    p.add_argument("--shift", default="2x1",
                   help="DXxDY analytic motion, px/frame")
    p.add_argument("--request-timeout-s", type=float, default=120.0)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.tiny:
        args.small = True
        args.precision = "fp32"
        args.iters = 3
        args.hw = "36x52"
        args.frames = 8
        args.sessions = 2
        args.slots = min(args.slots, 4)
        if args.stream_warm_iters is None:
            args.stream_warm_iters = 2
    if args.frames < 2:
        raise SystemExit("--frames must be >= 2")
    return args


def _epe(flow, gt, margin: int = 8):
    """Mean endpoint error on the interior crop (the analytic clip
    wraps at the border, so the edge band's truth is undefined)."""
    import numpy as np

    d = (flow[margin:-margin, margin:-margin]
         - gt[margin:-margin, margin:-margin])
    return float(np.sqrt((d ** 2).sum(-1)).mean())


def _build_engine(args, variables, model_cfg, streaming: bool):
    from raft_tpu.serve import InferenceEngine, ServeConfig

    cfg = ServeConfig(
        iters=args.iters, batching="slot", slots=args.slots,
        early_exit_threshold=max(args.early_exit_threshold, 0.0),
        max_queue=max(256, args.sessions * args.frames),
        stream_warm_iters=args.stream_warm_iters if streaming else None,
        stream_ttl_s=max(60.0, 2 * args.request_timeout_s),
        max_sessions=max(64, args.sessions))
    eng = InferenceEngine(variables, model_cfg, cfg)
    eng.start()
    return eng


def _run_stream_arm(args, variables, model_cfg, clips):
    """One thread per session streams its clip; returns (elapsed,
    flows-by-session, engine stats)."""
    eng = _build_engine(args, variables, model_cfg, streaming=True)
    results = {}
    errs = []

    def worker(sid, frames):
        try:
            eng.stream_open(sid, frames[0])
            out = []
            for f in frames[1:]:
                r = eng.stream_ingest(sid, f,
                                      timeout=args.request_timeout_s)
                out.append((r["warm"], r["flow"]))
            eng.stream_close(sid)
            results[sid] = out
        except Exception as e:  # surfaced after join
            errs.append((sid, e))

    threads = [threading.Thread(target=worker,
                                args=(f"cam{i}", clips[i]))
               for i in range(args.sessions)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    dt = time.perf_counter() - t0
    try:
        stats = eng.stats()
    finally:
        eng.stop()
    if errs:
        raise RuntimeError(f"stream arm failed: {errs[0]}") from \
            errs[0][1]
    return dt, results, stats


def _run_indep_arm(args, variables, model_cfg, clips):
    """Every consecutive pair as a stateless request (full budget)."""
    eng = _build_engine(args, variables, model_cfg, streaming=False)
    try:
        futs = {}
        t0 = time.perf_counter()
        for i in range(args.sessions):
            frames = clips[i]
            for t in range(len(frames) - 1):
                futs[(i, t)] = eng.submit(frames[t], frames[t + 1],
                                          iters=args.iters)
        flows = {k: f.result(timeout=args.request_timeout_s)
                 for k, f in futs.items()}
        dt = time.perf_counter() - t0
        stats = eng.stats()
    finally:
        eng.stop()
    return dt, flows, stats


def main(argv=None):
    args = parse_args(argv)

    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from scripts.make_demo_frames import make_clip

    h, w = (int(t) for t in args.hw.lower().split("x"))
    dx, dy = (int(t) for t in args.shift.lower().split("x"))

    mk = RAFTConfig.small_model if args.small else RAFTConfig.full
    model_cfg = mk(compute_dtype="bfloat16"
                   if args.precision == "bf16" else "float32")
    model = RAFT(model_cfg)
    key = jax.random.PRNGKey(args.seed)
    img = jax.numpy.zeros((1, 64, 96, 3))
    variables = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, img, img,
                             iters=2, train=False))(key)

    clips, gt = [], None
    for i in range(args.sessions):
        frames, gt = make_clip(args.frames, (h, w), shift=(dx, dy),
                               seed=args.seed + 3 + i)
        clips.append(frames)

    s_dt, s_results, s_stats = _run_stream_arm(args, variables,
                                               model_cfg, clips)
    i_dt, i_flows, _ = _run_indep_arm(args, variables, model_cfg,
                                      clips)

    n_dev = max(jax.local_device_count(), 1)
    pairs = args.sessions * (args.frames - 1)
    margin = min(8, h // 4, w // 4)
    stream_epes, indep_epes, warm_flags = [], [], []
    for i in range(args.sessions):
        for t, (warm, flow) in enumerate(s_results[f"cam{i}"]):
            warm_flags.append(bool(warm))
            stream_epes.append(_epe(flow, gt, margin))
            indep_epes.append(_epe(i_flows[(i, t)], gt, margin))
    stream_epe = float(np.mean(stream_epes))
    indep_epe = float(np.mean(indep_epes))

    warm_hist = s_stats["iters_used_warm"]
    cold_hist = s_stats["iters_used_cold"]
    warm_p50, cold_p50 = warm_hist.get("p50"), cold_hist.get("p50")
    saved_frac = (1.0 - warm_p50 / cold_p50
                  if warm_p50 and cold_p50 else None)

    # Encoder-work saving from the compile-time cost ledger: the warm
    # program runs the encoders over ONE image instead of two.
    enc_fpp = wenc_fpp = None
    for key_, c in (s_stats.get("cost") or {}).items():
        if key_.endswith("/enc"):
            enc_fpp = c.get("flops_per_pair")
        elif key_.endswith("/wenc"):
            wenc_fpp = c.get("flops_per_pair")
    enc_saved = (1.0 - wenc_fpp / enc_fpp
                 if enc_fpp and wenc_fpp else None)

    tag = "tiny" if args.tiny else f"{h}x{w}"
    record = {
        "metric": f"serve_stream_{tag}_f{args.frames}"
                  f"_s{args.sessions}_iters{args.iters}",
        "value": round(pairs / s_dt / n_dev, 3),
        "unit": "frames/sec/chip",
        "vs_baseline": 0.0,
        "config": {
            "hw": args.hw, "frames": args.frames,
            "sessions": args.sessions, "iters": args.iters,
            "stream_warm_iters": args.stream_warm_iters,
            "early_exit_threshold": args.early_exit_threshold,
            "slots": args.slots, "shift": args.shift,
            "precision": args.precision, "small": args.small,
            "seed": args.seed,
            # The two gate inputs (scripts/check_regression.py):
            "warm_iters_saved_frac": (round(saved_frac, 4)
                                      if saved_frac is not None
                                      else None),
            "stream_epe_delta": round(stream_epe - indep_epe, 4),
        },
        "stream_epe": round(stream_epe, 4),
        "indep_epe": round(indep_epe, 4),
        "warm_share": round(sum(warm_flags) / max(len(warm_flags), 1),
                            4),
        "iters_used_warm": warm_hist,
        "iters_used_cold": cold_hist,
        "encoder_flops_saved_frac": (round(enc_saved, 4)
                                     if enc_saved is not None
                                     else None),
        "sessions": s_stats.get("sessions"),
        "compiles": s_stats.get("compiles"),
        "arms": {
            "stream": {"value": round(pairs / s_dt / n_dev, 3),
                       "epe": round(stream_epe, 4)},
            "independent": {"value": round(pairs / i_dt / n_dev, 3),
                            "epe": round(indep_epe, 4)},
        },
    }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
