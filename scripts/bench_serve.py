"""Serving-engine load generator: closed-loop and Poisson open-loop.

Drives ``raft_tpu.serve.InferenceEngine`` in-process (no HTTP overhead in
the measurement) with mixed-resolution synthetic frame pairs and prints
ONE check_regression-format JSON line per run (metric / value / unit /
vs_baseline), plus the client-observed latency percentiles and the
engine's compile ledger.

Two canonical load shapes:

- ``--mode closed``: ``--concurrency`` workers each keep exactly one
  request in flight (submit, wait, repeat) — the saturation-throughput
  number, what "pairs/sec/chip can this engine do" means.
- ``--mode open``: requests arrive on a Poisson process at ``--rate``
  req/s regardless of completions — the production-realistic number,
  where latency percentiles and 429 rejections are the story (an open
  loop keeps arriving while the server falls behind; a closed loop
  politely waits and hides the collapse).

The workload is **mixed difficulty** (the traffic shape continuous
batching exists for): ``--easy-frac`` of the requests are low-motion
pairs (image2 = image1 + noise) submitted with a small per-request
iteration budget, the rest are independent random pairs at the full
budget — all drawn from ``--seed``, so every batching arm replays the
identical request sequence.

``--batching slot`` serves the workload at GRU-iteration granularity
(``ServeConfig.batching="slot"`` with ``--slots`` lanes and
``--early-exit-threshold``; docs/SERVING.md "Continuous batching");
``--batching both`` runs request-mode and slot-mode over the same
workload back to back and emits ONE record whose headline is the slot
arm, with the request arm and the slot/request p99 + throughput ratios
nested under ``arms`` / ``slot_vs_request``.  The record carries
``iters_used`` percentiles and slot ``occupancy`` next to the latency
percentiles — the two sides of the early-exit trade.

``--replicas N`` (N > 1) drives a ``ReplicaFleet`` behind the
``FlowRouter`` instead of a bare engine (optionally with
``--hedge-timeout-s``); the record gains per-replica engine sections
and the router counters.  Every record carries ``errors`` /
``timeouts`` (per ``--request-timeout-s``) / ``error_rate`` (failures
over submitted, 429 sheds excluded) and ``retries_total`` so
``scripts/check_regression.py --max-serve-error-rate`` can gate the
series — a fleet that posts throughput while losing requests fails.

``--quality-sample-rate`` (slot mode) turns on sampled scoring with the
unsupervised flow-quality proxies (``raft_tpu/obs/quality.py``); the
record then carries per-proxy p50/p95 (``quality``) next to the latency
percentiles — throughput, latency, and output quality in one line.

``--tiny``: CPU-friendly smoke preset (small model, fp32, 3 iters, two
tiny resolutions, ``--batching both``) so the serving path — and the
slot-vs-request comparison — stays testable without hardware::

    JAX_PLATFORMS=cpu python scripts/bench_serve.py --tiny
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --tiny --mode open
    JAX_PLATFORMS=cpu python scripts/bench_serve.py --tiny --replicas 2

There is no external serving baseline (the reference repo has no request
path at all); ``vs_baseline`` is 0.0 until a measured TPU number lands
in a ``BENCH_SERVE_r*.json`` and becomes the bar.
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
    p = argparse.ArgumentParser(description="RAFT-TPU serving benchmark")
    p.add_argument("--mode", default="closed", choices=["closed", "open"])
    p.add_argument("--tiny", action="store_true",
                   help="CPU smoke preset (small model, 3 iters, tiny "
                        "shapes, few requests, --batching both)")
    p.add_argument("--shapes", default="440x1024",
                   help="comma-separated HxW request resolutions, cycled "
                        "round-robin (mixed-shape traffic)")
    p.add_argument("--requests", type=int, default=64)
    p.add_argument("--concurrency", type=int, default=8,
                   help="closed-loop: in-flight requests")
    p.add_argument("--rate", type=float, default=20.0,
                   help="open-loop: Poisson arrival rate, req/s")
    p.add_argument("--small", action="store_true")
    p.add_argument("--precision", default="bf16", choices=["bf16", "fp32"])
    p.add_argument("--iters", type=int, default=32)
    p.add_argument("--batching", default=None,
                   choices=["request", "slot", "both"],
                   help="request-level batching (the parity oracle), "
                        "GRU-iteration-level slot batching, or both arms "
                        "over the same workload in one record (default: "
                        "request; --tiny defaults to both)")
    p.add_argument("--slots", type=int, default=8,
                   help="slot mode: persistent device lanes per bucket")
    p.add_argument("--early-exit-threshold", type=float, default=0.0,
                   help="slot mode: retire a lane when its max flow "
                        "update drops below this (0 = off; gate the "
                        "value with evaluate.py --early_exit_threshold)")
    p.add_argument("--early-exit-epe-delta", type=float, default=None,
                   help="measured |EPE delta| of --early-exit-threshold "
                        "vs the full-iteration baseline (from evaluate.py"
                        " --early_exit_threshold), stamped into the "
                        "record for check_regression.py "
                        "--max-early-exit-epe-delta; with the threshold "
                        "at 0 the delta is exactly 0 and stamps itself")
    p.add_argument("--quality-sample-rate", type=float, default=0.0,
                   help="slot mode: score this fraction of retiring "
                        "requests with the unsupervised flow-quality "
                        "proxies (raft_tpu/obs/quality.py); the record "
                        "then carries per-proxy p50/p95 next to the "
                        "latency percentiles (0 = off, the zero-"
                        "overhead default)")
    p.add_argument("--easy-frac", type=float, default=0.5,
                   help="fraction of requests that are low-motion pairs "
                        "with a reduced per-request iteration budget "
                        "(the mixed-difficulty workload)")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--max-wait-ms", type=float, default=5.0)
    p.add_argument("--max-queue", type=int, default=256)
    p.add_argument("--batch-sizes", default=None,
                   help="comma-separated compiled batch sizes")
    p.add_argument("--no-warmup", action="store_true",
                   help="include first-request compiles in the "
                        "measurement (cold-start experiment)")
    p.add_argument("--replicas", type=int, default=1,
                   help="drive a supervised replica fleet behind the "
                        "health-gated router instead of one engine "
                        "(docs/SERVING.md fleet section)")
    p.add_argument("--hedge-timeout-s", type=float, default=0.0,
                   help="fleet mode: router hedge timeout (0 = off)")
    p.add_argument("--request-timeout-s", type=float, default=120.0,
                   help="per-request wait bound; expiries count in the "
                        "'timeouts' figure instead of hanging the bench")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    if args.tiny:
        args.small = True
        args.precision = "fp32"
        args.iters = 3
        args.shapes = "64x96,36x52"
        args.requests = 36
        # Saturating in-flight load (2 buckets x 4 lanes): request mode
        # must queue whole waves while slot mode admits per iteration —
        # the regime continuous batching is for.  At concurrency ==
        # slots the closed loop never queues and the comparison is
        # vacuous.
        args.concurrency = 12
        args.rate = 40.0
        args.max_batch = 4
        args.batch_sizes = args.batch_sizes or "4"
        args.max_wait_ms = 10.0
        args.max_queue = 64
        args.slots = min(args.slots, 4)
        args.batching = args.batching or "both"
    args.batching = args.batching or "request"
    if args.replicas > 1 and args.batching != "request":
        raise SystemExit("--replicas > 1 serves request-mode engines; "
                         "use --batching request (slot-mode fleets are "
                         "future work)")
    if not 0.0 <= args.easy_frac <= 1.0:
        raise SystemExit(f"--easy-frac must be in [0, 1], got "
                         f"{args.easy_frac}")
    return args


def _make_workload(shapes, n_requests, iters_full, easy_frac, rng):
    """``[(im1, im2, iters), ...]`` — the seeded mixed-difficulty
    request sequence, identical across batching arms.

    Easy requests (``easy_frac`` of traffic) are low-motion pairs
    (image2 = image1 + noise) with a per-request budget drawn from the
    bottom half of ``[1, iters_full]``; hard requests are independent
    pairs at the full budget.  Request-level batching ignores the
    per-request budget (every lockstep lane pays ``iters_full``); slot
    mode honors it — that asymmetry IS the benchmark.
    """
    import numpy as np

    workload = []
    for i in range(n_requests):
        h, w = shapes[i % len(shapes)]
        im1 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
        if rng.random() < easy_frac:
            im2 = np.clip(im1 + rng.normal(0, 2, im1.shape), 0,
                          255).astype(np.float32)
            iters = int(rng.integers(1, max(iters_full // 2, 1) + 1))
        else:
            im2 = rng.uniform(0, 255, (h, w, 3)).astype(np.float32)
            iters = iters_full
        workload.append((im1, im2, iters))
    return workload


class _Outcomes:
    """Thread-safe request-outcome tally: a request either completes,
    is rejected at submit (429 shed — intentional, NOT an error), fails
    with an error, or times out client-side."""

    def __init__(self, timeout_s):
        self.timeout_s = timeout_s
        self.lock = threading.Lock()
        self.completed = 0
        self.rejected = 0
        self.errors = 0
        self.timeouts = 0

    def wait(self, fut) -> None:
        from concurrent.futures import TimeoutError as FutTimeout

        try:
            fut.result(timeout=self.timeout_s)
        except FutTimeout:
            with self.lock:
                self.timeouts += 1
        except Exception:
            with self.lock:
                self.errors += 1
        else:
            with self.lock:
                self.completed += 1


def _submit(service, item, with_iters: bool):
    im1, im2, iters = item
    if with_iters:
        return service.submit(im1, im2, iters=iters)
    return service.submit(im1, im2)


def _run_closed(service, workload, concurrency, out: "_Outcomes",
                with_iters: bool):
    """Each worker keeps one request in flight; returns elapsed seconds."""
    from raft_tpu.serve import QueueFullError

    next_i = [0]
    lock = threading.Lock()

    def worker():
        while True:
            with lock:
                i = next_i[0]
                if i >= len(workload):
                    return
                next_i[0] += 1
            try:
                fut = _submit(service, workload[i], with_iters)
            except QueueFullError:
                with out.lock:
                    out.rejected += 1
                continue
            out.wait(fut)

    threads = [threading.Thread(target=worker) for _ in range(concurrency)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return time.perf_counter() - t0


def _run_open(service, workload, rate, rng, out: "_Outcomes",
              with_iters: bool):
    """Poisson arrivals at ``rate`` req/s; returns elapsed seconds.

    Arrivals keep coming while earlier requests run — rejected submits
    (429 backpressure) are counted, not retried (a shed request's work
    is the balancer's problem, not this chip's)."""
    from raft_tpu.serve import QueueFullError

    futures = []
    t0 = time.perf_counter()
    for item in workload:
        time.sleep(rng.exponential(1.0 / rate))
        try:
            futures.append(_submit(service, item, with_iters))
        except QueueFullError:
            with out.lock:
                out.rejected += 1
    for f in futures:
        out.wait(f)
    return time.perf_counter() - t0


def _arm_cost_fields(stats: dict, iters: int, value: float) -> dict:
    """Hardware-normalized work figures for one arm, from the engine's
    compile-time cost ledger (``stats()["cost"]``, obs/cost.py).

    ``flops_per_pair`` is the full-budget pipeline (``enc`` +
    ``iters`` x ``iter``) per pair, averaged over the compiled buckets;
    ``achieved_tflops`` re-multiplies it by the measured pairs/sec/chip
    (slot-mode early exit makes this the NOMINAL figure — a lane that
    retires early did less work than stamped, so slot-mode MFU is an
    upper bound).  ``mfu`` stays None on unknown device peaks (CPU),
    which is what keeps those records out of ``--min-mfu``.
    """
    groups: dict = {}
    for key, c in (stats.get("cost") or {}).items():
        prefix, prog = key.rsplit("/", 1)
        groups.setdefault(prefix, {})[prog] = c
    fpps, peaks = [], []
    for progs in groups.values():
        enc, it = progs.get("enc"), progs.get("iter")
        if not enc or not it or not enc.get("flops_per_pair"):
            continue
        fpps.append(enc["flops_per_pair"]
                    + iters * it["flops_per_pair"])
        peaks.append(enc.get("peak_tflops"))
    if not fpps:
        return {}
    fpp = sum(fpps) / len(fpps)
    achieved = value * fpp / 1e12
    peak = peaks[0]
    return {"flops_per_pair": round(fpp, 1),
            "achieved_tflops": round(achieved, 4),
            "mfu": round(achieved / peak, 4) if peak else None}


def _run_arm(args, variables, model_cfg, workload, shapes,
             batching: str):
    """One batching arm over the shared workload: build the service,
    warm it, drive the load, return the arm's figures."""
    import jax
    import numpy as np

    from raft_tpu.serve import InferenceEngine, ServeConfig

    serve_cfg = ServeConfig(
        iters=args.iters, max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, max_queue=args.max_queue,
        batch_sizes=tuple(int(b) for b in args.batch_sizes.split(","))
        if args.batch_sizes else None,
        batching=batching, slots=args.slots,
        early_exit_threshold=args.early_exit_threshold
        if batching == "slot" else 0.0,
        quality_sample_rate=min(max(args.quality_sample_rate, 0.0), 1.0)
        if batching == "slot" else 0.0)
    fleet = None
    if args.replicas > 1:
        from raft_tpu.serve import (FleetConfig, FlowRouter,
                                    ReplicaFleet, RouterConfig)

        fleet = ReplicaFleet(
            variables, model_cfg, serve_cfg,
            FleetConfig(replicas=args.replicas,
                        warmup_shapes=() if args.no_warmup
                        else tuple(shapes)))
        fleet.start()
        service = FlowRouter(fleet, RouterConfig(
            hedge_timeout_s=max(args.hedge_timeout_s, 0.0)))
    else:
        service = InferenceEngine(variables, model_cfg, serve_cfg)
        service.start()
    out = _Outcomes(args.request_timeout_s or None)
    rng = np.random.default_rng(args.seed + 1)  # arrival jitter only
    try:
        if not args.no_warmup and fleet is None:
            service.warmup(shapes)
        with_iters = fleet is None  # router submit is (im1, im2) only
        if args.mode == "closed":
            assert args.concurrency <= args.max_queue, \
                "closed loop would trip its own backpressure"
            dt = _run_closed(service, workload, args.concurrency, out,
                             with_iters)
        else:
            dt = _run_open(service, workload, args.rate, rng, out,
                           with_iters)
        stats = service.stats()
    finally:
        if fleet is not None:
            fleet.stop()
        else:
            service.stop()

    n_dev = max(jax.local_device_count(), 1)
    # error_rate covers FAILED requests (errors + client timeouts) over
    # everything submitted; 429 sheds are intentional backpressure and
    # stay a separate figure (check_regression gates on error_rate).
    error_rate = (out.errors + out.timeouts) / max(len(workload), 1)
    arm = {
        "batching": batching,
        "value": round(out.completed / dt / n_dev, 3),
        "latency_ms": None,
        "rejected": out.rejected,
        "errors": out.errors,
        "timeouts": out.timeouts,
        "error_rate": round(error_rate, 6),
        "iters_used": None,
        "occupancy": None,
    }
    if fleet is not None:
        arm["replicas"] = {
            name: {"retries": rep.get("retries", 0),
                   "completed": rep.get("completed", 0),
                   "restarts": rep.get("restarts", 0)}
            for name, rep in stats["replicas"].items()}
        arm["retries_total"] = sum(r["retries"]
                                   for r in arm["replicas"].values())
        arm["latency_ms"] = stats["router"]["latency_ms"]
        arm["compiles"] = {name: rep.get("compiles", {})
                          for name, rep in stats["replicas"].items()}
        arm["router"] = {
            k: stats["router"][k]
            for k in ("requests_total", "failovers_total", "hedges_total",
                      "hedge_wins_total", "rejected_total",
                      "dropped_total")}
    else:
        arm["retries_total"] = stats["retries"]
        arm["latency_ms"] = stats["latency_ms"]
        arm["occupancy"] = stats["occupancy"]
        arm["compiles"] = stats["compiles"]
        arm["iters_used"] = stats.get("iters_used")
        arm["cost"] = stats.get("cost")
        # Sampled flow-quality proxies (raft_tpu/obs/quality.py):
        # per-proxy p50/p95 ride next to the latency percentiles when
        # quality scoring is on; {"enabled": False} arms stay silent.
        q = stats.get("quality")
        if isinstance(q, dict) and q.get("enabled"):
            arm["quality"] = q
        arm.update(_arm_cost_fields(stats, args.iters, arm["value"]))
    return arm


def main(argv=None):
    args = parse_args(argv)

    import jax
    import numpy as np

    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT

    mk = RAFTConfig.small_model if args.small else RAFTConfig.full
    model_cfg = mk(compute_dtype="bfloat16" if args.precision == "bf16"
                   else "float32")
    model = RAFT(model_cfg)
    key = jax.random.PRNGKey(args.seed)
    img = jax.numpy.zeros((1, 64, 96, 3))
    variables = jax.jit(
        lambda k: model.init({"params": k, "dropout": k}, img, img,
                             iters=2, train=False))(key)

    shapes = []
    for tok in args.shapes.split(","):
        h, w = tok.strip().lower().split("x")
        shapes.append((int(h), int(w)))
    workload = _make_workload(shapes, args.requests, args.iters,
                              args.easy_frac,
                              np.random.default_rng(args.seed))

    arm_names = (["request", "slot"] if args.batching == "both"
                 else [args.batching])
    arms = {name: _run_arm(args, variables, model_cfg, workload, shapes,
                           name)
            for name in arm_names}
    head = arms[arm_names[-1]]  # slot arm headlines a "both" run

    tag = "tiny" if args.tiny else "+".join(f"{h}x{w}"
                                            for (h, w) in shapes)
    load = (f"c{args.concurrency}" if args.mode == "closed"
            else f"r{args.rate:g}")
    rep_tag = f"_x{args.replicas}" if args.replicas > 1 else ""
    record = {
        "metric": f"serve_{args.mode}loop_{tag}_{load}"
                  f"_iters{args.iters}_{head['batching']}{rep_tag}",
        "value": head["value"],
        "unit": "image-pairs/sec/chip",
        "vs_baseline": 0.0,
        "config": {"mode": args.mode, "requests": args.requests,
                   "concurrency": args.concurrency, "rate": args.rate,
                   "shapes": args.shapes, "iters": args.iters,
                   "batching": args.batching, "slots": args.slots,
                   "early_exit_threshold": args.early_exit_threshold,
                   "easy_frac": args.easy_frac,
                   "max_batch": args.max_batch,
                   "max_wait_ms": args.max_wait_ms,
                   "max_queue": args.max_queue,
                   "batch_sizes": args.batch_sizes,
                   "warmup": not args.no_warmup,
                   "replicas": args.replicas,
                   "precision": args.precision, "small": args.small,
                   "seed": args.seed},
    }
    # Early-exit accuracy stamp for the regression gate: a disabled
    # threshold costs exactly zero EPE; a nonzero threshold needs the
    # measured figure from the evaluate.py sweep (no stamp -> the gate
    # refuses to pass vacuously).
    ee_delta = args.early_exit_epe_delta
    if ee_delta is None and "slot" in arms \
            and args.early_exit_threshold == 0.0:
        ee_delta = 0.0
    if ee_delta is not None:
        record["config"]["early_exit_epe_delta"] = abs(ee_delta)
    record.update({k: head[k] for k in
                   ("latency_ms", "rejected", "errors", "timeouts",
                    "error_rate", "retries_total", "occupancy",
                    "compiles", "iters_used", "cost", "flops_per_pair",
                    "achieved_tflops", "mfu", "quality") if k in head})
    for k in ("replicas", "router"):
        if k in head:
            record[k] = head[k]
    if args.batching == "both":
        record["arms"] = arms
        req, slot = arms["request"], arms["slot"]
        req_p99 = (req["latency_ms"] or {}).get("p99_ms") or 0.0
        slot_p99 = (slot["latency_ms"] or {}).get("p99_ms") or 0.0
        record["slot_vs_request"] = {
            # > 1.0 on both ratios = slot mode wins both ways.
            "throughput_ratio": round(
                slot["value"] / req["value"], 3) if req["value"] else None,
            "p99_ratio": round(req_p99 / slot_p99, 3) if slot_p99
            else None,
            "p99_ms_request": req_p99,
            "p99_ms_slot": slot_p99,
        }
    print(json.dumps(record))


if __name__ == "__main__":
    main()
