"""Traffic of kind ``serve``: ``InferenceEngine.submit`` under a closed loop.

The engine is built as ``cli/serve.py`` builds it (flags through its own
``parse_args``; the traffic file's ``flags`` on top of its defaults), on
weights made from the seed, warmed at the traffic's one shape only.  The
window's callers are threads of this process (one process holds the chip).
"""

from __future__ import annotations

import os
import threading
import time

import numpy as np

from benchmark import check


class _Altered:
    """The planted fault 'an answer altered where it is produced'."""

    def __init__(self, fut):
        self.fut = fut

    def result(self, timeout=None):
        flow = np.array(self.fut.result(timeout=timeout))
        h, w = flow.shape[:2]
        flow[h // 4:h // 2, w // 4:w // 2] *= -1.0    # a 16th of the field
        return flow


def run(ctx):
    import jax

    from raft_tpu.cli import serve as cli
    from raft_tpu.config import RAFTConfig
    from raft_tpu.models.raft import RAFT
    from raft_tpu.ops.pad import bucket_hw
    from raft_tpu.serve import InferenceEngine, ServeConfig
    from raft_tpu.utils.profiling import enable_persistent_compile_cache

    from benchmark import flops, reference, traffic, weights

    cfg, tr, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    enable_persistent_compile_cache()
    iters = int(tr.get("iters", cfg["serve_iters"]))
    args = cli.parse_args(["--random-init", "--iters", str(iters)]
                          + (["--small"] if cfg["small"] else [])
                          + [str(a) for a in tr.get("flags", [])])
    mk = RAFTConfig.small_model if args.small else RAFTConfig.full
    model_cfg = mk(
        compute_dtype="bfloat16" if args.precision == "bf16" else "float32")
    for key in ("hidden_dim", "context_dim", "corr_levels", "corr_radius"):
        if getattr(model_cfg, key) != cfg[key]:
            raise SystemExit(f"{key}: program {getattr(model_cfg, key)} != "
                             f"configuration file {cfg[key]}")
    serve_cfg = ServeConfig(
        iters=args.iters, batching=args.batching, slots=args.slots,
        early_exit_threshold=max(args.early_exit_threshold, 0.0),
        stream_ttl_s=max(args.stream_ttl_s, 1e-3),
        stream_warm_iters=args.stream_warm_iters,
        max_sessions=max(args.max_sessions, 1), max_batch=args.max_batch,
        max_wait_ms=args.max_wait_ms, max_queue=args.max_queue,
        batch_sizes=tuple(int(b) for b in args.batch_sizes.split(","))
        if args.batch_sizes else None,
        stall_timeout_s=max(args.stall_timeout_s, 0.0),
        device_retries=max(args.device_retries, 0),
        retry_backoff_s=max(args.retry_backoff_s, 0.0),
        retry_backoff_max_s=max(ServeConfig.retry_backoff_max_s,
                                args.retry_backoff_s))
    variables = weights.make_variables(RAFT(model_cfg), seed)
    shape = tuple(tr["shape"])
    pool = traffic.make_pool(seed, shape, int(tr["pool"]))
    engine = InferenceEngine(variables, model_cfg, serve_cfg)
    engine.start()
    trace_dir = os.path.join(ctx["workdir"], "xplane") if ctx["trace"] else None
    try:
        engine.warmup([shape])
        # the whole path before the clock starts, in the batches the
        # traffic makes: ``warm_batches`` lists how many requests are sent
        # together, one entry a warm-up batch
        for n in tr.get("warm_batches", [serve_cfg.max_batch]):
            for f in [engine.submit(*pool[i % len(pool)]) for i in range(n)]:
                f.result(timeout=600)
        submit = engine.submit
        if ctx.get("fault") == "answer_altered":
            submit = lambda a, b: _Altered(engine.submit(a, b))  # noqa: E731
        load = traffic.ClosedLoop(submit, pool, int(tr["clients"]), seed,
                                  min_gap_ms=tr.get("min_gap_ms", 0))
        before = engine.stats()
        tracer = None
        if trace_dir:
            def start_trace():
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracer = threading.Timer(
                max(ctx["seconds"] - float(tr.get("trace_seconds", 3.0)), 0),
                start_trace)
        t_window = time.perf_counter()
        ctx["mark"]("window_opens")
        load.run(ctx["seconds"],
                 on_start=tracer.start if tracer else None)
        if tracer:
            tracer.join()
            jax.profiler.stop_trace()
        after = engine.stats()
        ctx["mark"]("window_closed")
    finally:
        engine.stop()
    ctx["mark"]("engine_stopped")
    s = load.summary(ctx["seconds"])
    peak_bytes = ctx["memory_peak"]()
    done = after["completed"] - before["completed"]
    batches = after["batches"] - before["batches"]

    def total_lanes(st):      # real + ballast lanes the batches were cut for
        real = st["completed"] + st["failed_lanes"]
        return real / st["occupancy"] if st["occupancy"] else 0.0

    lanes = total_lanes(after) - total_lanes(before)
    bucket = bucket_hw(shape[0], shape[1], serve_cfg.bucket_multiple,
                       serve_cfg.buckets)
    result = {
        "attempted": s["attempted"], "failed": s["failed"],
        "setup_s": t_window - ctx["t_start"],
        "e2e": {"serve_pairs_per_s": s["pairs_per_s"],
                "serve_latency_p50_ms": s["latency_p50_ms"],
                "serve_latency_p95_ms": s["latency_p95_ms"]},
        "memory_peak_bytes": peak_bytes,
        "facts": {"window_s": ctx["seconds"], "chips": ctx["chips"],
                  "pairs_per_s": s["pairs_per_s"],
                  "ops_per_pair": flops.forward_ops(cfg, bucket[0],
                                                    bucket[1], iters, 1),
                  "lookup": {"h": bucket[0] // 8, "w": bucket[1] // 8,
                             "pairs_per_call": lanes / max(batches, 1)},
                  "engine": {"completed": done, "batches": batches,
                             "max_batch": serve_cfg.max_batch,
                             "compiles_in_window": sum(
                                 after["compiles"].values())
                             - sum(before["compiles"].values())},
                  "trace_dir": trace_dir},
    }

    # ---- correctness: a sample of the finished requests, once the window
    # has closed and the engine is gone ---------------------------------
    sample = load.sample(seed, int(tr.get("check_requests", 6)))
    served = [load.flows[key] for key, _ in sample]
    pairs = [pool[idx] for _, idx in sample]
    host_vars = jax.device_get(variables)
    del engine, variables, load.flows
    ctx["mark"]("reference_starts")
    t = time.perf_counter()
    refs = reference.serve_flows(cfg, host_vars, pairs, iters, bucket)
    info = {"reference_s": time.perf_counter() - t,
            "engine": result["facts"]["engine"], "slow": s["slow"],
            "ref_flow_rms_px": [float(np.sqrt(np.mean(r ** 2)))
                                for r in refs]}
    # The unit the gaps are also read in: what rounding every convolution's
    # and the correlation's operands to bfloat16 does to the reference
    # itself on these weights (the gain of 32 iterations of a random-weight
    # GRU differs from seed to seed, and moves every gap with it).
    unit = check.flow_gap(reference.serve_flows(
        cfg, host_vars, pairs[:1], iters, bucket,
        quant=reference.fake_bf16)[0], refs[0]) if refs else None
    info["bf16_unit_gap"] = unit
    numbers = compare(served, refs, info, unit)
    if ctx.get("reference_quant"):
        # The control: the reference in the program's place, one precision
        # below the configuration's; the first one named is what gets
        # judged.  The same process has read the program (a lower reading
        # on this seed).
        info["program"] = numbers
        info["controls"] = {
            q: compare(reference.serve_flows(cfg, host_vars, pairs, iters,
                                             bucket,
                                             quant=reference.QUANTS[q]),
                       refs, {}, unit)
            for q in ctx["reference_quant"].split(",")}
        numbers = dict(next(iter(info["controls"].values())))
    numbers["_info"] = info
    result["numbers"] = numbers
    return result


def compare(served, refs, info, unit):
    """Each sampled flow field against the reference's for the same pair:
    ``flow_gap`` the worst ||served - ref|| / ||ref|| of the sample,
    ``flow_gap_median`` its median, ``flow_gap_vs_bf16`` the worst in units
    of ``unit`` (the bfloat16-rounded reference's own gap)."""
    gaps = [check.flow_gap(a, b) for a, b in zip(served, refs)]
    info.update(gaps=gaps, checked=len(gaps))
    if not gaps:
        return {"flow_gap": None, "flow_gap_median": None,
                "flow_gap_vs_bf16": None}
    return {"flow_gap": max(gaps), "flow_gap_median": float(np.median(gaps)),
            "flow_gap_vs_bf16": max(gaps) / unit if unit else None}
