"""The training driver (reference ``train(args)``, train.py:136-212).

TPU-first shape of the loop:

- one jitted SPMD step over the device mesh (no DataParallel wrapper);
- a three-stage overlapped input pipeline (``DevicePipeline``,
  docs/PERFORMANCE.md): loader threads decode/augment, a background
  producer runs host prep (noise) + async ``device_put``, and the loop
  consumes already-device-resident batches — H2D transfer of batch N+1
  overlaps the device step on batch N (``cfg.device_prefetch``; 0 = the
  old serial fetch->prep->put->step path, bit-identical batches either
  way);
- ``cfg.accum_steps`` splits the per-host batch into microbatches with
  fp32 gradient accumulation (train/step.py) for HBM-bound configs;
- orbax checkpoints carry the full state; a preempted run auto-resumes
  from the latest step (the reference restarts its schedule, SURVEY.md §5);
- optional gaussian image noise parity (train.py:167-170), applied in
  the pipeline's producer in stream order so the per-step noise is
  identical with prefetch on or off.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import os
import time
from typing import Callable, Dict, Optional

import jax
import numpy as np

from raft_tpu import chaos
from raft_tpu.config import RAFTConfig, TrainConfig
from raft_tpu.data.prefetch import DevicePipeline, PipelineInterrupted
from raft_tpu.models.raft import (RAFT, attention_bytes, batch_norm_calls,
                                  predictions, window_attention_at)
from raft_tpu.obs import stages, trace
from raft_tpu.obs.health import HealthMonitor
from raft_tpu.obs.train import TrainTelemetry
from raft_tpu.obs.watchdog import StallWatchdog, stack_dump_path
from raft_tpu.parallel import (data_parallel_kernels, make_batch_sharder,
                               make_mesh, place_replicated)
from raft_tpu.train.checkpoint import CheckpointManager
from raft_tpu.train.logger import Logger
from raft_tpu.train.loss import sequence_loss  # noqa: F401 (re-export)
from raft_tpu.train.optim import make_optimizer, schedule_of
from raft_tpu.train.state import TrainState
from raft_tpu.train.step import init_state, make_train_step, step_cost
from raft_tpu.utils.profiling import (StepProfiler, annotate_step, hbm_usage,
                                     listen_for_compiles)

# Cooperative preemption: a SIGTERM handler (cli/train.py) sets this and
# the loop exits at the NEXT STEP BOUNDARY — an async exception could
# land mid-`mgr.save` and abort a registered-but-uncommitted orbax step,
# which the emergency path below would then mistake for a completed save.
import threading

_PREEMPT = threading.Event()
_warned_sync = False

#: Steps dispatched and not yet finished on the device, at most: one runs,
#: one waits behind it.  Each holds its input batch in HBM, and dispatch
#: is asynchronous: a loop fed faster than its device ran up to a Logger
#: interval ahead (+3.6 GB at `train_gmflow_chairs`, PERF.md, PR 37).
_MAX_IN_FLIGHT = 2

#: The dispatch after which the loop reads how often its step was built
#: (``step_builds``): a state whose type differs from what the step gives
#: back builds the step a second time at the second dispatch.
_BUILDS_SETTLED = 3

#: The name the compile listener gives a lowering of the mesh train step
#: (``mesh_step_fn`` in train/step.py, under ``jit``).
_STEP_LOWERING = "jit(mesh_step_fn)"


def request_preemption() -> None:
    """Ask the running train() loop to checkpoint and exit after the
    current step completes (safe to call from a signal handler).

    Single-host only (the CLI wires SIGTERM here when
    ``process_count() == 1``): a per-host flag has no cross-host
    agreement, so hosts could exit at different step boundaries and
    deadlock the gradient psum / orbax barrier.  Multi-host preemption
    instead rides JAX's coordination-service sync protocol — SIGTERM is
    its default preemption notice, and ``train()`` polls
    ``reached_preemption_sync_point(step)`` every step, which returns
    True on ALL hosts at the same agreed safe step."""
    _PREEMPT.set()


def _reached_preemption_sync(step: int) -> bool:
    """Multi-host agreed preemption step (False when the preemption
    service is unavailable)."""
    from jax.experimental import multihost_utils

    try:
        return multihost_utils.reached_preemption_sync_point(step)
    except Exception as e:  # service disabled/unavailable; JAX versions
        # differ in what they raise here.  Log once: this is a cross-host
        # sync point, and silently returning False on only SOME hosts
        # would desynchronize their exit steps.
        global _warned_sync
        if not _warned_sync:
            _warned_sync = True
            print(f"preemption sync unavailable ({type(e).__name__}: {e});"
                  " falling back to no multi-host preemption", flush=True)
        return False


def _compile_seq() -> int:
    """Sequence number of the compile ring's newest record (0: none)."""
    recs = stages.recent("compile")
    return recs[-1]["n"] if recs else 0


def _step_builds(since: int) -> int:
    """Lowerings of the mesh train step that the compile listener booked
    after the compile ring's record ``since``: 1 where every call of the
    step shares one jit cache key."""
    return sum(1 for r in stages.recent("compile")
               if r["n"] > since and r["kind"] == "lower"
               and r.get("name") == _STEP_LOWERING)


def add_image_noise(rng: np.random.Generator, batch: Dict) -> Dict:
    """Gaussian noise with stdv ~ U(0, 5), clipped to [0, 255]
    (reference train.py:167-170)."""
    out = dict(batch)
    stdv = rng.uniform(0.0, 5.0)  # one draw, both frames (train.py:168)
    for k in ("image1", "image2"):
        out[k] = np.clip(
            batch[k] + stdv * rng.standard_normal(batch[k].shape)
                               .astype(np.float32), 0.0, 255.0)
    return out


def train(model_cfg: RAFTConfig, cfg: TrainConfig,
          batches=None, *,
          loader=None,
          validators: Optional[Dict[str, Callable]] = None,
          restore_params=None,
          tensorboard_dir: Optional[str] = None,
          profile_dir: Optional[str] = None,
          telemetry_dir: Optional[str] = None,
          mesh=None, shard_spatial: bool = False) -> TrainState:
    """Run the full training loop.

    ``batches``: iterator of host batches (dicts of NHWC numpy arrays).
    ``loader``: alternatively a ``ShardedLoader`` — preferred, because on
    checkpoint auto-resume the stream continues from the restored step's
    position in the shuffle instead of replaying epoch 0.
    ``validators``: name -> fn(variables) -> dict, run every ``val_freq``
    steps (reference train.py:190-196).
    ``restore_params``: optional {'params', 'batch_stats'} to seed from a
    previous curriculum stage (reference --restore_ckpt, train.py:141-142).
    ``shard_spatial``: additionally shard image height over the mesh's
    ``spatial`` axis (pass a mesh built with ``num_spatial > 1``) — the
    activation/corr-volume sharding path for inputs too large for one
    chip's HBM.
    ``telemetry_dir``: write per-step JSONL telemetry (``step_time_s``,
    ``queue_wait_s``, ``h2d_s``, ``pairs_per_sec_per_chip``, compile +
    hbm events — docs/OBSERVABILITY.md) here; defaults to
    ``$RAFT_TELEMETRY_DIR``, unset = disabled.  All telemetry timing is
    host-side ``perf_counter`` — it adds NO device sync to the step path.

    Input overlap: ``cfg.device_prefetch`` batches are host-prepped and
    ``device_put`` ahead of the consuming step on a background producer
    (``raft_tpu/data/prefetch.py``); 0 restores the serial path.  The
    batch stream — order, content, and noise per global step, including
    mid-epoch resume via ``batches_from_step`` — is bit-identical either
    way.  ``cfg.accum_steps`` microbatches the step (train/step.py).
    """
    assert (batches is None) != (loader is None), \
        "pass exactly one of batches= or loader="
    _PREEMPT.clear()  # a new run starts unpreempted
    listen_for_compiles()  # the compile ring `step_builds` reads
    mesh = mesh or make_mesh()
    model = RAFT(model_cfg)
    tx = make_optimizer(cfg.lr, cfg.num_steps, cfg.wdecay, cfg.epsilon,
                        cfg.clip)
    # Tiny-shape init: conv/GRU param shapes don't depend on image size,
    # and full-size init would trace the whole model a second time.
    state = init_state(model, tx, jax.random.PRNGKey(cfg.seed), (48, 64))
    if restore_params is not None:
        state = state.replace(
            params=restore_params["params"],
            batch_stats=restore_params.get("batch_stats", state.batch_stats))
    print(f"Parameter Count: {state.param_count()}", flush=True)

    # Telemetry first: the checkpoint manager's ckpt_fallback events and
    # the loader's sample_quarantine events (docs/ROBUSTNESS.md) must
    # land in the same JSONL stream as the per-step records — resume
    # fallback happens BEFORE the first step is ever timed.
    telem = TrainTelemetry(telemetry_dir, batch_size=cfg.batch_size,
                           num_devices=max(jax.device_count(), 1),
                           image_size=cfg.image_size)
    if loader is not None and telem.enabled:
        loader.sink = telem.sink
        loader.registry = telem.registry

    ckpt_dir = os.path.join(cfg.ckpt_dir, cfg.name)
    mgr = CheckpointManager(
        ckpt_dir, sink=telem.sink if telem.enabled else None,
        commit_window=max(int(getattr(cfg, "ckpt_commit_window", 2)), 1))
    # Elastic resume: restore onto THIS run's mesh whatever topology the
    # checkpoint was saved under (previous pod slice, different device
    # count — docs/ROBUSTNESS.md "Elastic resume").
    resumed = mgr.restore_latest(state, mesh=mesh)
    if resumed is not None:
        state = resumed
        saved_on = mgr.saved_topology(int(state.step)) or {}
        topo = saved_on.get("mesh", saved_on.get("device_count"))
        print(f"resumed from step {int(state.step)}"
              + (f" (saved on {topo})" if topo else ""), flush=True)
    # Fresh, warm-started or resumed: the state enters step 0 placed and
    # typed as the step gives it back (replicated over the mesh), so every
    # call shares one jit cache key.  An unplaced state is a second key:
    # the step is traced, lowered and compiled (or loaded) twice.  The
    # unplaced trees are dropped with their names.
    state = place_replicated(state, mesh)
    restore_params = resumed = None

    step_fn = make_train_step(model, tx, cfg, mesh,
                              shard_spatial=shard_spatial)
    key = jax.random.PRNGKey(cfg.seed)

    step = int(state.step)
    if loader is not None:
        batches = loader.batches_from_step(step)
    prep_fn = None
    if cfg.add_noise:
        # Noise RNG keyed on the resume step so a resumed run doesn't
        # replay the same noise sequence from the beginning.  Applied by
        # the pipeline's single producer in stream order, so step k's
        # noise is identical whether device_prefetch is 0 or N (the
        # producer is the only consumer of this generator).
        noise_rng = np.random.default_rng(
            np.random.SeedSequence([cfg.seed + 1, step]))
        prep_fn = functools.partial(add_image_noise, noise_rng)
    # Distributed step tracing (docs/OBSERVABILITY.md): each sampled
    # step opens a `train_step` trace with queue_wait / prep / h2d /
    # step_dispatch / ckpt_commit child spans.  rate 0 leaves ``tracer``
    # None — the loop then does nothing per step but one identity check.
    tracer = None
    trace_rate = float(getattr(cfg, "trace_sample_rate", 0.0) or 0.0)
    if trace_rate > 0:
        tracer = trace.configure(
            sample_rate=trace_rate, seed=cfg.seed,
            sink=telem.sink if telem.enabled else None)
    # On-demand XProf window (--profile-steps A:B): capture into
    # <telemetry_dir>/xprof/ in ABSOLUTE step numbers and stamp the
    # artifact dir onto concurrently recorded trace spans.
    profile_steps = getattr(cfg, "profile_steps", None)
    if profile_steps:
        a, b = int(profile_steps[0]), int(profile_steps[1])
        pdir = profile_dir or (os.path.join(telem.directory, "xprof")
                               if telem.enabled else "xprof")
        profiler = StepProfiler(pdir, start_step=a,
                                num_steps=max(b - a, 1), absolute=True)
    else:
        profiler = StepProfiler(profile_dir)
    telem.start(start_step=step, num_steps=cfg.num_steps)
    # Training health (docs/OBSERVABILITY.md "Training health"): the
    # monitor is fed by the Logger's once-per-interval flush — the only
    # device->host metric transfer — and writes forensic bundles for
    # guard-flagged steps.  Telemetry off = no monitor (the in-graph
    # guard in make_train_step still protects the params regardless).
    health = None
    if telem.enabled:
        initial_nonfinite = 0
        if getattr(state, "nonfinite_steps", None) is not None:
            # One scalar pull at startup (resume carries the lifetime
            # counter in the checkpoint), never per step.
            initial_nonfinite = int(jax.device_get(state.nonfinite_steps))
        health = HealthMonitor(
            telem,
            forensics_dir=os.path.join(telem.directory, "forensics"),
            seed=cfg.seed, keep=max(int(getattr(cfg, "forensic_keep", 8)),
                                    0),
            initial_nonfinite=initial_nonfinite,
            run_meta={"model_cfg": dataclasses.asdict(model_cfg),
                      "train_cfg": dataclasses.asdict(cfg)})
    logger = Logger(cfg.log_freq, lr_fn=schedule_of(cfg.lr, cfg.num_steps),
                    tensorboard_dir=tensorboard_dir,
                    on_flush=health.observe_flush if health else None)
    # The overlapped input pipeline: decode (loader threads) -> host prep
    # (noise) -> async device_put, double/triple-buffered ahead of the
    # consuming step.  depth 0 = the old serial path, same batch stream.
    pipeline = DevicePipeline(
        batches, put_fn=make_batch_sharder(mesh, spatial=shard_spatial),
        prep_fn=prep_fn,
        depth=max(int(getattr(cfg, "device_prefetch", 0)), 0),
        keep_host=health is not None
        and getattr(cfg, "forensic_keep", 8) > 0,
        # Single-host preemption can interrupt an input-stalled consumer
        # (the pipeline polls the flag while its buffer is empty);
        # multi-host exits only through the agreed-step sync below.
        interrupt=_PREEMPT.is_set if jax.process_count() == 1 else None,
        registry=telem.registry)
    # Stall watchdog: per-iteration heartbeats; no heartbeat within
    # cfg.watchdog_timeout -> all-thread stack dump + `stall` event
    # (+ optional hard exit).  Paused around save/validate, whose
    # minutes-long runtime is legitimate.
    watchdog = None
    wd_timeout = float(getattr(cfg, "watchdog_timeout", 0.0) or 0.0)
    if wd_timeout > 0:
        watchdog = StallWatchdog(
            wd_timeout, sink=telem.sink,
            dump_path=stack_dump_path(telem.directory),
            hard_exit=bool(getattr(cfg, "watchdog_exit", False)),
            recent_records=telem.recent_records)
        watchdog.start()
    t0, steps_t0 = time.time(), step
    # What arch 'gma' carries through a step beside the pyramid: one
    # attention matrix a pair (0 for the other architectures).  Quoted by
    # every step's stage record and summed in the registry.
    attn_bytes = attention_bytes(model_cfg, cfg.batch_size,
                                 cfg.image_size[0] // 8,
                                 cfg.image_size[1] // 8)
    # ... and which window attention the step was traced with (arch
    # 'gmflow': 'mosaic' on a TPU, 'xla' off it; 'none' elsewhere), asked
    # as make_train_step's trace asks it.
    with data_parallel_kernels(mesh, rows_split=shard_spatial):
        attn_path = window_attention_at(model_cfg, cfg.image_size[0] // 8,
                                        cfg.image_size[1] // 8)
    attn_counter = telem.registry.counter(
        "raft_attention_bytes_total",
        "bytes of global-motion attention matrix built and held "
        "through the refinement loop (arch gma), summed over units")
    # What the architecture makes of a step: flow predictions a pair
    # (iters, or iters + 1 where the loop starts from a regressed flow)
    # and encoder calls that normalise with batch statistics (3 for arch
    # 'searaft', 1 for 'full' and 'gma', none where batch norm is frozen).
    n_pred = predictions(model_cfg, cfg.iters)
    bn_calls = 0 if cfg.freeze_bn else batch_norm_calls(model_cfg)
    bn_counter = telem.registry.counter(
        "raft_batch_norm_calls_total",
        "encoder calls that normalised with their own batch statistics, "
        "summed over train steps")
    first_dispatched = False
    run_step, compiled = step_fn, None
    # The step's builds, read from the compile ring once after the third
    # dispatch: they ride the `train` records from then on and the
    # `compile` event, with the first dispatch's step and seconds.
    first_step, since = step, _compile_seq()
    step_builds = first_compile = None
    compile_key = ("train_step", tuple(cfg.image_size), cfg.batch_size)
    in_flight = collections.deque()  # the losses of steps on the device
    try:
        while True:
            # queue_wait_s: time blocked on the input pipeline — the
            # input-bound detector (host perf_counter only; the step
            # loop stays async).  With device prefetch on this is pure
            # consumer-side queue wait (near 0 when the producer keeps
            # up); at depth 0 it degrades to the full serial
            # fetch+prep+H2D cost — the old data_wait_s.
            if watchdog is not None:
                watchdog.beat(step)
            # The stage clock (obs/stages.py) times every step, telemetry
            # or not: input_wait / dispatch / host.  The step's record is
            # the one set of stamps the trace spans, the train_step event
            # and the histograms read.
            unit = stages.begin("train")
            # One trace root per sampled step; None when tracing is off
            # (the rate=0 hot path costs only this identity check).
            st = (tracer.start_trace("train_step", step=step)
                  if tracer is not None else None)
            try:
                with stages.stage("train", "input_wait"):
                    sharded = next(pipeline)
            except StopIteration:
                break
            except PipelineInterrupted:
                # Preemption observed DURING the input wait (the old
                # caveat: the flag used to go unseen until a batch
                # arrived).  State is the last completed step —
                # consistent, same as the boundary exit below.
                raise SystemExit(143)
            if step >= cfg.num_steps:
                break
            if health is not None:
                # Reference append into the forensics ring (the host
                # copy the pipeline retained) — no transfers, no copies.
                health.note_batch(step, pipeline.last_host_batch)
            # `preempt` chaos fault (docs/ROBUSTNESS.md): drive the
            # cooperative kill-and-resume path deterministically in
            # tests without delivering real signals.  Single-host only
            # in effect — the flag it sets is gated below exactly like
            # the CLI's SIGTERM handler.
            if chaos.should_inject("preempt", step=step,
                                   point="train.preempt"):
                request_preemption()
            if (jax.process_count() == 1 and _PREEMPT.is_set()) or (
                    jax.process_count() > 1
                    and _reached_preemption_sync(step)):
                raise SystemExit(143)  # step boundary; state is consistent
            profiler.maybe_start(step)
            if watchdog is not None and not first_dispatched:
                # The first dispatch trace+compiles synchronously —
                # minutes, and legitimate; don't let it look like a
                # stall (resumed below).
                watchdog.pause()
            if not first_dispatched and (telem.hbm_enabled
                                         or telem.cost_enabled):
                # XLA memory + cost analysis want the compiled step, and
                # a jit call does not hand its executable out.  So
                # compile it ahead of time ONCE and run that executable
                # from here on: what is analysed is what runs, at no
                # second compile.  (Lowering the step a second time
                # after the jit call — what this block used to do —
                # does not even hit the persistent cache: a re-lowering
                # numbers its private functions differently, so the
                # cache key moves and a minutes-long step compiles
                # twice.)  A non-lowerable step_fn (stubbed in tests)
                # keeps the plain call and the unavailable record.
                try:
                    compiled = step_fn.lower(state, sharded,
                                             key).compile()
                except Exception:
                    compiled = None
                else:
                    run_step = compiled
            try:
                with stages.stage("train", "dispatch"), annotate_step(step):
                    while len(in_flight) >= _MAX_IN_FLIGHT:
                        # Backpressure, not a stall: a step still waits
                        # on the device behind the one this waits for.
                        old = in_flight.popleft()
                        jax.block_until_ready(old)  # raftlint: disable=JIT103
                    state, metrics = run_step(state, sharded, key)
                    in_flight.append(metrics.get("loss"))
            except BaseException as e:
                if st is not None:
                    trace.record_span(st, "step_dispatch",
                                      *unit.spans["dispatch"],
                                      status="error",
                                      error=type(e).__name__)
                    st.end(status="error", error=type(e).__name__)
                raise
            with stages.stage("train", "host"):
                profiler.maybe_stop(step, sync_on=metrics.get("loss"))
                step += 1
                logger.push(step - 1, metrics)
                if step - first_step == _BUILDS_SETTLED:
                    step_builds = _step_builds(since)
                    telem.record_compile(*first_compile, key=compile_key,
                                         step_builds=step_builds)
            rec = stages.end("train", registry=telem.registry,
                             step=step - 1, model=model_cfg.arch,
                             attn_bytes=attn_bytes,
                             window_attention=attn_path,
                             predictions=n_pred, bn_calls=bn_calls,
                             step_builds=step_builds)
            if attn_bytes:
                attn_counter.inc(attn_bytes, loop="train")
            if bn_calls:
                bn_counter.inc(bn_calls, loop="train")
            # step_time_s covers queue wait + dispatch.  Dispatch is
            # async, so once the pipeline fills this converges to the
            # device step time without ever forcing a transfer.
            step_time_s = rec["t_end"] - rec["t_start"]
            if not first_dispatched:
                first_dispatched = True
                # The first dispatch of this signature traces+compiles
                # synchronously — its wall time IS the compile figure.
                first_compile = (step - 1, step_time_s)
                if telem.hbm_enabled or telem.cost_enabled:
                    # From the executable compiled above (host-side
                    # metadata, runs once; RAFT_TELEMETRY_HBM=0 /
                    # RAFT_TELEMETRY_COST=0 skip each half).
                    if telem.hbm_enabled:
                        # tpu_custom_calls: how many Mosaic kernels the
                        # compiled step really holds — 0 means a Pallas
                        # impl was swapped for XLA (off-TPU fallback) or
                        # ran through the interpreter.
                        telem.record_hbm(
                            dict(hbm_usage(compiled),
                                 tpu_custom_calls=compiled.as_text()
                                 .count("tpu_custom_call"))
                            if compiled is not None
                            else {"peak_hbm": "unavailable"})
                    if telem.cost_enabled and compiled is not None:
                        telem.record_cost(step_cost(
                            compiled, cfg.batch_size,
                            telem.num_devices))
                if watchdog is not None:
                    watchdog.resume()  # compile window over
            feed = pipeline.last_unit
            telem.record_step(rec, feed)
            if st is not None:
                # The step's spans, from its record; the producer's
                # (stamped on its thread) from the delivered batch's.
                trace.record_span(st, "queue_wait",
                                  *rec["spans"]["input_wait"])
                for name in ("prep", "h2d"):
                    trace.record_span(st, name, *feed["spans"][name])
                trace.record_span(st, "step_dispatch",
                                  *rec["spans"]["dispatch"])
                # Flush point: sampled/kept traces emit now; the rest
                # park in the dropped ring for a late verdict (the
                # health monitor re-keeps non-finite steps at flush).
                st.end(step_time_s=round(step_time_s, 6))

            # Second preemption check before the (potentially minutes-
            # long) validate block, so a SIGTERM during the step exits
            # here instead of after full validation.  Single-host only:
            # the per-host flag has no cross-host agreement, so an
            # early exit here on one host would strand the others in the
            # collective save/validate block — multi-host preemption
            # exits solely through the agreed-step sync at the top of
            # the loop.  A SIGTERM while the consumer waits on the input
            # pipeline is observed within the pipeline's interrupt poll
            # (PipelineInterrupted above); only a depth-0 pipeline
            # blocked inside the source iterator itself (host IO)
            # remains uninterruptible until the batch arrives.
            if jax.process_count() == 1 and _PREEMPT.is_set():
                raise SystemExit(143)

            if step % cfg.val_freq == 0:
                if watchdog is not None:
                    watchdog.pause()  # save+validate is legitimately slow
                # Non-blocking: the committer thread owns the I/O; this
                # costs one on-device snapshot dispatch (bounded by the
                # manager's commit window — docs/ROBUSTNESS.md).  The
                # step's trace context rides along so the committer's
                # ckpt_commit span lands in the right tree (a late
                # child: the root already flushed).
                if st is not None:
                    with trace.use_context(st):
                        mgr.save_async(step, state, mesh=mesh)
                else:
                    mgr.save_async(step, state, mesh=mesh)
                if validators:
                    variables = {"params": state.params}
                    if state.batch_stats:
                        variables["batch_stats"] = state.batch_stats
                    results = {}
                    for name, fn in validators.items():
                        results.update(fn(variables))
                    logger.write_dict(step, results)
                dt = time.time() - t0
                ips = (step - steps_t0) * cfg.batch_size / max(dt, 1e-9)
                print(f"throughput: {ips:.2f} image-pairs/sec (host)",
                      flush=True)
                t0, steps_t0 = time.time(), step
                if watchdog is not None:
                    watchdog.resume()

        if mgr.last_requested_step() != int(state.step):
            mgr.save(int(state.step), state, force=True, mesh=mesh)
    except (KeyboardInterrupt, SystemExit):
        # Preemption: flush the last COMPLETED step so auto-resume
        # continues exactly where the pod died — optimizer/LR state and
        # the loader's mid-epoch shuffle position included.  The
        # reference loses all three (its every-5000-step weights-only
        # torch.save, train.py:185-187,141-142).  SIGTERM arrives via
        # the cooperative _PREEMPT flag (raised only at the step-
        # boundary check above), so ``state`` is a consistent snapshot;
        # an interactive Ctrl-C can still land mid-save, in which case
        # the force-save below may be skipped if orbax already
        # registered the step — acceptable for the interactive case.
        print(f"preempted at step {int(state.step)}; checkpointing",
              flush=True)
        try:
            # Drain in-flight background commits first so the check
            # below sees the true newest step (and a committer failure
            # is reported, not swallowed into the preemption exit).
            mgr.wait()
        except Exception as e:
            print(f"checkpoint flush failed during preemption: {e}",
                  flush=True)
        if mgr.latest_step() != int(state.step):
            mgr.save(int(state.step), state, force=True, mesh=mesh)
        raise
    finally:
        if watchdog is not None:
            watchdog.stop()  # first: teardown below can be slow
        if first_compile is not None and step_builds is None:
            # fewer than three steps ran: their builds, read at the end
            telem.record_compile(*first_compile, key=compile_key,
                                 step_builds=_step_builds(since))
        pipeline.close()
        mgr.wait()
        mgr.close()
        profiler.close()
        logger.close()
        telem.close()
    return state
