"""Async TPU inference serving engine: shape buckets + dynamic batching.

The offline entry points (``train.py`` / ``evaluate.py`` / ``demo.py``)
stream known datasets; a server sees *concurrent requests of unknown
resolution*.  Two classic levers make that fast on XLA backends, and both
live here:

1. **Shape-bucketed compile cache.**  Every request's ``(H, W)`` is
   rounded to a /8-aligned bucket (:func:`raft_tpu.ops.pad.bucket_hw` —
   the same policy the validators use, optionally snapped to a coarse
   configured ladder), and the engine keeps one AOT-compiled test-mode
   forward per ``(bucket_hw, batch_size)``.  Ahead-of-time
   ``jit.lower(...).compile()`` — not plain ``jax.jit`` call-site caching
   — so a compile is an *explicit, counted event*
   (:class:`raft_tpu.utils.profiling.CompileCounter`) and a shape that
   slipped past the bucketing would raise instead of silently
   recompiling per request.  Steady-state traffic never compiles.

2. **Dynamic micro-batching.**  Requests landing in the same bucket are
   coalesced by a per-bucket dispatcher into one device batch: the batch
   closes at ``max_batch`` items or ``max_wait_ms`` after its first
   item, whichever comes first (the latency/throughput trade-off knob —
   docs/SERVING.md).  Partial batches are padded (repeating the last
   item) up to the nearest compiled batch size, so batch shapes come
   from a small fixed set.

3. **Iteration-granular continuous batching** (``batching="slot"``).
   The unit of device work drops from one whole request to ONE GRU
   iteration over a persistent slot batch (``serve/slots.py``): the
   per-bucket dispatcher admits waiting requests into free slots
   (running the ``encode`` program for the new lanes), runs one
   ``iter_step`` over every active slot, and retires lanes whose
   iteration budget is spent or whose convergence predicate fired
   (max flow-update magnitude below ``early_exit_threshold``) — so a
   24-iteration straggler no longer pins lanes that finished, and easy
   inputs exit early (SEA-RAFT-style).  Whole-request mode stays the
   default (``batching="request"``) and is the parity oracle: BOTH
   modes drive the same two compiled ``encode``/``iter_step``
   executables — ``iter_step`` is a device loop that takes its step
   count at run time: request mode passes ``cfg.iters`` (two program
   calls a batch), slot mode ``1`` — so with early
   exit disabled their outputs are bit-identical by construction —
   XLA specializes fusion/reduction order per program, so this is the
   only robust way to pin parity (see models/raft.py).

Architecture (four kinds of thread, one device):

- caller threads: ``submit()`` — bucket lookup, backpressure check,
  handoff to the engine's event loop.  Returns a
  ``concurrent.futures.Future``.
- the engine's asyncio loop thread: per-bucket dispatcher tasks coalesce
  micro-batches.  Pure bookkeeping, never touches the device.
- one device-worker thread: in request mode the ISSUING side — it
  pads/stacks a batch, uploads it and issues its two program calls
  (nothing awaited), in the order the dispatchers cut them, so device
  work serializes instead of interleaving; in slot mode it runs whole
  awaited cycles.
- one completing thread (request mode only): waits for each issued
  batch in the order of issue, copies its flow back, unpads and answers.
  While it waits for batch n the issuing side uploads and issues batch
  n+1, which queues on the device behind n: at most
  ``_MAX_IN_FLIGHT`` (2) batches are issued and unanswered, and a batch
  with nothing behind it is answered as soon as the device is done.

Backpressure: a bounded in-flight count (``max_queue``).  ``submit()``
beyond it raises :class:`QueueFullError` (the HTTP layer maps it to 429)
— the queue can never grow without bound, and latency under overload
stays bounded instead of collapsing.

4. **Streaming sessions** (``stream_open`` / ``stream_submit`` /
   ``stream_close``; ``POST /v1/stream/{id}`` at the HTTP layer).  A
   session pins one lane of its bucket's slot pool across frames: frame
   N+1 uploads only the NEW image — the previous frame's flow is
   forward-warped on-device into the lane's ``coords1`` init and the
   previous frame's feature map / context are reused as the next pair's
   frame-1 features (consecutive-frame identity), so a warm frame runs
   the feature encoder once instead of twice and starts its GRU
   iterations near the answer.  A session's FIRST pair goes through the
   unmodified ``encode_admit`` program (bit-identical to the stateless
   slot path); idle sessions are evicted back to the free pool after
   ``stream_ttl_s``.  See docs/SERVING.md "Streaming sessions".

Scope: single-host, single-device per engine (multi-chip serving is one
engine process per chip behind an external balancer).  Stateless
``submit()`` requests stay independent frame pairs; cross-request warm
start exists ONLY inside an explicit streaming session, whose device
state dies with the engine (rolling weight updates and replica failover
restart streams cold — the router re-seeds them, docs/SERVING.md).
"""

from __future__ import annotations

import asyncio
import dataclasses
import inspect
import queue
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from raft_tpu import chaos
from raft_tpu.chaos import (InjectedDeviceError, InjectedReplicaKill,
                            ReplicaWedgedInterrupt, is_transient_error)
from raft_tpu.config import RAFTConfig
from raft_tpu.obs import EventSink, MetricRegistry
from raft_tpu.obs import cost as cost_mod
from raft_tpu.obs import stages, trace
from raft_tpu.ops.pad import InputPadder, bucket_hw
from raft_tpu.serve.stats import Counters, LatencyRecorder
from raft_tpu.utils.profiling import CompileCounter


class QueueFullError(RuntimeError):
    """Backpressure rejection: ``max_queue`` requests already in flight.

    The 429-style signal — the caller should shed load or retry with
    backoff; the engine never queues without bound.  Carries the
    structured overload detail the HTTP layer returns (429 JSON body +
    ``Retry-After`` header) so load generators and the fleet router can
    back off *proportionally* instead of hammering: ``queue_depth`` is
    the in-flight count at rejection time, ``retry_after_s`` the
    suggested wait."""

    def __init__(self, msg: str, *, queue_depth: int = 0,
                 retry_after_s: float = 1.0):
        super().__init__(msg)
        self.queue_depth = int(queue_depth)
        self.retry_after_s = float(retry_after_s)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine knobs (model hyperparameters stay in ``RAFTConfig``).

    ``max_wait_ms`` trades tail latency for batch fill: 0 ships every
    request alone (lowest latency, worst throughput); large values fill
    batches under light traffic but add up to that wait to p99.
    ``buckets`` is an optional explicit ``(H, W)`` ladder — with unknown
    traffic a coarse ladder coalesces nearby resolutions into one
    program instead of fragmenting per shape.  ``batch_sizes`` is the
    set of compiled batch shapes (default: powers of two up to
    ``max_batch``); micro-batches round up to the nearest one.
    ``stall_timeout_s``: readiness threshold — with requests pending
    and no device batch completed for this long, ``health()`` reports
    not-ready (``GET /v1/healthz`` -> 503) so a balancer drains a
    wedged replica; must exceed ``max_wait_ms`` + the worst cold
    compile (or warm up first); 0 disables the check.
    ``device_retries``: device-call re-dispatches for errors classified
    transient (:func:`raft_tpu.chaos.is_transient_error`) before the
    whole batch fails — one flaky dispatch no longer 500s every
    co-batched request; deterministic errors always fail fast
    (docs/ROBUSTNESS.md).  Retry backoff is EXPONENTIAL with jitter:
    attempt k sleeps ``min(retry_backoff_s * 2^(k-1),
    retry_backoff_max_s)`` scaled by a ±``retry_jitter`` fraction
    (seeded per engine, so chaos drills replay), and the whole retry
    ladder is capped by ``retry_deadline_s`` measured from the first
    failure — a batch never spends longer retrying than a client would
    plausibly wait.  The actual sleep lands in each ``serve_retry``
    event (``backoff_s``) so drills can assert the schedule.
    ``retry_after_s``: the backoff hint a 429 rejection carries.
    ``aot_dir``: warm-start artifact directory
    (``raft_tpu/serve/aot.py``) — compatible ``(bucket, batch)``
    executables are imported at construction so the first request
    compiles NOTHING; an incompatible/corrupt artifact is skipped
    (``aot_import_error`` event) and the engine compiles lazily.
    ``chaos_slow_s``/``chaos_hang_max_s`` size the injected
    ``replica_slow`` straggler sleep and the ``replica_hang`` wedge cap
    (drills only; no effect without an installed fault plan).
    ``batching``: ``"request"`` (whole-request micro-batches, the
    default) or ``"slot"`` (iteration-granular continuous batching over
    a persistent ``slots``-lane batch per bucket — docs/SERVING.md
    "Continuous batching").  ``early_exit_threshold``: per-sample
    convergence cut — a slot retires once its max flow-update magnitude
    (flow units at 1/8 resolution) drops below this; ``0`` disables
    (full budget always runs).  Slot mode honors a per-request
    ``iters`` budget (capped at ``cfg.iters``); request mode runs every
    lane to ``cfg.iters`` (lockstep).
    ``quality_sample_rate``: fraction of retiring slot-mode requests
    scored with the label-free photometric quality proxy
    (``raft_tpu/obs/quality.py``; docs/OBSERVABILITY.md "Flow
    quality") — scored requests emit ``quality_score`` events and feed
    the ``raft_quality_*`` histograms plus the drift detector; the
    free convergence residual is recorded for EVERY retirement while
    sampling is on.  ``0`` (the default) disables quality scoring
    entirely: no monitor is built, no extra device fetch or program
    exists on the hot path (the zero-overhead contract,
    tests/test_quality.py).  ``quality_cycle`` additionally runs a
    sampled forward-backward cycle-consistency pass (one extra
    inference on the swapped frames per scored request).  The
    ``quality_drift_*`` knobs size the PSI drift detector (reference
    sample count, rolling window, firing threshold).
    Streaming-session knobs (slot mode only — docs/SERVING.md
    "Streaming sessions"): ``stream_ttl_s`` evicts a session whose
    client went quiet back to the free pool (its pinned lane is what
    the TTL protects); ``stream_warm_iters`` is the per-frame
    iteration budget for WARM-started frames (``None`` keeps the
    session budget — warm frames then rely on ``early_exit_threshold``
    to retire early; set it below ``iters`` to cap warm frames
    outright, the streaming policy RAFT's warm-start convergence
    buys); ``max_sessions`` bounds the open-session registry (opens
    beyond it are rejected 429-style).
    SLO / incident knobs (docs/OBSERVABILITY.md "Incidents & SLOs"):
    ``slo_availability_target`` (0 disables) is the non-error request
    fraction objective; ``slo_latency_target_ms`` (0 disables) tracks
    "99% of requests under this many ms"; ``slo_quality_bound``
    (0 disables; needs ``quality_sample_rate`` > 0) marks a sampled
    retirement bad when its photometric proxy exceeds the bound;
    ``slo_mfu_floor`` (0 disables) marks an iteration bad below the
    floor — constructed only when ``PEAK_SPECS`` knows the device
    peak.  ``slo_window_s`` rescales the Google-SRE burn-rate
    policy's 1h long window (obs/slo.py).  ``incidents`` builds an
    :class:`~raft_tpu.obs.incident.IncidentManager` on this engine's
    sink (leave False under a :class:`~raft_tpu.serve.fleet
    .ReplicaFleet`, which owns ONE manager for the shared stream);
    the ``incident_*`` knobs size its correlation window, quiet-close
    threshold, and post-close cooldown.  All of it is host-side deque
    arithmetic — zero device syncs, CompileCounter-pinned."""

    iters: int = 32
    max_batch: int = 8
    max_wait_ms: float = 5.0
    max_queue: int = 256
    bucket_multiple: int = 8
    buckets: Optional[Tuple[Tuple[int, int], ...]] = None
    batch_sizes: Optional[Tuple[int, ...]] = None
    pad_mode: str = "sintel"
    latency_window: int = 4096
    stall_timeout_s: float = 120.0
    device_retries: int = 1
    retry_backoff_s: float = 0.05
    retry_backoff_max_s: float = 2.0
    retry_jitter: float = 0.25
    retry_deadline_s: float = 10.0
    retry_after_s: float = 1.0
    aot_dir: Optional[str] = None
    chaos_slow_s: float = 0.5
    chaos_hang_max_s: float = 30.0
    batching: str = "request"
    slots: int = 8
    early_exit_threshold: float = 0.0
    quality_sample_rate: float = 0.0
    quality_cycle: bool = False
    quality_drift_reference: int = 256
    quality_drift_window: int = 64
    quality_drift_threshold: float = 0.5
    stream_ttl_s: float = 60.0
    stream_warm_iters: Optional[int] = None
    max_sessions: int = 64
    slo_availability_target: float = 0.0
    slo_latency_target_ms: float = 0.0
    slo_quality_bound: float = 0.0
    slo_mfu_floor: float = 0.0
    slo_window_s: float = 3600.0
    incidents: bool = False
    incident_window_s: float = 10.0
    incident_quiet_s: float = 30.0
    incident_cooldown_s: float = 60.0

    def __post_init__(self):
        if self.stream_ttl_s <= 0:
            raise ValueError("stream_ttl_s must be > 0")
        if self.stream_warm_iters is not None \
                and self.stream_warm_iters < 1:
            raise ValueError("stream_warm_iters must be >= 1 (None "
                             "keeps the session budget)")
        if self.max_sessions < 1:
            raise ValueError("max_sessions must be >= 1")
        if self.max_batch < 1 or self.max_queue < 1:
            raise ValueError("max_batch and max_queue must be >= 1")
        if self.batching not in ("request", "slot"):
            raise ValueError(f"batching must be 'request' or 'slot', "
                             f"got {self.batching!r}")
        if self.slots < 1:
            raise ValueError("slots must be >= 1")
        if self.early_exit_threshold < 0:
            raise ValueError("early_exit_threshold must be >= 0 "
                             "(0 disables early exit)")
        if not 0.0 <= self.quality_sample_rate <= 1.0:
            raise ValueError(
                f"quality_sample_rate must be in [0, 1] (0 disables "
                f"quality scoring), got {self.quality_sample_rate}")
        if (self.quality_drift_reference < 4
                or self.quality_drift_window < 2
                or self.quality_drift_threshold <= 0):
            raise ValueError(
                "need quality_drift_reference >= 4, "
                "quality_drift_window >= 2 and "
                "quality_drift_threshold > 0")
        if self.max_wait_ms < 0:
            raise ValueError("max_wait_ms must be >= 0")
        if self.stall_timeout_s < 0:
            raise ValueError("stall_timeout_s must be >= 0")
        if self.device_retries < 0 or self.retry_backoff_s < 0:
            raise ValueError(
                "device_retries and retry_backoff_s must be >= 0")
        if (self.retry_backoff_max_s < self.retry_backoff_s
                or self.retry_deadline_s <= 0
                or not 0 <= self.retry_jitter < 1):
            raise ValueError(
                "need retry_backoff_max_s >= retry_backoff_s, "
                "retry_deadline_s > 0 and 0 <= retry_jitter < 1")
        if not 0.0 <= self.slo_availability_target < 1.0:
            raise ValueError(
                "slo_availability_target must be in [0, 1) — 0 "
                f"disables, 1.0 leaves no error budget; got "
                f"{self.slo_availability_target}")
        if (self.slo_latency_target_ms < 0 or self.slo_quality_bound < 0
                or self.slo_window_s <= 0):
            raise ValueError(
                "need slo_latency_target_ms >= 0, slo_quality_bound "
                ">= 0 and slo_window_s > 0")
        if not 0.0 <= self.slo_mfu_floor < 1.0:
            raise ValueError("slo_mfu_floor must be in [0, 1) "
                             "(0 disables)")
        if (self.incident_window_s <= 0 or self.incident_quiet_s <= 0
                or self.incident_cooldown_s < 0):
            raise ValueError(
                "need incident_window_s > 0, incident_quiet_s > 0 and "
                "incident_cooldown_s >= 0")
        m = self.bucket_multiple
        for hw in self.buckets or ():
            if hw[0] % m or hw[1] % m:
                raise ValueError(
                    f"bucket {hw} not /{m}-aligned (the model "
                    f"takes multiples of {m})")

    def resolved_batch_sizes(self) -> Tuple[int, ...]:
        if self.batch_sizes:
            sizes = tuple(sorted({int(b) for b in self.batch_sizes}))
            if sizes[0] < 1:
                raise ValueError(f"batch_sizes must be >= 1: {sizes}")
            return sizes
        sizes, b = set(), 1
        while b < self.max_batch:
            sizes.add(b)
            b *= 2
        sizes.add(self.max_batch)
        return tuple(sorted(sizes))


class _Request:
    __slots__ = ("image1", "image2", "bucket", "padder", "future",
                 "t_submit", "trace", "iters", "session", "warm")

    def __init__(self, image1, image2, bucket, padder, iters=None,
                 session=None, warm=False):
        self.image1 = image1
        self.image2 = image2
        self.bucket = bucket
        self.padder = padder
        # Per-request iteration budget (slot mode honors it, capped at
        # cfg.iters; request mode runs the full cfg.iters in lockstep).
        self.iters = iters
        # Streaming-session frame: ``session`` pins the request to the
        # session's lane, ``warm`` selects the warm-encode admit (carry
        # + forward-warped flow init) over the cold one.  Stateless
        # requests carry (None, False) and behave exactly as before.
        self.session = session
        self.warm = warm
        self.future: Future = Future()
        self.t_submit = time.perf_counter()
        # Trace context captured on the SUBMITTING thread (the router's
        # attempt span, or whatever the caller had open) and carried
        # across the dispatcher to the device worker, which records the
        # per-request queue/pad/device child spans under it.  None when
        # tracing is off or the request is untraced — the device worker
        # then skips span recording entirely.
        self.trace = trace.current()


#: Request-mode batches issued and not yet answered: one running on the
#: device and one queued behind it.  A constant, not an option: in a
#: device-bound cycle a third buys nothing, holds another request's
#: state on the device and delays the discovery of an error.
_MAX_IN_FLIGHT = 2


class _Issued:
    """One request-mode batch on its way from the issuing side
    (``_run_batch``) to the completing side (``_complete``): what was
    uploaded and launched, and the open stage unit that travels with
    it."""

    __slots__ = ("exe", "a1", "a2", "flow", "issued", "error", "calls",
                 "bucket", "reqs", "lanes", "seq", "t_in", "t_handed",
                 "ahead", "unit")

    def __init__(self, exe=None, a1=None, a2=None):
        self.exe, self.a1, self.a2 = exe, a1, a2
        # ``flow`` is the launched batch's device result.  ``issued``
        # says the issuing side has run h2d + launch, so the first
        # attempt of the retry thunk only drains (or re-raises
        # ``error``, what the issue raised, for the ladder to judge).
        self.flow = None
        self.issued = False
        self.error: Optional[BaseException] = None
        self.calls = 0
        # 1 when another batch was issued and unanswered at the issue
        self.ahead = 0


class _StreamSession:
    """One streaming session's host-side record.  Device state (coords,
    carry) lives in the pinned lane; this object holds the bookkeeping
    the client API and the TTL sweep need.  Field ownership: created by
    ``stream_open`` (caller thread); ``lane`` is written only by the
    device worker at first-pair admission; ``inflight``/``t_last`` are
    written by ``stream_submit`` under the engine's sessions lock and
    cleared by the future's done callback; ``carry_ok`` flips on the
    device worker (stash/warm-encode success or failure)."""

    __slots__ = ("sid", "bucket", "padder", "shape", "iters", "ttl_s",
                 "lane", "frames", "pairs", "warm_pairs", "last_image",
                 "inflight", "t_last", "t_open", "carry_ok", "closed")

    def __init__(self, sid, bucket, padder, shape, iters, ttl_s,
                 first_image):
        self.sid = sid
        self.bucket = bucket
        self.padder = padder
        self.shape = shape
        self.iters = iters
        self.ttl_s = ttl_s
        self.lane: Optional[int] = None
        self.frames = 1          # the opening frame
        self.pairs = 0           # pairs retired successfully
        self.warm_pairs = 0
        self.last_image = first_image
        self.inflight: Optional[Future] = None
        self.t_last = time.time()
        self.t_open = self.t_last
        self.carry_ok = False    # device carry (fmap/ctx) is valid
        self.closed = False


#: The step count slot mode passes the iteration program: one host look
#: at ``active`` a step.
_ONE_STEP = np.int32(1)


class _Programs:
    """One ``(bucket, lanes)``'s compiled ``encode``/``iter_step`` pair
    plus cached call constants: the all-zeros device-resident state the
    lockstep (request-mode) pipeline restarts from, the all-lanes admit
    mask, the full-budget vector, the disabled threshold and the step
    count request mode passes ``it`` (``steps_full``: the whole budget
    in one call; slot mode passes ``_ONE_STEP``).  The compiled programs
    are pure — ``state0`` is an input, never mutated — so one
    ``_Programs`` serves every batch of its shape."""

    __slots__ = ("enc", "it", "template", "state0", "mask_all",
                 "budget_full", "thr_off", "steps_full", "bucket",
                 "lanes", "wenc", "stash", "carry0")

    def __init__(self, enc, it, template, bucket, lanes, full_iters):
        self.enc = enc
        self.it = it
        self.template = template
        self.state0 = jax.device_put(template)
        self.mask_all = np.ones((lanes,), bool)
        self.budget_full = np.full((lanes,), full_iters, np.int32)
        self.thr_off = np.float32(0.0)
        self.steps_full = np.int32(full_iters)
        self.bucket = bucket
        self.lanes = lanes
        # Streaming programs (warm encode / carry stash) + the zero
        # carry: compiled lazily by _get_stream_programs on the first
        # streamed pair — non-streaming engines never build them.
        self.wenc = None
        self.stash = None
        self.carry0 = None


class _SlotPool:
    """Per-bucket slot bookkeeping for continuous batching: the
    device-resident state pytree plus host mirrors of the lane
    assignments.  Touched only by the bucket's dispatcher coroutine and
    the device-worker call it awaits, so it needs no locking."""

    __slots__ = ("progs", "state", "reqs", "budgets", "active_np",
                 "t_admit", "carry", "pins")

    def __init__(self, slots: int):
        self.progs: Optional[_Programs] = None
        self.state = None
        self.reqs: List[Optional[_Request]] = [None] * slots
        self.budgets = np.zeros((slots,), np.int32)
        self.active_np = np.zeros((slots,), bool)
        self.t_admit = [0.0] * slots
        # Streaming: device-resident carry pytree (previous frame's
        # fmap/ctx per lane) and the lane -> session pin map.  A pinned
        # lane is excluded from regular admission even while idle — its
        # coords/carry are the session's warm-start state.
        self.carry = None
        self.pins: Dict[int, _StreamSession] = {}

    def live(self) -> List[_Request]:
        return [r for r in self.reqs if r is not None]

    def reset(self) -> None:
        """Zero the device state after a failed cycle.  Pinned sessions
        stay pinned but their warm-start state is gone — the caller
        marks them cold (``carry_ok = False``) so their next frame
        re-seeds through the cold path."""
        slots = len(self.reqs)
        self.reqs = [None] * slots
        self.budgets = np.zeros((slots,), np.int32)
        self.active_np = np.zeros((slots,), bool)
        if self.progs is not None:
            self.state = self.progs.state0
            self.carry = self.progs.carry0
        for s in self.pins.values():
            s.carry_ok = False


class InferenceEngine:
    """See module docstring.  Lifecycle::

        engine = InferenceEngine(variables, model_cfg, ServeConfig(...))
        engine.start()                      # or: with engine: ...
        engine.warmup([(436, 1024)])        # optional pre-compile
        flow = engine.infer(image1, image2)  # or .submit() -> Future
        print(engine.stats())
        engine.stop()
    """

    def __init__(self, variables, model_cfg: RAFTConfig,
                 cfg: ServeConfig = ServeConfig(), *,
                 registry: Optional[MetricRegistry] = None,
                 sink: Optional[EventSink] = None):
        # Deferred import: evaluate.py pulls the dataset stack, and the
        # dependency is one function (the shared inference overrides).
        from raft_tpu.evaluate import make_inference_model
        from raft_tpu.serve import slots as slots_mod

        model = make_inference_model(model_cfg)
        # The serve hot path is the encode/iter_step program pair
        # (serve/slots.py) for BOTH batching modes — request mode drives
        # them in lockstep so slot mode is bit-identical to it by
        # construction (the parity pin, tests/test_serve_slots.py).  A
        # matching model (``RAFTConfig.global_matching``: archs 'gmflow'
        # and 'unimatch', whose refinements run inside it) is ONE program
        # a request, ``flow`` (_get_executable); the pair
        # below is never lowered for it, and what needs the loop's state
        # is refused by name here.
        self._model_cfg = model.config
        from raft_tpu.models.raft import predictions, refuse_loop_state

        if cfg.batching == "slot":
            refuse_loop_state(self._model_cfg, "batching='slot'")
        if cfg.early_exit_threshold > 0:
            refuse_loop_state(self._model_cfg, "early exit")
        # The bucket policy rounds to the model's pad multiple (8, or 16
        # where the 1/8 map is split into 2x2 windows): the configured
        # multiple is a floor, never a way under the model's.
        m = int(np.lcm(cfg.bucket_multiple, self._model_cfg.pad_multiple))
        if m != cfg.bucket_multiple:
            cfg = dataclasses.replace(cfg, bucket_multiple=m)
        self.cfg = cfg
        # flow predictions a request's programs make (iters, or iters + 1
        # where ``enc`` regresses a first flow; 1 where there is no
        # loop): quoted by the compile ring's ``program`` records
        self._predictions = (1 if self._model_cfg.global_matching
                             else predictions(self._model_cfg, cfg.iters))
        self._slots_mod = slots_mod
        self._flow_jit = jax.jit(slots_mod.make_flow_fn(self._model_cfg,
                                                        cfg.iters))
        self._encode_jit = jax.jit(slots_mod.make_encode_fn(
            self._model_cfg))
        self._iter_jit = jax.jit(slots_mod.make_iter_fn(self._model_cfg))
        # Streaming-session programs (warm encode + carry stash): jit
        # wrappers are cheap to build; nothing traces or compiles until
        # the first streamed pair (_get_stream_programs).
        self._warm_jit = jax.jit(slots_mod.make_warm_encode_fn(
            self._model_cfg))
        self._stash_jit = jax.jit(slots_mod.make_stash_fn(
            self._model_cfg))
        # Keep params resident on device: the executable is called with
        # this exact pytree every batch, so requests never re-upload it.
        self._variables = jax.device_put(variables)
        self._batch_sizes = cfg.resolved_batch_sizes()
        self._max_group = min(cfg.max_batch, self._batch_sizes[-1])

        # Compiled-program cache: keys are (bucket_hw, lanes, program)
        # with program in {"enc", "iter"}; _programs wraps each
        # (bucket, lanes) pair with its cached zero state / lockstep
        # constants.
        self._executables: Dict[tuple, object] = {}
        self._programs: Dict[tuple, _Programs] = {}
        # "<H>x<W>/b<lanes>" -> "mosaic" | "xla": the correlation lookup
        # found in each iter executable taken into use (stats()["lookup"]).
        self._lookup: Dict[str, str] = {}
        self._compile_lock = threading.Lock()
        # Crash/stop state: ``crashed`` holds the reason string once the
        # device worker hit a fatal (replica-killing) fault — the fleet
        # supervisor polls it through health(); ``_stopped`` makes a
        # post-stop submit() fail with a CLEAR error instead of the
        # ambiguous not-started one.
        self.crashed: Optional[str] = None
        self._stopped = False
        # stop() must be idempotent under CONCURRENT callers: the fleet
        # supervisor restarting a crashed replica can race fleet.stop().
        self._stop_lock = threading.Lock()
        # Seeded per-engine jitter source for the retry backoff ladder
        # (chaos drills must replay the recorded backoff_s values).
        self._retry_rng = np.random.default_rng(0)
        # Retries the most recent _retry_call performed (read on the
        # thread that made it: the completing thread in request mode,
        # the device worker in slot mode) — stamped onto traced
        # requests' device spans and the tail-keep trigger for retried
        # batches.
        self._last_retries = 0
        # One registry per engine: every stats/exposition figure below
        # reads these same metric objects (see serve/stats.py), and
        # cli/serve.py renders them at GET /metrics.
        self.registry = registry or MetricRegistry()
        self._sink = sink if sink is not None else EventSink.from_env()
        self.compile_counter = CompileCounter(
            registry=self.registry, metric="raft_serve_compiles_total",
            labeler=lambda key: {"bucket": f"{key[0][0]}x{key[0][1]}",
                                 "batch": str(key[1]),
                                 "program": key[2]})

        self._latency = LatencyRecorder(cfg.latency_window,
                                        registry=self.registry)
        # Iterations each request actually consumed before retiring —
        # the early-exit win is this histogram's p50/p95 dropping below
        # cfg.iters (docs/OBSERVABILITY.md).
        self._iters_used = LatencyRecorder(
            cfg.latency_window, registry=self.registry,
            metric="raft_serve_iters_used",
            help="refinement iterations a request consumed before "
                 "retiring (early exit / per-request budget)",
            scale=1.0, suffix="")
        # Warm/cold split of the same observation: every retirement
        # lands in ONE of these two plus the combined histogram above,
        # so cold-vs-warm convergence is separable downstream
        # (telemetry_summary.py warm_iters_saved_frac).
        self._iters_used_warm = LatencyRecorder(
            cfg.latency_window, registry=self.registry,
            metric="raft_serve_iters_used_warm",
            help="iterations consumed by warm-started streamed frames",
            scale=1.0, suffix="")
        self._iters_used_cold = LatencyRecorder(
            cfg.latency_window, registry=self.registry,
            metric="raft_serve_iters_used_cold",
            help="iterations consumed by cold-started requests "
                 "(stateless pairs and session first pairs)",
            scale=1.0, suffix="")
        self._counters = Counters(registry=self.registry)
        # Streaming-session registry: sid -> _StreamSession.  Guarded
        # by _sessions_lock (caller threads open/submit/close; the
        # device worker pins lanes and the TTL sweep evicts).
        self._sessions: Dict[str, _StreamSession] = {}
        self._sessions_lock = threading.Lock()
        self._sessions_gauge = self.registry.gauge(
            "raft_serve_sessions_open",
            "streaming sessions currently open")
        self._stream_frames = self.registry.counter(
            "raft_serve_stream_frames_total",
            "streamed frames received (session mode)")
        self._stream_evicted = self.registry.counter(
            "raft_serve_stream_evictions_total",
            "streaming sessions evicted by the idle TTL")
        # Flow-quality scoring (obs/quality.py): built ONLY when the
        # sample rate is nonzero — at 0 the hot path carries no
        # monitor, no extra device fetch in _iter_slots, and no
        # quality program (the zero-overhead pin,
        # tests/test_quality.py).
        self._quality = None
        if cfg.quality_sample_rate > 0:
            from raft_tpu.obs import quality as quality_mod

            self._quality = quality_mod.QualityMonitor(
                registry=self.registry, sink=self._sink,
                sample_rate=cfg.quality_sample_rate,
                cycle=cfg.quality_cycle,
                drift_reference=cfg.quality_drift_reference,
                drift_window=cfg.quality_drift_window,
                drift_threshold=cfg.quality_drift_threshold,
                reservoir=cfg.latency_window)
        # Compile-time work accounting, keyed by the SAME (bucket,
        # lanes, prog) ledger keys as _executables: stamped once in
        # _get_programs, read back by spans/stats with zero device
        # work (obs/cost.py; docs/OBSERVABILITY.md "Cost model").
        self.cost_book = cost_mod.CostBook(registry=self.registry,
                                           sink=self._sink)
        # SLO tracking (obs/slo.py): specs are constructed ONLY for the
        # objectives the config enables — with every knob at 0 (the
        # default) there is no tracker, no per-request deque append,
        # and the hot path is byte-identical to before (the same
        # conditional-construction pattern as the quality monitor).
        self._slo = None
        # Last measured iteration MFU (None until a cost-stamped device
        # call completes on a known-peak device) — an autoscaler signal
        # (load_signals), not an SLO.
        self._last_mfu: Optional[float] = None
        slo_specs = []
        if (cfg.slo_availability_target > 0 or cfg.slo_latency_target_ms
                > 0 or cfg.slo_quality_bound > 0 or cfg.slo_mfu_floor
                > 0):
            from raft_tpu.obs import slo as slo_mod

            policy = slo_mod.scaled_policy(cfg.slo_window_s)
            if cfg.slo_availability_target > 0:
                slo_specs.append(slo_mod.SLOSpec(
                    "availability", cfg.slo_availability_target,
                    "non-error request fraction", windows=policy))
            if cfg.slo_latency_target_ms > 0:
                slo_specs.append(slo_mod.SLOSpec(
                    "latency", 0.99,
                    f"requests under {cfg.slo_latency_target_ms}ms",
                    windows=policy))
            if cfg.slo_quality_bound > 0 and cfg.quality_sample_rate > 0:
                slo_specs.append(slo_mod.SLOSpec(
                    "quality", 0.99,
                    f"sampled photometric proxy <= "
                    f"{cfg.slo_quality_bound}", windows=policy))
            if cfg.slo_mfu_floor > 0 and (
                    cost_mod.peak_spec().tflops or 0):
                # MFU floor only when PEAK_SPECS knows this device's
                # peak — on cpu/unknown kinds MFU is undefined and the
                # spec is silently skipped.
                slo_specs.append(slo_mod.SLOSpec(
                    "mfu", 0.9, f"iter MFU >= {cfg.slo_mfu_floor}",
                    windows=policy))
            if slo_specs:
                self._slo = slo_mod.SLOTracker(
                    slo_specs, registry=self.registry, sink=self._sink)
        # Incident correlation (obs/incident.py): one manager per
        # telemetry stream — a fleet builds its engines with
        # incidents=False and owns the manager itself (the engines
        # share its sink, and N observers would open N incidents for
        # one cascade).
        self._incidents = None
        if cfg.incidents:
            from raft_tpu.obs import incident as incident_mod

            self._incidents = incident_mod.IncidentManager(
                registry=self.registry,
                window_s=cfg.incident_window_s,
                quiet_close_s=cfg.incident_quiet_s,
                cooldown_s=cfg.incident_cooldown_s)
            self._incidents.attach(self._sink)
            self._incidents.recorder.add_provider("engine_stats",
                                                  self.stats)
            self._incidents.recorder.add_provider(
                "serve_config", lambda: dataclasses.asdict(self.cfg))
        self._pending_gauge = self.registry.gauge(
            "raft_serve_pending_requests", "requests in flight")
        self.registry.add_collect_hook(self._collect_pending)
        self.registry.add_collect_hook(stages.compile_seconds_hook())

        self._pending = 0
        self._pending_lock = threading.Lock()
        # Serve-side stall signal: perf_counter of the last COMPLETED
        # device batch (success or failure — either proves the device
        # worker is alive) and of start(); health() derives readiness.
        # _pending_since marks the 0 -> nonzero transition: a stall is
        # measured from when the waiting work ARRIVED, never from a
        # batch completed before an idle stretch (else a replica idle
        # longer than stall_timeout_s reads as stalled the instant a
        # request lands, and the fleet supervisor would restart it).
        self._last_batch_done: Optional[float] = None
        self._pending_since: Optional[float] = None
        self._t_started: Optional[float] = None
        self._stale_gauge = self.registry.gauge(
            "raft_serve_seconds_since_last_batch",
            "seconds since the last completed device batch (refreshed "
            "at scrape; absent before the first batch)")

        # Device-batch ordinal (1-based; device-worker thread only) —
        # the `device_err@batch=N` chaos trigger context.
        self._batch_seq = 0
        # Request mode, issuing side -> completing side: the batches
        # issued and not yet answered, in the order of issue (None is
        # the completing thread's signal to exit).  ``_in_flight``
        # counts them, under ``_flight``; the issuing side waits there
        # while it reads _MAX_IN_FLIGHT.
        self._issued: "queue.Queue[Optional[_Issued]]" = queue.Queue()
        self._flight = threading.Condition()
        self._in_flight = 0
        self._completer: Optional[threading.Thread] = None
        # When the issuing side last handed a batch over (its thread
        # only): where the next batch's ``wait`` starts.
        self._issuer_free: Optional[float] = None
        # Set once stop() no longer waits for the backlog: a batch cut
        # but not yet issued then fails with 'engine stopped'.
        self._abandon = False

        self._loop: Optional[asyncio.AbstractEventLoop] = None
        self._thread: Optional[threading.Thread] = None
        self._queues: Dict[tuple, asyncio.Queue] = {}
        self._dispatchers: Dict[tuple, asyncio.Task] = {}
        self._device_pool = ThreadPoolExecutor(
            max_workers=1, thread_name_prefix="raft-serve-device")
        self._accepting = False

        # AOT warm-start (raft_tpu/serve/aot.py): import serialized
        # executables so the first request compiles nothing.  The
        # fingerprint binds artifact to (model config, variables tree
        # shapes/dtypes, iters) — the executable takes variables as a
        # runtime argument, so the same artifact warm-starts restarted
        # replicas AND rolling-update engines carrying NEW weights.
        from raft_tpu.serve import aot as aot_mod

        self._aot_fingerprint = aot_mod.model_fingerprint(
            model_cfg, self._variables, cfg.iters)
        self.aot_info: dict = {"dir": cfg.aot_dir, "imported": 0,
                               "ok": None}
        if cfg.aot_dir:
            self._preload_aot(cfg.aot_dir)

    # ------------------------------------------------------------------
    # AOT warm-start artifacts
    # ------------------------------------------------------------------

    def _preload_aot(self, directory: str) -> None:
        from raft_tpu.serve import aot as aot_mod

        try:
            exes = aot_mod.import_executables(
                directory, fingerprint=self._aot_fingerprint,
                execution_devices=jax.tree_util.tree_leaves(
                    self._variables)[0].devices(),
                corr_impl=self._corr_impl_at, arch=self._model_cfg.arch,
                calls=self._program_calls())
        except aot_mod.AOTImportError as e:
            # A warm-start MISS, not a serve failure: log it and fall
            # back to lazy JIT compiles.
            self.aot_info.update(ok=False, error=str(e))
            self._sink.emit("aot_import_error", dir=directory,
                            error=str(e)[:300])
            return
        with self._compile_lock:
            self._executables.update(exes)
        self.aot_info.update(ok=True, imported=len(exes))
        self._sink.emit("aot_import", dir=directory, keys=len(exes))

    def _corr_impl_at(self, bucket: tuple) -> str:
        """The correlation implementation this engine's model picks
        for ``bucket`` when a program traces (``models.raft.
        corr_impl_at``): part of what an AOT artifact has to match,
        because it sets the layout of the slot state's pyramid."""
        from raft_tpu.models.raft import corr_impl_at

        if self._model_cfg.global_matching:
            return "none"       # no pyramid in a slot state
        return corr_impl_at(self._model_cfg, bucket[0] // 8,
                            bucket[1] // 8)

    def _program_calls(self) -> Dict[str, str]:
        """``{program: "fn(args)"}`` for every program this engine
        lowers, read off the functions themselves: what an AOT artifact
        records beside each key and an importer holds against its own,
        so a program whose argument list changed is refused by name."""
        progs = ((("enc", self._encode_jit), ("iter", self._iter_jit),
                  ("stash", self._stash_jit), ("wenc", self._warm_jit))
                 if not self._model_cfg.global_matching
                 else (("flow", self._flow_jit),))
        return {prog: f"{fn.__name__}{inspect.signature(fn)}"
                for prog, fn in progs}

    def export_aot(self, directory: str) -> dict:
        """Serialize every compiled ``(bucket, lanes, program)``
        executable into ``directory`` (atomic per file) so a fresh
        engine built with ``ServeConfig(aot_dir=directory)`` serves its
        first request with zero compiles.  Returns the manifest.
        Raises when the cache is empty (warm up first)."""
        from raft_tpu.serve import aot as aot_mod

        with self._compile_lock:
            exes = dict(self._executables)
        manifest = aot_mod.export_executables(
            exes, directory, fingerprint=self._aot_fingerprint,
            corr_impl=self._corr_impl_at, arch=self._model_cfg.arch,
            calls=self._program_calls())
        self._sink.emit("aot_export", dir=directory,
                        keys=len(manifest["keys"]))
        return manifest

    # ------------------------------------------------------------------
    # lifecycle
    # ------------------------------------------------------------------

    def start(self) -> "InferenceEngine":
        if self._thread is not None:
            raise RuntimeError("engine already started")
        if self._stopped:
            raise RuntimeError(
                "engine stopped — engines are single-use; build a new "
                "InferenceEngine (the fleet supervisor does this on "
                "restart)")
        self._loop = asyncio.new_event_loop()
        started = threading.Event()

        def run():
            asyncio.set_event_loop(self._loop)
            self._loop.call_soon(started.set)
            self._loop.run_forever()

        self._thread = threading.Thread(target=run, name="raft-serve-loop",
                                        daemon=True)
        self._thread.start()
        started.wait()
        if self.cfg.batching == "request":
            self._completer = threading.Thread(
                target=self._complete_loop, name="raft-serve-complete",
                daemon=True)
            self._completer.start()
        self._counters.mark_started()
        self._t_started = time.perf_counter()
        self._accepting = True
        return self

    def stop(self, drain: bool = True, timeout: float = 60.0) -> None:
        """Stop accepting, optionally drain in-flight work, shut down.

        Batches already issued to the device are answered either way.
        Requests that are still queued once the drain is over
        (``drain=False``, or the drain timed out) — with a dispatcher
        or cut into a batch that has not been issued — fail with
        ``RuntimeError('engine stopped')``."""
        with self._stop_lock:
            self._stop_locked(drain, timeout)

    def _stop_locked(self, drain: bool, timeout: float) -> None:
        if self._thread is None:
            self._stopped = True
            return
        self._accepting = False
        self._stopped = True
        if drain:
            deadline = time.perf_counter() + timeout
            while time.perf_counter() < deadline:
                with self._pending_lock:
                    if self._pending == 0:
                        break
                time.sleep(0.005)
        self._abandon = True

        async def _cancel_all():
            tasks = list(self._dispatchers.values())
            for t in tasks:
                t.cancel()
            await asyncio.gather(*tasks, return_exceptions=True)

        asyncio.run_coroutine_threadsafe(
            _cancel_all(), self._loop).result(timeout=10)
        self._device_pool.shutdown(wait=True)
        if self._completer is not None:
            # Everything the issuing side handed over is ahead of the
            # sentinel: the completing thread answers it, then exits.
            self._issued.put(None)
            self._completer.join(timeout=10)
            self._completer = None
        self._loop.call_soon_threadsafe(self._loop.stop)
        self._thread.join(timeout=10)
        self._loop.close()
        self._thread = None
        self._dispatchers.clear()
        self._queues.clear()
        if self._incidents is not None:
            # Finalize: an incident still open at shutdown closes with
            # its bundle written (close_reason="finalized").
            self._incidents.close()

    def __enter__(self) -> "InferenceEngine":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ------------------------------------------------------------------
    # client API (any thread)
    # ------------------------------------------------------------------

    def submit(self, image1, image2,
               iters: Optional[int] = None) -> Future:
        """Enqueue one frame pair; returns a Future resolving to the
        ``(H, W, 2)`` float32 flow at the ORIGINAL resolution.

        ``iters`` is an optional per-request refinement budget, capped
        at ``cfg.iters``; honored in slot mode (request mode runs every
        lane to ``cfg.iters`` in lockstep — the parity oracle ignores
        per-request budgets by design).

        Raises :class:`QueueFullError` immediately (never blocks) when
        ``max_queue`` requests are already in flight."""
        if iters is not None:
            self._refuse_loop_state("a per-request iters budget")
            if int(iters) < 1:
                raise ValueError(f"iters must be >= 1, got {iters}")
        if not self._accepting:
            # Fail FAST with the precise lifecycle state — a client
            # racing stop() must get an immediate, classifiable error
            # (the fleet router treats it as a failover signal), never
            # a hang on a dead loop.
            if self.crashed:
                raise RuntimeError(f"engine crashed: {self.crashed}")
            if self._stopped:
                raise RuntimeError(
                    "engine stopped — engines are single-use; build a "
                    "new InferenceEngine or route to a live replica")
            raise RuntimeError("engine not started (or stopping)")
        im1 = np.asarray(image1, dtype=np.float32)
        im2 = np.asarray(image2, dtype=np.float32)
        if im1.ndim != 3 or im1.shape[-1] != 3 or im1.shape != im2.shape:
            raise ValueError(
                f"expected two matching (H, W, 3) images, got "
                f"{im1.shape} and {im2.shape}")
        h, w = im1.shape[:2]
        bucket = bucket_hw(h, w, self.cfg.bucket_multiple, self.cfg.buckets)
        padder = InputPadder((h, w), mode=self.cfg.pad_mode, target=bucket)
        with self._pending_lock:
            if self._pending >= self.cfg.max_queue:
                self._counters.add_rejected()
                raise QueueFullError(
                    f"{self._pending} requests in flight >= max_queue="
                    f"{self.cfg.max_queue}; retry after "
                    f"{self.cfg.retry_after_s:g}s",
                    queue_depth=self._pending,
                    retry_after_s=self.cfg.retry_after_s)
            if self._pending == 0:
                self._pending_since = time.perf_counter()
            self._pending += 1
        req = _Request(im1, im2, bucket, padder,
                       None if iters is None else int(iters))
        try:
            self._loop.call_soon_threadsafe(self._enqueue, req)
        except RuntimeError:  # loop closed under our feet (stop race)
            with self._pending_lock:
                self._pending -= 1
            raise RuntimeError("engine stopped")
        return req.future

    def infer(self, image1, image2, timeout: Optional[float] = None,
              iters: Optional[int] = None) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`submit`."""
        return self.submit(image1, image2,
                           iters=iters).result(timeout=timeout)

    # ------------------------------------------------------------------
    # client API — streaming sessions (any thread)
    # ------------------------------------------------------------------

    def _refuse_loop_state(self, what: str) -> None:
        from raft_tpu.models.raft import refuse_loop_state

        refuse_loop_state(self._model_cfg, what)

    def _check_accepting(self) -> None:
        if self._accepting:
            return
        if self.crashed:
            raise RuntimeError(f"engine crashed: {self.crashed}")
        if self._stopped:
            raise RuntimeError(
                "engine stopped — engines are single-use; build a "
                "new InferenceEngine or route to a live replica")
        raise RuntimeError("engine not started (or stopping)")

    def stream_open(self, session_id: str, image, *,
                    iters: Optional[int] = None,
                    ttl_s: Optional[float] = None) -> dict:
        """Open a streaming session seeded with its first frame.

        No device work happens here — the frame is held host-side; the
        first :meth:`stream_submit` forms the session's first (cold)
        pair, which pins a lane in the bucket's slot pool.  ``iters``
        is the per-session refinement budget (capped at ``cfg.iters``);
        ``ttl_s`` overrides ``cfg.stream_ttl_s``.  Raises
        :class:`QueueFullError` when ``max_sessions`` sessions are
        already open (after sweeping expired ones)."""
        self._check_accepting()
        self._refuse_loop_state("a streaming session")
        if self.cfg.batching != "slot":
            raise ValueError(
                "streaming sessions require batching='slot' (a session "
                "is a pinned lane in the slot pool)")
        if self._model_cfg.context_reads_pair:
            # a session carries a frame's context to the next pair; this
            # model has none a frame (models/raft.py says it by name)
            from raft_tpu.models.raft import refuse_frame_cache

            refuse_frame_cache(self._model_cfg)
        if iters is not None and int(iters) < 1:
            raise ValueError(f"iters must be >= 1, got {iters}")
        if ttl_s is not None and float(ttl_s) <= 0:
            raise ValueError(f"ttl_s must be > 0, got {ttl_s}")
        im = np.asarray(image, dtype=np.float32)
        if im.ndim != 3 or im.shape[-1] != 3:
            raise ValueError(f"expected an (H, W, 3) image, got "
                             f"{im.shape}")
        h, w = im.shape[:2]
        bucket = bucket_hw(h, w, self.cfg.bucket_multiple,
                           self.cfg.buckets)
        padder = InputPadder((h, w), mode=self.cfg.pad_mode,
                             target=bucket)
        sess = _StreamSession(
            str(session_id), bucket, padder, (h, w),
            None if iters is None else int(iters),
            float(ttl_s) if ttl_s is not None else self.cfg.stream_ttl_s,
            im)
        with self._sessions_lock:
            evicted = self._sweep_unpinned_locked(time.time())
            if sess.sid in self._sessions:
                raise ValueError(f"session {sess.sid!r} already open")
            if len(self._sessions) >= self.cfg.max_sessions:
                self._counters.add_rejected()
                raise QueueFullError(
                    f"{len(self._sessions)} sessions open >= "
                    f"max_sessions={self.cfg.max_sessions}",
                    queue_depth=len(self._sessions),
                    retry_after_s=self.cfg.retry_after_s)
            self._sessions[sess.sid] = sess
            self._sessions_gauge.set(len(self._sessions))
        self._emit_evictions(evicted)
        self._sink.emit("stream_open", sid=sess.sid,
                        bucket=f"{bucket[0]}x{bucket[1]}",
                        iters=sess.iters, ttl_s=sess.ttl_s)
        return {"session": sess.sid, "frame": 0,
                "bucket": list(bucket)}

    def stream_submit(self, session_id: str, image) -> Future:
        """Stream the next frame into an open session; returns a Future
        resolving to the flow from the PREVIOUS frame to this one.

        Only this one new image crosses the wire/PCIe: the previous
        frame's features and flow are already device-resident in the
        session's lane (warm path) or held host-side for the first
        pair (cold path).  One frame may be in flight per session —
        streaming is ordered by construction."""
        self._check_accepting()
        im = np.asarray(image, dtype=np.float32)
        with self._sessions_lock:
            sess = self._sessions.get(str(session_id))
            if sess is None:
                raise ValueError(f"unknown session {session_id!r} "
                                 "(expired, closed, or never opened)")
            if im.shape != sess.last_image.shape:
                raise ValueError(
                    f"frame shape {im.shape} != session shape "
                    f"{sess.last_image.shape} (a session is fixed to "
                    "one resolution)")
            if sess.inflight is not None and not sess.inflight.done():
                raise ValueError(
                    f"session {sess.sid!r} already has a frame in "
                    "flight (stream frames sequentially)")
            warm = sess.carry_ok
            budget = sess.iters
            if warm and self.cfg.stream_warm_iters is not None:
                budget = self.cfg.stream_warm_iters
            with self._pending_lock:
                if self._pending >= self.cfg.max_queue:
                    self._counters.add_rejected()
                    raise QueueFullError(
                        f"{self._pending} requests in flight >= "
                        f"max_queue={self.cfg.max_queue}; retry after "
                        f"{self.cfg.retry_after_s:g}s",
                        queue_depth=self._pending,
                        retry_after_s=self.cfg.retry_after_s)
                if self._pending == 0:
                    self._pending_since = time.perf_counter()
                self._pending += 1
            req = _Request(sess.last_image, im, sess.bucket, sess.padder,
                           None if budget is None else int(budget),
                           session=sess, warm=warm)
            sess.last_image = im
            sess.frames += 1
            sess.t_last = time.time()
            sess.inflight = req.future
            # Stamped for the blocking facades (stream_ingest / the
            # HTTP route): which pair this Future resolves and whether
            # it took the warm path — decided here, race-free.
            req.future.stream_frame = sess.frames - 1
            req.future.stream_warm = warm
            self._stream_frames.inc()

        def _clear_inflight(_fut, sess=sess):
            with self._sessions_lock:
                if sess.inflight is _fut:
                    sess.inflight = None
                sess.t_last = time.time()

        req.future.add_done_callback(_clear_inflight)
        try:
            self._loop.call_soon_threadsafe(self._enqueue, req)
        except RuntimeError:  # loop closed under our feet (stop race)
            with self._pending_lock:
                self._pending -= 1
            raise RuntimeError("engine stopped")
        return req.future

    def stream_frame(self, session_id: str, image,
                     timeout: Optional[float] = None) -> np.ndarray:
        """Blocking convenience wrapper around :meth:`stream_submit`."""
        return self.stream_submit(session_id,
                                  image).result(timeout=timeout)

    def stream_ingest(self, session_id: str, image, *,
                      iters: Optional[int] = None,
                      ttl_s: Optional[float] = None,
                      timeout: Optional[float] = None) -> dict:
        """Open-on-first-use blocking facade (the ``POST
        /v1/stream/{id}`` semantics): an unknown session id opens the
        session with ``image`` as frame 0 (``flow=None``); a known one
        streams the frame and blocks for its flow.  Returns
        ``{"session", "frame", "warm", "flow"}``."""
        sid = str(session_id)
        with self._sessions_lock:
            known = sid in self._sessions
        if not known:
            ack = self.stream_open(sid, image, iters=iters,
                                   ttl_s=ttl_s)
            return {"session": sid, "frame": ack["frame"],
                    "warm": False, "flow": None}
        fut = self.stream_submit(sid, image)
        flow = fut.result(timeout=timeout)
        return {"session": sid, "frame": fut.stream_frame,
                "warm": fut.stream_warm, "flow": flow}

    def stream_close(self, session_id: str) -> dict:
        """Close a session and release its registry entry; the pinned
        lane returns to the free pool at the dispatcher's next sweep.
        Returns the session summary."""
        with self._sessions_lock:
            sess = self._sessions.get(str(session_id))
            if sess is None:
                raise ValueError(f"unknown session {session_id!r} "
                                 "(expired, closed, or never opened)")
            if sess.inflight is not None and not sess.inflight.done():
                raise ValueError(
                    f"session {sess.sid!r} has a frame in flight — "
                    "wait for it before closing")
            del self._sessions[sess.sid]
            sess.closed = True
            self._sessions_gauge.set(len(self._sessions))
        summary = {"session": sess.sid, "frames": sess.frames,
                   "pairs": sess.pairs, "warm_pairs": sess.warm_pairs}
        self._sink.emit("stream_close", sid=sess.sid,
                        frames=sess.frames, pairs=sess.pairs,
                        warm_pairs=sess.warm_pairs)
        return summary

    def _sweep_unpinned_locked(self, now: float) -> list:
        """Evict expired sessions that never pinned a lane (opened,
        then abandoned) — pinned ones are swept by their bucket's
        dispatcher, which owns the lane.  Caller holds
        ``_sessions_lock``; events are emitted by the caller OUTSIDE
        the lock (:meth:`_emit_evictions`)."""
        out = []
        for sid, s in list(self._sessions.items()):
            if s.lane is not None:
                continue
            if s.inflight is not None and not s.inflight.done():
                continue
            if now - s.t_last > s.ttl_s:
                del self._sessions[sid]
                s.closed = True
                out.append(s)
        if out:
            self._sessions_gauge.set(len(self._sessions))
        return out

    def _emit_evictions(self, evicted: list) -> None:
        for s in evicted:
            self._stream_evicted.inc()
            self._sink.emit(
                "stream_evict", sid=s.sid,
                bucket=f"{s.bucket[0]}x{s.bucket[1]}",
                lane=-1 if s.lane is None else int(s.lane),
                idle_s=round(time.time() - s.t_last, 3),
                ttl_s=s.ttl_s)

    def warmup(self, image_shapes: Sequence[Tuple[int, int]],
               batch_sizes: Optional[Sequence[int]] = None) -> List[tuple]:
        """Pre-compile the ``(bucket, lanes)`` program pairs for the
        given raw image ``(H, W)`` shapes (rounded through the same
        bucket policy as live traffic), so first requests don't pay the
        compile.  Slot mode compiles one pair per bucket at
        ``cfg.slots`` lanes (its only batch shape); request mode one
        pair per ``(bucket, batch_size)``.  Returns the list of
        ``(bucket, lanes)`` keys compiled or already present."""
        keys = []
        for (h, w) in image_shapes:
            bucket = bucket_hw(h, w, self.cfg.bucket_multiple,
                               self.cfg.buckets)
            if self.cfg.batching == "slot":
                self._get_programs(bucket, self.cfg.slots)
                keys.append((bucket, self.cfg.slots))
            else:
                for bs in (batch_sizes or self._batch_sizes):
                    self._get_executable(bucket, int(bs))
                    keys.append((bucket, int(bs)))
        return keys

    def compiled_keys(self) -> List[tuple]:
        """``(bucket, lanes, program)`` keys currently in the compile
        cache (compiled here or AOT-imported) — what :meth:`export_aot`
        would serialize."""
        with self._compile_lock:
            return sorted(self._executables)

    def _collect_pending(self, _reg) -> None:
        with self._pending_lock:
            pending = self._pending
            last = self._last_batch_done
        self._pending_gauge.set(pending)
        if last is not None:
            self._stale_gauge.set(time.perf_counter() - last)

    def health(self) -> dict:
        """Readiness snapshot (``GET /v1/healthz``).

        Liveness alone ("the HTTP thread answers") misses the real
        failure mode: a wedged device worker with requests piling up.
        Not-ready ⇔ accepting is off, OR requests are pending and no
        device batch has completed within ``stall_timeout_s`` of the
        NEWEST of {last completed batch, when the pending backlog
        started, start()} — the backlog term keeps a long-idle replica
        from reading as stalled the instant traffic resumes."""
        now = time.perf_counter()
        with self._pending_lock:
            pending = self._pending
            last = self._last_batch_done
            pending_since = self._pending_since
        since = None if last is None else now - last
        stalled = False
        if self.cfg.stall_timeout_s and pending > 0:
            refs = [t for t in (last, pending_since, self._t_started)
                    if t is not None]
            stalled = (bool(refs)
                       and now - max(refs) > self.cfg.stall_timeout_s)
        return {
            "ready": bool(self._accepting and not stalled
                          and not self.crashed),
            "accepting": bool(self._accepting),
            "stalled": stalled,
            "crashed": self.crashed,
            "pending": pending,
            "seconds_since_last_batch":
                None if since is None else round(since, 3),
            "stall_timeout_s": self.cfg.stall_timeout_s,
        }

    def metrics_text(self) -> str:
        """Prometheus text exposition of the engine registry (the same
        counters/histograms ``stats()`` reads — the surfaces cannot
        drift)."""
        return self.registry.render_prometheus()

    def queue_capacity(self) -> int:
        """This engine's admission-queue capacity — the router's spill
        math and the fleet autoscaler read it through the replica
        facade, so heterogeneous fleets (a remote replica with a
        different ``max_queue``) scale each replica by its OWN
        capacity, not a shared config's."""
        return self.cfg.max_queue

    def load_signals(self) -> dict:
        """Cheap load snapshot for the fleet autoscaler — reads only
        locks/atomics already maintained on the hot path (no device
        work, safe at the supervisor's poll cadence).

        Keys: ``pending`` / ``max_queue`` / ``queue_frac`` (admission
        pressure), ``occupancy`` (slot utilization), ``burn_rate``
        (worst SLO burn, 0.0 when SLOs are off), ``mfu`` (last measured
        iteration MFU, None before a known-peak measurement), and
        ``latency_p95_ms`` over the recent window."""
        with self._pending_lock:
            pending = self._pending
        cap = max(int(self.cfg.max_queue), 1)
        burn = 0.0
        if self._slo is not None:
            for snap in self._slo.snapshot().values():
                if isinstance(snap, dict):
                    burn = max(burn, float(snap.get("burn_rate") or 0.0))
        counters = self._counters.snapshot(
            max(jax.local_device_count(), 1))
        lat = self._latency.snapshot()
        return {
            "pending": pending,
            "max_queue": self.cfg.max_queue,
            "queue_frac": round(pending / cap, 4),
            "occupancy": float(counters.get("occupancy") or 0.0),
            "burn_rate": round(burn, 4),
            "mfu": self._last_mfu,
            "latency_p95_ms": float(lat.get("p95_ms") or 0.0),
        }

    def quality_drift(self) -> Optional[dict]:
        """Per-proxy drift-detector state (``None`` when quality
        scoring is disabled) — the fleet supervisor polls this to
        surface ``fleet_quality_drift`` events."""
        if self._quality is None:
            return None
        return self._quality.drift_snapshot()

    def stats(self) -> dict:
        """One JSON-able snapshot: counters, latency percentiles over the
        recent window, per-``(bucket, batch)`` compile counts."""
        out = self._counters.snapshot(max(jax.local_device_count(), 1))
        with self._pending_lock:
            out["pending"] = self._pending
        out["max_queue"] = self.cfg.max_queue
        out["latency_ms"] = self._latency.snapshot()
        out["batching"] = self.cfg.batching
        out["iters_used"] = self._iters_used.snapshot()
        out["iters_used_warm"] = self._iters_used_warm.snapshot()
        out["iters_used_cold"] = self._iters_used_cold.snapshot()
        # Streaming-session snapshot; sweeping lane-less expired
        # sessions here keeps the gauge honest even when no frames
        # arrive to trigger the open-path sweep.
        with self._sessions_lock:
            expired = self._sweep_unpinned_locked(time.time())
        self._emit_evictions(expired)
        with self._sessions_lock:
            out["sessions"] = {
                "open": len(self._sessions),
                "pinned": sum(1 for s in self._sessions.values()
                              if s.lane is not None),
                "frames_total": int(self._stream_frames.value()),
                "evicted_total": int(self._stream_evicted.value()),
            }
        out["compiles"] = {
            f"{hw[0]}x{hw[1]}/b{bs}/{prog}": n
            for (hw, bs, prog), n in sorted(
                self.compile_counter.counts().items())
        }
        out["num_buckets"] = len(
            {k[0] for k in self.compile_counter.counts()})
        # Which correlation lookup is IN each iter program in use, as
        # read off the executable when it was taken into use
        # (_get_programs): "mosaic" where it holds a Mosaic call.
        # (dict(dict) is one step under the GIL: no lock, so a scrape
        # never waits out a compile.)
        out["lookup"] = dict(self._lookup)
        # The model the programs are of, and for arch 'gma' the bytes of
        # attention matrix each (bucket, lanes) state holds on the
        # device beside its pyramid.
        out["model"] = self._model_cfg.arch
        # whether ``enc`` regresses a first flow (arch 'searaft': the
        # state's coords1 then starts off the grid) or starts from zero
        out["first_flow"] = self._model_cfg.regressed_first_flow
        out["attn_bytes"] = {
            f"{hw[0]}x{hw[1]}/b{bs}": int(p.template["attn"].nbytes)
            for (hw, bs), p in sorted(dict(self._programs).items())
            if "attn" in p.template}
        # Stage clock (obs/stages.py): where the device worker's batch
        # cycles went, cumulative seconds by stage — the very counter
        # /metrics renders as raft_stage_seconds_total{loop="serve"}.
        out["stage_seconds"] = {
            dict(key)["stage"]: round(v, 6) for key, v in
            self.registry.counter("raft_stage_seconds_total").items()}
        # AOT warm-start provenance: how many executables this engine
        # imported instead of compiling (docs/SERVING.md fleet section).
        out["aot"] = dict(self.aot_info)
        # Flow-quality snapshot (obs/quality.py): per-proxy p50/p95 +
        # drift-detector state when sampling is on; a bare disabled
        # marker otherwise, so clients can branch without a key check.
        out["quality"] = (self._quality.snapshot()
                          if self._quality is not None
                          else {"enabled": False})
        # Compile-time work accounting per ledger key (obs/cost.py):
        # the `raft_tpu cost` table and bench_serve's per-pair stamps
        # read this — flops/bytes/roofline, captured once at compile.
        out["cost"] = {
            f"{hw[0]}x{hw[1]}/b{bs}/{prog}": c.as_record()
            for (hw, bs, prog), c in sorted(
                self.cost_book.table().items())
        }
        # SLO / incident snapshots (obs/slo.py, obs/incident.py): live
        # burn rates and the open-incident id ride /v1/stats; disabled
        # markers keep the key shape stable for clients.
        out["slo"] = (self._slo.snapshot() if self._slo is not None
                      else {"enabled": False})
        out["incidents"] = (self._incidents.snapshot()
                            if self._incidents is not None
                            else {"enabled": False})
        return out

    # ------------------------------------------------------------------
    # internals — event-loop thread
    # ------------------------------------------------------------------

    def _enqueue(self, req: _Request) -> None:
        q = self._queues.get(req.bucket)
        if q is None:
            q = self._queues[req.bucket] = asyncio.Queue()
            runner = (self._slot_dispatcher
                      if self.cfg.batching == "slot"
                      else self._dispatcher)
            self._dispatchers[req.bucket] = self._loop.create_task(
                runner(req.bucket, q))
        q.put_nowait(req)

    async def _dispatcher(self, bucket: tuple, q: asyncio.Queue) -> None:
        """Coalesce one bucket's requests into micro-batches forever.

        A batch is cut ``max_wait_ms`` after its first request (or at
        ``max_batch``) and handed to the device worker's queue; the
        hand-over is NOT awaited, so the next batch fills meanwhile and
        cut batches wait their turn there, whatever their bucket.  The
        worker is the issuing side only (:meth:`_run_batch`: pad,
        upload, the two program calls); the completing thread answers
        (:meth:`_complete`).  That is what overlaps a batch's host work
        with the device: batch n+1 is padded, uploaded and issued while
        batch n runs, and starts the moment n ends."""
        batch: List[_Request] = []
        try:
            while True:
                batch = [await q.get()]
                deadline = self._loop.time() + self.cfg.max_wait_ms / 1e3
                while len(batch) < self._max_group:
                    wait = deadline - self._loop.time()
                    if wait <= 0:
                        break
                    try:
                        batch.append(
                            await asyncio.wait_for(q.get(), timeout=wait))
                    except asyncio.TimeoutError:
                        break
                fut = self._loop.run_in_executor(
                    self._device_pool, self._run_batch, bucket, batch)
                batch = []
                fut.add_done_callback(lambda f: f.exception())
        except asyncio.CancelledError:
            leftovers = batch
            while not q.empty():
                leftovers.append(q.get_nowait())
            self._fail_stopped(leftovers)
            raise

    async def _slot_dispatcher(self, bucket: tuple,
                               q: asyncio.Queue) -> None:
        """Continuous-batching dispatcher (``batching="slot"``): one
        persistent ``cfg.slots``-lane batch per bucket.  Each cycle
        admits waiting requests into free slots and runs one
        ``iter_step`` over the actives; the device work runs on the
        single worker thread and IS awaited — unlike the request-mode
        dispatcher there is nothing to pipeline (the next cycle's
        admission depends on which lanes just retired), and awaiting
        makes the pool/waiting-list single-owner (no locking).  The
        loop blocks on the queue only when no lane is live and nothing
        waits — otherwise it spins cycles, which is the point: device
        work at iteration granularity."""
        pool = _SlotPool(self.cfg.slots)
        waiting: List[_Request] = []
        try:
            while True:
                if not waiting and not pool.live():
                    if pool.pins:
                        # Idle but holding pinned session lanes: wake
                        # at the earliest possible TTL expiry so the
                        # sweep in _slot_cycle can evict and return
                        # lanes to the free pool.
                        try:
                            waiting.append(await asyncio.wait_for(
                                q.get(),
                                timeout=self._pin_poll_s(pool)))
                        except asyncio.TimeoutError:
                            pass
                    else:
                        waiting.append(await q.get())
                while True:
                    try:
                        waiting.append(q.get_nowait())
                    except asyncio.QueueEmpty:
                        break
                await self._loop.run_in_executor(
                    self._device_pool, self._slot_cycle, bucket, pool,
                    waiting)
        except asyncio.CancelledError:
            leftovers = list(waiting) + pool.live()
            waiting.clear()
            pool.reset()
            while not q.empty():
                leftovers.append(q.get_nowait())
            self._fail_stopped(leftovers)
            raise

    def _fail_stopped(self, reqs: List[_Request]) -> None:
        """Fail requests a stopping engine will not serve."""
        for r in reqs:
            if not r.future.done():
                r.future.set_exception(RuntimeError("engine stopped"))
        if reqs:
            with self._pending_lock:
                self._pending -= len(reqs)

    def _pin_poll_s(self, pool: _SlotPool) -> float:
        """Idle-poll interval while lanes are pinned: sleep until the
        earliest session TTL could expire, clamped to [0.05, 1.0] s."""
        now = time.time()
        nxt = min((s.t_last + s.ttl_s for s in pool.pins.values()),
                  default=now + 1.0)
        return float(min(max(nxt - now, 0.05), 1.0))

    # ------------------------------------------------------------------
    # internals — device-worker thread
    # ------------------------------------------------------------------

    def _get_programs(self, bucket: tuple, lanes: int) -> _Programs:
        """The compiled ``encode``/``iter_step`` pair for ``(bucket,
        lanes)`` — compiled (or AOT-imported) exactly once per key, as
        two explicit, counted events (``program`` label ``enc`` /
        ``iter``).  Both batching modes call through here, so slot and
        request mode can never run different device code."""
        pkey = (bucket, lanes)
        with self._compile_lock:
            progs = self._programs.get(pkey)
            if progs is not None:
                return progs
            H, W = bucket
            template = self._slots_mod.state_template(
                self._model_cfg, self._variables, lanes, bucket)
            state_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                template)
            im = jax.ShapeDtypeStruct((lanes, H, W, 3), jnp.float32)
            mask = jax.ShapeDtypeStruct((lanes,), jnp.bool_)
            budg = jax.ShapeDtypeStruct((lanes,), jnp.int32)
            thr = jax.ShapeDtypeStruct((), jnp.float32)
            steps = jax.ShapeDtypeStruct((), jnp.int32)
            enc = self._executables.get((bucket, lanes, "enc"))
            if enc is None:
                enc = self._encode_jit.lower(
                    self._variables, im, im, state_spec, mask,
                    budg).compile()
                self._executables[(bucket, lanes, "enc")] = enc
                self.compile_counter.record((bucket, lanes, "enc"))
            it = self._executables.get((bucket, lanes, "iter"))
            imported, t_build = it is not None, time.perf_counter()
            if it is None:
                it = self._iter_jit.lower(
                    self._variables, state_spec, thr, steps).compile()
                self._executables[(bucket, lanes, "iter")] = it
                self.compile_counter.record((bucket, lanes, "iter"))
            # Which lookup is in the executable, read off its own text
            # (not asked of the selection again): a program that fell
            # back to XLA, or was imported from elsewhere, says so.
            # Kept for stats(), and noted once beside jax's own records
            # of a build (0 s when the program was imported).
            built_s = time.perf_counter() - t_build
            lookup = ("mosaic" if "tpu_custom_call" in it.as_text()
                      else "xla")
            self._lookup[f"{H}x{W}/b{lanes}"] = lookup
            stages.note("compile", "program", built_s,
                        name=f"{H}x{W}/b{lanes}/iter", imported=imported,
                        lookup=lookup, model=self._model_cfg.arch,
                        predictions=self._predictions)
            # Stamp compile-time cost under the executables' own ledger
            # keys — pure host metadata off the Compiled objects (works
            # for AOT-imported executables too; never runs the program).
            for prog, exe in (("enc", enc), ("iter", it)):
                key = (bucket, lanes, prog)
                if self.cost_book.get(key) is None:
                    self.cost_book.stamp(key, cost_mod.program_cost(
                        exe, program=f"serve_{prog}_{H}x{W}_b{lanes}",
                        pairs_per_call=lanes))
            progs = _Programs(enc, it, template, bucket, lanes,
                              self.cfg.iters)
            self._programs[pkey] = progs
            return progs

    def _get_stream_programs(self, bucket: tuple,
                             lanes: int) -> _Programs:
        """The streaming extras for ``(bucket, lanes)`` — the zero
        carry plus compiled ``stash`` (frame-2 feature snapshot) and
        ``wenc`` (warm encode) programs — filled into the bucket's
        ``_Programs`` on the first streamed pair, so non-streaming
        traffic never compiles them.  Same compile-once,
        AOT-importable, cost-stamped discipline as :meth:`_get_programs`
        (``wenc``'s smaller ``flops_per_pair`` vs ``enc`` IS the
        per-frame encoder saving, visible in ``stats()["cost"]``)."""
        progs = self._get_programs(bucket, lanes)
        if progs.wenc is not None:
            return progs
        with self._compile_lock:
            if progs.wenc is not None:
                return progs
            H, W = bucket
            carry_tpl = self._slots_mod.carry_template(
                self._model_cfg, self._variables, lanes, bucket)
            carry_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                carry_tpl)
            state_spec = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                progs.template)
            im = jax.ShapeDtypeStruct((lanes, H, W, 3), jnp.float32)
            mask = jax.ShapeDtypeStruct((lanes,), jnp.bool_)
            budg = jax.ShapeDtypeStruct((lanes,), jnp.int32)
            stash = self._executables.get((bucket, lanes, "stash"))
            if stash is None:
                stash = self._stash_jit.lower(
                    self._variables, im, carry_spec, mask).compile()
                self._executables[(bucket, lanes, "stash")] = stash
                self.compile_counter.record((bucket, lanes, "stash"))
            wenc = self._executables.get((bucket, lanes, "wenc"))
            if wenc is None:
                wenc = self._warm_jit.lower(
                    self._variables, im, carry_spec, state_spec,
                    mask, budg).compile()
                self._executables[(bucket, lanes, "wenc")] = wenc
                self.compile_counter.record((bucket, lanes, "wenc"))
            for prog, exe in (("stash", stash), ("wenc", wenc)):
                key = (bucket, lanes, prog)
                if self.cost_book.get(key) is None:
                    self.cost_book.stamp(key, cost_mod.program_cost(
                        exe, program=f"serve_{prog}_{H}x{W}_b{lanes}",
                        pairs_per_call=lanes))
            progs.carry0 = jax.device_put(carry_tpl)
            progs.stash = stash
            progs.wenc = wenc
            return progs

    def _pipeline_cost_attrs(self, bucket: tuple, lanes: int,
                             iters: int, seconds: float) -> dict:
        """Trace-span cost attrs for one request-mode pipeline call
        (``enc`` + ``iters`` x ``iter`` over the stamped ledger
        entries; the one ``flow`` program where the model has no loop):
        ``flops``/``bytes`` always, ``mfu`` when the device peak is
        known.  ``{}`` before the programs are stamped."""
        if self._model_cfg.global_matching:
            total = self.cost_book.get((bucket, lanes, "flow"))
            if total is None:
                return {}
        else:
            enc = self.cost_book.get((bucket, lanes, "enc"))
            it = self.cost_book.get((bucket, lanes, "iter"))
            if enc is None or it is None:
                return {}
            total = cost_mod.ProgramCost(
                program=f"serve_pipeline_{bucket[0]}x{bucket[1]}_b{lanes}",
                flops=enc.flops + iters * it.flops,
                bytes=enc.bytes + iters * it.bytes,
                pairs_per_call=lanes, source=enc.source,
                device_kind=enc.device_kind)
        attrs = {"flops": total.flops, "bytes": total.bytes}
        m = total.mfu(seconds)
        if m is not None:
            attrs["mfu"] = round(m, 4)
            self._last_mfu = attrs["mfu"]
        return attrs

    def _get_executable(self, bucket: tuple, batch_size: int):
        """Request-mode device callable for one ``(bucket, batch)``:
        ``(variables, a1, a2) -> (None, flow_up)``.

        A thin lockstep pipeline over the SAME compiled program pair
        slot mode runs — admit all lanes into a fresh zero state, then
        ONE call of the iteration program with ``steps = cfg.iters`` and
        the threshold disabled: two program calls a request, the 32
        iterations a device loop.  Every lane retires on the final step,
        which upsamples in-graph.  Going through the pair (instead of
        one monolithic forward) is what makes slot-vs-request parity
        bit-exact: XLA specializes fusion/reduction order per program,
        so only sharing the executables pins the bits (models/raft.py);
        slot mode passes the same executable ``steps = 1``.

        A model without a refinement loop is one program,
        ``flow(variables, a1, a2) -> flow_up``: one call a request and
        no iteration call counted."""
        if self._model_cfg.global_matching:
            return self._get_flow_executable(bucket, batch_size)
        progs = self._get_programs(bucket, batch_size)
        iters = self.cfg.iters

        def pipeline(variables, a1, a2):
            state = progs.enc(variables, a1, a2, progs.state0,
                              progs.mask_all, progs.budget_full)
            _, flow_up = progs.it(variables, state, progs.thr_off,
                                  progs.steps_full)
            self._counters.add_iter_call(iters)
            return None, flow_up

        # Program calls one run of it issues: what a batch's stage
        # record adds up, over its launches, as ``calls``.
        pipeline.calls = 2
        return pipeline

    def _get_flow_executable(self, bucket: tuple, lanes: int):
        """The one program of a model without a refinement loop for
        ``(bucket, lanes)``, compiled (or AOT-imported) once under the
        ledger key ``(bucket, lanes, "flow")``, noted and cost-stamped as
        :meth:`_get_programs` does its pair."""
        key = (bucket, lanes, "flow")
        H, W = bucket
        with self._compile_lock:
            exe = self._executables.get(key)
            imported, t_build = exe is not None, time.perf_counter()
            if exe is None:
                im = jax.ShapeDtypeStruct((lanes, H, W, 3), jnp.float32)
                exe = self._flow_jit.lower(self._variables, im,
                                           im).compile()
                self._executables[key] = exe
                self.compile_counter.record(key)
            if self.cost_book.get(key) is None:
                stages.note("compile", "program",
                            time.perf_counter() - t_build,
                            name=f"{H}x{W}/b{lanes}/flow",
                            imported=imported, lookup="none",
                            model=self._model_cfg.arch,
                            predictions=self._predictions)
                self.cost_book.stamp(key, cost_mod.program_cost(
                    exe, program=f"serve_flow_{H}x{W}_b{lanes}",
                    pairs_per_call=lanes))

        def pipeline(variables, a1, a2):
            return None, exe(variables, a1, a2)

        pipeline.calls = 1
        return pipeline

    def _issue(self, item: _Issued) -> None:
        """Upload ``item``'s batch and issue its program calls; nothing
        is awaited.  Runs on the issuing side, and again on the
        completing side when the retry ladder re-runs the batch whole
        (each run adds to the unit's ``h2d`` and ``launch``)."""
        # The upload gets an edge of its own (it used to hide in the
        # first jitted call's argument handling).
        with stages.stage("serve", "h2d"):
            d1, d2 = jax.device_put(item.a1), jax.device_put(item.a2)
        with stages.stage("serve", "launch"):
            _, item.flow = item.exe(self._variables, d1, d2)
        item.calls += getattr(item.exe, "calls", 0)

    def _drain(self, item: _Issued, bucket: tuple,
               seq: int) -> np.ndarray:
        """Wait for ``item``'s flow and copy it back, with
        transient-error retry (the request-mode thunk over
        :meth:`_retry_call`).  The first attempt takes what the issuing
        side launched (or raises what its upload or launch raised);
        every later one runs the batch whole — ``h2d``, ``launch``,
        ``drain`` — on this thread.  The programs are pure and nothing
        is donated, so a batch issued behind this one is not disturbed
        by the re-run."""

        def thunk():
            if item.issued:
                item.issued = False
                if item.error is not None:
                    raise item.error
            else:
                self._issue(item)
            # np.asarray blocks on the transfer — async dispatch
            # errors surface here, inside the retry scope.
            with stages.stage("serve", "drain"):
                return np.asarray(item.flow)

        return self._retry_call(bucket, seq, thunk)

    def _call_device(self, exe, a1: np.ndarray, a2: np.ndarray,
                     bucket: tuple, seq: int) -> np.ndarray:
        """Run one compiled batch serially on this thread — upload,
        launch, drain — with transient-error retry: :meth:`_drain` of a
        batch nobody has issued yet."""
        return self._drain(_Issued(exe, a1, a2), bucket, seq)

    def _retry_call(self, bucket: tuple, seq: int, thunk):
        """Run one device call (``thunk``) with transient-error retry.

        Errors classified transient (:func:`is_transient_error` — flaky
        dispatch/transport, or the injected ``device_err`` fault) are
        retried up to ``cfg.device_retries`` times with EXPONENTIAL
        backoff + jitter under a total ``retry_deadline_s`` cap
        (linear backoff hammered a recovering device runtime in lock
        step; the jitter de-correlates co-located replicas), each retry
        counted (``raft_serve_device_retries_total``) and logged as a
        ``serve_retry`` event carrying the ACTUAL ``backoff_s`` slept —
        chaos drills assert the schedule from the event stream.
        Anything deterministic (shape/dtype/compile errors) raises on
        the first attempt.  Host-side pad/stack work stays OUTSIDE the
        retry: it is deterministic, so re-running it could only repeat
        its failure.  ``thunk`` must be safe to re-run — the slot
        programs are pure (state in, state out), so a failed attempt
        leaves the device state it read untouched."""
        attempt = 0
        t_first_try = time.perf_counter()
        while True:
            try:
                if chaos.should_inject("device_err", step=seq,
                                       point="serve.device"):
                    raise InjectedDeviceError(
                        f"chaos-injected transient device error "
                        f"(batch {seq})")
                out = thunk()
                self._last_retries = attempt
                return out
            except Exception as e:
                if attempt >= self.cfg.device_retries \
                        or not is_transient_error(e):
                    raise
                attempt += 1
                self._last_retries = attempt
                base = min(self.cfg.retry_backoff_s * 2 ** (attempt - 1),
                           self.cfg.retry_backoff_max_s)
                backoff = base * (1.0 + self.cfg.retry_jitter
                                  * float(self._retry_rng.uniform(-1, 1)))
                elapsed = time.perf_counter() - t_first_try
                if elapsed + backoff > self.cfg.retry_deadline_s:
                    # Total-deadline cap: the ladder must not outlive
                    # what a waiting client would tolerate.
                    self._sink.emit(
                        "serve_retry_deadline",
                        bucket=f"{bucket[0]}x{bucket[1]}",
                        attempt=attempt, elapsed_s=round(elapsed, 4),
                        deadline_s=self.cfg.retry_deadline_s)
                    raise
                self._counters.add_retry()
                self._sink.emit("serve_retry",
                                bucket=f"{bucket[0]}x{bucket[1]}",
                                attempt=attempt,
                                backoff_s=round(backoff, 6),
                                elapsed_s=round(elapsed, 4),
                                error=f"{type(e).__name__}: {e}")
                time.sleep(backoff)

    def _crash(self, reason: str) -> None:
        """Mark this replica dead (fleet supervisor restarts it): stop
        accepting, stamp the reason, emit the forensic event.  In-flight
        and queued requests fail with replica-fatal errors the router
        classifies as failover signals."""
        self.crashed = reason
        self._accepting = False
        self._sink.emit("replica_crash", reason=reason[:300])

    def _chaos_replica_faults(self, seq: int) -> None:
        """The ``serve.replica`` injection seam (device-worker thread,
        step context = device-batch ordinal): the three replica-level
        faults the fleet drill kills/hangs/slows a member with.  No
        plan installed = three module-global ``None`` checks."""
        if chaos.should_inject("replica_slow", step=seq,
                               point="serve.replica"):
            # A straggler, not a failure: the batch completes late —
            # what the router's bounded hedge exists to cover.
            time.sleep(self.cfg.chaos_slow_s)
        if chaos.should_inject("replica_hang", step=seq,
                               point="serve.replica"):
            # Wedge the (single) device worker: health() turns stalled
            # once stall_timeout_s passes with requests pending, the
            # supervisor stops the engine, and the poll below notices
            # and aborts the batch with a replica-fatal error.
            t0 = time.perf_counter()
            while (self._accepting
                   and time.perf_counter() - t0
                   < self.cfg.chaos_hang_max_s):
                time.sleep(0.02)
            raise ReplicaWedgedInterrupt(
                f"chaos-injected device wedge interrupted after "
                f"{time.perf_counter() - t0:.2f}s (batch {seq})")
        if chaos.should_inject("replica_kill", step=seq,
                               point="serve.replica"):
            self._crash(f"chaos-injected replica kill (batch {seq})")
            raise InjectedReplicaKill(
                f"chaos-injected replica kill (batch {seq})")

    def _slo_request(self, ok: bool,
                     latency_s: Optional[float] = None,
                     n: int = 1) -> None:
        """Feed ``n`` request outcomes into the SLO tracker (callers
        gate on ``self._slo`` so the disabled path stays untouched).
        The latency SLO only observes SUCCESSFUL requests — an errored
        request already burned the availability budget, and a latency
        observation for it would double-count the failure."""
        self._slo.record("availability", ok, n=n)
        if ok and latency_s is not None:
            self._slo.record(
                "latency",
                latency_s * 1000.0 <= self.cfg.slo_latency_target_ms,
                n=n)

    def _run_batch(self, bucket: tuple, reqs: List[_Request]) -> None:
        """The issuing side of one request-mode batch, on the device
        worker: ``wait`` (this side had nothing to issue), ``pad``,
        ``hold`` (only while :data:`_MAX_IN_FLIGHT` batches are issued
        and unanswered), ``h2d``, ``launch`` — then the batch and its
        open stage unit go to the completing thread (:meth:`_complete`)
        and this side takes the next batch: its pad, upload and program
        calls run while the device works on this one.  A batch that
        fails here (a chaos fault, the pad, a compile) is handed over
        all the same, so that answers leave in the order of issue."""
        n = len(reqs)
        if self._abandon:
            self._fail_stopped(reqs)
            return
        item = _Issued()
        item.bucket, item.reqs, item.t_in = bucket, reqs, time.perf_counter()
        item.lanes = next((s for s in self._batch_sizes if s >= n), n)
        # ``wait`` starts where this side handed the last batch over,
        # or where that batch's record closed if it already has: with
        # nothing overlapping (one caller) a record's stages then stay
        # inside its own cycle, as before.
        item.unit = stages.begin("serve", t_start=max(
            self._issuer_free or item.t_in, self._last_batch_done or 0.0))
        item.unit.add("wait", item.unit.t_start, item.t_in)
        self._batch_seq += 1
        item.seq = self._batch_seq
        try:
            self._chaos_replica_faults(item.seq)
            item.exe = self._get_executable(bucket, item.lanes)
            with stages.stage("serve", "pad"):
                im1 = [r.padder.pad_np(r.image1) for r in reqs]
                im2 = [r.padder.pad_np(r.image2) for r in reqs]
                if item.lanes > n:  # ballast keeps the compiled shape
                    im1 += [im1[-1]] * (item.lanes - n)
                    im2 += [im2[-1]] * (item.lanes - n)
                item.a1, item.a2 = np.stack(im1), np.stack(im2)
        except Exception as e:
            item.error = e
        with self._flight:
            if self._in_flight >= _MAX_IN_FLIGHT:
                with stages.stage("serve", "hold"):
                    self._flight.wait_for(
                        lambda: self._in_flight < _MAX_IN_FLIGHT)
            ahead = self._in_flight > 0
            self._in_flight += 1
        if item.error is None:
            item.issued, item.ahead = True, int(ahead)
            if ahead:
                self._counters.add_issued_ahead()
            try:
                self._issue(item)
            except Exception as e:   # the completing side's ladder judges
                item.error = e
        stages.detach("serve")
        self._issuer_free = item.t_handed = time.perf_counter()
        self._issued.put(item)

    def _complete_loop(self) -> None:
        """The completing thread: answer issued batches in the order of
        issue until stop() sends None."""
        while True:
            item = self._issued.get()
            if item is None:
                return
            try:
                self._complete(item)
            except Exception as e:   # a bug past the batch's own
                # handling must not take the thread, and every later
                # answer, with it
                self._sink.emit(
                    "serve_batch_error",
                    bucket=f"{item.bucket[0]}x{item.bucket[1]}",
                    real=len(item.reqs), error=f"{type(e).__name__}: {e}")

    def _complete(self, item: _Issued) -> None:
        """The completing side of one request-mode batch: ``drain``
        (the wait for the device and the flow's copy back, inside the
        retry ladder of :meth:`_drain`), ``reply``, then the batch's
        stage record is closed — it starts where the previous batch's
        ended, so the records tile the worker's time — and its place in
        flight is given back.  The record is the one set of stamps: the
        per-request ``queue`` / ``pad`` / ``device`` trace spans, the
        ``serve_batch`` event and the ring all read it."""
        bucket, reqs, unit = item.bucket, item.reqs, item.unit
        n, bs, seq, t_in = len(reqs), item.lanes, item.seq, item.t_in
        stages.attach("serve", unit)
        if item.issued:
            # ``drain`` is the wait for the device from the moment this
            # batch is the oldest one unanswered: handed over, and the
            # batch before it answered (the hop between the two threads
            # is part of the wait, not a hole in the record).
            unit.add("drain", max(item.t_handed,
                                  self._last_batch_done or 0.0),
                     time.perf_counter())
        self._last_retries = 0
        bk = f"{bucket[0]}x{bucket[1]}"
        # Requests carrying a trace context get per-request queue/pad/
        # device child spans; a batch with no traced request pays only
        # this list comprehension.
        traced = [r for r in reqs if r.trace is not None]
        error = None
        try:
            if not item.issued and item.error is not None:
                raise item.error    # failed before its device call
            flow_up = self._drain(item, bucket, seq)
            with stages.stage("serve", "reply"):
                t_done = unit.spans["drain"][1]
                for j, r in enumerate(reqs):
                    r.future.set_result(
                        np.asarray(r.padder.unpad(flow_up[j:j + 1])[0]))
                    self._latency.record(t_done - r.t_submit)
                    if self._slo is not None:
                        self._slo_request(True, t_done - r.t_submit)
                self._counters.add_batch(real=n, padded=bs - n,
                                         failed=False)
                self._sink.emit("serve_batch", bucket=bk, real=n,
                                ballast=bs - n,
                                seconds=round(t_done - t_in, 6))
        except Exception as e:
            error = type(e).__name__
            for r in reqs:
                if not r.future.done():
                    r.future.set_exception(e)
            # The batch's REAL lanes must stay in the lane accounting
            # (as failed_lanes) or occupancy/mean_batch_fill read too
            # healthy under errors — see Counters.add_batch.
            self._counters.add_batch(real=n, padded=bs - n, failed=True)
            if self._slo is not None:
                self._slo_request(False, n=n)
            self._sink.emit("serve_batch_error", bucket=bk, real=n,
                            error=f"{error}: {e}")
        finally:
            if self._last_batch_done is not None:
                unit.t_start = self._last_batch_done
            rec = stages.end(
                "serve", registry=self.registry, batch=seq, bucket=bk,
                real=n, ballast=bs - n, retries=self._last_retries,
                calls=item.calls, ahead=item.ahead,
                queue_s=[t_in - r.t_submit for r in reqs], error=error,
                model=self._model_cfg.arch)
            with self._pending_lock:
                self._pending -= len(reqs)
                self._last_batch_done = rec["t_end"]
            with self._flight:
                self._in_flight -= 1
                self._flight.notify()
        if traced:
            self._trace_batch(traced, rec, bucket)

    def _trace_batch(self, traced: List[_Request], rec: dict,
                     bucket: tuple) -> None:
        """Per-request trace spans of one batch, from its stage record:
        ``queue`` (submit -> batch entered), ``pad``, and ``device``
        (upload through copy-back) as the parent of ``h2d`` /
        ``launch`` / ``drain``."""
        spans, seq = rec["spans"], rec["batch"]
        t_in = spans["wait"][1]
        for r in traced:
            trace.record_span(r.trace, "queue", r.t_submit, t_in,
                              batch=seq)
        if rec["error"] is not None:
            for r in traced:
                trace.record_span(r.trace, "device", t_in, rec["t_end"],
                                  status="error", error=rec["error"],
                                  batch=seq)
            return
        t_dev0, t_dev1 = spans["h2d"][0], spans["drain"][1]
        # A batch issued ahead spent the first part of its ``device``
        # span queued behind the batch before it: ``mfu`` is taken over
        # the part after that batch's record closed.
        cost_attrs = self._pipeline_cost_attrs(
            bucket, rec["real"] + rec["ballast"], self.cfg.iters,
            t_dev1 - max(t_dev0, rec["t_start"]))
        for r in traced:
            trace.record_span(r.trace, "pad", *spans["pad"],
                              real=rec["real"], ballast=rec["ballast"])
            dev = trace.record_span(
                r.trace, "device", t_dev0, t_dev1, bucket=rec["bucket"],
                batch=seq, retries=rec["retries"], **cost_attrs)
            for name in ("h2d", "launch", "drain"):
                trace.record_span(dev, name, *spans[name])
            if rec["retries"]:  # tail-keep: a retried batch is news
                r.trace.mark_keep()

    # ------------------------------------------------------------------
    # internals — device-worker thread, slot mode
    # ------------------------------------------------------------------

    def _slot_cycle(self, bucket: tuple, pool: _SlotPool,
                    waiting: List[_Request]) -> None:
        """One continuous-batching cycle: admit -> iterate.  Runs on
        the device-worker thread while the bucket's dispatcher awaits;
        ``waiting`` is the dispatcher's FIFO (drained here, oldest
        first, into the lowest free slots)."""
        self._batch_seq += 1
        seq = self._batch_seq
        try:
            self._chaos_replica_faults(seq)
            if pool.progs is None:
                pool.progs = self._get_programs(bucket, self.cfg.slots)
                pool.state = pool.progs.state0
            self._sweep_pins(bucket, pool)
            if waiting:
                self._admit_cycle(bucket, pool, waiting, seq)
            if pool.active_np.any():
                self._iter_slots(bucket, pool, seq)
        except Exception as e:
            # Replica-fatal fault (kill/wedge) or an unexpected bug:
            # every live lane's request dies with it; waiting requests
            # stay queued (a crashed engine's stop() fails them, a
            # surviving one serves them next cycle from a reset pool).
            live = pool.live()
            for r in live:
                if not r.future.done():
                    r.future.set_exception(e)
            if live:
                self._counters.add_failed_lanes(len(live))
                if self._slo is not None:
                    self._slo_request(False, n=len(live))
                with self._pending_lock:
                    self._pending -= len(live)
            pool.reset()
            self._sink.emit("serve_slot_error",
                            bucket=f"{bucket[0]}x{bucket[1]}",
                            lanes=len(live),
                            error=f"{type(e).__name__}: {e}")
        finally:
            with self._pending_lock:
                self._last_batch_done = time.perf_counter()

    def _sweep_pins(self, bucket: tuple, pool: _SlotPool) -> None:
        """Evict closed/expired pinned sessions and return their lanes
        to the free pool.  Runs on the device worker at the top of
        every cycle (the dispatcher's idle poll guarantees cycles keep
        happening while pins exist); no device work — the lane's carry
        simply stops being referenced and the next admit overwrites
        it."""
        now = time.time()
        evicted = []
        for lane, s in list(pool.pins.items()):
            if pool.reqs[lane] is not None or (
                    s.inflight is not None and not s.inflight.done()):
                continue
            if s.closed:
                del pool.pins[lane]
                s.lane = None
            elif now - s.t_last > s.ttl_s:
                del pool.pins[lane]
                s.lane = None
                s.closed = True
                evicted.append((lane, s))
        if not evicted:
            return
        with self._sessions_lock:
            for _, s in evicted:
                self._sessions.pop(s.sid, None)
            self._sessions_gauge.set(len(self._sessions))
        for lane, s in evicted:
            self._stream_evicted.inc()
            self._sink.emit("stream_evict", sid=s.sid,
                            bucket=f"{bucket[0]}x{bucket[1]}",
                            lane=lane,
                            idle_s=round(now - s.t_last, 3),
                            ttl_s=s.ttl_s)

    def _admit_cycle(self, bucket: tuple, pool: _SlotPool,
                     waiting: List[_Request], seq: int) -> None:
        """Partition the waiting FIFO into this cycle's admissions.
        Stateless requests fill free UNPINNED lanes (oldest first,
        lowest lane first).  A session frame goes to its pinned lane —
        pinning one on its first pair — via the warm program when the
        lane's carry is valid, else the cold path plus a carry stash.
        Requests that found no lane stay in ``waiting`` in order."""
        S = self.cfg.slots
        free = [i for i in range(S)
                if pool.reqs[i] is None and i not in pool.pins]
        cold: List[tuple] = []    # (lane, request): full-pair encode
        warm: List[tuple] = []    # (lane, request): warm encode
        stash: List[tuple] = []   # cold subset that seeds a carry
        leftover: List[_Request] = []
        for r in waiting:
            sess = r.session
            if sess is None:
                if free:
                    cold.append((free.pop(0), r))
                else:
                    leftover.append(r)
                continue
            lane = sess.lane
            if lane is None or pool.pins.get(lane) is not sess:
                if not free:
                    leftover.append(r)
                    continue
                lane = free.pop(0)
                sess.lane = lane
                pool.pins[lane] = sess
            if pool.reqs[lane] is not None:
                # One frame in flight per session makes this
                # unreachable in practice; requeue defensively.
                leftover.append(r)
                continue
            if r.warm and sess.carry_ok:
                warm.append((lane, r))
            else:
                # Cold (first pair, or carry invalidated by a reset /
                # stash failure): the unmodified encode program — bit
                # parity with the stateless path — plus a carry stash
                # so the NEXT frame can run warm.
                r.warm = False
                cold.append((lane, r))
                stash.append((lane, r))
        waiting[:] = leftover
        if cold:
            if self._admit_slots(bucket, pool, cold, seq) and stash:
                self._stash_carry(bucket, pool, stash, seq)
        if warm:
            self._admit_warm(bucket, pool, warm, seq)

    def _admit_slots(self, bucket: tuple, pool: _SlotPool,
                     admits: List[tuple], seq: int) -> bool:
        """Encode ``admits`` (``(slot_index, request)`` pairs) into
        their lanes.  The encode program scatters fresh state into the
        admitted lanes only — the other lanes' device state is carried
        through bit-for-bit, so a failed admit (retries exhausted)
        fails just the admitted requests and leaves every live lane
        serving."""
        S = self.cfg.slots
        H, W = bucket
        t0 = time.perf_counter()
        a1 = np.zeros((S, H, W, 3), np.float32)
        a2 = np.zeros((S, H, W, 3), np.float32)
        admit = np.zeros((S,), bool)
        budgets = pool.budgets.copy()
        for i, r in admits:
            a1[i] = r.padder.pad_np(r.image1)
            a2[i] = r.padder.pad_np(r.image2)
            admit[i] = True
            budgets[i] = min(int(r.iters or self.cfg.iters),
                             self.cfg.iters)
        t_pad = time.perf_counter()

        def thunk():
            state = pool.progs.enc(self._variables, a1, a2, pool.state,
                                   admit, budgets)
            # Blocks on a small leaf — async dispatch errors surface
            # here, inside the retry scope, before the pool commits.
            active = np.asarray(state["active"])
            return state, active

        try:
            state, active = self._retry_call(bucket, seq, thunk)
        except Exception as e:
            for _, r in admits:
                if not r.future.done():
                    r.future.set_exception(e)
            self._counters.add_failed_lanes(len(admits))
            if self._slo is not None:
                self._slo_request(False, n=len(admits))
            self._sink.emit("serve_admit_error",
                            bucket=f"{bucket[0]}x{bucket[1]}",
                            admits=len(admits), warm=False,
                            error=f"{type(e).__name__}: {e}")
            with self._pending_lock:
                self._pending -= len(admits)
            return False
        pool.state = state
        pool.active_np = active
        pool.budgets = budgets
        t_done = time.perf_counter()
        for i, r in admits:
            pool.reqs[i] = r
            pool.t_admit[i] = t_done
            if r.trace is not None:
                trace.record_span(r.trace, "queue", r.t_submit, t0,
                                  batch=seq, slot=i)
                trace.record_span(r.trace, "pad", t0, t_pad, slot=i)
        self._sink.emit("serve_admit",
                        bucket=f"{bucket[0]}x{bucket[1]}",
                        admits=len(admits), seq=seq, warm=False,
                        seconds=round(t_done - t0, 6))
        return True

    def _stash_carry(self, bucket: tuple, pool: _SlotPool,
                     admits: List[tuple], seq: int) -> None:
        """Snapshot frame 2's features into freshly cold-admitted
        session lanes' carry.  One extra encoder pass per session
        open — deliberately a SEPARATE program so the cold pair itself
        runs the unmodified ``enc`` executable (bit parity with the
        stateless path).  Failure never fails the pair: the session
        just stays cold and re-seeds on its next frame."""
        S = self.cfg.slots
        H, W = bucket
        t0 = time.perf_counter()
        progs = self._get_stream_programs(bucket, S)
        if pool.carry is None:
            pool.carry = progs.carry0
        a2 = np.zeros((S, H, W, 3), np.float32)
        admit = np.zeros((S,), bool)
        for i, r in admits:
            a2[i] = r.padder.pad_np(r.image2)
            admit[i] = True

        def thunk():
            carry = progs.stash(self._variables, a2, pool.carry, admit)
            # Blocks on a small slice — async dispatch errors surface
            # here, inside the retry scope, before the carry commits.
            np.asarray(carry["fmap"][0, :1, 0, 0])
            return carry

        try:
            carry = self._retry_call(bucket, seq, thunk)
        except Exception as e:
            for _, r in admits:
                if r.session is not None:
                    r.session.carry_ok = False
            self._sink.emit("stream_stash_error",
                            bucket=f"{H}x{W}", lanes=len(admits),
                            error=f"{type(e).__name__}: {e}")
            return
        pool.carry = carry
        for _, r in admits:
            if r.session is not None:
                r.session.carry_ok = True

    def _admit_warm(self, bucket: tuple, pool: _SlotPool,
                    admits: List[tuple], seq: int) -> None:
        """Warm-admit session frames into their pinned lanes: only the
        new image runs through the encoders (the carried fmap/ctx
        stand in for frame 1) and ``coords1`` starts from the lane's
        previous flow forward-warped by itself.  Scatter semantics
        match :meth:`_admit_slots` — a failed warm admit fails just
        the admitted frames (marking their sessions cold) and leaves
        every live lane serving."""
        S = self.cfg.slots
        H, W = bucket
        t0 = time.perf_counter()
        progs = self._get_stream_programs(bucket, S)
        if pool.carry is None:
            pool.carry = progs.carry0
        a2 = np.zeros((S, H, W, 3), np.float32)
        admit = np.zeros((S,), bool)
        budgets = pool.budgets.copy()
        for i, r in admits:
            a2[i] = r.padder.pad_np(r.image2)
            admit[i] = True
            budgets[i] = min(int(r.iters or self.cfg.iters),
                             self.cfg.iters)
        t_pad = time.perf_counter()

        def thunk():
            state, carry = progs.wenc(self._variables, a2, pool.carry,
                                      pool.state, admit, budgets)
            active = np.asarray(state["active"])
            return state, carry, active

        try:
            state, carry, active = self._retry_call(bucket, seq, thunk)
        except Exception as e:
            for _, r in admits:
                if not r.future.done():
                    r.future.set_exception(e)
                if r.session is not None:
                    r.session.carry_ok = False
            self._counters.add_failed_lanes(len(admits))
            if self._slo is not None:
                self._slo_request(False, n=len(admits))
            self._sink.emit("serve_admit_error",
                            bucket=f"{H}x{W}",
                            admits=len(admits), warm=True,
                            error=f"{type(e).__name__}: {e}")
            with self._pending_lock:
                self._pending -= len(admits)
            return
        pool.state = state
        pool.carry = carry
        pool.active_np = active
        pool.budgets = budgets
        t_done = time.perf_counter()
        for i, r in admits:
            pool.reqs[i] = r
            pool.t_admit[i] = t_done
            if r.trace is not None:
                trace.record_span(r.trace, "queue", r.t_submit, t0,
                                  batch=seq, slot=i)
                trace.record_span(r.trace, "pad", t0, t_pad, slot=i)
        self._sink.emit("serve_admit",
                        bucket=f"{H}x{W}",
                        admits=len(admits), seq=seq, warm=True,
                        seconds=round(t_done - t0, 6))

    def _iter_slots(self, bucket: tuple, pool: _SlotPool,
                    seq: int) -> None:
        """One ``iter_step`` over the active lanes; retire lanes whose
        budget is spent or whose convergence predicate fired.  On a
        non-transient failure every live request dies and the pool
        resets to the zero state — the programs are pure, so a FAILED
        attempt never corrupts state, and a retried one is
        bit-identical to an uninterrupted run
        (tests/test_serve_slots.py chaos case)."""
        prev_active = pool.active_np.copy()
        n_active = int(prev_active.sum())
        thr = np.float32(self.cfg.early_exit_threshold)
        t0 = time.perf_counter()

        def thunk():
            moved, flow_up = pool.progs.it(self._variables, pool.state,
                                           thr, _ONE_STEP)
            self._counters.add_iter_call(1)
            active = np.asarray(moved["active"])
            iters_done = np.asarray(moved["iters_done"])
            return (self._slots_mod.advance(pool.state, moved), flow_up,
                    active, iters_done)

        try:
            state, flow_up, active, iters_done = self._retry_call(
                bucket, seq, thunk)
        except Exception as e:
            live = pool.live()
            for r in live:
                if not r.future.done():
                    r.future.set_exception(e)
            self._counters.add_failed_lanes(len(live))
            if self._slo is not None and live:
                self._slo_request(False, n=len(live))
            self._sink.emit("serve_iter_error",
                            bucket=f"{bucket[0]}x{bucket[1]}",
                            lanes=len(live),
                            error=f"{type(e).__name__}: {e}")
            with self._pending_lock:
                self._pending -= len(live)
            pool.reset()
            return
        retries = self._last_retries
        t_done = time.perf_counter()
        pool.state = state
        pool.active_np = active
        self._counters.add_slot_step(n_active, self.cfg.slots)
        bk = f"{bucket[0]}x{bucket[1]}"
        # Iteration-level trace attribution: every traced request that
        # was active this cycle gets an iter_step child span under its
        # request root (trace_report.py critical paths then show which
        # iterations a request actually waited on).  The cost attrs
        # (flops/bytes, mfu on known peaks) come from the ledger entry
        # stamped at compile time — observe() also refreshes the
        # raft_cost_mfu/raft_cost_hbm_bw_util gauges, no device work.
        iter_attrs = self.cost_book.observe(
            (bucket, self.cfg.slots, "iter"), t_done - t0)
        if "mfu" in iter_attrs:
            self._last_mfu = iter_attrs["mfu"]
        if self._slo is not None and "mfu" in iter_attrs:
            # The MFU-floor SLO (only constructed on known peaks):
            # one observation per measured iteration.
            self._slo.record(
                "mfu", iter_attrs["mfu"] >= self.cfg.slo_mfu_floor)
        for i in np.nonzero(prev_active)[0]:
            r = pool.reqs[int(i)]
            if r is not None and r.trace is not None:
                trace.record_span(r.trace, "iter_step", t0, t_done,
                                  batch=seq, slot=int(i),
                                  active=n_active, **iter_attrs)
        newly = prev_active & ~active
        if not newly.any():
            return
        flow_np = np.asarray(flow_up)
        converged_np = np.asarray(state["converged"])
        # delta_max is only fetched when the quality monitor exists —
        # at quality_sample_rate=0 the retirement path transfers
        # exactly what it always did (the zero-overhead contract).
        dmax_np = (np.asarray(state["delta_max"])
                   if self._quality is not None else None)
        for i in np.nonzero(newly)[0]:
            i = int(i)
            r = pool.reqs[i]
            pool.reqs[i] = None
            if r is None:
                continue
            out = np.asarray(r.padder.unpad(flow_np[i:i + 1])[0])
            if not r.future.done():
                r.future.set_result(out)
            used = int(iters_done[i])
            self._latency.record(t_done - r.t_submit)
            self._iters_used.record(used)
            (self._iters_used_warm if r.warm
             else self._iters_used_cold).record(used)
            self._counters.add_completed()
            if self._slo is not None:
                self._slo_request(True, t_done - r.t_submit)
            if r.session is not None:
                r.session.pairs += 1
                if r.warm:
                    r.session.warm_pairs += 1
            self._sink.emit("serve_retire", bucket=bk, slot=i,
                            iters=used, warm=bool(r.warm),
                            converged=bool(converged_np[i]),
                            seconds=round(t_done - r.t_submit, 6))
            qattrs = None
            if self._quality is not None:
                qattrs = self._quality.note_retirement(
                    future=r.future, image1=r.image1, image2=r.image2,
                    flow=out, bucket=bk, residual=float(dmax_np[i]),
                    converged=bool(converged_np[i]), iters=used)
                if (self._slo is not None and qattrs is not None
                        and "quality_photometric" in qattrs):
                    # Quality SLO: one observation per SCORED
                    # retirement — bad when the photometric proxy
                    # breached its calibrated bound.
                    self._slo.record(
                        "quality",
                        qattrs["quality_photometric"]
                        <= self.cfg.slo_quality_bound)
                if qattrs is not None and self.cfg.quality_cycle:
                    # Sampled forward-backward pass: score THIS flow
                    # against a second inference on the swapped
                    # frames.  Best-effort — backpressure or an
                    # engine racing stop() just skips the cycle
                    # measurement, never fails the retirement.
                    try:
                        bfut = self.submit(r.image2, r.image1,
                                           iters=r.iters)
                    except Exception:
                        pass
                    else:
                        self._quality.begin_cycle(bfut, out, bk)
            if r.trace is not None:
                trace.record_span(r.trace, "device", pool.t_admit[i],
                                  t_done, bucket=bk, iters=used,
                                  warm=bool(r.warm),
                                  retries=retries, **(qattrs or {}))
                if retries:  # tail-keep: a retried request is news
                    r.trace.mark_keep()
            with self._pending_lock:
                self._pending -= 1
