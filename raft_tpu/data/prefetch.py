"""Background device-prefetch: the overlapped training input pipeline.

The serial loop (PRs 0-2) ran fetch -> noise -> ``shard_batch``
(``jax.device_put``) -> step entirely on the consumer thread, so host
decode, host prep, and the H2D enqueue all sat in the step's critical
path — exactly the gap PR 2's ``data_wait_s`` input-bound detector made
visible.  :class:`DevicePipeline` moves stages (2) host prep (noise
injection) and (3) device placement onto ONE background
producer thread feeding a bounded buffer, so while the device runs step
N the producer is already prepping and transferring batches
N+1..N+depth.  ``device_put`` dispatch is async, so "transfer" costs the
producer only the enqueue; the copy itself overlaps device compute.
RAFT's 12-32 refinement iterations make each step long enough to hide
all of it (PAPER.md; docs/PERFORMANCE.md has the overlap model).

Ordering/determinism contract: exactly ONE producer pulls the host
iterator and applies ``prep_fn`` in stream order — the same order the
serial path uses — so a *stateful* prep (the noise RNG keyed on the
resume step, ``raft_tpu/train/loop.py``) sees an identical call
sequence whether the pipeline is buffered (``depth > 0``) or serial
(``depth == 0``), and resume via ``ShardedLoader.batches_from_step``
stays bit-equivalent.  ``depth == 0`` is not a degraded mode but the
exact old serial path (prep + put inline in ``__next__``), kept for
A/B against the overlapped one.

Boundedness: a semaphore of ``depth`` slots is acquired BEFORE the
producer touches the source iterator and released when the consumer
takes a batch, so at most ``depth`` batches are ever held by the
pipeline (pulled-but-undelivered) beyond the one the consumer is
stepping on — the device buffers of a deep queue would otherwise
accumulate in HBM.

Telemetry: every batch is one ``input`` unit of the stage clock
(``obs/stages.py``) with stages ``slot_wait`` (no buffer budget: the
loader has slack), ``source`` (the wait on the loader: loader-bound),
``prep`` and ``h2d``, closed on the thread that produced it.  The
consumer reads the delivered batch's record (``last_unit``) right after
``next()``, so the train loop can split the old ``data_wait_s`` into
consumer-side queue wait and the producer-side ``h2d`` / ``prep`` spans
(docs/OBSERVABILITY.md).
"""

from __future__ import annotations

import queue
import threading
from typing import Callable, Iterable, Iterator, Optional

from raft_tpu import chaos
from raft_tpu.chaos import InjectedProducerCrash
from raft_tpu.obs import stages

# Producer -> consumer message kinds.
_ITEM, _END, _ERROR = "item", "end", "error"


class PipelineInterrupted(Exception):
    """Raised out of ``next(pipeline)`` when the consumer's
    ``interrupt`` predicate turns true while the queue is empty — the
    cooperative-preemption path out of a blocked input wait
    (docs/ROBUSTNESS.md).  Not a stream error: the pipeline stays
    usable, the train loop translates it to its preemption exit."""


def _landed(batch) -> bool:
    """Whether every array of a placed batch holds its data (a host array,
    where ``put_fn`` is the identity, always does)."""
    leaves = batch.values() if isinstance(batch, dict) else (batch,)
    return all(getattr(x, "is_ready", lambda: True)() for x in leaves)


def _chaos_producer_point(ordinal: int) -> None:
    """`pipeline.producer` injection seam (docs/ROBUSTNESS.md): fires
    the ``producer_err`` fault before batch ``ordinal`` is pulled — on
    the producer thread when buffered, inline at depth 0 — exercising
    the error-propagation contract (the consumer's ``next()`` re-raises,
    ``close()`` joins).  One no-op module check when chaos is off."""
    if chaos.should_inject("producer_err", step=ordinal,
                           point="pipeline.producer"):
        raise InjectedProducerCrash(
            f"chaos-injected producer crash before batch {ordinal}")


class DevicePipeline:
    """Iterator of device-resident batches with bounded background
    prefetch.

    ``batches``: the host batch iterator (e.g. ``ShardedLoader.batches``
    or ``batches_from_step``).
    ``put_fn``: host batch -> device-resident batch (e.g.
    :func:`raft_tpu.parallel.make_batch_sharder`); None = identity
    (host-only pipelining, used by tests and the input microbench).
    ``prep_fn``: host-side prep applied before ``put_fn`` (noise
    injection); called in stream order by exactly one thread.
    ``depth``: buffered batches beyond the one handed to the consumer;
    0 = synchronous serial path (no thread).
    ``registry``: the ``MetricRegistry`` that takes the batches' stage
    seconds (``raft_stage_seconds_total{loop="input"}``); None = only
    the stage clock's ring.

    Iteration: ``next(pipeline)`` returns the next device-resident
    batch; ``StopIteration`` when the source ends.  A producer-side
    exception re-raises in the consumer's ``next()``.  ``close()``
    (also via context manager) stops the producer and drops buffered
    batches so their device memory frees promptly; it is called by the
    train loop's ``finally``.
    """

    def __init__(self, batches: Iterable, *,
                 put_fn: Optional[Callable] = None,
                 prep_fn: Optional[Callable] = None,
                 depth: int = 2, keep_host: bool = False,
                 interrupt: Optional[Callable[[], bool]] = None,
                 interrupt_poll_s: float = 0.1, registry=None):
        if depth < 0:
            raise ValueError(f"device-prefetch depth must be >= 0, "
                             f"got {depth}")
        self._src: Iterator = iter(batches)
        self._put = put_fn if put_fn is not None else (lambda b: b)
        self._prep = prep_fn
        self.depth = int(depth)
        # keep_host: retain the post-prep HOST batch alongside each
        # device batch (``last_host_batch`` after next()) — the
        # forensics ring needs the exact host arrays the poisoned step
        # consumed (raft_tpu/obs/health.py).  Off by default: holding
        # the references keeps up to depth+ring batches of host RAM
        # alive that the serial path would have freed.
        self.keep_host = bool(keep_host)
        # interrupt: optional predicate polled while the consumer waits
        # on an empty buffer (the SIGTERM fix for the old caveat: a
        # preemption flag set while ``next()`` was blocked in
        # ``queue.get`` went unobserved until a batch arrived).  When it
        # turns true mid-wait, ``next()`` raises
        # :class:`PipelineInterrupted` instead of blocking on.  Only the
        # buffered path polls — at depth 0 the consumer is inside the
        # source iterator itself (host IO), which stays uninterruptible
        # exactly like the pre-pipeline serial loop.
        self._interrupt = interrupt
        self._interrupt_poll_s = max(float(interrupt_poll_s), 1e-3)
        # The stage-clock record of the batch just delivered (seconds
        # and perf_counter spans of slot_wait / source / prep / h2d),
        # valid right after next() returns — lets the train loop's step
        # trace place the producer-side prep/h2d spans on the shared
        # monotonic timeline (obs/trace.record_span).  ``registry``
        # takes the ``raft_stage_seconds_total{loop="input"}`` seconds.
        self.last_unit: Optional[dict] = None
        self._registry = registry
        self.last_host_batch = None
        # (device batch, host batch) pairs whose copy may be in flight.
        self._landing: list = []
        # Cumulative, for the input microbench / pipeline stats.
        self.prep_total_s = 0.0
        self.h2d_total_s = 0.0
        self.batches_out = 0
        self._closed = False
        if self.depth > 0:
            # The queue itself is unbounded; _slots enforces the
            # in-flight bound (acquired BEFORE the source is pulled).
            self._q: queue.Queue = queue.Queue()
            self._slots = threading.Semaphore(self.depth)
            self._stop = threading.Event()
            self._thread = threading.Thread(
                target=self._produce, name="raft-device-prefetch",
                daemon=True)
            self._thread.start()

    # -- producer (depth > 0) -------------------------------------------
    def _make(self):
        """Pull, prep and place one batch inside the open ``input``
        unit -> (device batch, host batch kept, the unit's record).
        The loader workers' sample totals ride on the record as they
        stood when it closed, so a reader takes a window's share of
        them as last - first.  StopIteration propagates."""
        with stages.stage("input", "source"):
            batch = next(self._src)
        with stages.stage("input", "prep"):
            if self._prep is not None:
                batch = self._prep(batch)
        host = batch
        with stages.stage("input", "h2d"):
            batch = self._put(host)
        # The loader writes a batch slot again only once the last view of
        # it is gone: hold each host batch until its copy has landed, not
        # merely until it was enqueued.
        self._landing = [(d, h) for d, h in self._landing
                         if not _landed(d)] + [(batch, host)]
        return batch, host if self.keep_host else None, stages.end(
            "input", registry=self._registry,
            sample_seconds_total=stages.total("data_sample_seconds"),
            samples_total=stages.total("data_samples"))

    def _produce(self) -> None:
        produced = 0  # pull ordinal, matches the serial path's count
        try:
            while True:
                # Slot first: never pull (or decode, or device_put) a
                # batch there is no buffer budget for.
                stages.begin("input")
                with stages.stage("input", "slot_wait"):
                    while not self._slots.acquire(timeout=0.05):
                        if self._stop.is_set():
                            return
                if self._stop.is_set():
                    return
                _chaos_producer_point(produced)
                produced += 1
                try:
                    self._q.put((_ITEM,) + self._make())
                except StopIteration:
                    self._q.put((_END, None, None, None))
                    return
        except BaseException as e:  # re-raised in the consumer
            self._q.put((_ERROR, e, None, None))

    # -- consumer --------------------------------------------------------
    def __iter__(self) -> "DevicePipeline":
        return self

    def _account(self, unit: dict) -> None:
        self.last_unit = unit
        self.prep_total_s += unit["stages"]["prep"]
        self.h2d_total_s += unit["stages"]["h2d"]
        self.batches_out += 1

    def __next__(self):
        if self._closed:
            raise StopIteration
        if self.depth == 0:
            # The exact old serial path: prep + put inline, on this
            # thread, one batch at a time.
            _chaos_producer_point(self.batches_out)
            stages.begin("input")
            batch, self.last_host_batch, unit = self._make()
            self._account(unit)
            return batch
        if self._interrupt is None:
            kind, payload, host, unit = self._q.get()
        else:
            # Timed wait + flag re-check: a preemption request cannot
            # interrupt queue.get, so poll.  The poll costs nothing on
            # the hot path (the queue is non-empty whenever the
            # producer keeps up) and bounds the observation latency of
            # a SIGTERM during an input stall to interrupt_poll_s.
            while True:
                try:
                    kind, payload, host, unit = self._q.get(
                        timeout=self._interrupt_poll_s)
                    break
                except queue.Empty:
                    if self._interrupt():
                        raise PipelineInterrupted(
                            "preemption requested while waiting on the "
                            "input pipeline")
        if kind == _END:
            self._closed = True
            raise StopIteration
        if kind == _ERROR:
            self._closed = True
            raise payload
        self._slots.release()
        self.last_host_batch = host
        self._account(unit)
        return payload

    def buffered(self) -> int:
        """Batches currently sitting in the buffer (0 on the serial
        path); bounded by ``depth``."""
        return 0 if self.depth == 0 else self._q.qsize()

    def close(self) -> None:
        """Stop the producer and drop buffered batches.  Idempotent.

        The producer may be blocked inside ``next(source)`` (host IO);
        like the serial loop, that cannot be interrupted — the stop flag
        is observed at the next slot/batch boundary, and the thread is
        daemonic so a wedged loader cannot hang interpreter exit."""
        self._closed = True
        self._landing = []
        if self.depth == 0:
            return
        self._stop.set()
        self._thread.join(timeout=5.0)
        try:
            while True:  # free buffered device arrays promptly
                self._q.get_nowait()
        except queue.Empty:
            pass

    def __enter__(self) -> "DevicePipeline":
        return self

    def __exit__(self, *exc) -> bool:
        self.close()
        return False
