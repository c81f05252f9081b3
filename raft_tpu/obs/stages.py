"""The stage clock: where each unit of a loop spent its wall time.

:func:`raft_tpu.obs.registry.span` times a block into a histogram.  A
*stage* times a block into the open **unit** of a loop — a serve batch,
a train step, a producer batch — and a closed unit is one record in a
process-wide bounded ring::

    stages.begin("serve")
    with stages.stage("serve", "pad"):
        ...
    rec = stages.end("serve", registry=engine.registry, real=3)
    # {"loop": "serve", "n": 41, "t_start": ..., "t_end": ...,
    #  "stages": {"pad": 0.0012}, "spans": {"pad": (t0, t1)}, "real": 3}

It is always on: no flag, no environment variable, no sample rate.  A
stage costs two ``perf_counter`` reads, a dict update and a
``jax.profiler.TraceAnnotation`` named ``raft/<loop>/<stage>``, which
does nothing without a capture and under one lands on the host plane of
the same ``.xplane.pb`` as the device's operations: that annotation is
the only thing that puts the program's stages on the profiler's clock.
Nothing here touches a device array or starts a thread.

Readers (``benchmark/readers``, an operator in a debugger) ask
:func:`recent` for the ring, or for the records that ended in the
``window_s`` seconds before the newest one — the rule of
``obs.incident.FlightRecorder.recent``: the window trails the stream,
not the wall clock, so a run that stopped a minute ago still has one.
Closing a unit also adds its stage seconds to
``raft_stage_seconds_total{loop,stage}`` in the registry its loop owns
(docs/OBSERVABILITY.md has the table of loops and stages).
"""

from __future__ import annotations

import collections
import threading
import time
from typing import Dict, List, Optional

from jax.profiler import TraceAnnotation

#: Records kept per loop.  A 30 s window holds ~400 serve batches or
#: ~135 train steps; 8192 is hours of steps and minutes of batches.
RING = 8192

_lock = threading.Lock()
_rings: Dict[str, collections.deque] = {}
_seq: Dict[str, int] = {}
_totals: Dict[tuple, float] = {}
_open = threading.local()


class Unit:
    """The open unit of one loop on one thread (see :func:`begin`)."""

    __slots__ = ("t_start", "stages", "spans")

    def __init__(self, t_start: float):
        self.t_start = t_start
        self.stages: Dict[str, float] = {}
        self.spans: Dict[str, tuple] = {}

    def add(self, name: str, t0: float, t1: float) -> None:
        """Book ``[t0, t1]`` under ``name``: seconds add up over
        repeats (a retried ``h2d``), the span runs from the first start
        to the last end."""
        self.stages[name] = self.stages.get(name, 0.0) + (t1 - t0)
        first = self.spans.get(name)
        self.spans[name] = (first[0] if first else t0, t1)


def _units() -> Dict[str, Unit]:
    try:
        return _open.units
    except AttributeError:
        units = _open.units = {}
        return units


def begin(loop: str, t_start: Optional[float] = None) -> Unit:
    """Open ``loop``'s unit on this thread (an abandoned one — a loop
    that left through ``break`` or an exception — is dropped).
    ``t_start`` backdates it, e.g. to the end of the previous unit."""
    unit = _units()[loop] = Unit(
        time.perf_counter() if t_start is None else t_start)
    return unit


def detach(loop: str) -> Optional[Unit]:
    """Take this thread's open unit of ``loop`` off it, still open, so
    that another thread can :func:`attach` and close it (a serve batch
    is issued on one thread and answered on another)."""
    return _units().pop(loop, None)


def attach(loop: str, unit: Unit) -> None:
    """Make ``unit`` this thread's open unit of ``loop``: later stages
    and :func:`end` on this thread book into it."""
    _units()[loop] = unit


class stage:
    """``with stage(loop, name):`` — time the block into this thread's
    open unit of ``loop`` and annotate it for the profiler.  With no
    unit open only the annotation remains."""

    __slots__ = ("_unit", "_name", "_ann", "_t0")

    def __init__(self, loop: str, name: str):
        self._unit = _units().get(loop)
        self._name = name
        self._ann = TraceAnnotation(f"raft/{loop}/{name}")

    def __enter__(self) -> "stage":
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._unit is not None:
            self._unit.add(self._name, self._t0, t1)
        return False


def _append_locked(loop: str, rec: dict) -> None:
    ring = _rings.get(loop)
    if ring is None:
        ring = _rings[loop] = collections.deque(maxlen=RING)
    rec["n"] = _seq[loop] = _seq.get(loop, 0) + 1
    ring.append(rec)


def end(loop: str, registry=None, **fields) -> Optional[dict]:
    """Close this thread's unit of ``loop``: one record appended to the
    ring (``fields`` ride along), its stage seconds added to
    ``raft_stage_seconds_total{loop,stage}`` of ``registry``.  -> the
    record, or None when no unit was open."""
    unit = _units().pop(loop, None)
    if unit is None:
        return None
    rec = {"loop": loop, "t_start": unit.t_start,
           "t_end": time.perf_counter(), "stages": unit.stages,
           "spans": unit.spans}
    rec.update(fields)
    with _lock:
        _append_locked(loop, rec)
        for name, s in unit.stages.items():
            _totals[loop, name] = _totals.get((loop, name), 0.0) + s
    if registry is not None:
        counter = registry.counter(
            "raft_stage_seconds_total",
            "wall seconds per loop stage (obs/stages.py)")
        for name, s in unit.stages.items():
            counter.inc(s, loop=loop, stage=name)
    return rec


def note(loop: str, kind: str, seconds: float, **fields) -> dict:
    """A unit that was timed elsewhere (a compile that jax reports when
    it is over): one record that ends now and lasted ``seconds``, which
    are also added to ``total(loop, kind)``."""
    t_end = time.perf_counter()
    rec = {"loop": loop, "t_start": t_end - seconds, "t_end": t_end,
           "seconds": seconds, "kind": kind}
    rec.update(fields)
    with _lock:
        _append_locked(loop, rec)
        _totals[loop, kind] = _totals.get((loop, kind), 0.0) + seconds
    return rec


def compile_seconds_hook():
    """-> a ``MetricRegistry`` collect hook that brings the registry's
    ``raft_compile_seconds_total{kind}`` up to what the compile
    listener (``utils.profiling.listen_for_compiles``) has booked for
    this process: pulled at scrape/snapshot time, so a compile never
    has to know which registries exist."""
    seen: Dict[str, float] = {}

    def hook(registry) -> None:
        counter = registry.counter(
            "raft_compile_seconds_total",
            "seconds this process spent in XLA backend compiles "
            "(kind=compile) and persistent-cache loads "
            "(kind=cache_load)")
        for kind in ("compile", "cache_load"):
            now = total("compile", kind)
            if now > seen.get(kind, 0.0):
                counter.inc(now - seen.get(kind, 0.0), kind=kind)
                seen[kind] = now

    return hook


def bump(name: str, by: float) -> None:
    """Add to a process-wide cumulative total that unit records quote
    (the loader workers' sample seconds: a producer record carries the
    total as it stood when the record closed)."""
    with _lock:
        _totals[name,] = _totals.get((name,), 0.0) + by


def total(*key: str) -> float:
    """``total(loop, stage)`` or ``total(name)`` of :func:`bump`."""
    with _lock:
        return _totals.get(key, 0.0)


def recent(loop: str, window_s: Optional[float] = None) -> List[dict]:
    """``loop``'s ring, oldest first; with ``window_s`` only the
    records that ended in the ``window_s`` seconds before the newest
    one.  The records are the ring's own: read, do not write."""
    with _lock:
        out = list(_rings.get(loop, ()))
    if window_s is not None and out:
        horizon = out[-1]["t_end"] - float(window_s)
        out = [r for r in out if r["t_end"] >= horizon]
    return out


def reset() -> None:
    """Forget every ring and total (tests)."""
    with _lock:
        _rings.clear()
        _seq.clear()
        _totals.clear()
