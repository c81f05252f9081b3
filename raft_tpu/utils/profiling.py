"""Profiling hooks (SURVEY.md §5: the reference has no tracing/profiling;
the TPU plan is ``jax.profiler`` traces viewable in XProf/TensorBoard).

``trace_steps`` wraps a window of training steps in a profiler trace:
the driver calls ``maybe_start``/``maybe_stop`` around each step, and the
captured trace lands in ``<dir>/plugins/profile/...``.
"""

from __future__ import annotations

import dataclasses
import os.path as osp
import threading
from typing import Dict, Hashable, Optional

import jax


class CompileCounter:
    """Per-key compile-event accounting.

    XLA exposes no portable "how many programs did this process build"
    counter, so callers that manage their own executables (the serving
    engine's AOT-compiled ``(bucket, batch)`` forwards,
    ``raft_tpu/serve/engine.py``) record one event per executable they
    actually build.  Tests then assert the serving invariant directly:
    steady-state traffic compiles exactly once per key, never per
    request.  Thread-safe (the engine compiles from worker threads).

    Optionally mirrored into a telemetry registry
    (``raft_tpu.obs.MetricRegistry``, duck-typed so this module stays
    import-light): pass ``registry`` and events also increment the
    ``metric`` counter, labeled via ``labeler(key) -> {label: value}``
    (default: one ``key=str(key)`` label)."""

    def __init__(self, registry=None, metric: str = "raft_compiles_total",
                 labeler=None) -> None:
        self._lock = threading.Lock()
        self._counts: Dict[Hashable, int] = {}
        self._metric = (registry.counter(metric, "XLA compile events")
                        if registry is not None else None)
        self._labeler = labeler

    def record(self, key: Hashable) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + 1
        if self._metric is not None:
            labels = (self._labeler(key) if self._labeler
                      else {"key": str(key)})
            self._metric.inc(1, **labels)

    def count(self, key: Hashable) -> int:
        with self._lock:
            return self._counts.get(key, 0)

    def counts(self) -> Dict[Hashable, int]:
        with self._lock:
            return dict(self._counts)

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()


#: The ``jax.monitoring`` duration events the compile listener reads, as
#: the installed jax 0.9.0 emits them (docs/OBSERVABILITY.md).
#: ``backend_compile_duration`` wraps ``compiler.compile_or_get_cached``
#: (``pxla.py``), so it fires for a backend compile AND for a
#: persistent-cache hit, after ``cache_retrieval_time_sec``, which only a
#: hit emits (``compiler.py``) on the same thread.  The other two time a
#: jitted function's trace to a jaxpr and its lowering to MLIR — what a
#: retrace costs before any compile; a function traced inside another's
#: trace reports too, so these overlap and are never summed, and the
#: thousands under ``_MIN_BUILD_STEP_S`` a process makes (every small
#: jitted helper, once a call site) would only push the programs out of
#: the ring.
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_RETRIEVAL_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"
_BUILD_STEPS = {"/jax/core/compile/jaxpr_trace_duration": "trace",
                "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower"}
_MIN_BUILD_STEP_S = 0.05

_listen_lock = threading.Lock()
_listening = False
_cache_hit = threading.local()


def _on_compile_event(event: str, seconds: float, **kwargs) -> None:
    if event == CACHE_RETRIEVAL_EVENT:
        _cache_hit.pending = True
        return
    kind = _BUILD_STEPS.get(event)
    if kind is not None and seconds < _MIN_BUILD_STEP_S:
        return
    if event == BACKEND_COMPILE_EVENT:
        kind = ("cache_load" if getattr(_cache_hit, "pending", False)
                else "compile")
        _cache_hit.pending = False
    if kind is not None:
        from raft_tpu.obs import stages

        stages.note("compile", kind, float(seconds),
                    name=str(kwargs.get("fun_name", "")))


def listen_for_compiles() -> None:
    """Register, once a process, the listener that turns every program
    XLA builds or loads from the persistent cache into a ``compile``
    record of the stage clock (``obs.stages.recent("compile")``:
    ``{t_end, seconds, kind: compile|cache_load, name}``), and every
    trace and lowering on the way there into one of ``kind``
    ``trace|lower``.  Unlike :class:`CompileCounter`, which counts what
    callers report, it sees what ``jit`` does on its own: a retrace is
    a second ``trace`` record with the function's name."""
    global _listening
    with _listen_lock:
        if _listening:
            return
        _listening = True
    jax.monitoring.register_event_duration_secs_listener(_on_compile_event)


@dataclasses.dataclass
class StepProfiler:
    """Capture a ``jax.profiler`` trace for steps [start, stop).

    Inactive (no overhead beyond two int compares) when ``trace_dir`` is
    None.  The first few steps are skipped by default so compilation does
    not pollute the trace.

    ``absolute``: interpret ``start_step`` as an ABSOLUTE global step
    number instead of an offset from the first observed step — the
    ``--profile-steps A:B`` train flag targets a specific window of a
    (possibly resumed) run.  While a capture is running the artifact
    directory is stamped onto concurrently recorded trace spans
    (``raft_tpu.obs.trace.set_active_profile``), linking the step
    waterfall straight to its device profile.
    """

    trace_dir: Optional[str] = None
    start_step: int = 10          # relative to the first observed step
    num_steps: int = 5
    absolute: bool = False
    _first_step: Optional[int] = None
    _running: bool = False
    _done: bool = False

    def _link_trace(self, directory) -> None:
        try:
            from raft_tpu.obs import trace

            trace.set_active_profile(directory)
        except Exception:
            pass  # profiling must not depend on the obs layer

    def maybe_start(self, step: int) -> None:
        if self.trace_dir is None or self._running or self._done:
            return
        # Anchor to the first step this run actually executes, so a
        # checkpoint-resumed run still skips its compile steps
        # (absolute mode anchors at 0: start_step IS the global step).
        if self._first_step is None:
            self._first_step = 0 if self.absolute else step
        if step - self._first_step < self.start_step:
            return
        jax.profiler.start_trace(self.trace_dir)
        self._running = True
        self._link_trace(self.trace_dir)

    def maybe_stop(self, step: int, sync_on=None) -> None:
        """``sync_on``: a device array from the traced step (e.g. the loss).
        The step loop dispatches asynchronously, so without a sync the
        trace would stop before the device executed the traced steps."""
        if not self._running:
            return
        if step - self._first_step + 1 >= self.start_step + self.num_steps:
            if sync_on is not None:
                jax.block_until_ready(sync_on)
            jax.profiler.stop_trace()
            self._running = False
            self._done = True
            self._link_trace(None)
            print(f"profiler trace written to {self.trace_dir}",
                  flush=True)

    def close(self) -> None:
        if self._running:
            jax.profiler.stop_trace()
            self._running = False
            self._link_trace(None)


def annotate_step(step: int):
    """Named step annotation shown on the XProf timeline."""
    return jax.profiler.StepTraceAnnotation("train", step_num=step)


def hbm_usage(compiled_or_fn, *args) -> dict:
    """HBM accounting for a jitted step from XLA's buffer assignment.

    The compiled executable knows its device allocation before anything
    runs (arguments + outputs + temps, with donation already applied),
    so the figure exists for a program that has never executed and costs
    no device sync.  (The runtime counterpart is
    ``device.memory_stats()["peak_bytes_in_use"]``, which is a property
    of the process, not of one program.)  Pass either an
    already-``.compile()``d executable or a jitted function plus example
    args.

    Neither of ``CompiledMemoryStats``' two views is complete on every
    backend of jaxlib 0.9.0: XLA:CPU's ``peak_memory_in_bytes`` leaves
    the temporaries out, and XLA:TPU can report ``temp_size_in_bytes``
    as 0 for a program whose peak plainly holds an intermediate.  Each
    is a lower bound of the true peak, so the figure is the larger of
    the two.

    Returns a dict with GiB figures, or ``{"peak_hbm": "unavailable"}``
    if the executable does not expose memory analysis.
    """
    try:
        compiled = (compiled_or_fn if not args
                    else compiled_or_fn.lower(*args).compile())
        ma = compiled.memory_analysis()
        if ma is None:
            return {"peak_hbm": "unavailable"}
        peak = max(ma.peak_memory_in_bytes,
                   ma.argument_size_in_bytes + ma.output_size_in_bytes
                   + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
        gib = float(2 ** 30)
        return {
            "peak_hbm_gb": round(peak / gib, 3),
            "args_gb": round(ma.argument_size_in_bytes / gib, 3),
            "output_gb": round(ma.output_size_in_bytes / gib, 3),
            "temp_gb": round(ma.temp_size_in_bytes / gib, 3),
        }
    except Exception as e:  # pragma: no cover - backend-specific
        return {"peak_hbm": f"unavailable ({type(e).__name__})"}


def probe_error_is_oom(exc: BaseException) -> bool:
    """Whether an allocation-probe failure is an out-of-memory verdict.

    XLA surfaces allocator refusal as RESOURCE_EXHAUSTED (sometimes just
    an "out of memory"/"OOM" message, depending on backend and path).
    Anything else — a lost device, a DEADLINE_EXCEEDED, an INTERNAL
    error — is a *broken probe*, not a measurement."""
    msg = f"{type(exc).__name__}: {exc}".lower()
    return ("resource_exhausted" in msg or "resource exhausted" in msg
            or "out of memory" in msg or "oom" in msg)


def measure_hbm_limit(max_gb: float = 64.0, chunk_mb: int = 256) -> dict:
    """Measured usable device-memory limit via an allocation probe.

    Preference order: the backend's own ``memory_stats()['bytes_limit']``
    (a backend may report none), else allocate
    ``chunk_mb``-MiB live buffers until the allocator refuses — the total
    successfully resident is the *usable* limit, which is what a "fits"
    verdict actually needs (the XLA allocator reserves a slice of the
    16 GB spec for itself, so the spec constant overstates headroom —
    VERDICT r4 weak #4).  TPU-only: the CPU backend would happily swap.

    Only an OOM-classified failure (:func:`probe_error_is_oom`)
    terminates the probe as a measurement; any other error (e.g. the
    runtime losing the device mid-probe) returns the ``"unavailable"`` marker
    so a flaky backend can't write a plausible-but-wrong
    ``HBM_LIMIT.json`` that poisons every downstream "fits" verdict.

    Returns ``{"hbm_limit_gb": float, "source": str}`` or a
    ``{"hbm_limit_gb": "unavailable"}`` marker off-TPU.
    """
    import jax
    import jax.numpy as jnp

    dev = jax.local_devices()[0]
    stats = dev.memory_stats() or {}
    if "bytes_limit" in stats:
        return {"hbm_limit_gb": round(stats["bytes_limit"] / 2**30, 2),
                "source": "memory_stats.bytes_limit"}
    if dev.platform != "tpu":
        return {"hbm_limit_gb": "unavailable",
                "source": f"non-tpu backend {dev.platform!r}"}
    held, total_mb = [], 0
    n = chunk_mb * 1024 * 1024 // 4
    try:
        while total_mb < max_gb * 1024:
            try:
                buf = jax.device_put(jnp.zeros((n,), jnp.float32), dev)
                buf.block_until_ready()
            except Exception as e:
                if probe_error_is_oom(e):
                    break  # allocator refused: that IS the measurement
                return {"hbm_limit_gb": "unavailable",
                        "source": ("allocation probe aborted by non-OOM "
                                   f"{type(e).__name__}: {str(e)[:160]}")}
            held.append(buf)
            total_mb += chunk_mb
    finally:
        del held
    if total_mb < 1024:
        # A sub-GB "limit" means the probe ran against an occupied or
        # broken device, not that the chip has <1 GB — refusing to
        # report it keeps a degenerate artifact from poisoning every
        # downstream "fits" verdict.
        return {"hbm_limit_gb": "unavailable",
                "source": f"allocation probe got only {total_mb} MiB "
                          "(device occupied or broken?)"}
    return {"hbm_limit_gb": round(total_mb / 1024, 2),
            "source": f"allocation probe ({chunk_mb} MiB chunks)"}


def load_hbm_limit(default_gb=None, path=None):
    """The measured device-memory limit from ``HBM_LIMIT.json`` at the
    repo root (written by ``scripts/hbm_limit.py``), else
    ``(default_gb, reason)``.  One loader so the beyond-HBM scripts
    can't drift in how they validate the artifact.  ``path`` overrides
    the artifact location (tests)."""
    import json

    if path is None:
        root = osp.dirname(osp.dirname(osp.dirname(osp.abspath(__file__))))
        path = osp.join(root, "HBM_LIMIT.json")
    if osp.exists(path):
        try:
            with open(path) as f:
                rec = json.load(f)
        except (OSError, ValueError):
            # e.g. truncated by a killed probe — fall back, don't crash
            # the (expensive) run that merely wanted the limit.
            return default_gb, "corrupt HBM_LIMIT.json"
        if not isinstance(rec, dict):
            return default_gb, "corrupt HBM_LIMIT.json"
        v = rec.get("hbm_limit_gb")
        if isinstance(v, (int, float)) and v >= 1.0:
            return float(v), rec.get("source", "HBM_LIMIT.json")
    return default_gb, "no (valid) HBM_LIMIT.json"


def enable_persistent_compile_cache(force: bool = False) -> str:
    """Turn on JAX's persistent XLA compilation cache; returns the cache
    directory ("" when skipped).

    Where it lives is decided outside the code whenever possible: with
    ``JAX_COMPILATION_CACHE_DIR`` set, JAX itself already honours it and
    this function sets no directory.  Without it the cache is
    ``<checkout>/.jax_cache`` (git-ignored) — one fixed path, because
    the path is part of where entries are found again: a directory
    built from a tempdir, a uid, a user name, a pid or a time differs
    between machines and runs and so never hits.

    Called from the CLI mains (train / evaluate / serve / curriculum),
    ``chip_smoke.py`` and the multi-stage scripts, after the backend is
    known and before the first compile — never at import.  A 12-iteration
    RAFT-full train step is minutes of XLA compile; every later process
    on the same machine reads it back in seconds.

    No-op on the CPU backend unless ``force``: jaxlib 0.9.0's XLA:CPU
    loader warns ``cpu_aot_loader.cc ... machine type ... doesn't
    match`` when it reads an entry back and the deserialized train-step
    executable is not trustworthy (earlier jaxlibs aborted the process
    on its first execution), and a cache enabled under the CPU tests
    would be read back by every later test run.  TPU deserialization is
    the supported path.

    Every caller is about to compile, so this is also where the compile
    listener is registered (:func:`listen_for_compiles`), on every
    backend."""
    import os

    listen_for_compiles()
    if jax.default_backend() == "cpu" and not force:
        return ""
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = osp.join(
            osp.dirname(osp.dirname(osp.dirname(osp.abspath(__file__)))),
            ".jax_cache")
        os.makedirs(cache_dir, mode=0o700, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
