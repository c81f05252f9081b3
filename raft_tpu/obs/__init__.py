"""Unified telemetry layer (docs/OBSERVABILITY.md).

Three composable pieces, shared by train/eval/serve:

- :class:`MetricRegistry` — thread-safe counters / gauges / histograms
  (bounded reservoirs), renderable as Prometheus text exposition
  (``GET /metrics`` on the serving CLI).
- :func:`span` — time a block into a histogram, optionally emitting a
  JSONL event.
- :class:`EventSink` — structured JSONL event log under
  ``RAFT_TELEMETRY_DIR`` (or ``--telemetry-dir``); one record per
  event with wall+monotonic timestamps, step, and process index.
  ``scripts/telemetry_summary.py`` folds a log into a one-line JSON summary.
- :class:`Tracer` / :func:`trace_span` — distributed request/step
  trace trees emitted as ``trace_span`` events through the sink
  (``obs.trace``; reconstructed by ``scripts/trace_report.py``).

Hot-path contract: recording is lock-cheap, never forces a device
sync, and the whole layer is a no-op when disabled.

Cost-model accounting lives in ``obs.cost`` (imported directly, like
the health modules): per-compiled-program FLOPs/bytes from XLA's
``cost_analysis()`` with analytic Pallas fallbacks, the per-device-kind
peak table, and the MFU / roofline derivations behind the
``raft_cost_*`` gauges and ``cost_report`` events
(docs/OBSERVABILITY.md → "Cost model & roofline").

Training health lives in the sibling modules (imported directly, not
re-exported, to keep this package import light): ``obs.health`` — the
in-graph non-finite guard helpers, the host-side :class:`HealthMonitor`
and forensic bundles — and ``obs.watchdog`` — the stall
:class:`StallWatchdog` and the SIGQUIT stack dump
(docs/OBSERVABILITY.md → "Training health").
"""

from raft_tpu.obs.events import (
    EventSink,
    default_sink,
    reset_default_sink,
)
from raft_tpu.obs.exposition import CONTENT_TYPE as PROMETHEUS_CONTENT_TYPE
from raft_tpu.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricRegistry,
    default_registry,
    span,
)
from raft_tpu.obs.trace import (
    Tracer,
    default_tracer,
    record_span,
    trace_span,
    use_context,
)

__all__ = [
    "Counter",
    "EventSink",
    "Gauge",
    "Histogram",
    "MetricRegistry",
    "PROMETHEUS_CONTENT_TYPE",
    "Tracer",
    "default_registry",
    "default_sink",
    "default_tracer",
    "record_span",
    "reset_default_sink",
    "span",
    "trace_span",
    "use_context",
]
