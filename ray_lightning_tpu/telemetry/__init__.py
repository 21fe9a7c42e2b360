"""Unified telemetry: spans, step stats, fleet views, live monitoring.

The observability subsystem (ISSUEs 2 + 3).  One import surface:

* :class:`Telemetry` / :class:`TelemetryConfig` — the per-rank runtime
  and its tier knobs (``off`` / ``cheap`` default / ``full``), coerced
  from ``telemetry=`` on the strategies or the ``RLT_TELEMETRY`` env bus;
* :class:`SpanTracer` — phase spans with JSONL + Chrome-trace export,
  and ``phase()``: one timed phase as profiler annotation, counter and
  span (:data:`PHASES` names them all);
* :class:`StepStats` — step-time split, throughput, analytic-FLOPs MFU,
  recompile counters, device memory stats;
* :func:`merge_snapshots` / :func:`host_stats` — driver-side fleet
  aggregation (``trainer.telemetry_report``) and straggler host context;
* the **live plane** (ISSUE 3): :class:`HeartbeatPublisher` (worker
  liveness/progress beats over the DriverQueue), :class:`RunMonitor` /
  :class:`MonitorConfig` (driver-side hang/straggler watchdog feeding
  ``trainer.monitor_report``), :class:`FlightRecorder` (crash bundles),
  :class:`RankLogHandler` (rank-tagged log ring + forwarding), and
  :mod:`.export_prom` (OpenMetrics textfile/HTTP export);
* :mod:`.schema` — the wire-schema validators
  ``tests/test_wire_schemas.py`` holds every producer to (device traces
  are read by ``benchmarks/lib/xplane.py``);
* the **SLO & capacity plane** (ISSUE 18): :class:`TimeSeriesStore`
  (bounded fixed-interval ring store with windowed rate/percentile/
  slope/ETA queries), :class:`SloSpec` / :class:`SloEvaluator`
  (multi-window multi-burn-rate alerting), feeding
  ``serve/capacity.py``'s headroom oracle.

See ``docs/OBSERVABILITY.md`` for the workflow.
"""

from ray_lightning_tpu.telemetry.aggregate import (
    format_report,
    host_stats,
    merge_snapshots,
    straggler_ranks,
)
from ray_lightning_tpu.telemetry.flight_recorder import FlightRecorder
from ray_lightning_tpu.telemetry.heartbeat import HeartbeatPublisher
from ray_lightning_tpu.telemetry.logs import RankLogHandler
from ray_lightning_tpu.telemetry.monitor import MonitorConfig, RunMonitor
from ray_lightning_tpu.telemetry.runtime import (
    TIERS,
    Telemetry,
    TelemetryConfig,
)
from ray_lightning_tpu.telemetry.propagate import (
    TraceContext,
    child_context,
    extract,
    inject,
    root_context,
)
from ray_lightning_tpu.telemetry.slo import (
    SloEvaluator,
    SloSpec,
    default_serve_slos,
)
from ray_lightning_tpu.telemetry.spans import PHASES, Span, SpanTracer
from ray_lightning_tpu.telemetry.timeseries import TimeSeriesStore
from ray_lightning_tpu.telemetry.step_stats import (
    StepStats,
    compile_event_count,
    flops_for_module,
    model_flops_per_token,
    peak_flops_per_chip,
    vit_flops_per_example,
)

__all__ = [
    "Telemetry",
    "TelemetryConfig",
    "TIERS",
    "SpanTracer",
    "Span",
    "PHASES",
    "TraceContext",
    "root_context",
    "child_context",
    "inject",
    "extract",
    "StepStats",
    "HeartbeatPublisher",
    "RunMonitor",
    "MonitorConfig",
    "FlightRecorder",
    "RankLogHandler",
    "model_flops_per_token",
    "vit_flops_per_example",
    "flops_for_module",
    "peak_flops_per_chip",
    "compile_event_count",
    "merge_snapshots",
    "host_stats",
    "straggler_ranks",
    "format_report",
    "TimeSeriesStore",
    "SloSpec",
    "SloEvaluator",
    "default_serve_slos",
]
