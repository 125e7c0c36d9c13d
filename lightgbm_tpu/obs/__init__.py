"""Structured training telemetry.

The reference's only introspection is the compile-time TIMETAG section
timer (ref: include/LightGBM/utils/common.h:978); SURVEY §5 calls the
profiling gap out explicitly, and ad-hoc wall-clock timing around
asynchronous device dispatch measures the enqueue, not the work.  This
package is the permanent, low-overhead replacement:

- :class:`Telemetry` (registry.py) — thread-safe registry of counters,
  gauges and per-section timing distributions, plus a structured event
  stream (degradations with reasons, compile events, per-iteration
  records) that can sink to a JSONL file;
- :class:`JsonlSink` (events.py) — the rank-aware JSONL writer behind
  ``telemetry_out=<path>``;
- jaxmon.py — ``jax.monitoring`` bridge (XLA compile events) and device
  memory stats;
- trace.py — Perfetto/Chrome-trace exporter behind ``trace_out=<path>``
  (one track per rank, spans for sections/collectives/compiles);
- :class:`HealthAuditor` (health.py) — periodic cross-rank model-hash +
  straggler auditing behind ``health_check_period``;
- :class:`MetricsExporter` (export.py) — live OpenMetrics/Prometheus
  HTTP endpoint over the registry behind ``metrics_port=<p>`` (per-rank
  ports under multi-process; rank 0 appends the fleet counter view);
- reqtrace.py — request-scoped serving traces: a ``trace_id`` minted at
  ``PredictionService.submit()`` rides through the micro-batcher and
  engine dispatch into one ``serve_access`` JSONL record and one
  Perfetto span per request;
- :class:`ProfileControl` (export.py) — the on-demand profiling handoff
  behind ``POST /profile?iters=N``: the exporter arms it, the driver
  opens/closes a bounded ``jax.profiler`` window at its next drain
  boundary;
- :class:`CostLedger` (cost.py) — device-time cost ledger: per fresh
  executable signature ``cost_analysis()`` joined with measured wall
  times, collective payloads and the analytic histogram byte model into
  ``cost.*`` gauges and per-batch ``cost_ledger`` records;
- report.py — the schema-versioned consolidated run report
  (``run_report_out=<path>`` / ``GET /report``) that
  ``scripts/run_diff.py`` compares with deterministic-counter
  strictness;
- drift.py — the drift & lineage plane: training-data profiles
  (embedded in model artifacts + checkpoints), PSI/JS divergence, the
  serving-side :class:`DriftMonitor` and the provenance record chained
  through rollovers (docs/Observability.md §13);
- :class:`SloEngine` (slo.py) — the SLO plane: declarative objectives
  (built-in catalog + ``slo_config=<path>``) evaluated on a host-side
  ticker with multi-window burn-rate alerting, ``alert`` events,
  fleet/liveness watchdogs and bounded incident artifacts
  (docs/Observability.md §14).

Every recording method is a no-op behind a single attribute check while
the registry is disabled, so instrumentation stays in the hot driver
paths permanently, like the reference's TIMETAG sections.
"""
from .cost import CostLedger
from .drift import (DriftMonitor, build_profile, build_provenance,
                    canonical_json, js_divergence, profile_digest, psi)
from .events import JsonlSink
from .export import MetricsExporter, ProfileControl, render_openmetrics
from .health import HealthAuditor, model_state_hash
from .jaxmon import device_memory_stats, memory_watermarks
from .registry import Telemetry, allgather_json
from .report import (build_report, compare_reports, load_report,
                     render_markdown, write_report)
from .slo import BUILTIN_OBJECTIVES, SloEngine, SloSpec
from .trace import chrome_trace_events, write_trace

__all__ = ["Telemetry", "JsonlSink", "device_memory_stats",
           "memory_watermarks", "allgather_json", "HealthAuditor",
           "model_state_hash", "chrome_trace_events", "write_trace",
           "MetricsExporter", "render_openmetrics", "ProfileControl",
           "CostLedger", "build_report", "compare_reports",
           "load_report", "render_markdown", "write_report",
           "DriftMonitor", "build_profile", "build_provenance",
           "canonical_json", "js_divergence", "profile_digest", "psi",
           "SloEngine", "SloSpec", "BUILTIN_OBJECTIVES"]
