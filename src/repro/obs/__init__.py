"""Observability subsystem: metrics, simulated-time traces, plan audits.

Layered over the federated engine without touching its hot path:

  records   versioned typed records (counter/gauge/series) + the single
            round-summary constructor every producer shares
  metrics   MetricsPipeline fanning records into pluggable sinks
            (memory / jsonl / csv)
  trace     Chrome/Perfetto trace-event rendering of the simulated
            schedule
  jitwatch  named engine programs: dispatches, compiles, host spans on
            the profiler's clock, the op-to-layer map of a device trace
  audit     reconcile ExecutionPlan predictions against observed runs
  debug     env/flag-wired jax_debug_nans / x64 toggles

See src/repro/obs/README.md for the schema and sink contracts.
"""
from repro.obs import debug, jitwatch
from repro.obs.audit import AuditReport, PlanDriftError, audit_run
from repro.obs.metrics import (CsvSink, JsonlSink, MemorySink,
                               MetricsPipeline, make_sink)
from repro.obs.records import (SCHEMA_VERSION, MetricRecord,
                               annotate_schedule, counter, fedbuff_summary,
                               gauge, records_from_round, round_summary,
                               series)
from repro.obs.trace import (TraceBuilder, span_seconds_by_track,
                             validate_trace)

# env-gated: a no-op unless REPRO_DEBUG_NANS / REPRO_X64 are set
debug.configure_from_env()

__all__ = [
    "AuditReport", "CsvSink", "JsonlSink", "MemorySink", "MetricRecord",
    "MetricsPipeline", "PlanDriftError", "SCHEMA_VERSION", "TraceBuilder",
    "annotate_schedule", "audit_run", "counter", "debug",
    "fedbuff_summary", "gauge", "jitwatch", "make_sink",
    "records_from_round", "round_summary", "series",
    "span_seconds_by_track", "validate_trace",
]
