"""Observability: structured tracing, typed metrics, machine-readable reports.

The paper reads the relative costs of its methods straight off Hadoop's
built-in counters and per-phase runtimes; this package is that instrument
panel for the reproduction -- zero external dependencies, and a **true no-op
when disabled**: the hot paths (wave dispatch, serving batches) see a shared
null singleton, no added host syncs, no allocations.

  * :mod:`repro.obs.trace`   -- nested span tracer (context-manager API, host
    wall clock, each span also a ``jax.profiler.TraceAnnotation`` on the
    profiler's clock, never a device sync) exporting Chrome/Perfetto
    ``trace_event`` JSON;
  * :mod:`repro.obs.metrics` -- typed registry of counters, gauges and
    fixed-boundary histograms (p50/p95/p99 without sample storage), plus the
    canonical job-counter glossary and merge/normalization policy that the
    executor paths share;
  * :mod:`repro.obs.report`  -- JSONL sink, human-readable summary table,
    environment metadata stamp, and the trace/metrics schema validators the
    CI smoke step runs.
"""
from .metrics import (COUNTER_DOC, MetricsRegistry, get_registry,
                      merge_counter_dicts, normalize_counters, null_registry,
                      set_registry)
from .trace import NULL_SPAN, Tracer, disable_tracing, enable_tracing, \
    get_tracer, span, span_coverage
from .report import (environment_metadata, setup, summary_table,
                     validate_metrics, validate_trace, write_jsonl)

__all__ = [
    "COUNTER_DOC", "MetricsRegistry", "get_registry", "merge_counter_dicts",
    "normalize_counters", "null_registry", "set_registry",
    "NULL_SPAN", "Tracer", "disable_tracing", "enable_tracing", "get_tracer",
    "span", "span_coverage",
    "environment_metadata", "setup", "summary_table", "validate_metrics",
    "validate_trace", "write_jsonl",
]
