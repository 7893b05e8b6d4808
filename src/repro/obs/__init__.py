"""repro.obs — unified tracing, metrics & flush-accounting layer.

The paper's evaluation currency is operations REMOVED — redundant CASes
and cache flushes elided from PMwCAS — and this package is the lens that
makes those removals (and the wall-clock they buy) first-class,
measurable numbers across the whole stack:

- :mod:`repro.obs.metrics` — the registry: counters, gauges and
  microsecond histograms with labeled series; a process-global default
  (:func:`get_registry`) backs the live committer/service accounting.
- :mod:`repro.obs.trace` — the span tracer: nested wall-clock spans at
  the load-bearing seams (round execute, WAL commit/persist/prune,
  recovery phases, stacked dispatch, scheduler waves, chaos
  crash→recover), near-zero overhead while disabled, thread-safe ring
  buffer while enabled.
- :mod:`repro.obs.export` — JSONL and Chrome-trace exporters (Perfetto
  loads the latter directly) plus the schema validator CI runs.
- :mod:`repro.obs.provenance` — the flush-provenance ledger: a
  thread-local ``flush_reason(component, reason)`` stack the persist
  seam reads, plus the redundant-fence detector counters
  (``flush_fences`` / ``redundant_fences``, DESIGN §13).
- :mod:`repro.obs.slo` — declarative :class:`SloSpec` objectives
  evaluated over sliding observation windows with multi-window burn
  rates; every bench section writes its verdicts as
  ``SLO_<section>.json``.
- :mod:`repro.obs.adapters` — idempotent folds of the five legacy
  ``*Stats`` dataclasses into registry series (duck-typed; this package
  imports nothing above ``repro.pmwcas`` — nothing of ``repro`` at
  all, which is what lets the checkpoint layer use it).

Layering: anything may import ``repro.obs`` (the committer below the
public surface, the service and chaos layers above it, benchmarks);
``repro.obs`` itself has no in-repo dependencies.  The AST surface
guard in ``tests/test_public_surface.py`` enforces both directions.
"""
from .adapters import (fold_check, fold_dispatch, fold_durability,
                       fold_service, fold_workload)
from .export import (chrome_trace, export_chrome_trace, export_jsonl,
                     span_tree, validate_chrome_trace)
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      get_registry, reset_metrics)
from .provenance import (current_flush_reason, flush_reason, record_fence)
from .slo import SloEngine, SloSpec, validate_slo_report
from .trace import (NULL_SPAN, SpanTracer, disable_tracing,
                    enable_tracing, get_tracer, instant, op_tracing,
                    span, tracing_enabled)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "reset_metrics",
    "SpanTracer", "NULL_SPAN", "span", "instant", "get_tracer",
    "enable_tracing", "disable_tracing", "tracing_enabled", "op_tracing",
    "chrome_trace", "export_chrome_trace", "export_jsonl",
    "validate_chrome_trace", "span_tree",
    "flush_reason", "current_flush_reason", "record_fence",
    "SloSpec", "SloEngine", "validate_slo_report",
    "fold_durability", "fold_dispatch", "fold_service", "fold_check",
    "fold_workload",
]
