"""``repro.obs`` — zero-dependency tracing + metrics for the repro.

Spans (:func:`span`, monotonic timing, rid correlation, contextvar
nesting), a typed :class:`MetricsRegistry` (counters / gauges /
log-bucket histograms, Prometheus text exposition), a validated JSONL
export, report rendering, and :mod:`repro.obs.xla`, which resolves a
profile's device ops to the ``jax.named_scope`` they ran under.
Disabled by default; the no-op fast path is benchmarked and gated in
``scripts/check.sh``.  See the "Observability contract" section of
``docs/api.md``.

Quick start::

    from repro import obs

    with obs.capture(jsonl="trace.jsonl") as get_events:
        search(q, store, k=5)
    print(obs.report.stage_table(get_events()))
"""
from repro.obs import export, metrics, report, trace, xla
from repro.obs.export import OBS_SCHEMA_VERSION, SchemaError, read_jsonl, validate_events, write_jsonl
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry, record_stats, registry
from repro.obs.trace import (
    Span,
    bind,
    capture,
    current_rid,
    current_span_id,
    disable,
    drain,
    enable,
    enabled,
    event,
    events,
    exception_chain,
    new_rid,
    span,
    start_span,
)

__all__ = [
    "OBS_SCHEMA_VERSION",
    "SchemaError",
    "Span",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "bind",
    "capture",
    "current_rid",
    "current_span_id",
    "disable",
    "drain",
    "enable",
    "enabled",
    "event",
    "events",
    "exception_chain",
    "export",
    "metrics",
    "new_rid",
    "read_jsonl",
    "record_stats",
    "registry",
    "report",
    "span",
    "start_span",
    "trace",
    "validate_events",
    "write_jsonl",
    "xla",
]
