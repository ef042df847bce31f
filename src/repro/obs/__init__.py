"""Unified tracing + telemetry for the serving stack.

``repro.obs.tracer`` is the hot-path-safe recording core (plain-python
appends only — linted wholesale by ``repro.analysis.hotpath_lint``);
``repro.obs.export`` renders the recorded rings into Perfetto JSON,
Prometheus text and summary tables off the step path.  See
``docs/observability.md`` for the trace schema and track layout.
"""
from repro.obs.export import (
    d2h_summary,
    prometheus_text,
    reuse_by_adapter,
    to_perfetto,
    write_perfetto,
)
from repro.obs.tracer import (
    TRACE_RING_KEEP,
    TRACE_RING_MAX,
    Tracer,
    trace_enabled_default,
)

__all__ = [
    "TRACE_RING_KEEP",
    "TRACE_RING_MAX",
    "Tracer",
    "d2h_summary",
    "prometheus_text",
    "reuse_by_adapter",
    "to_perfetto",
    "trace_enabled_default",
    "write_perfetto",
]
