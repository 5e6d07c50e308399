"""CacheFlow observability layer (DESIGN.md §15).

``registry``  — catalog-enforced counters/gauges/histograms
(:data:`METRIC_CATALOG` is the single source of metric names, and
:data:`SPAN_CATALOG` of span names, :data:`SCOPE_CATALOG` of the
programs' named scopes; codelint checks every literal against
them).  ``spans`` — the in-program span recorder on the host clock that a
device trace maps onto (on under ``CACHEFLOW_TELEMETRY=1`` or while a
profiler collects).  ``telemetry`` — the opt-in
:class:`Telemetry` hook ``EngineCore`` drives (``telemetry=`` /
``CACHEFLOW_TELEMETRY=1`` / ``serve --telemetry``).  ``timeline`` — the
Perfetto/Chrome trace-event exporter
(``python -m repro.obs.timeline trace.json``).
"""
from repro.obs.registry import (METRIC_CATALOG, SCOPE_CATALOG, SPAN_CATALOG,
                                Counter, Gauge, Histogram, MetricsRegistry)
from repro.obs.telemetry import Telemetry, telemetry_env_enabled
from repro.obs.timeline import (ops_to_chrome, result_to_chrome,
                                trace_to_chrome)

__all__ = [
    "METRIC_CATALOG", "SCOPE_CATALOG", "SPAN_CATALOG", "Counter", "Gauge",
    "Histogram", "MetricsRegistry",
    "Telemetry", "telemetry_env_enabled",
    "ops_to_chrome", "result_to_chrome", "trace_to_chrome",
]
