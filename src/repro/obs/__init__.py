"""repro.obs — observability for the heterogeneous runtime.

Three pillars (ISSUE 8):

* :mod:`repro.obs.trace` — a lock-cheap, ring-buffered span tracer with
  typed events and Chrome/Perfetto ``trace_event`` export.
* :mod:`repro.obs.metrics` — counters / gauges / fixed-bucket histograms
  with Prometheus text exposition, fed at collect time from the existing
  ``Telemetry`` / ``ServeStats`` / ``TenantStats`` views (no double
  bookkeeping on the hot path).
* :mod:`repro.obs.flightrec` — a flight recorder that dumps the last N
  events + a runtime ``stats()`` snapshot to ``results/flightrec-*.json``
  on timeouts, admission rejections, and quarantines.

The package deliberately imports nothing from ``repro.soc`` /
``repro.core`` / ``repro.engines`` so every execution layer can import
it without cycles.
"""

from repro.obs.flightrec import FlightRecorder
from repro.obs.metrics import (MetricsRegistry, REGISTRY, parse_prometheus,
                               render_prometheus)
from repro.obs.trace import (EVENT_KINDS, TraceEvent, Tracer, annotate,
                             get_default_tracer, load_chrome_trace,
                             set_default_tracer, trace_scope,
                             validate_events)

__all__ = [
    "EVENT_KINDS", "FlightRecorder", "MetricsRegistry", "REGISTRY",
    "TraceEvent", "Tracer", "annotate", "get_default_tracer",
    "load_chrome_trace", "parse_prometheus", "render_prometheus",
    "set_default_tracer", "trace_scope", "validate_events",
]
