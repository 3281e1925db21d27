"""Execution-trace observability for the look-ahead engine (DESIGN.md §14).

* :mod:`repro.obs.tracer` — zero-dependency span recorder; ``trace()``
  installs it, instrumented layers emit PF/TU/PU spans with in-flight depth,
  each also a ``jax.profiler`` annotation.
* :mod:`repro.obs.metrics` — counters/gauges/histograms (canonical home of
  the former ``repro.serve.metrics``; one registry for serve + traces).
* :mod:`repro.obs.report` — overlap efficiency, critical path, and the
  model-vs-measured attainment join.

The timeline is the profiler's own: ``jax.profiler.trace(dir,
create_perfetto_trace=True)`` holds the engine's device-visible scopes
(``repro.PF`` …) on the device ops and these spans on the host.
``report`` is imported lazily by consumers (it pulls in the tune model and
HLO accounting); this package init stays dependency-light so the engine's
instrumentation import can never cycle.
"""
from repro.obs.metrics import (Counter, Gauge, Histogram, Metrics,
                               throughput_summary)
from repro.obs.tracer import Span, Tracer, active, trace

__all__ = ["Span", "Tracer", "active", "trace", "Counter", "Gauge",
           "Histogram", "Metrics", "throughput_summary"]
