"""Observability substrate: virtual-clock tracing, metrics, flight recorder.

Three independent parts, all publishing through the :mod:`repro.core.events`
shim so instrumented call sites stay a single global load when disabled:

* :class:`SpanTracer` (:mod:`repro.obs.trace`) — spans and counter tracks on
  the shared virtual clock, exported as Chrome/Perfetto ``trace_event`` JSON.
* :class:`MetricsRegistry` (:mod:`repro.obs.metrics`) — counters, gauges and
  explicit-bucket histograms with Prometheus text exposition and a one-shot
  JSON dump.
* :class:`FlightRecorder` (:mod:`repro.obs.recorder`) — a bounded ring of
  recent balancer decisions dumped to disk when an SLO burn or an invariant
  contract (IV00x) trips.

Beside them, on the host clock and outside the virtual set,
:class:`HostSpans` (:mod:`repro.obs.host`) keeps the wall-clock spans that
:func:`repro.core.events.span` opens inside the engine step, and
:class:`CompileCounter` counts the programs JAX lowers.
"""

from repro.obs.host import CompileCounter, HostSpan, HostSpans
from repro.obs.metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                               TPOT_BUCKETS, TTFT_BUCKETS, lint_exposition)
from repro.obs.recorder import DecisionRecord, FlightRecorder
from repro.obs.trace import SpanTracer, validate_trace

__all__ = [
    "SpanTracer",
    "validate_trace",
    "MetricsRegistry",
    "Counter",
    "Gauge",
    "Histogram",
    "TTFT_BUCKETS",
    "TPOT_BUCKETS",
    "lint_exposition",
    "FlightRecorder",
    "DecisionRecord",
    "HostSpans",
    "HostSpan",
    "CompileCounter",
]
