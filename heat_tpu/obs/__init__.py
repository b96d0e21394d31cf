"""``heat_tpu.obs`` — the serving-observability facade.

One import surface for the request-scoped observability layer built on
:mod:`heat_tpu.telemetry` (docs/design.md §19):

- :func:`trace_ctx` — request-scoped trace context.  Everything emitted
  inside ``with obs.trace_ctx("req-42"):`` — spans, events, their stats in a
  profiler trace, flight-recorder notes — carries the request id under
  ``rid``, and the serve stack propagates the ids across the
  MicroBatcher queue onto the per-micro-batch ``serve:batch`` span, so
  one request is walkable end to end: loadgen reply → tagged serve span
  → profiler-trace event → postmortem dump.
- :func:`observe` / :class:`Histogram` — fixed-memory streaming
  latency distributions (log8 buckets, ~4.4% relative quantile bound,
  mergeable across threads).
- :class:`SloMonitor` — multi-window burn-rate SLO alerting that
  publishes ``slo.*`` gauges and records a structured incident on burn.
- :mod:`flight <heat_tpu.telemetry.flight>` — the always-on flight
  recorder whose deterministic postmortem JSON dumps on every incident.
- :class:`MetricsServer` — the loopback-only ``/metrics`` + ``/healthz``
  + ``/varz`` endpoint (``ServeEngine.start_metrics_server`` binds one
  with the engine's ``varz``).

Everything here is re-exported from :mod:`heat_tpu.telemetry`; this
module adds no state — it exists so serving code and operators have one
obvious name for the observability toolkit.
"""

from ..telemetry import (  # noqa: F401
    Histogram,
    MetricsServer,
    SloMonitor,
    current_trace,
    flight,
    histogram,
    observe,
    prometheus_text,
    trace_ctx,
)
from ..telemetry._core import snapshot  # noqa: F401

__all__ = [
    "trace_ctx",
    "current_trace",
    "observe",
    "histogram",
    "snapshot",
    "Histogram",
    "SloMonitor",
    "flight",
    "MetricsServer",
    "prometheus_text",
]
