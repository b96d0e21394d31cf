"""``heat_tpu.telemetry`` — unified runtime observability.

One registry for everything the runtime can tell you about itself:

- **spans** — ``telemetry.span("site", kind=...)`` (context manager +
  decorator): one record with ``id`` / ``parent`` / ``root`` and the
  ``kind`` of layer boundary it stands at (``entry``, ``launch``,
  ``sync``, ``comm``, ``io``, ``other``), emitted automatically by the
  hot paths: estimator ``fit``/``predict`` and ``ht.spatial`` entries,
  every launch of a compiled program (``jitted()``, ``ht.fuse``
  build/replay, the estimators' own ``jax.jit`` programs; a first call
  carries ``miss=True``), every blocking host read
  (:func:`host_read`), reshards and collectives, checkpoint saves.
  :func:`self_times` gives each span's time less its children's;
- **counters & gauges** — device dispatches, compile-cache hits /
  misses / size, collective invocations with exact-vs-wire byte
  accounting per precision mode (the compression ratio is the live
  gauge ``comm.wire_ratio.<mode>``), guard incidents, checkpoint
  save/load/resume events;
- **one timeline** — while a ``jax.profiler`` trace is being taken every
  span is also a ``jax.profiler.TraceAnnotation`` of the same name, so
  the program's spans lie in the profiler's own trace, on the clock of
  the device planes, and the trace itself switches recording on
  (:func:`recording`): ``jax.profiler.start_trace(dir)`` … ``stop_trace()``
  then :func:`profiled_spans` for the same spans in memory;
- **the start-up record** — always on, like the two counters: what each
  import statement of the package took (kind ``import``) and every
  program's trace, lower and compile-or-load seconds by name (kind
  ``compile``, fed by jax's own monitoring events), because neither can
  wait for ``enable()``.  :func:`startup` gives the records,
  :func:`startup_report` the text an operator reads after a script's first
  fit to see why seconds passed before it; it costs a clock read an import
  statement and three short calls a compiled program, nothing on a warm
  replay;
- **exporters** — ``events()`` / ``snapshot()`` (in memory) and a JSONL
  sink (``set_jsonl(path)``);
- **request tracing** — ``trace_ctx("req-1")`` tags every span and
  event emitted inside the context with the active request ids
  (``rid``), which is how a serve request is walked from the loadgen
  reply through the ``serve:batch`` span (its ``rid`` stat in a profiler
  trace) and the flight-recorder postmortem;
- **streaming histograms & SLOs** — ``observe(name, value)`` feeds a
  fixed-memory log-bucketed :class:`~heat_tpu.telemetry.hist.Histogram`
  (quantiles within a documented ~4.4% relative bound, mergeable across
  threads); :class:`~heat_tpu.telemetry.slo.SloMonitor` turns a latency
  stream into multi-window burn-rate gauges and a structured incident
  when the error budget burns;
- **flight recorder** — :mod:`heat_tpu.telemetry.flight`, an always-on
  bounded ring of recent events that dumps a deterministic postmortem
  JSON whenever an incident records;
- **live endpoint** — :class:`~heat_tpu.telemetry.httpz.MetricsServer`,
  a loopback-only ``/metrics`` (Prometheus text) + ``/healthz`` +
  ``/varz`` listener (``ServeEngine.start_metrics_server``).

Disabled (the default) it costs one predicate per instrumented site and
contributes nothing to compile-cache keys; ``enable(deterministic=True)``
swaps timestamps for a monotone sequence so tests can assert on event
streams bitwise.  ``HEAT_TELEMETRY=1`` enables collection from the
environment, ``HEAT_TELEMETRY_JSONL=<path>`` opens the JSONL sink and
``HEAT_FLIGHT_DIR=<dir>`` points the flight recorder's dumps at a
directory (the hooks of the CI telemetry lane,
scripts/run_test_matrix.sh).  See docs/design.md ("Observability") and the tutorial
walkthrough for a worked example.
"""

import os as _os

from ._core import (
    account_bytes,
    clock,
    counting_dispatches,
    disable,
    dispatch_count,
    enable,
    events,
    gauge,
    inc,
    is_deterministic,
    is_enabled,
    current_trace,
    histogram,
    host_read,
    host_sync_count,
    jsonl_path,
    observe,
    profiled_spans,
    record_dispatch,
    record_event,
    recording,
    reset,
    reset_dispatch_count,
    self_times,
    set_clock,
    set_jsonl,
    set_max_events,
    snapshot,
    span,
    spanned,
    startup,
    startup_report,
    trace_ctx,
)
from .hist import Histogram
from .slo import SloMonitor
from . import flight
from .httpz import MetricsServer, prometheus_text

__all__ = [
    "enable",
    "disable",
    "is_enabled",
    "is_deterministic",
    "enabled",
    "clock",
    "set_clock",
    "span",
    "inc",
    "gauge",
    "record_event",
    "account_bytes",
    "events",
    "snapshot",
    "reset",
    "set_jsonl",
    "jsonl_path",
    "record_dispatch",
    "dispatch_count",
    "reset_dispatch_count",
    "counting_dispatches",
    "recording",
    "spanned",
    "self_times",
    "profiled_spans",
    "startup",
    "startup_report",
    "host_read",
    "host_sync_count",
    "trace_ctx",
    "current_trace",
    "observe",
    "histogram",
    "set_max_events",
    "Histogram",
    "SloMonitor",
    "flight",
    "MetricsServer",
    "prometheus_text",
]


def _env_autostart() -> None:
    """The CI-lane hooks (see the module docstring)."""
    if _os.environ.get("HEAT_TELEMETRY") == "1":
        enable()
    jsonl = _os.environ.get("HEAT_TELEMETRY_JSONL")
    if jsonl:
        enable()
        set_jsonl(jsonl)
    flight_dir = _os.environ.get("HEAT_FLIGHT_DIR")
    if flight_dir:
        flight.set_dump_dir(flight_dir)


_env_autostart()


def __getattr__(name):
    # `telemetry.enabled` must track the live flag; a from-import at
    # package init would freeze the boolean at its import-time value
    if name == "enabled":
        from . import _core

        return _core.enabled
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
