"""Process-wide telemetry runtime: spans, counters, gauges, events.

This module is the single registry behind ``heat_tpu.telemetry`` — every
instrumented hot path (the ``jitted()`` replay wrapper, ``ht.fuse`` build
and replay, the communication layer's reshards and collectives, the
compressed rings' wire-byte accounting, guard incidents, checkpoint
save/load/resume) reports here, and every exporter (``snapshot()``, the
JSONL sink, the profiler sink below) reads from here.

One timeline
------------
A span is one record (:class:`_Span`): ``site``, ``kind`` (the layer
boundary it stands at), ``id`` / ``parent`` / ``root``, ``ts``, ``dur``.
It is kept in memory (:func:`events`) and, while a jax profiler trace is
being taken, it is also a ``jax.profiler.TraceAnnotation`` of the same
name: the program's spans then lie in the profiler's own trace, on the
launching thread, on the clock of the device planes.  There is no second
trace file.  Recording is on after :func:`enable` **or while the profiler
runs** (:func:`recording`), so tracing a run records its spans with no
switch of its own, and an untraced run records nothing.

Overhead contract
-----------------
Telemetry is off by default and *disabled mode costs one predicate per
site*: instrumented library code guards every report with
``if _core.enabled:`` (counters, events) or ``if _core.recording():``
(span sites: the flag load plus one ``TraceAnnotation.is_enabled()``
call) — no object allocation, no lock, no clock read.  Enabling flips one
module-level flag; nothing is registered with the compile-cache key
context, so toggling telemetry can never change what a cached program
means or force a retrace (asserted by tests/test_telemetry.py).

The always-on state is two counters and the start-up record.  The
*dispatch counter* (it predates telemetry; tier-1 dispatch-count gates
consume it through the :mod:`heat_tpu.core._tracing` shim) and the
*host-sync counter* (:func:`host_read`: blocking device-to-host reads) keep
counting with telemetry disabled and are guarded by the registry lock, so
threaded serving does not lose increments.

The start-up record
-------------------
What a process does before its first result is mostly not the program's
own work: importing, and tracing, lowering and compiling (or loading from
the persistent cache) every program once.  Neither can wait for
:func:`enable`, so one bounded list (:func:`startup`) keeps both, in the
shape of a span event and with telemetry disabled:

- kind ``import``: one record a statement of ``heat_tpu/__init__.py`` and
  ``heat_tpu/core/__init__.py`` (``import:jax``, ``import:core.io``, ...),
  the latter's as children of ``import:core``, all under the root
  ``import:heat_tpu``.  The two files stamp ``time.monotonic()`` after each
  statement and hand the stamps over once (:func:`record_imports`);
- kind ``compile``: one record each time jax reports that it traced
  (``compile:trace``), lowered (``compile:lower``) or compiled or loaded
  (``compile:backend``, with ``cache_hit``) a program, named by ``fun``
  (:func:`record_compile_stage`, called by the one ``jax.monitoring``
  listener of ``core/_compile.py``).  Traces nest (a ``jax.numpy`` function
  traced inside a program's trace reports its own), so a total is the union
  of the intervals (:func:`covered_s`), never the sum.

It is written when an import statement of the package finishes or jax
compiles, never on a warm replay: the one-predicate contract of the span
sites stands as it was.  :func:`reset` leaves it (like the counters);
overflow is counted (``snapshot()["startup"]["dropped"]``).  While
:func:`recording`, a ``compile`` record is also an event on the stream,
the child of the ``launch`` span that caused it.  :func:`startup_report`
is the operator's reading: call it after a script's first fit.

Determinism
-----------
``enable(deterministic=True)`` replaces the wall clock with a monotone
integer sequence: every ``clock()`` read returns the next integer, so
span timestamps and durations become pure functions of the event order
and two identical runs (after ``reset()``) produce bitwise-identical
event streams.  ``set_clock()`` injects an arbitrary clock — the
resilience incident log stamps its records through :func:`clock`, so
chaos-lane runs can pin time entirely.

Kept free of jax imports (like :mod:`heat_tpu.core._tracing`) so every
core module can import it without ordering constraints.
"""

from __future__ import annotations

import contextlib
import contextvars
import functools
import json
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .hist import Histogram

__all__ = [
    "enabled",
    "enable",
    "disable",
    "is_enabled",
    "is_deterministic",
    "clock",
    "set_clock",
    "recording",
    "install_profiler",
    "span",
    "spanned",
    "self_times",
    "profiled_spans",
    "covered_s",
    "record_imports",
    "record_compile_stage",
    "startup",
    "startup_report",
    "host_read",
    "host_sync_count",
    "inc",
    "gauge",
    "observe",
    "histogram",
    "record_event",
    "account_bytes",
    "events",
    "snapshot",
    "reset",
    "set_jsonl",
    "jsonl_path",
    "set_max_events",
    "trace_ctx",
    "current_trace",
    "record_dispatch",
    "dispatch_count",
    "reset_dispatch_count",
    "counting_dispatches",
]

#: THE module-level flag.  Instrumented hot paths read this attribute
#: directly (``if _core.enabled:``); everything else in this module is
#: behind that predicate.
enabled: bool = False

_lock = threading.RLock()
_deterministic = False
_det_seq = 0
_wall: Callable[[], float] = time.monotonic  # injectable via set_clock()

_counters: Dict[str, int] = {}
_gauges: Dict[str, float] = {}
#: per-site span aggregates: site -> [count, total_seconds]
_spans: Dict[str, List[float]] = {}
#: streaming histograms (telemetry.hist.Histogram) fed by observe()
_hists: Dict[str, Histogram] = {}
#: the bounded event list (newest last); spans append one event at exit
_events: List[dict] = []
_MAX_EVENTS = 1 << 16

#: the flight recorder's always-on ring append, registered by
#: :mod:`heat_tpu.telemetry.flight` at import so _emit never has to
#: import it (None until that module loads)
_flight_append: Optional[Callable[[dict], None]] = None

#: the ambient request-trace ids (tentpole: request-scoped tracing).
#: A contextvar, not a threading.local: the serve engine re-establishes
#: it per micro-batch from the Request records, so worker threads and
#: async callers both see the right ids.
_trace_var: "contextvars.ContextVar[Tuple[str, ...]]" = contextvars.ContextVar(
    "heat_tpu_trace_ids", default=()
)

#: optional JSONL sink: every event is also appended to this file
_jsonl = None  # type: Optional[Any]
_jsonl_path: Optional[str] = None

#: the profiler sink: ``jax.profiler.TraceAnnotation`` and its static
#: ``is_enabled``, installed by :func:`install_profiler` from a module that
#: imports jax anyway (``core/_compile.py``) so this one never has to.
#: Until then ``bool`` stands in: ``bool()`` is ``False``.
_annotation: Optional[Any] = None
_profiler_on: Callable[[], bool] = bool
#: what the last poll of the profiler saw, and the positions in ``_events``
#: at which a poll first saw it on and first saw it off again
_prof_seen = False
_prof_lo = 0
_prof_hi: Optional[int] = 0

#: the innermost open span of this context as ``(id, root)``
_span_var: "contextvars.ContextVar[Optional[Tuple[int, int]]]" = contextvars.ContextVar(
    "heat_tpu_span", default=None
)
_next_id = 0

#: the layer boundary a span stands at; readers select by it (``import``
#: and ``compile`` are the start-up record's, see :func:`startup`)
KINDS = ("entry", "launch", "sync", "comm", "io", "other", "import", "compile")

#: the start-up record (module docstring): always on, bounded, kept by reset()
_startup: List[dict] = []
_MAX_STARTUP = 1 << 12
_startup_dropped = 0
_COMPILE_SITES = ("compile:trace", "compile:lower", "compile:backend")

#: thread ids -> small stable indices (first-seen order), so exported
#: ``tid`` values are deterministic in single-threaded runs
_tids: Dict[int, int] = {}


# --------------------------------------------------------------------- #
# clock                                                                 #
# --------------------------------------------------------------------- #
def clock() -> float:
    """The telemetry timestamp source (seconds, monotonic).

    In deterministic mode every read returns the next integer of a
    monotone sequence instead of a wall-clock value; :func:`reset`
    rewinds the sequence, making event streams bitwise replayable.
    The resilience incident log (:mod:`heat_tpu.resilience.incidents`)
    stamps its records through this function, so a test can pin incident
    timestamps with :func:`set_clock` or deterministic mode.
    """
    global _det_seq
    if _deterministic:
        with _lock:
            t = float(_det_seq)
            _det_seq += 1
        return t
    return _wall()


def set_clock(fn: Optional[Callable[[], float]]) -> None:
    """Inject a replacement wall clock (``None`` restores
    ``time.monotonic``).  Ignored while deterministic mode is active."""
    global _wall
    _wall = time.monotonic if fn is None else fn


# --------------------------------------------------------------------- #
# enable / disable                                                      #
# --------------------------------------------------------------------- #
def enable(deterministic: bool = False) -> None:
    """Turn telemetry collection on.

    ``deterministic=True`` switches :func:`clock` to the monotone
    integer sequence (see the module docstring)."""
    global enabled, _deterministic, _det_seq
    with _lock:
        _deterministic = bool(deterministic)
        if _deterministic:
            _det_seq = 0
        enabled = True


def disable() -> None:
    """Turn telemetry collection off (recorded data stays until
    :func:`reset`; :func:`snapshot` answers ``{}`` while disabled)."""
    global enabled, _deterministic
    with _lock:
        enabled = False
        _deterministic = False


def is_enabled() -> bool:
    return enabled


def install_profiler(annotation) -> None:
    """Hand over ``jax.profiler.TraceAnnotation`` (the sink of every
    recorded span, and through its ``is_enabled`` the switch)."""
    global _annotation, _profiler_on
    _annotation = annotation
    _profiler_on = annotation.is_enabled


def recording() -> bool:
    """True when spans are being recorded: :func:`enable` was called, or a
    jax profiler trace is being taken.  The guard of every span site; it is
    also the poll that notes where in the event list a trace began and
    ended (:func:`profiled_spans`)."""
    on = _profiler_on()
    if on != _prof_seen:
        _profiler_edge(on)
    return on or enabled


def _profiler_edge(on: bool) -> None:
    global _prof_seen, _prof_lo, _prof_hi
    with _lock:
        if on == _prof_seen:
            return
        _prof_seen = on
        if on:
            _prof_lo, _prof_hi = len(_events), None
        else:
            _prof_hi = len(_events)


def is_deterministic() -> bool:
    return _deterministic


def reset() -> None:
    """Drop all recorded counters, gauges, span aggregates, and events,
    and rewind the deterministic sequence and the span ids.  The dispatch
    and host-sync counters are NOT touched — tests scope them with
    :func:`counting_dispatches` or a difference of :func:`host_sync_count` —
    and neither is the start-up record (:func:`startup`): what the process
    imported and compiled does not happen again."""
    global _det_seq, _next_id, _prof_lo, _prof_hi
    with _lock:
        _counters.clear()
        _gauges.clear()
        _spans.clear()
        _hists.clear()
        _events.clear()
        _tids.clear()
        _det_seq = 0
        _next_id = 0
        _prof_lo, _prof_hi = 0, (None if _prof_seen else 0)


# --------------------------------------------------------------------- #
# emission                                                              #
# --------------------------------------------------------------------- #
def _tid() -> int:
    ident = threading.get_ident()
    t = _tids.get(ident)
    if t is None:
        t = len(_tids) + 1
        _tids[ident] = t
    return t


def _emit(ev: dict) -> None:
    """Append one event under the lock: bounded in-memory list, JSONL
    sink and the flight-recorder ring.

    Overflow of the bounded list is NEVER silent: the drop is counted
    under ``telemetry.events.dropped`` — surfaced by ``snapshot()`` and
    the ``/metrics`` endpoint — so a long-running server that outlives
    the buffer shows exactly how much of the stream it lost.  The JSONL
    sink and the flight ring still receive the event (each is bounded or
    externally drained on its own)."""
    with _lock:
        if len(_events) < _MAX_EVENTS:
            _events.append(ev)
        else:
            _counters["telemetry.events.dropped"] = (
                _counters.get("telemetry.events.dropped", 0) + 1
            )
        if _jsonl is not None:
            _jsonl.write(json.dumps(ev, sort_keys=True, default=str) + "\n")
        if _flight_append is not None:
            _flight_append(ev)


def set_max_events(n: Optional[int]) -> int:
    """Cap the bounded in-memory event list at ``n`` (``None`` restores
    the default 2**16); returns the previous cap.  Tests shrink the cap
    to exercise the ``telemetry.events.dropped`` overflow accounting
    without emitting 65k events."""
    global _MAX_EVENTS
    with _lock:
        prev = _MAX_EVENTS
        _MAX_EVENTS = (1 << 16) if n is None else int(n)
    return prev


def record_event(etype: str, site: str = "", **fields) -> None:
    """Record one instant event (guard incidents, checkpoint saves,
    compile-cache misses …) of type ``etype``.  No-op while disabled.
    Events emitted inside a :func:`trace_ctx` carry the active request
    ids under ``rid``."""
    if not enabled:
        return
    ev = {"type": etype, "site": site, "ts": clock(), "tid": _tid()}
    rids = _trace_var.get()
    if rids:
        ev["rid"] = list(rids)
    ev.update(fields)
    _emit(ev)


def inc(name: str, n: int = 1) -> None:
    """Add ``n`` to a named counter.  No-op while disabled."""
    if not enabled:
        return
    with _lock:
        _counters[name] = _counters.get(name, 0) + n


def gauge(name: str, value: float) -> None:
    """Set a named gauge to ``value``.  No-op while disabled."""
    if not enabled:
        return
    with _lock:
        _gauges[name] = value


def observe(name: str, value: float) -> None:
    """Record one observation into the named streaming histogram
    (:class:`heat_tpu.telemetry.hist.Histogram` — fixed memory,
    log-bucketed, quantiles within the documented ~4.4% relative bound).
    No-op while disabled; the histogram appears in ``snapshot()`` under
    ``hists`` and on ``/metrics`` as a Prometheus histogram."""
    if not enabled:
        return
    with _lock:
        h = _hists.get(name)
        if h is None:
            h = _hists[name] = Histogram()
        h.record(value)


def histogram(name: str) -> Optional[Histogram]:
    """The live histogram registered under ``name`` (None if nothing has
    been observed there).  The object is shared — copy() before mutating."""
    with _lock:
        return _hists.get(name)


# --------------------------------------------------------------------- #
# request-scoped trace context                                          #
# --------------------------------------------------------------------- #
@contextlib.contextmanager
def trace_ctx(*request_ids):
    """Tag everything telemetry records in this context with request ids.

    The tentpole of request-scoped observability: ``trace_ctx("rq-17")``
    installs the id in a contextvar, and every span and instant event
    that closes inside the context carries ``rid=[...]`` — on the event
    stream, in the JSONL sink, in the flight-recorder ring, and as the
    ``rid`` stat of the span's event in a profiler trace, so one slow
    request can be walked
    from its reply back through the micro-batch's ``serve:*`` span and
    any nested ``comm:*`` spans to the device dispatch that served it.

    Nested contexts ACCUMULATE: a micro-batch context carrying every
    coalesced request's id may sit inside (or around) a single request's
    context, and the union is what lands on the events.  Ids may be
    strings or anything ``str()``-able; an iterable argument is
    flattened one level so ``trace_ctx(ids_list)`` works.

    Cost: one contextvar set/reset per ``with`` block — no predicate on
    the telemetry flag, because the context must already be installed
    when collection is enabled mid-request; the per-site disabled cost
    contract is untouched (sites still guard on ``_core.enabled``).

    Host-side only: inside a jit/shard_map/fuse-traced body the context
    manager runs at *trace* time and tags nothing at run time — spmdlint
    rule SPMD210 flags that misuse.
    """
    flat: List[str] = []
    for rid in request_ids:
        if isinstance(rid, (list, tuple, set, frozenset)):
            flat.extend(str(r) for r in rid)
        else:
            flat.append(str(rid))
    token = _trace_var.set(_trace_var.get() + tuple(flat))
    try:
        yield tuple(flat)
    finally:
        _trace_var.reset(token)


def current_trace() -> Tuple[str, ...]:
    """The active request ids (empty tuple outside any trace_ctx)."""
    return _trace_var.get()


def account_bytes(op: str, mode: str, exact_bytes: int, wire_bytes: int) -> None:
    """Credit one collective's traffic to the exact-vs-wire ledger.

    ``exact_bytes`` is what the payload would cost on the wire as exact
    f32 (the common denominator of the wire models);
    ``wire_bytes`` what the resolved precision mode actually ships.  The
    per-mode compression ratio is maintained as a live gauge
    ``comm.wire_ratio.<mode>`` — for ``int8_block`` ring traffic it sits
    at ``(BLOCK + 4) / (4 * BLOCK)`` = 0.258x (see heat_tpu.comm).
    No-op while disabled."""
    if not enabled:
        return
    with _lock:
        _counters[f"comm.collectives.{op}"] = (
            _counters.get(f"comm.collectives.{op}", 0) + 1
        )
        for name, val in (
            (f"comm.exact_bytes.{mode}", exact_bytes),
            (f"comm.wire_bytes.{mode}", wire_bytes),
            ("comm.exact_bytes", exact_bytes),
            ("comm.wire_bytes", wire_bytes),
        ):
            _counters[name] = _counters.get(name, 0) + int(val)
        exact = _counters[f"comm.exact_bytes.{mode}"]
        if exact:
            _gauges[f"comm.wire_ratio.{mode}"] = (
                _counters[f"comm.wire_bytes.{mode}"] / exact
            )
        total_exact = _counters["comm.exact_bytes"]
        if total_exact:
            _gauges["comm.wire_ratio"] = _counters["comm.wire_bytes"] / total_exact


# --------------------------------------------------------------------- #
# spans                                                                 #
# --------------------------------------------------------------------- #
def _stat(value):
    """A span field as a stat of its ``TraceAnnotation``: numbers as they
    are, anything else as text without the two characters (``,`` ``#``) the
    profiler's own encoding of stats is built from."""
    if isinstance(value, (int, float)):
        return value
    if isinstance(value, (list, tuple)):
        value = ";".join(str(v) for v in value)
    return str(value).replace(",", ";").replace("#", "")


class _Span:
    """One ``telemetry.span("site")`` — context manager and decorator.

    Enter/exit are each a single predicate when nothing records.  A
    recorded span carries ``id``, ``parent`` (the span open on this
    context when it opened, ``None`` for a root) and ``root`` (the id of
    the outermost span: what all spans of one public call share), and its
    ``kind``.  An ``entry`` span also records, at the same boundary as its
    time, the ``launches`` and ``syncs`` counted while it was open.  On
    exit the span lands in the per-site aggregate (count + total seconds,
    what ``snapshot()`` reports) and as one event on the stream (what the
    JSONL sink and :func:`profiled_spans` consume); while the profiler
    runs it is also a ``TraceAnnotation`` in the profiler's trace.
    Exceptions propagate; the span still records, tagged with the
    exception type."""

    __slots__ = ("site", "kind", "fields", "_t0", "_id", "_up", "_token", "_ann", "_base")

    def __init__(self, site: str, kind: str = "other", fields: Optional[dict] = None):
        self.site = site
        self.kind = kind
        self.fields = fields or None
        self._t0 = None

    def __enter__(self):
        global _next_id
        if not recording():
            return self
        with _lock:
            self._id = _next_id
            _next_id += 1
        self._up = _span_var.get()
        self._token = _span_var.set((self._id, self._id if self._up is None else self._up[1]))
        self._base = (_dispatches, _host_syncs) if self.kind == "entry" else None
        self._ann = None
        self._t0 = clock()
        if _prof_seen:
            stats = {"kind": self.kind, "id": self._id}
            if self._up is not None:
                stats["parent"] = self._up[0]
            rids = _trace_var.get()
            if rids:
                stats["rid"] = _stat(rids)
            if self.fields:
                for k, v in self.fields.items():
                    stats[k] = _stat(v)
            self._ann = _annotation(self.site, **stats)
            self._ann.__enter__()
        return self

    def __exit__(self, exc_type, exc, tb):
        if self._t0 is None:
            return False
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        t1 = clock()
        dur = t1 - self._t0
        _span_var.reset(self._token)
        ev = {
            "type": "span",
            "site": self.site,
            "kind": self.kind,
            "id": self._id,
            "parent": None if self._up is None else self._up[0],
            "root": self._id if self._up is None else self._up[1],
            "ts": self._t0,
            "dur": dur,
            "tid": _tid(),
        }
        rids = _trace_var.get()
        if rids:
            ev["rid"] = list(rids)
        if self.fields:
            ev.update(self.fields)
        if self._base is not None:
            ev["launches"] = _dispatches - self._base[0]
            ev["syncs"] = _host_syncs - self._base[1]
        if exc_type is not None:
            ev["error"] = exc_type.__name__
        with _lock:
            agg = _spans.get(self.site)
            if agg is None:
                _spans[self.site] = [1, dur]
            else:
                agg[0] += 1
                agg[1] += dur
            _emit(ev)
        self._t0 = None
        return False

    def __call__(self, fn):
        """Decorator form: ``@telemetry.span("site")``.  The wrapper
        re-checks the switch per call, so decoration at import time with
        telemetry disabled still records once it is enabled."""
        site, kind, fields = self.site, self.kind, self.fields

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not recording():
                return fn(*args, **kwargs)
            with _Span(site, kind, fields):
                return fn(*args, **kwargs)

        wrapper.__telemetry_site__ = site
        return wrapper


def span(site: str, kind: str = "other", **fields) -> _Span:
    """A host-side timing span — use as a ``with`` block or a decorator.

    ``kind`` names the layer boundary the span stands at, one of
    :data:`KINDS`: ``entry`` (a public call), ``launch`` (a compiled
    program is issued), ``sync`` (the host waits for a device value),
    ``comm``, ``io``, ``other``.  Readers select by it, never by a name's
    prefix.

    NOTE: spans are host-side by construction.  Inside a ``jax.jit`` /
    ``shard_map`` / ``ht.fuse``-traced function a span measures *trace*
    time, not run time — spmdlint rule SPMD205 flags that misuse; put
    spans around the eager call site instead.
    """
    return _Span(site, kind, fields or None)


def spanned(site: str, kind: str, fn: Callable, *args):
    """``fn(*args)``, as a span ``site`` of ``kind`` when recording: the
    guarded form of ``with span(...)`` for a site that is one call (no span
    object is made when nothing records)."""
    if recording():
        with _Span(site, kind):
            return fn(*args)
    return fn(*args)


def self_times(spans) -> Dict[int, float]:
    """Self time of each closed span of ``spans`` (span events, as
    :func:`events` gives them), by ``id``: its duration less the union of
    its direct children's intervals, so what two overlapping children cover
    together is taken off once.  Children are found among ``spans`` alone."""
    children: Dict[int, List[Tuple[float, float]]] = {}
    for ev in spans:
        if ev.get("parent") is not None:
            children.setdefault(ev["parent"], []).append((ev["ts"], ev["ts"] + ev["dur"]))
    return {
        ev["id"]: ev["dur"] - covered_s(children.get(ev["id"], ()), ev["ts"], ev["ts"] + ev["dur"])
        for ev in spans
    }


def covered_s(intervals, lo: float = float("-inf"), hi: float = float("inf")) -> float:
    """Seconds of ``[lo, hi]`` that the ``(start, end)`` pairs of
    ``intervals`` cover together: what two that overlap or nest cover both
    is counted once."""
    covered, end = 0.0, lo
    for a, b in sorted(intervals):
        a, b = max(a, end), min(b, hi)
        if b > a:
            covered += b - a
            end = b
    return covered


def profiled_spans() -> Tuple[dict, ...]:
    """The span events recorded during the most recent profiler trace:
    those between the position in the event list at which a poll
    (:func:`recording`, which every span site calls) first saw the profiler
    on and the one at which a poll, this function's own included, first saw
    it off again.  Empty where no poll has seen a trace."""
    recording()
    with _lock:
        hi = len(_events) if _prof_hi is None else _prof_hi
        return tuple(e for e in _events[_prof_lo:hi] if e.get("type") == "span")


# --------------------------------------------------------------------- #
# the start-up record                                                   #
# --------------------------------------------------------------------- #
def _startup_append(rec: dict) -> None:
    """Under the lock: keep ``rec``, or count it as dropped."""
    global _startup_dropped
    if len(_startup) < _MAX_STARTUP:
        _startup.append(rec)
    else:
        _startup_dropped += 1


def record_imports(t0: float, stages, nested) -> None:
    """The hand-over of the package's import stamps, once, at the end of
    ``heat_tpu/__init__.py``: ``t0`` is the clock at the file's first line,
    ``stages`` the ``(what, end)`` of each import statement in file order (a
    statement runs from the end of the one before it), ``nested`` the same
    list of a subpackage by the ``what`` of the statement that first
    imported it.  Records of kind ``import`` whose ``id`` / ``parent`` number
    the rows of this hand-over alone (0 is the root ``import:heat_tpu``)."""
    rows = [("import:heat_tpu", None, t0, stages[-1][1])]
    nested = dict(nested)

    def walk(prefix, parent, start, stages):
        for what, end in stages:
            rows.append((f"import:{prefix}{what}", parent, start, end))
            inner = nested.pop(what, None)
            if inner:
                walk(f"{prefix}{what}.", len(rows) - 1, start, inner)
            start = end

    walk("", 0, t0, stages)
    with _lock:
        tid = _tid()
        for i, (site, parent, start, end) in enumerate(rows):
            _startup_append({
                "type": "span", "site": site, "kind": "import", "id": i, "parent": parent,
                "root": 0, "ts": start, "dur": end - start, "tid": tid,
            })


def record_compile_stage(site: str, dur: float, **fields) -> None:
    """One stage of one program's compilation, as jax reported it when the
    stage ended: a record of kind ``compile`` that began ``dur`` ago.  While
    :func:`recording` it is also an event on the stream, with an id of its
    own, under the span open on this context (the ``launch`` whose first
    call traced the program).  Not in deterministic mode: whether a program
    compiles depends on what the process ran before, which a replay of the
    event order does not repeat."""
    global _next_id
    rec = {
        "type": "span", "site": site, "kind": "compile", "id": None, "parent": None,
        "root": None, "ts": _wall() - dur, "dur": dur,
    }
    rec.update(fields)
    on_stream = recording() and not _deterministic
    with _lock:
        rec["tid"] = _tid()
        if on_stream:
            up = _span_var.get()
            rec["id"] = _next_id
            _next_id += 1
            rec["parent"], rec["root"] = (None, rec["id"]) if up is None else up
            _emit(rec)
        _startup_append(rec)


def startup() -> Tuple[dict, ...]:
    """The start-up record (module docstring), oldest first: what the
    package's import statements took and every program's trace, lower and
    compile-or-load, kept whether or not telemetry is enabled."""
    with _lock:
        return tuple(_startup)


def _startup_totals(records) -> dict:
    """The four totals of ``records``: the package's import, the union of
    the trace and lower intervals, the union of the compile-or-load
    intervals, and how many programs were compiled or loaded."""
    by_site: Dict[str, List[Tuple[float, float]]] = {}
    for r in records:
        by_site.setdefault(r["site"], []).append((r["ts"], r["ts"] + r["dur"]))
    trace, lower, backend = (by_site.get(site, []) for site in _COMPILE_SITES)
    return {
        "import_s": sum(b - a for a, b in by_site.get("import:heat_tpu", ())),
        "trace_lower_s": covered_s(trace + lower),
        "compile_load_s": covered_s(backend),
        "programs": len(backend),
    }


def startup_report() -> str:
    """The start-up record as text, for whoever asks why seconds passed
    before a script's first result: the import stages largest first, each
    with its share of ``import:heat_tpu`` (a subpackage's own statements
    indented under it), then one row a program in the order they first ran
    (trace, lower, compile-or-load seconds over all its shapes, how many of
    its loads the persistent cache served), then the totals, in which nested
    traces count once."""
    records = startup()
    totals = _startup_totals(records)
    lines = [f"import heat_tpu {totals['import_s']:.3f} s"]
    stages: Dict[Any, Dict[str, float]] = {}  # parent id -> site -> seconds
    ids: Dict[str, int] = {}  # site -> id of its first record
    for r in records:
        if r["kind"] == "import" and r["parent"] is not None:
            under = stages.setdefault(r["parent"], {})
            under[r["site"]] = under.get(r["site"], 0.0) + r["dur"]
            ids.setdefault(r["site"], r["id"])

    def rows(parent, indent):
        small = []
        for site, secs in sorted(stages.get(parent, {}).items(), key=lambda kv: -kv[1]):
            share = 100.0 * secs / totals["import_s"] if totals["import_s"] else 0.0
            if share < 0.1:
                small.append(secs)
                continue
            lines.append(f"{indent}{site:<34}{secs:9.3f} s {share:5.1f} %")
            rows(ids[site], indent + "  ")
        if small:
            lines.append(f"{indent}{f'({len(small)} more, each under 0.1 %)':<34}{sum(small):9.3f} s")

    rows(0, "  ")
    programs: Dict[str, dict] = {}  # fun -> the program's row, in the order they first ran
    for r in records:
        if r["kind"] == "compile":
            row = programs.setdefault(r["fun"], {site: [] for site in _COMPILE_SITES} | {"hits": 0})
            row[r["site"]].append((r["ts"], r["ts"] + r["dur"]))
            row["hits"] += bool(r.get("cache_hit"))
    lines.append(f"programs {totals['programs']}: trace s, lower s, compile-or-load s, from the cache / loads")
    for fun, row in programs.items():
        trace, lower, backend = (row[site] for site in _COMPILE_SITES)
        if lower or backend:  # else traced inside another program's trace: its seconds are in that row
            lines.append(
                f"  {fun:<34}{covered_s(trace):9.3f}{covered_s(lower):9.3f}{covered_s(backend):9.3f}"
                f"  {row['hits']}/{len(backend)}"
            )
    lines.append(
        f"totals: import {totals['import_s']:.3f} s, trace+lower {totals['trace_lower_s']:.3f} s, "
        f"compile-or-load {totals['compile_load_s']:.3f} s, programs {totals['programs']}, "
        f"records dropped {_startup_dropped}"
    )
    return "\n".join(lines)


# --------------------------------------------------------------------- #
# reading                                                               #
# --------------------------------------------------------------------- #
def events() -> Tuple[dict, ...]:
    """Snapshot of the recorded event stream (oldest first)."""
    with _lock:
        return tuple(_events)


def snapshot() -> dict:
    """The in-memory export: counters, gauges, per-site span totals and
    the start-up record's totals (``startup``: ``import_s``,
    ``trace_lower_s``, ``compile_load_s``, ``programs``, ``dropped``).

    Empty dict while telemetry is disabled — the cheap way for callers
    to branch on "was anything collected"."""
    if not enabled:
        return {}
    with _lock:
        return {
            "counters": dict(_counters),
            "gauges": dict(_gauges),
            "spans": {
                site: {"count": int(c), "total_s": t}
                for site, (c, t) in sorted(_spans.items())
            },
            "hists": {name: _hists[name].state() for name in sorted(_hists)},
            "events": len(_events),
            "startup": dict(_startup_totals(_startup), dropped=_startup_dropped),
        }


# --------------------------------------------------------------------- #
# JSONL sink                                                            #
# --------------------------------------------------------------------- #
def set_jsonl(path: Optional[str]) -> None:
    """Stream every subsequent event to ``path`` as one JSON object per
    line (``None`` closes the sink)."""
    global _jsonl, _jsonl_path
    with _lock:
        if _jsonl is not None:
            _jsonl.close()
            _jsonl = None
            _jsonl_path = None
        if path is not None:
            _jsonl = open(path, "a", buffering=1)
            _jsonl_path = str(path)


def jsonl_path() -> Optional[str]:
    return _jsonl_path


# --------------------------------------------------------------------- #
# dispatch counter (the _tracing shim's backing store)                  #
# --------------------------------------------------------------------- #
_dispatches = 0


def record_dispatch() -> None:
    """Count one device program launch.  Always on (tier-1 dispatch-count
    gates read it through :mod:`heat_tpu.core._tracing` with telemetry
    disabled); the increment is lock-guarded, so threaded serving does
    not lose launches.  With telemetry enabled the launch also lands on
    the ``dispatches`` registry counter."""
    global _dispatches
    with _lock:
        _dispatches += 1
        if enabled:
            _counters["dispatches"] = _counters.get("dispatches", 0) + 1


def dispatch_count() -> int:
    """Device program launches recorded since the last reset."""
    return _dispatches


def reset_dispatch_count() -> None:
    global _dispatches
    with _lock:
        _dispatches = 0


# --------------------------------------------------------------------- #
# host-sync counter                                                     #
# --------------------------------------------------------------------- #
_host_syncs = 0


def host_read(site: str, value, convert: Callable[[Any], Any]):
    """``convert(value)`` where that blocks until the device has produced
    ``value`` and copies it to the host (``int``, ``float``,
    ``np.asarray``): the one way the library reads a device value back.
    Always counts one host sync (one lock and one add, like the dispatch
    counter); when recording, the conversion is a span of kind ``sync``
    named ``site``, whose duration is how long this thread waited."""
    global _host_syncs
    with _lock:
        _host_syncs += 1
        if enabled:
            _counters["host_syncs"] = _counters.get("host_syncs", 0) + 1
    return spanned(site, "sync", convert, value)


def host_sync_count() -> int:
    """Blocking device-to-host reads counted since process start."""
    return _host_syncs


class _DispatchWindow:
    """Handle yielded by :func:`counting_dispatches`: ``.count`` is the
    number of dispatches since the window opened."""

    __slots__ = ("_base",)

    def __init__(self, base: int):
        self._base = base

    @property
    def count(self) -> int:
        return _dispatches - self._base


@contextlib.contextmanager
def counting_dispatches():
    """Scoped dispatch counting.

    Yields a window whose ``.count`` property reads the launches made
    since entry — a baseline diff, not a global reset, so concurrent
    tests (or nested windows) never leak counter state into each other::

        with counting_dispatches() as d:
            fused_pipeline(x)
        assert d.count == 1
    """
    yield _DispatchWindow(_dispatches)
