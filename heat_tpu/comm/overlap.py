"""Latency-hiding policy for the ring collectives.

Every hot ring in the tree — zig-zag causal ring attention, the
compressed allreduce/allgather rings, planned-redistribution rotations,
and the generic ``ring_map`` primitive — alternates "ship a slab" and
"do math on the slab".  Run strictly step-by-step, each round pays
``compute + wire``; TPU hardware runs the ICI DMA and the MXU
concurrently, so the roofline is ``max(compute, wire)``.  This module is
the ONE policy seam that flips the ring bodies between the two
schedules:

``ht.comm.set_overlap("on")``
    Every converted ring runs its double-buffered body: round ``k``
    issues the ``ppermute`` for the round-``k+1`` operand while the
    round-``k`` operand is consumed (two slabs — ``cur``, ``inflight``
    — carried through the ``fori_loop``), or, for rings whose hops are
    data-dependent (the compressed reduce-scatter), splits each payload
    into two independent streams whose wire and math interleave.  The
    fold schedule is bitwise-pinned: the overlapped body performs the
    same adds on the same operands in the same order as the serial one.
``ht.comm.set_overlap("off")``
    The serial step-by-step bodies — the exact twin every overlapped
    ring is validated against in the same run.
``ht.comm.set_overlap("auto")``
    The default: overlap on TPU backends (where the DMA actually runs
    concurrently with compute), serial elsewhere — CPU test runs keep
    the seed's dispatch shape unless a test opts in.

Like the collective-precision and redistribution knobs, the policy is
registered in every compiled-program cache key
(:func:`heat_tpu.core._compile.register_key_context`), so flipping it
retraces fresh programs instead of replaying bodies built under the
other schedule — which is also what lets one run hold the overlapped
ring and its serial twin side by side.

Telemetry (all behind the single ``_tel.enabled`` predicate — zero
overhead while disabled):

- ``comm.ring.dispatch.overlapped`` / ``comm.ring.dispatch.serial``
  counters and the ``comm.overlap_ratio`` gauge (overlapped fraction of
  eager ring dispatches so far);
- per-ring ``comm:<ring>:step:issue`` / ``comm:<ring>:step:consume``
  span pairs around each eager ring dispatch: the *issue* span covers
  the (asynchronous) dispatch enqueue, the *consume* span covers the
  wait for the result — in a profiler trace an overlapped ring shows a
  short issue slice and the whole wait in consume.  Spans are host-side
  by construction (SPMD205): they wrap the eager call site, never the
  traced body.

docs/design.md §18 documents the double-buffer carry shapes.
"""

from __future__ import annotations

import contextlib
from typing import Tuple

import jax

from ..core._compile import register_key_context
from ..telemetry import _core as _tel

__all__ = [
    "get_overlap",
    "overlap",
    "overlap_enabled",
    "set_overlap",
    "timed_dispatch",
]

_MODES = ("on", "off", "auto")
_OVERLAP = "auto"


# --------------------------------------------------------------------- #
# policy (mirrors compressed.set_collective_precision)                   #
# --------------------------------------------------------------------- #
def set_overlap(mode: str) -> None:
    """Set the process-wide ring-overlap policy.

    ``"on"``
        Every converted ring runs its double-buffered (latency-hiding)
        body.
    ``"off"``
        The serial step-by-step bodies (the exact twins).
    ``"auto"``
        The default: double-buffered on TPU backends, serial elsewhere.
    """
    global _OVERLAP
    if mode not in _MODES:
        raise ValueError(
            f"unknown overlap mode {mode!r}: expected one of {_MODES}"
        )
    _OVERLAP = mode


def get_overlap() -> str:
    """The current process-wide ring-overlap policy."""
    return _OVERLAP


@contextlib.contextmanager
def overlap(mode: str):
    """Context-manager form of :func:`set_overlap`."""
    prev = _OVERLAP
    set_overlap(mode)
    try:
        yield
    finally:
        set_overlap(prev)


@register_key_context
def _overlap_token() -> Tuple:
    """The overlap policy's contribution to every compiled-program cache
    key: flipping the policy keys fresh entries (the serial twin and the
    overlapped ring coexist in one run), instead of replaying a body
    built under the other schedule.  The backend check inside
    :func:`overlap_enabled` is deliberately NOT part of the token — the
    process backend is fixed for the life of the cache."""
    return ("overlap", _OVERLAP)


def overlap_enabled(size: int) -> bool:
    """Whether a ring over ``size`` devices should trace its
    double-buffered body under the current policy.

    Size-1 "rings" have no wire to hide and always stay serial; under
    ``"auto"`` only TPU backends — where DMA and MXU genuinely run
    concurrently — pay the double-buffer's extra live slab.
    """
    if _OVERLAP == "off" or size <= 1:
        return False
    if _OVERLAP == "on":
        return True
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# telemetry: overlap ratio + issue/consume span pairs                    #
# --------------------------------------------------------------------- #
def _note_ring(overlapped: bool) -> None:
    """Count one eager ring dispatch and refresh the
    ``comm.overlap_ratio`` gauge.  Caller holds the ``_tel.enabled``
    predicate."""
    _tel.inc(
        "comm.ring.dispatch.overlapped" if overlapped
        else "comm.ring.dispatch.serial"
    )
    with _tel._lock:
        ov = _tel._counters.get("comm.ring.dispatch.overlapped", 0)
        se = _tel._counters.get("comm.ring.dispatch.serial", 0)
    _tel.gauge("comm.overlap_ratio", ov / (ov + se))


def timed_dispatch(ring: str, overlapped: bool, launch):
    """Run one eager ring dispatch under a ``comm:<ring>:step`` span
    pair: the *issue* span times the dispatch enqueue, the *consume*
    span times the wait for the result (``jax.block_until_ready``).
    With telemetry disabled this is exactly ``launch()`` — one predicate
    read, no spans, no sync (the zero-overhead contract)."""
    if not _tel.enabled:
        return launch()
    _note_ring(overlapped)
    with _tel.span(f"comm:{ring}:step:issue", "comm", overlapped=overlapped):
        out = launch()
    with _tel.span(f"comm:{ring}:step:consume", "comm", overlapped=overlapped):
        jax.block_until_ready(out)
    return out
