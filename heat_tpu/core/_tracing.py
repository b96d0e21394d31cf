"""Trace-mode state shared by the compile cache, the communication layer,
DNDarray, and :mod:`heat_tpu.core.fuse`.

``heat_tpu`` normally runs ops eagerly: every op commits its result's
layout with a real ``device_put`` and any host-side inspection
(``float(x)``, ``repr(x)``, ``x.numpy()``) simply reads the committed
array back.  Under :func:`heat_tpu.fuse` the same library code runs once
*inside* a ``jax.jit`` trace, where arrays are abstract tracers: committed
shardings do not exist yet (layout requests become
``jax.lax.with_sharding_constraint`` hints for GSPMD) and reading a value
back is impossible by construction.  This module holds the process-global
flag that tells the rest of the core which of the two worlds it is in,
plus the diagnostic error raised when traced code demands a concrete
value.

It also hosts the *dispatch counter* — the shim that counts
device program launches at the library level.  Counting at the jax/XLA
layer is not reliable from Python (the C++ pjit fast path bypasses any
Python wrapper after the first call), so the counter is incremented by the
two places heat_tpu itself launches programs: the ``jitted()`` executable
wrapper and the ``device_put``-based reshard in the communication layer.
The counter's storage moved into :mod:`heat_tpu.telemetry` (one registry
for all runtime accounting, lock-guarded so threaded serving does not
lose increments); the functions here are the stable shim over it, and
:func:`counting_dispatches` is the leak-free way for tests to scope a
reading.

Kept free of jax imports so every core module can import it without
ordering constraints (:mod:`heat_tpu.telemetry._core` holds the same
property).
"""

from __future__ import annotations

import contextlib

from ..telemetry import _core as _telemetry

__all__ = [
    "FuseTraceError",
    "NO_OVERRIDE",
    "applying_layout_plan",
    "consume_layout_override",
    "trace_mode",
    "in_trace",
    "layout_plan_active",
    "require_concrete",
    "record_dispatch",
    "dispatch_count",
    "reset_dispatch_count",
    "counting_dispatches",
]


class FuseTraceError(RuntimeError):
    """A value-forcing operation ran on a traced DNDarray.

    Raised when code inside an ``ht.fuse``-compiled pipeline (or a
    ``fuse.trace()`` block) tries to materialize a concrete value —
    ``float(x)``, ``x.item()``, ``print(x)``, ``x.numpy()``, file I/O.
    Inside a trace there is no value yet, only an abstract shape; the fix
    is to keep the computation on-device (``jnp.where`` / ``lax.cond``
    instead of Python ``if``), or to move the host-side step outside the
    fused function.
    """


_trace_depth = 0


def in_trace() -> bool:
    """True while a ``fuse`` trace (or explicit ``fuse.trace()`` block)
    is active on this thread of control."""
    return _trace_depth > 0


@contextlib.contextmanager
def trace_mode():
    """Enter tracing mode: the communication layer swaps committed-layout
    inspection for ``with_sharding_constraint`` hints and value-forcing
    DNDarray operations raise :class:`FuseTraceError`.  Re-entrant."""
    global _trace_depth
    _trace_depth += 1
    try:
        yield
    finally:
        _trace_depth -= 1


def require_concrete(what: str) -> None:
    """Raise the diagnostic :class:`FuseTraceError` if tracing is active.

    Called by every value-forcing DNDarray entry point with a short
    description of the operation (``"float()"``, ``".numpy()"`` …).
    """
    if _trace_depth > 0:
        raise FuseTraceError(
            f"{what} forces a concrete value, but this DNDarray is being "
            "traced inside ht.fuse — no value exists yet. Keep the decision "
            "on-device (jnp.where / lax.cond) or move this step outside the "
            "fused function."
        )


# ---------------------------------------------------------------------- #
# layout-plan overrides (ht.autoshard → manipulations.resplit)            #
# ---------------------------------------------------------------------- #
#: sentinel distinguishing "no override recorded" from "override to None"
NO_OVERRIDE = object()

_layout_plan = None  # {signature: [apply, ...]} FIFO while a plan is active


def layout_plan_active() -> bool:
    """True while an ``ht.autoshard`` plan is being applied on this call."""
    return _layout_plan is not None


@contextlib.contextmanager
def applying_layout_plan(decisions):
    """Expose a solved layout plan to ``manipulations.resplit`` for the
    dynamic extent of one pipeline call.

    ``decisions`` is the solver's list (see
    :meth:`heat_tpu.comm._costs.LayoutSolver.solve`); each is keyed by the
    *signature* of the hand-written resplit it replaces — ``(shape,
    dtype, src split, requested dst)`` — NOT by call position, so library
    resplits the plan never saw (e.g. ``__binary_op``'s implicit reshard)
    pass through untouched.  Same-signature calls consume their overrides
    in FIFO order, matching the solver's program-order chain walk.  The
    table is rebuilt per call: a plan application never leaks into the
    next call, and nesting restores the outer plan.
    """
    global _layout_plan
    table = {}
    for d in decisions:
        key = (tuple(d["shape"]), d["dtype"], d["src"], d["requested"])
        table.setdefault(key, []).append(d["apply"])
    prev = _layout_plan
    _layout_plan = table
    try:
        yield
    finally:
        _layout_plan = prev


def consume_layout_override(shape, dtype_name, src, requested):
    """Pop the next planned placement for a resplit with this signature,
    or :data:`NO_OVERRIDE` when the active plan has nothing for it."""
    if _layout_plan is None:
        return NO_OVERRIDE
    queue = _layout_plan.get((tuple(shape), dtype_name, src, requested))
    if not queue:
        return NO_OVERRIDE
    return queue.pop(0)


# ---------------------------------------------------------------------- #
# dispatch counting (shim over the telemetry registry)                    #
# ---------------------------------------------------------------------- #
def record_dispatch() -> None:
    """Count one device program launch.

    No-ops inside trace mode: a call that happens while tracing is being
    inlined into the enclosing program, not dispatched.  The increment
    itself lives in :mod:`heat_tpu.telemetry` — thread-safe, and visible
    as the ``dispatches`` counter when telemetry is enabled.
    """
    if _trace_depth == 0:
        _telemetry.record_dispatch()


def dispatch_count() -> int:
    """Device program launches recorded since the last reset."""
    return _telemetry.dispatch_count()


def reset_dispatch_count() -> None:
    _telemetry.reset_dispatch_count()


def counting_dispatches():
    """Scoped dispatch counting: ``with counting_dispatches() as d: ...``
    then read ``d.count`` — a baseline diff over the process counter, so
    tests never have to reset (and therefore never leak) global state.
    See :func:`heat_tpu.telemetry.counting_dispatches`."""
    return _telemetry.counting_dispatches()
