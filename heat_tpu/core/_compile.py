"""Cached-jit dispatch for the op engine.

Every user-level op (``ht.add``, ``ht.mean``, ``ht.sqrt`` …) runs a short
chain of jnp primitives.  Dispatching those eagerly costs one host→device
launch *per primitive*, each far above the kernel time of a small op.  The
reference never faces this (torch eager ops run in-process, reference
heat/core/_operations.py drives local torch kernels directly); the
TPU-native answer is to compile each op chain once and replay the cached
executable.

``jitted(key, make_fn)`` memoizes ``jax.jit(make_fn())`` under a hashable
key describing the op and its static parameters (axis, kwargs, cast dtype,
scalar operands).  Subsequent calls with the same key skip tracing and
lowering entirely — XLA replays the compiled program, fusing the whole op
chain into one device round trip.
"""

from __future__ import annotations

import sys
import types as _types
from typing import Any, Callable, Dict, Tuple

import numpy as np

import jax

from ..telemetry import _core as _tel
from ._tracing import record_dispatch

__all__ = [
    "jitted",
    "cache_stable",
    "clear_cache",
    "cache_size",
    "register_key_context",
    "context_token",
]

_CACHE: Dict[Tuple, Any] = {}

#: Process-wide state whose value changes what a cached program MEANS
#: (e.g. the collective-compression policy) registers a token provider
#: here; its current token joins every ``jitted`` key, so flipping the
#: state keys fresh entries instead of replaying stale programs.
_KEY_CONTEXT: list = []


def register_key_context(provider: Callable[[], Tuple]) -> Callable[[], Tuple]:
    """Register a zero-arg provider whose tuple joins every cache key."""
    if provider not in _KEY_CONTEXT:
        _KEY_CONTEXT.append(provider)
    return provider


def context_token() -> Tuple:
    """Concatenated tokens of all registered key-context providers."""
    out: Tuple = ()
    for provider in _KEY_CONTEXT:
        out = out + tuple(provider())
    return out


def _traced(args, kwargs) -> bool:
    """True when the call belongs to an enclosing jax trace (an ``ht.fuse``
    program, a ``jit``/``shard_map`` body): some operand is a tracer.  Such a
    call inlines into the surrounding program — it is no dispatch of its own
    and must never reach a compiled executable."""
    return any(
        isinstance(leaf, jax.core.Tracer)
        for leaf in jax.tree_util.tree_leaves((args, kwargs))
    )


def cache_stable(fn: Any) -> bool:
    """True when ``fn``'s identity repeats across calls, so it is safe to
    embed in a ``jitted`` key.

    Import-time singletons qualify: plain module-level ``def``s, numpy
    ufuncs, and any other callable that IS the attribute of its module
    under its own name (``jnp.add`` is a ``ufunc`` instance, ``jnp.where``
    a ``PjitFunction`` — both created once at import).  Lambdas, closures
    (anything defined inside a function — ``"<locals>"`` in the qualname),
    bound methods, and per-call ``partial`` objects do not: keying on a
    per-call identity grows the cache by one dead entry per call without
    ever hitting.  Callers must route unstable functions to a transient
    ``jax.jit`` or the eager path instead (spmdlint rule SPMD401).
    """
    if getattr(fn, "__self__", None) is not None:
        return False  # bound method: per-instance identity
    if isinstance(fn, _types.FunctionType):
        return (
            fn.__closure__ is None
            and "<locals>" not in fn.__qualname__
            and fn.__name__ != "<lambda>"
        )
    if isinstance(fn, np.ufunc):
        return True  # ufuncs only exist as import-time singletons
    mod = sys.modules.get(getattr(fn, "__module__", None) or "")
    name = getattr(fn, "__name__", None)
    return mod is not None and name is not None and getattr(mod, name, None) is fn


def jitted(key: Tuple, make_fn: Callable[[], Callable], jit_kwargs=None) -> Callable:
    """Return a cached ``jax.jit`` of ``make_fn()`` memoized under ``key``.

    ``make_fn`` is only invoked on a cache miss; it should return a function
    closing over all static parameters named in ``key``.

    ``jit_kwargs`` (a dict, used only on a miss) passes straight through to
    :func:`jax.jit` — e.g. ``out_shardings`` where the exact committed spec
    form matters (the redistribution planner pins its output layout so it
    compares EQUAL to the monolithic reshard's).  The key must determine the
    kwargs, exactly as it determines the traced function.

    The cached entry is a thin wrapper that records one device dispatch per
    eager invocation (see :mod:`heat_tpu.core._tracing`); calls made while a
    trace is active — an enclosing ``ht.fuse`` program or any jax trace —
    inline into the surrounding program and are not counted.
    """
    if _KEY_CONTEXT:
        key = key + context_token()
    fn = _CACHE.get(key)
    if fn is None:
        if _tel.enabled:
            _tel.inc("compile.cache.misses")
        jfn = jax.jit(make_fn(), **(jit_kwargs or {}))
        site = key[0] if key and isinstance(key[0], str) else getattr(
            jfn, "__name__", "op"
        )
        staged = [False]  # first-call stage timing done (telemetry only)

        def fn(*args, _jfn=jfn, **kwargs):
            clean = not _traced(args, kwargs)
            if clean:
                record_dispatch()
            if _tel.enabled and clean:
                if not staged[0]:
                    staged[0] = True
                    out = _timed_first_call(site, _jfn, args, kwargs)
                    if out is not _AOT_UNAVAILABLE:
                        return out
                with _tel.span(f"jitted:{site}"):
                    return _jfn(*args, **kwargs)
            return _jfn(*args, **kwargs)

        fn.lower = jfn.lower  # HLO inspection passthrough (tests)
        fn.jitted = jfn
        _CACHE[key] = fn
        if _tel.enabled:
            _tel.gauge("compile.cache.size", len(_CACHE))
    elif _tel.enabled:
        _tel.inc("compile.cache.hits")
    return fn


_AOT_UNAVAILABLE = object()


def _timed_first_call(site: str, jfn, args, kwargs):
    """Telemetry-enabled first invocation of a freshly built ``jitted``
    entry: stage the call through the AOT API so the compile-miss event
    records trace+lower time and XLA compile time separately, then run
    the compiled executable (one dispatch, already counted by the
    caller).  Falls back to the plain call — returning the
    ``_AOT_UNAVAILABLE`` sentinel — when the AOT path does not apply
    (kwargs, older jax)."""
    if kwargs:
        return _AOT_UNAVAILABLE
    t0 = _tel.clock()
    try:
        lowered = jfn.lower(*args)
        t1 = _tel.clock()
        compiled = lowered.compile()
        t2 = _tel.clock()
    except Exception:
        return _AOT_UNAVAILABLE
    _tel.record_event(
        "compile", site=site, trace_lower_s=t1 - t0, compile_s=t2 - t1
    )
    with _tel.span(f"jitted:{site}", phase="first_run"):
        return compiled(*args)


def clear_cache() -> None:
    """Drop all cached executables (mainly for tests)."""
    _CACHE.clear()


def cache_size() -> int:
    return len(_CACHE)
