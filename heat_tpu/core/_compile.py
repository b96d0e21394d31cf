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

import functools
import sys
import threading
import types as _types
from typing import Any, Callable, Dict, Tuple

import numpy as np

import jax

from ..telemetry import _core as _tel
from ._tracing import in_trace, record_dispatch

# the profiler is the sink of every recorded span and, through
# ``TraceAnnotation.is_enabled``, the switch (telemetry/_core.py imports no jax)
_tel.install_profiler(jax.profiler.TraceAnnotation)

#: jax's own report of each stage of a program's compilation, by the site it
#: takes in the start-up record (``jax/_src/dispatch.py``); ``backend`` wraps
#: the persistent cache's read, whose duration jax reports just before it
_COMPILE_STAGE = {
    "/jax/core/compile/jaxpr_trace_duration": "compile:trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile:lower",
    "/jax/core/compile/backend_compile_duration": "compile:backend",
}
_CACHE_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_retrieval = threading.local()  # .s: the read of this thread's compile request, on a hit


def _on_compile_stage(event: str, duration_secs: float, fun_name: str = "", **_) -> None:
    """The package's one listener on jax's compile events: each stage lands
    in the start-up record under the program's name (``jit(stat.moment2)``
    reads ``stat.moment2``).  Called only when jax traces, lowers or
    compiles, never on a replay; starts no backend."""
    site = _COMPILE_STAGE.get(event)
    if site is None:
        if event == _CACHE_RETRIEVAL:
            _retrieval.s = duration_secs
        return
    fields = {"fun": fun_name[4:-1] if fun_name.startswith("jit(") and fun_name.endswith(")") else fun_name}
    if site == "compile:backend":
        read_s = getattr(_retrieval, "s", None)
        _retrieval.s = None
        fields["cache_hit"] = read_s is not None
        if read_s is not None:
            fields["retrieval_s"] = read_s
    _tel.record_compile_stage(site, duration_secs, **fields)


jax.monitoring.register_event_duration_secs_listener(_on_compile_stage)

__all__ = [
    "entry",
    "jitted",
    "launch",
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


_NO_KWARGS: Dict[str, Any] = {}  # never written to


def launch(site: str, fn: Callable, args: Tuple = (), kwargs=None, kind: str = "launch", **fields):
    """Launch a compiled program: ``fn(*args, **kwargs)``, counted as one
    device dispatch (always on) and, when recording, spanned as ``site``.

    The one owner of "a program is issued": the ``jitted`` wrapper,
    ``ht.fuse``'s dispatch and the estimators' own ``jax.jit`` programs go
    through it with kind ``launch``, the ``device_put`` reshard with kind
    ``comm`` — so the counter and the spans cannot drift apart, and there
    is one span a counted dispatch.  A call made while a ``fuse`` trace is
    active inlines into the surrounding program: neither counted nor
    spanned."""
    if kwargs is None:
        kwargs = _NO_KWARGS
    if in_trace():
        return fn(*args, **kwargs)
    record_dispatch()
    if _tel.recording():
        with _tel.span(site, kind, **fields):
            return fn(*args, **kwargs)
    return fn(*args, **kwargs)


def entry(site: str):
    """A public entry as a span of kind ``entry`` (one predicate a call when
    nothing records).  Inside an ``ht.fuse`` trace the call inlines into the
    surrounding program and is no entry of its own."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if in_trace() or not _tel.recording():
                return fn(*args, **kwargs)
            with _tel.span(site, "entry"):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def _named(fn: Callable, name: str) -> Callable:
    """``fn`` under ``name``, which ``jax.jit`` takes for the compiled
    module's (``jit_<name>`` on the trace's ``XLA Modules`` line, where a
    lambda would read ``jit__lambda_``).  A fresh closure is renamed in
    place; a shared object (a module-level function, a ``PjitFunction``, a
    partial) is wrapped instead."""
    if isinstance(fn, _types.FunctionType) and not cache_stable(fn):
        fn.__name__ = fn.__qualname__ = name
        return fn

    @functools.wraps(fn)
    def named(*args, **kwargs):
        return fn(*args, **kwargs)

    named.__name__ = named.__qualname__ = name
    return named


def jitted(
    key: Tuple, make_fn: Callable[[], Callable], jit_kwargs=None, fields=None
) -> Callable:
    """Return a cached ``jax.jit`` of ``make_fn()`` memoized under ``key``.

    ``make_fn`` is only invoked on a cache miss; it should return a function
    closing over all static parameters named in ``key``.

    ``jit_kwargs`` (a dict, used only on a miss) passes straight through to
    :func:`jax.jit` — e.g. ``out_shardings`` where the exact committed spec
    form matters (the redistribution planner pins its output layout so it
    compares EQUAL to the monolithic reshard's).  The key must determine the
    kwargs, exactly as it determines the traced function.

    ``fields`` (a dict, used only on a miss) joins every launch span of the
    entry, to say which of a program's forms the key stands for
    (``jitted:dist.euclidean`` carries ``form``); the key must determine it
    too.

    The cached entry is a thin wrapper that goes through :func:`launch`: one
    device dispatch per eager invocation (see :mod:`heat_tpu.core._tracing`)
    and, when recording, one ``jitted:<key[0]>`` span of kind ``launch`` (the
    entry's first call carries ``miss=True``: its duration holds trace, lower
    and compile, each of which the start-up record names apart as a
    ``compile:*`` child of the span).  Calls made while a trace is active — an enclosing
    ``ht.fuse`` program or any jax trace — inline into the surrounding
    program and are neither counted nor spanned.  The compiled function is
    named after the key's site (and the operation, where the key's second
    part is one), so the device trace shows ``jit_dist.euclidean``.
    """
    if _KEY_CONTEXT:
        key = key + context_token()
    fn = _CACHE.get(key)
    if fn is None:
        if _tel.enabled:
            _tel.inc("compile.cache.misses")
        made = make_fn()
        if key and isinstance(key[0], str):
            site = key[0]
            op = getattr(key[1], "__name__", None) if len(key) > 1 and callable(key[1]) else None
            made = _named(made, f"{site}.{op}" if op else site)
        else:
            site = getattr(made, "__name__", "op")
        jfn = jax.jit(made, **(jit_kwargs or {}))
        span_site = f"jitted:{site}"
        fresh = [True]  # the entry has not been called yet
        fields = dict(fields or ())

        def fn(*args, _jfn=jfn, **kwargs):
            if _traced(args, kwargs):
                return _jfn(*args, **kwargs)
            if fresh[0]:
                fresh[0] = False
                return launch(span_site, _jfn, args, kwargs, miss=True, **fields)
            return launch(span_site, _jfn, args, kwargs, **fields)

        fn.lower = jfn.lower  # HLO inspection passthrough (tests)
        fn.jitted = jfn
        _CACHE[key] = fn
        if _tel.enabled:
            _tel.gauge("compile.cache.size", len(_CACHE))
    elif _tel.enabled:
        _tel.inc("compile.cache.hits")
    return fn


def clear_cache() -> None:
    """Drop all cached executables (mainly for tests)."""
    _CACHE.clear()


def cache_size() -> int:
    return len(_CACHE)
