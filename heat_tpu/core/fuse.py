"""Whole-program compilation over DNDarrays: ``ht.fuse``.

``jitted()`` (:mod:`heat_tpu.core._compile`) compiles each *single* op's
primitive chain, so an eager pipeline of N DNDarray ops still pays N
host→device launches — a dispatch tax that dwarfs the device compute of
small and medium ops.  ``fuse`` closes the gap the way "Automatic Full
Compilation of Julia Programs and ML Models to Cloud TPUs"
(arXiv:1810.09868) does for whole programs and "Large Scale Distributed
Linear Algebra With TPUs" (arXiv:2112.09017) assumes for its kernels:
trace the entire user pipeline once, compile it into ONE XLA executable,
and replay that for every subsequent call.

How it works
------------
``fuse(fn)`` returns a wrapper that, per call:

1. flattens ``(args, kwargs)`` with DNDarray leaves kept whole, splitting
   every leaf into a *dynamic* operand (the DNDarray's at-rest global
   ``jax.Array`` buffer, or a raw ``jax.Array``/numpy leaf) plus *static*
   metadata (gshape, split, heat dtype, balanced flag — and the value
   itself for non-array leaves);
2. looks up a compiled program keyed on
   ``(fn identity, treedef, per-leaf avals/splits, statics, comm, donate)``
   — ``fn`` identity follows :func:`~heat_tpu.core._compile.cache_stable`,
   so module-level pipelines cache across calls while lambdas/closures get
   a transient (per-call) compile;
3. on a miss, traces ``fn`` once under :func:`~heat_tpu.core._tracing.
   trace_mode`: DNDarrays are rebuilt around the traced buffers, the
   communication layer swaps committed-layout work (``device_put``,
   ``.sharding`` inspection) for ``jax.lax.with_sharding_constraint``
   hints, and any value-forcing operation (``float()``, ``.item()``,
   printing, I/O) raises :class:`FuseTraceError`;
4. replays the compiled program — one device dispatch — and re-wraps the
   output buffers as DNDarrays with the split metadata inferred at trace
   time.

Static metadata is part of the key, so python-scalar arguments that vary
per call (thresholds, axes) each compile their own specialization — pass
them as 0-d DNDarrays/jax arrays if they genuinely vary.

``donate=True`` donates the input buffers to XLA (in-place pipelines):
the caller's input DNDarrays are consumed and must not be used afterwards.

``fuse.trace()`` exposes the bare tracing mode as a context manager — the
communication-layer swap and the value-forcing guard without the
compile-and-cache machinery — for embedding DNDarray code inside a wider
``jax.jit``/``shard_map`` region of your own.
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import numpy as np

import jax

from ..telemetry import _core as _tel
from . import _compile
from ._compile import cache_stable
from ._tracing import (
    FuseTraceError,
    applying_layout_plan,
    in_trace,
    trace_mode,
)
from .dndarray import DNDarray

__all__ = ["fuse", "FuseTraceError"]

_FUSE_CACHE: Dict[Tuple, Any] = {}

#: active AOT capture sinks (:func:`heat_tpu.core.aot.capture_programs`):
#: each is a dict keyed by fuse-cache key, fed one entry per distinct
#: cache-keyed call so a warm process can export its executables
_CAPTURE_SINKS: list = []


@contextlib.contextmanager
def _null_ctx():
    yield


def _is_dnd(x: Any) -> bool:
    return isinstance(x, DNDarray)


def _guards():
    """Lazy import of the health-guard seam (the resilience package sits
    above core in the import graph)."""
    from ..resilience import guards

    return guards


class _Program:
    """A traced-and-compiled pipeline plus its output re-wrap recipe.

    ``guarded`` marks programs traced under an active health-guard
    policy: they carry one extra output, the on-device health flag over
    every inexact result buffer.  The guard policy is part of the fuse
    cache key (:func:`heat_tpu.core._compile.context_token`), so a
    guarded and an unguarded trace of the same pipeline never collide.
    """

    __slots__ = ("jfn", "out_treedef", "out_meta", "guarded", "aot_payload")

    def __init__(self, jfn):
        self.jfn = jfn
        self.out_treedef = None
        self.out_meta = None
        self.guarded = False
        # set only on installed programs: the original serialized
        # (payload, in_tree, out_tree) triple, kept so a warm replica can
        # re-export without re-serializing a loaded executable (which
        # XLA cannot soundly deserialize a second time)
        self.aot_payload = None


def _build(fn: Callable, slots: Tuple, treedef, donate: bool) -> _Program:
    """Compile ``fn`` over the leaf layout described by ``slots``.

    ``slots`` entries are ``("dnd", gshape, dtype, split, device, comm,
    balanced)``, ``("arr",)``, or ``("static", value)``; dynamic operands
    are threaded through in slot order.
    """
    program = _Program(None)

    def _runner(operands):
        it = iter(operands)
        leaves = []
        for slot in slots:
            if slot[0] == "dnd":
                _, gshape, dtype, split, device, comm, balanced = slot
                leaves.append(DNDarray(next(it), gshape, dtype, split, device, comm, balanced))
            elif slot[0] == "arr":
                leaves.append(next(it))
            else:
                leaves.append(slot[1])
        args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
        with trace_mode():
            out = fn(*args, **kwargs)
            out_leaves, out_treedef = jax.tree_util.tree_flatten(out, is_leaf=_is_dnd)
            raws, meta = [], []
            for leaf in out_leaves:
                if isinstance(leaf, DNDarray):
                    buf = leaf._buffer
                    # pin the at-rest layout at the program boundary; the
                    # buffer is canonically padded, so the split axis is
                    # divisible and commits genuinely sharded
                    sh = leaf.comm.sharding(buf.ndim, leaf.split)
                    raws.append(jax.lax.with_sharding_constraint(buf, sh))
                    meta.append(
                        ("dnd", leaf.gshape, leaf.dtype, leaf.split, leaf.device,
                         leaf.comm, leaf.balanced)
                    )
                elif isinstance(leaf, jax.Array):
                    raws.append(leaf)
                    meta.append(("raw",))
                else:
                    # trace-time constant (python scalar, string, None-like):
                    # deterministic given the cache key, so bake it in
                    meta.append(("const", leaf))
        program.out_treedef = out_treedef
        program.out_meta = tuple(meta)
        if _guards().active():
            # one extra scalar output: the fused-program health flag —
            # all(isfinite) and below the overflow limit, over every
            # inexact result buffer, computed on device in the same
            # dispatch
            raws.append(_guards().health_flag(raws))
            program.guarded = True
        return tuple(raws)

    program.jfn = jax.jit(_runner, donate_argnums=(0,) if donate else ())
    return program


class _FusedFunction:
    """The callable returned by :func:`fuse`."""

    def __init__(self, fn: Callable, donate: bool = False, layout_plan=None):
        self._fn = fn
        self._donate = bool(donate)
        self._stable = cache_stable(fn)
        # a solved ht.autoshard plan: its decisions steer every resplit
        # inside the trace, and its fingerprint joins the cache key so a
        # planned and an unplanned trace of the same fn never collide
        self._layout_plan = layout_plan
        self._plan_token = layout_plan["fingerprint"] if layout_plan else None
        functools.update_wrapper(self, fn)

    def __call__(self, *args, **kwargs):
        if in_trace():
            # nested fuse (or inside fuse.trace()): inline into the
            # enclosing program instead of compiling a second one
            return self._fn(*args, **kwargs)
        leaves, treedef = jax.tree_util.tree_flatten((args, kwargs), is_leaf=_is_dnd)
        operands, slots, keyparts = [], [], []
        comm = None
        for leaf in leaves:
            if isinstance(leaf, DNDarray):
                buf = leaf._buffer
                operands.append(buf)
                slots.append(
                    ("dnd", leaf.gshape, leaf.dtype, leaf.split, leaf.device,
                     leaf.comm, leaf.balanced)
                )
                keyparts.append(
                    ("dnd", tuple(buf.shape), str(buf.dtype), leaf.gshape,
                     leaf.dtype, leaf.split, leaf.balanced, leaf.comm)
                )
                comm = comm if comm is not None else leaf.comm
            elif isinstance(leaf, (jax.Array, np.ndarray)):
                operands.append(leaf)
                slots.append(("arr",))
                keyparts.append(("arr", tuple(leaf.shape), str(leaf.dtype)))
            else:
                slots.append(("static", leaf))
                keyparts.append(("static", leaf))
        slots = tuple(slots)

        program = None
        key = None
        if self._stable and self._cacheable_statics(leaves):
            # context_token(): process-wide state (collective-compression
            # policy, comm overlap, io prefetch — every provider behind
            # _compile.register_key_context) that changes what the traced
            # program computes or how its dispatches are attributed —
            # fused programs re-trace under a new policy, never replay
            key = (self._fn, self._donate, self._plan_token, treedef,
                   tuple(keyparts), comm, _compile.context_token())
            try:
                program = _FUSE_CACHE.get(key)
            except TypeError:  # unhashable static leaf slipped through
                key = None
        if program is None:
            if _tel.enabled:
                _tel.inc("fuse.cache.misses")
            program = _build(self._fn, slots, treedef, self._donate)
            if key is not None:
                _FUSE_CACHE[key] = program
                if _tel.enabled:
                    _tel.gauge("fuse.cache.size", len(_FUSE_CACHE))
        elif _tel.enabled:
            _tel.inc("fuse.cache.hits")

        # AOT capture: operand specs must be snapshotted BEFORE the call
        # (donation may consume the buffers), the entry recorded after it
        # (the first call populates program.out_meta)
        capture_specs = None
        if _CAPTURE_SINKS and key is not None:
            capture_specs = tuple(
                jax.ShapeDtypeStruct(
                    tuple(op.shape), op.dtype,
                    sharding=op.sharding if isinstance(op, jax.Array) else None,
                )
                for op in operands
            )

        # jax.jit is lazy, so the plan context must cover EVERY launch:
        # the first call runs the DNDarray trace (where resplits consult
        # the plan) inside jfn, and jit may silently retrace later
        plan_ctx = (
            applying_layout_plan(self._layout_plan["decisions"])
            if self._layout_plan is not None else _null_ctx()
        )
        with plan_ctx:
            # a program whose out_treedef is still unset runs its DNDarray
            # trace + XLA compile inside this first call, so that is the
            # "build" span; later calls replay
            raws = _compile.launch(
                "fuse:build" if program.out_treedef is None else "fuse:replay",
                program.jfn, (tuple(operands),),
                name=getattr(self._fn, "__name__", "<pipeline>"),
            )

        if capture_specs is not None:
            entry = {
                "fn": self._fn,
                "donate": self._donate,
                "plan_token": self._plan_token,
                "treedef": treedef,
                "keyparts": tuple(keyparts),
                "comm": comm,
                "program": program,
                "specs": capture_specs,
            }
            for sink in _CAPTURE_SINKS:
                sink.setdefault(key, entry)

        flag = None
        if program.guarded:
            flag = raws[-1]
            raws = raws[:-1]

        it = iter(raws)
        out_leaves = []
        for meta in program.out_meta:
            if meta[0] == "dnd":
                _, gshape, dtype, split, device, comm_, balanced = meta
                out_leaves.append(DNDarray(next(it), gshape, dtype, split, device, comm_, balanced))
            elif meta[0] == "raw":
                out_leaves.append(next(it))
            else:
                out_leaves.append(meta[1])
        result = jax.tree_util.tree_unflatten(program.out_treedef, out_leaves)

        if flag is not None and not bool(flag):
            if self._donate:
                # the unhealthy program consumed its input buffers —
                # there is nothing left to re-run the exact path on
                degrade_fn = None
            else:
                def degrade_fn():
                    from ..comm.compressed import collective_precision

                    # exact-collective re-trace: the policy change flows
                    # into the cache key, so this compiles (and caches)
                    # its own program instead of mutating the fast one
                    with collective_precision("f32"):
                        return self(*args, **kwargs)

            site = f"fuse:{getattr(self._fn, '__name__', '<pipeline>')}"
            return _guards().handle(site, result, degrade_fn)
        return result

    @staticmethod
    def _cacheable_statics(leaves) -> bool:
        """Static leaves must be hashable, and callable statics must have a
        call-stable identity — otherwise every call would add a dead cache
        entry (same rule as jitted keys, spmdlint SPMD401)."""
        for leaf in leaves:
            if isinstance(leaf, (DNDarray, jax.Array, np.ndarray)):
                continue
            if callable(leaf) and not cache_stable(leaf):
                return False
            try:
                hash(leaf)
            except TypeError:
                return False
        return True


def fuse(fn: Optional[Callable] = None, *, donate: bool = False,
         layout_plan=None):
    """Compile a DNDarray pipeline into one XLA program (one dispatch).

    Use as a decorator (``@ht.fuse`` / ``@ht.fuse(donate=True)``) or
    inline (``fused = ht.fuse(my_pipeline)``).  See the module docstring
    for caching, static-argument, and donation semantics.

    ``layout_plan`` is the :func:`heat_tpu.autoshard` seam: a solved plan
    dict (:meth:`heat_tpu.comm._costs.LayoutSolver.solve`) whose decisions
    override the hand-placed resplits during tracing and whose fingerprint
    becomes part of the compile-cache key.
    """
    if fn is None:
        return functools.partial(fuse, donate=donate, layout_plan=layout_plan)
    return _FusedFunction(fn, donate=donate, layout_plan=layout_plan)


#: context-manager variant: bare tracing mode without compile-and-cache
fuse.trace = trace_mode


def fuse_cache_size() -> int:
    """Number of cached fused programs (mainly for tests)."""
    return len(_FUSE_CACHE)


def fuse_clear_cache() -> None:
    """Drop all cached fused programs (mainly for tests)."""
    _FUSE_CACHE.clear()


fuse.cache_size = fuse_cache_size
fuse.clear_cache = fuse_clear_cache
