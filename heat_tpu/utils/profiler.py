"""Thin profiling hooks.

Reference: ABSENT — the reference has no profiler (SURVEY.md §5.1); its
benchmarks use bare ``time.perf_counter``.  The TPU stack gets
device-accurate tracing for free from ``jax.profiler``; this module wraps
it in the context-manager form the build plan calls for, plus a
wall-clock timer matching the reference benchmarks' measurement style.
"""

from __future__ import annotations

import contextlib
import time
from typing import Iterator, Optional

import jax

from ..telemetry import _core as _tel

__all__ = ["profile", "timer", "annotate"]


@contextlib.contextmanager
def profile(logdir: str = "/tmp/heat_tpu_profile") -> Iterator[None]:
    """Capture a device trace viewable in TensorBoard/XProf.

    >>> with ht.utils.profiler.profile("/tmp/trace"):
    ...     ht.linalg.qr(x)
    """
    jax.profiler.start_trace(logdir)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str):
    """Label a region: a :func:`heat_tpu.telemetry.span` of kind ``other``.
    While a profiler trace runs it is a ``TraceAnnotation`` in that trace
    and a span in ``telemetry.events()``; otherwise it costs one predicate."""
    return _tel.span(name)


class timer(contextlib.AbstractContextManager):
    """Wall-clock timer that blocks on device completion.

    >>> with ht.utils.profiler.timer() as t:
    ...     y = (x @ x.T).sum()
    >>> t.seconds
    """

    def __init__(self, sync: bool = True):
        self.sync = sync
        self.seconds: Optional[float] = None

    def __enter__(self):
        self._start = time.perf_counter()
        return self

    def __exit__(self, *exc):
        if self.sync:
            try:
                jax.effects_barrier()
            except Exception:
                pass
        self.seconds = time.perf_counter() - self._start
        return False
