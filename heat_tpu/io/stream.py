"""Out-of-core streaming: chunked per-rank reads double-buffered against
compute.

Every fit in the tree historically assumed the dataset fits on-device.
This module is the io half of the mini-batch streaming path (the
estimator half lives in ``cluster/kmeans.py`` and ``regression/lasso.py``):
a :class:`StreamSource` exposes row-wise random access over an on-disk
HDF5/NetCDF dataset (or an in-memory array — the bitwise twin), and
:func:`stream_chunks` turns it into a sequence of device-resident,
row-sharded, zero-padded chunks.  Under ``ht.io.set_prefetch("on")`` the
sequence is double-buffered: while the compiled program consumes chunk
*t*, a single worker thread is already reading chunk *t+1*'s slab from
disk and committing it to a second device buffer — the PR 11 two-stream
overlap idiom applied at the io boundary, so steady-state cost per chunk
is ``max(read + copy, compute)`` instead of their sum
(:func:`heat_tpu.comm._costs.stream_model` is the modeled pair).

Determinism contract (what makes the streaming fits' twins bitwise):

- chunk geometry is a pure function of ``(rows, mini_batch)`` — chunk
  ``t`` covers global rows ``[t*mb, min(n, (t+1)*mb))``, the ragged tail
  is ZERO-padded to the canonical chunk width and reported through the
  explicit ``nvalid`` count (the PR 4 pad + valid-count discipline), so
  the consuming program masks pads exactly;
- the prefetch policy changes host scheduling ONLY — both arms read the
  same bytes in the same order and dispatch the same compiled program,
  so prefetch-on is bitwise-equal to prefetch-off by construction
  (``tests/test_stream.py`` asserts it);
- every chunk read crosses the ``faults.io_open(..., site="stream.read")``
  seam under the bounded, seeded io retry policy: an injected transient
  ``OSError`` mid-stream heals with the attempt incident-logged, and the
  chaos lane replays the exact schedule from ``HEAT_CHAOS_SEED``.

Peak host memory is bounded by construction: at most TWO chunk slabs are
ever live (the one being consumed and the one in flight) under prefetch,
ONE without — :func:`slab_peak` reports the high-water mark the tests
assert against the model's ``peak_host_slabs``.

Like ``set_overlap`` and the collective-precision knob, the policy is
registered in every compiled-program cache key
(:func:`heat_tpu.core._compile.register_key_context`), so a run can hold
the prefetch-on fit and its serial twin side by side without replaying a
program traced under the other policy's dispatch statistics.

docs/design.md §24 documents the segment/carry model, the policy × cache
keys interaction, the bandwidth roofline, and the resume contract.
"""

from __future__ import annotations

import contextlib
import threading
from concurrent.futures import ThreadPoolExecutor
from typing import Iterator, Optional, Sequence, Tuple, Union

import numpy as np

import jax

from ..core import devices as _devices
from ..core import io as _cio
from ..core import types
from ..core._compile import register_key_context
from ..core.communication import comm_for_device, sanitize_comm
from ..core.dndarray import DNDarray
from ..telemetry import _core as _tel

__all__ = [
    "ArraySource",
    "HDF5Source",
    "NetCDFSource",
    "StreamSource",
    "as_source",
    "get_prefetch",
    "prefetch",
    "prefetch_enabled",
    "reset_slab_peak",
    "set_prefetch",
    "slab_peak",
    "stream_chunks",
]

_MODES = ("on", "off", "auto")
_PREFETCH = "auto"


# --------------------------------------------------------------------- #
# policy (mirrors comm.set_overlap)                                      #
# --------------------------------------------------------------------- #
def set_prefetch(mode: str) -> None:
    """Set the process-wide host→device prefetch policy.

    ``"on"``
        Double-buffered streaming: chunk ``t+1``'s read + device commit
        runs on a worker thread while chunk ``t``'s compiled program
        executes (two host slabs live).
    ``"off"``
        Strictly sequential read → copy → compute (one slab live) — the
        exact twin every overlapped stream is validated against.
    ``"auto"``
        The default: prefetch on TPU backends (where the h2d DMA runs
        concurrently with the MXU), sequential elsewhere — CPU test runs
        keep the single-threaded schedule unless a test opts in.
    """
    global _PREFETCH
    if mode not in _MODES:
        raise ValueError(
            f"unknown prefetch mode {mode!r}: expected one of {_MODES}"
        )
    _PREFETCH = mode


def get_prefetch() -> str:
    """The current process-wide prefetch policy."""
    return _PREFETCH


@contextlib.contextmanager
def prefetch(mode: str):
    """Context-manager form of :func:`set_prefetch`."""
    prev = _PREFETCH
    set_prefetch(mode)
    try:
        yield
    finally:
        set_prefetch(prev)


@register_key_context
def _prefetch_token() -> Tuple:
    """The prefetch policy's contribution to every compiled-program cache
    key.  The traced chunk programs are schedule-independent (prefetch
    only reorders host work), but keying on the policy keeps each arm's
    first-dispatch/compile telemetry attributable to its own setting —
    the same discipline as ``set_overlap``, and what lets one process
    hold both arms side by side.  The backend check inside
    :func:`prefetch_enabled` is deliberately NOT part of the token — the
    process backend is fixed for the life of the cache."""
    return ("prefetch", _PREFETCH)


def prefetch_enabled() -> bool:
    """Whether :func:`stream_chunks` should double-buffer under the
    current policy (``"auto"`` resolves by backend, like
    ``overlap_enabled``)."""
    if _PREFETCH == "off":
        return False
    if _PREFETCH == "on":
        return True
    return jax.default_backend() == "tpu"


# --------------------------------------------------------------------- #
# host-slab accounting                                                   #
# --------------------------------------------------------------------- #
class _SlabLedger:
    """Live/peak count of host chunk slabs (a slab is live from the start
    of its read until its consuming dispatch returns).  The streaming
    memory contract — ≤ 2 slabs under prefetch, ≤ 1 without — is asserted
    against this ledger, not inferred."""

    def __init__(self):
        self._lock = threading.Lock()
        self.live = 0
        self.peak = 0

    def acquire(self) -> None:
        with self._lock:
            self.live += 1
            if self.live > self.peak:
                self.peak = self.live
                if _tel.enabled:
                    _tel.gauge("io.stream.host_slabs_peak", float(self.peak))

    def release(self) -> None:
        with self._lock:
            self.live = max(0, self.live - 1)

    def reset(self) -> None:
        with self._lock:
            self.peak = self.live


_SLABS = _SlabLedger()


def slab_peak() -> int:
    """High-water mark of simultaneously live host chunk slabs since the
    last :func:`reset_slab_peak`."""
    return _SLABS.peak


def reset_slab_peak() -> None:
    """Reset the slab high-water mark (test bracketing)."""
    _SLABS.reset()


# --------------------------------------------------------------------- #
# sources                                                                #
# --------------------------------------------------------------------- #
class StreamSource:
    """Row-wise random-access reader over a (possibly on-disk) dataset.

    Subclasses provide ``shape`` (global), ``np_dtype``, and
    ``read(lo, hi)`` returning host rows ``[lo, hi)`` as a numpy array.
    ``read`` must be safe to call from a worker thread (the file-backed
    sources open a fresh handle per call for exactly this reason) and
    must be a pure function of the byte range — the bitwise twins depend
    on replays returning identical bytes.
    """

    #: fault-seam label for in-memory sources; file sources override
    path = "<memory>"

    shape: Tuple[int, ...]
    np_dtype: np.dtype

    @property
    def rows(self) -> int:
        return int(self.shape[0])

    def read(self, lo: int, hi: int) -> np.ndarray:
        raise NotImplementedError

    def __len__(self) -> int:
        return self.rows


class ArraySource(StreamSource):
    """In-memory stream source — the twin that makes streaming-vs-resident
    equality a testable gate: a DNDarray/ndarray fed through the SAME
    chunk geometry, pad, and segment programs as an on-disk stream."""

    def __init__(self, array, dtype=types.float32):
        hdtype = types.canonical_heat_type(dtype)
        self.np_dtype = np.dtype(hdtype._np_type)
        if isinstance(array, DNDarray):
            array = array.larray
        self._arr = np.asarray(array, dtype=self.np_dtype)
        self.shape = tuple(int(s) for s in self._arr.shape)

    def read(self, lo: int, hi: int) -> np.ndarray:
        return self._arr[int(lo):int(hi)]


class HDF5Source(StreamSource):
    """Chunked reader over one HDF5 dataset (per-chunk slab reads; a
    fresh file handle per read keeps the worker thread independent of
    the main thread's io)."""

    def __init__(self, path: str, dataset: str, dtype=types.float32):
        if not _cio.supports_hdf5():
            raise RuntimeError("h5py is required for HDF5 support")
        if not isinstance(path, str):
            raise TypeError(f"path must be str, not {type(path)}")
        if not isinstance(dataset, str):
            raise TypeError(f"dataset must be str, not {type(dataset)}")
        self.path = path
        self.dataset = dataset
        hdtype = types.canonical_heat_type(dtype)
        self.np_dtype = np.dtype(hdtype._np_type)

        def _probe():
            _cio._faults().io_open(path)
            with _cio.h5py.File(path, "r") as handle:
                member = _cio._named_member(path, handle, dataset, "dataset")
                return tuple(int(s) for s in member.shape)

        self.shape = _cio._retry_open(_probe, "io.stream.open")

    def read(self, lo: int, hi: int) -> np.ndarray:
        with _cio.h5py.File(self.path, "r") as f:
            return np.asarray(f[self.dataset][int(lo):int(hi)], dtype=self.np_dtype)


class NetCDFSource(StreamSource):
    """Chunked reader over one NetCDF variable (netCDF4 backend, or
    scipy's classic NetCDF-3 reader as the fallback — the same gating as
    :func:`heat_tpu.core.io.load_netcdf`)."""

    def __init__(self, path: str, variable: str, dtype=types.float32):
        if not _cio.supports_netcdf():
            raise RuntimeError("a NetCDF backend (netCDF4 or scipy) is required")
        if not isinstance(path, str):
            raise TypeError(f"path must be str, not {type(path)}")
        if not isinstance(variable, str):
            raise TypeError(f"variable must be str, not {type(variable)}")
        self.path = path
        self.variable = variable
        hdtype = types.canonical_heat_type(dtype)
        self.np_dtype = np.dtype(hdtype._np_type)

        if _cio.nc is not None:
            def _probe():
                _cio._faults().io_open(path)
                with _cio.nc.Dataset(path, "r") as handle:
                    member = _cio._named_member(
                        path, handle.variables, variable, "variable"
                    )
                    return tuple(int(s) for s in member.shape)
        else:
            def _probe():
                _cio._faults().io_open(path)
                with _cio._scipy_nc(path, "r", mmap=False) as handle:
                    member = _cio._named_member(
                        path, handle.variables, variable, "variable"
                    )
                    return tuple(int(s) for s in member.shape)

        self.shape = _cio._retry_open(_probe, "io.stream.open")

    def read(self, lo: int, hi: int) -> np.ndarray:
        lo, hi = int(lo), int(hi)
        if _cio.nc is not None:
            with _cio.nc.Dataset(self.path, "r") as f:
                return np.asarray(
                    f.variables[self.variable][lo:hi], dtype=self.np_dtype
                )
        with _cio._scipy_nc(self.path, "r", mmap=False) as f:
            return np.array(f.variables[self.variable][lo:hi], dtype=self.np_dtype)


def as_source(data, dtype=types.float32) -> StreamSource:
    """Coerce ``data`` to a :class:`StreamSource`: sources pass through,
    DNDarrays and array-likes wrap as the in-memory twin."""
    if isinstance(data, StreamSource):
        return data
    return ArraySource(data, dtype=dtype)


# --------------------------------------------------------------------- #
# the chunk pipeline                                                     #
# --------------------------------------------------------------------- #
def _read_chunk(source: StreamSource, lo: int, hi: int) -> np.ndarray:
    """One slab read across the chaos seam under the seeded io retry
    policy (a transient injected/real ``OSError`` heals with the attempt
    incident-logged; only an exhausted policy propagates)."""
    from ..resilience import retry as _retry

    def _read():
        _cio._faults().io_open(source.path, site="stream.read")
        return source.read(lo, hi)

    return _retry.call(_read, policy=_retry.IO_POLICY, site="io.stream.read")


def stream_chunks(
    sources: Union[StreamSource, Sequence[StreamSource]],
    mini_batch: int,
    start: int,
    stop: int,
    *,
    comm=None,
    device=None,
) -> Iterator[Tuple[Tuple[jax.Array, ...], int]]:
    """Yield device-resident chunks for global steps ``[start, stop)``.

    Each yield is ``(arrays, nvalid)``: one row-sharded, zero-padded
    device array per source (``ceil(mb/p)*p`` rows so every mesh size
    shards evenly) plus the chunk's valid-row count.  Step ``s`` maps to
    chunk ``s % h`` of an ``h = ceil(n/mb)``-chunk epoch, so a driver
    resuming from a snapshotted step re-enters mid-epoch at exactly the
    right stream position.  Multiple sources (e.g. an X and a y stream)
    are read over the identical row range per step.

    Under :func:`prefetch_enabled` the next chunk's read + device commit
    runs on a single worker thread while the caller consumes the current
    one (≤ 2 host slabs live); otherwise strictly sequential (≤ 1).
    Reads are credited to the telemetry ledger as ``io:read``/``io:h2d``
    spans with ``account_bytes("io", ...)``, so the measured streaming
    bandwidth reconciles byte-for-byte.
    """
    if isinstance(sources, StreamSource):
        sources = (sources,)
    sources = tuple(sources)
    if not sources:
        raise ValueError("stream_chunks needs at least one source")
    device = _devices.sanitize_device(device)
    comm = comm_for_device(device.platform) if comm is None else sanitize_comm(comm)
    mb = int(mini_batch)
    if mb <= 0:
        raise ValueError(f"mini_batch must be >= 1, got {mb}")
    n = sources[0].rows
    for s in sources[1:]:
        if s.rows != n:
            raise ValueError(
                f"stream sources disagree on length: {n} vs {s.rows} rows"
            )
    h = max(1, -(-n // mb))
    p = comm.size
    rows_dev = -(-mb // p) * p
    shardings = tuple(comm.sharding(len(s.shape), 0) for s in sources)

    def _build(step: int):
        t = step % h
        lo = t * mb
        hi = min(n, lo + mb)
        nv = hi - lo
        _SLABS.acquire()
        try:
            arrs = []
            for src, sh in zip(sources, shardings):
                if _tel.enabled:
                    with _tel.span("io:read", "io", path=str(src.path), rows=nv):
                        block = np.asarray(_read_chunk(src, lo, hi))
                    _tel.account_bytes("io", "read", block.nbytes, block.nbytes)
                else:
                    block = np.asarray(_read_chunk(src, lo, hi))
                if block.shape != (nv,) + tuple(src.shape[1:]):
                    raise ValueError(
                        f"{src.path}: read({lo}, {hi}) returned shape "
                        f"{block.shape}, expected {(nv,) + tuple(src.shape[1:])}"
                    )
                buf = np.zeros(
                    (rows_dev,) + tuple(src.shape[1:]), dtype=src.np_dtype
                )
                buf[:nv] = block

                def _cb(index, _buf=buf):
                    return _buf[index]

                if _tel.enabled:
                    with _tel.span("io:h2d", "io", path=str(src.path), bytes=buf.nbytes):
                        garr = jax.make_array_from_callback(buf.shape, sh, _cb)
                    _tel.account_bytes("io", "h2d", buf.nbytes, buf.nbytes)
                else:
                    garr = jax.make_array_from_callback(buf.shape, sh, _cb)
                arrs.append(garr)
            if _tel.enabled:
                _tel.inc("io.stream.chunks")
            return tuple(arrs), nv
        except BaseException:
            _SLABS.release()
            raise

    if not prefetch_enabled():
        for step in range(int(start), int(stop)):
            arrs, nv = _build(step)
            try:
                yield arrs, nv
            finally:
                _SLABS.release()
        return

    ex = ThreadPoolExecutor(max_workers=1, thread_name_prefix="ht-stream")
    fut = None
    try:
        if int(start) < int(stop):
            fut = ex.submit(_build, int(start))
        for step in range(int(start), int(stop)):
            arrs, nv = fut.result()
            fut = ex.submit(_build, step + 1) if step + 1 < int(stop) else None
            try:
                yield arrs, nv
            finally:
                _SLABS.release()
    finally:
        if fut is not None:
            # an abandoned in-flight build (early generator close, a
            # consumer fault) still holds a slab ticket — drain it
            try:
                fut.result()
            except BaseException:
                pass
            else:
                _SLABS.release()
        ex.shutdown(wait=True)
