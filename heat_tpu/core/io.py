"""Parallel IO: HDF5, NetCDF, CSV.

Reference: heat/core/io.py:19-923 — per-rank chunk reads (each MPI process
reads only its ``chunk()`` slice of the dataset, io.py:104-111), slab
writes with Isend/Recv ordering, and a byte-range CSV partitioner.

TPU-native formulation: reads go through
:func:`jax.make_array_from_callback`, which asks for exactly the index
ranges each device's shard covers — so a sharded load reads each slab once,
straight into its device buffer (the direct analog of the reference's
per-rank slab read, generalized to any mesh).  Writes gather per-shard
slices on the host and write slabs sequentially (single-controller: no
inter-process ordering protocol needed).  netCDF4 is optional exactly like
the reference's try-import gating (io.py:26-41).
"""

from __future__ import annotations

import os
import shutil
from typing import List, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from ..telemetry import _core as _tel
from . import devices as _devices
from . import factories, types
from .communication import comm_for_device, sanitize_comm
from .dndarray import DNDarray
from .stride_tricks import sanitize_axis

try:
    import h5py
except ImportError:
    h5py = None

try:
    import netCDF4 as nc
except ImportError:
    nc = None

try:
    # fallback NetCDF backend: scipy's pure-python NetCDF-3 reader/writer
    # (classic format only — no groups, no 64-bit integer variables)
    from scipy.io import netcdf_file as _scipy_nc
except ImportError:
    _scipy_nc = None

__all__ = [
    "load",
    "load_csv",
    "load_hdf5",
    "load_netcdf",
    "save",
    "save_csv",
    "save_hdf5",
    "save_netcdf",
    "supports_hdf5",
    "supports_netcdf",
]

__HDF5_EXTENSIONS = frozenset([".h5", ".hdf5"])
#: public alias — estimator checkpointing shares the routing table
HDF5_EXTENSIONS = __HDF5_EXTENSIONS
__NETCDF_EXTENSIONS = frozenset([".nc", ".nc4", ".netcdf"])
__CSV_EXTENSIONS = frozenset([".csv", ".txt"])


def supports_hdf5() -> bool:
    """True when h5py is importable (reference io.py:26-33)."""
    return h5py is not None


def supports_netcdf() -> bool:
    """True when a NetCDF backend is importable: netCDF4 (full NetCDF-4),
    else scipy's classic NetCDF-3 reader/writer (reference io.py:34-41
    gates on netCDF4 alone)."""
    return nc is not None or _scipy_nc is not None


def _faults():
    """Lazy import of the fault-injection seams (the resilience package
    imports this module, so the dependency must stay one-way at import
    time)."""
    from ..resilience import faults

    return faults


def _retry_open(fn, site: str):
    """Run a file-open probe under the bounded, seeded io retry policy:
    a transient ``OSError`` (flaky NFS, a file mid-failover, an injected
    ``io_error`` fault) heals on retry with every attempt incident-logged
    and counted; only an exhausted policy propagates.  Lazy import for
    the same one-way-dependency reason as :func:`_faults`."""
    from ..resilience import retry as _r

    return _r.call(fn, policy=_r.IO_POLICY, site=site)


def _named_member(path: str, mapping, name: str, kind: str):
    """Look up ``name`` in a file's member ``mapping`` (h5py File, NetCDF
    ``.variables``), naming BOTH the file and the missing member on
    failure — a bare ``KeyError: 'x'`` from a 40-file ingest loop says
    nothing about which file lacked which dataset."""
    try:
        return mapping[name]
    except KeyError:
        try:
            available = ", ".join(sorted(map(str, mapping.keys()))) or "<none>"
        except Exception:  # noqa: BLE001 — the lookup error is the story
            available = "<unknown>"
        raise ValueError(
            f"{path}: no {kind} named {name!r} (available: {available})"
        ) from None


# --------------------------------------------------------------------- #
# atomic writes                                                          #
# --------------------------------------------------------------------- #
# Every writer path stages into a same-directory temp file and commits
# with os.replace only after a successful close: a crash (or injected
# preemption) anywhere mid-save leaves the previous file byte-identical.
# Append modes first copy the existing file into the temp so the commit
# is still all-or-nothing.
def _atomic_begin(path: str, mode: str = "w") -> str:
    """Start an atomic write of ``path``: returns the temp path to write
    to.  Same directory as the target so :func:`os.replace` stays a
    rename, never a copy."""
    tmp = f"{path}.tmp-{os.getpid()}"
    if mode not in ("w", "w-") and os.path.exists(path):
        shutil.copyfile(path, tmp)
    return tmp


def _atomic_commit(tmp: str, path: str) -> None:
    """Publish a finished atomic write (rename over the target)."""
    os.replace(tmp, path)


def _atomic_abort(tmp: Optional[str]) -> None:
    """Discard a failed atomic write; the target was never touched."""
    if tmp is not None:
        try:
            os.remove(tmp)
        except OSError:
            pass


def _sharded_from_reader(shape, np_dtype, split, device, comm, read_slices):
    """Build a sharded global jax.Array by reading only each shard's slab
    (the parallel-read core; reference io.py:104-111 per-rank slab read)."""
    device = _devices.sanitize_device(device)
    comm = comm_for_device(device.platform) if comm is None else sanitize_comm(comm)
    split = sanitize_axis(shape, split)
    hdtype = types.canonical_heat_type(np_dtype)
    # io:read brackets the slab reads, io:h2d the device commit, and both
    # credit account_bytes("io", ...): streamed bytes reconcile against
    # this ledger like every collective's
    total_bytes = int(np.prod(shape, dtype=np.int64)) * np.dtype(hdtype._np_type).itemsize
    if split is not None and shape[split] % comm.size == 0 and comm.size > 1:
        sharding = comm.sharding(len(shape), split)

        def _cb(index):
            if _tel.enabled:
                with _tel.span("io:read", "io", sharded=True):
                    block = np.asarray(read_slices(index))
                _tel.account_bytes("io", "read", block.nbytes, block.nbytes)
                return block
            return read_slices(index)

        if _tel.enabled:
            with _tel.span("io:h2d", "io", bytes=total_bytes):
                garr = jax.make_array_from_callback(tuple(shape), sharding, _cb)
            _tel.account_bytes("io", "h2d", total_bytes, total_bytes)
        else:
            garr = jax.make_array_from_callback(tuple(shape), sharding, _cb)
    else:
        if _tel.enabled:
            with _tel.span("io:read", "io", sharded=False):
                block = np.asarray(read_slices(tuple(slice(None) for _ in shape)))
            _tel.account_bytes("io", "read", block.nbytes, block.nbytes)
            with _tel.span("io:h2d", "io", bytes=total_bytes):
                garr = jnp.asarray(block)
            _tel.account_bytes("io", "h2d", total_bytes, total_bytes)
        else:
            garr = jnp.asarray(read_slices(tuple(slice(None) for _ in shape)))
        garr = comm.apply_sharding(garr, split)
    return DNDarray(garr, tuple(shape), hdtype, split, device, comm, True)


def load_hdf5(
    path: str,
    dataset: str,
    dtype=types.float32,
    split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Load an HDF5 dataset with per-shard slab reads
    (reference io.py:43-128)."""
    if not supports_hdf5():
        raise RuntimeError("h5py is required for HDF5 support")
    if not isinstance(path, str):
        raise TypeError(f"path must be str, not {type(path)}")
    if not isinstance(dataset, str):
        raise TypeError(f"dataset must be str, not {type(dataset)}")
    dtype = types.canonical_heat_type(dtype)

    def _probe():
        _faults().io_open(path)
        with h5py.File(path, "r") as handle:
            return tuple(_named_member(path, handle, dataset, "dataset").shape)

    gshape = _retry_open(_probe, "io.load_hdf5")

    np_dtype = np.dtype(dtype._np_type)

    def read_slices(index):
        with h5py.File(path, "r") as f:
            return np.asarray(f[dataset][index], dtype=np_dtype)

    return _sharded_from_reader(gshape, dtype, split, device, comm, read_slices)


def _emit_slabs(data: DNDarray, write):
    """Feed host slabs of ``data`` to ``write(slices, np_block)`` one shard
    at a time (bounding host memory by one shard).  ``write`` may be None —
    the process then still participates in slab fetches: on multihost
    (``jax.process_count() > 1``) fetching a slab is a cross-process
    allgather that EVERY process must join, while only process 0 writes
    the file (the analog of the reference's rank-ordered MPI-IO writes,
    reference io.py:129-234).

    A ``write`` failure is RETURNED, not raised: the fetch sequence is a
    collective program that must run to completion in lockstep on every
    process — aborting it mid-way on one process would hang the others in
    their next allgather.  Callers re-raise after the barrier."""
    multihost = jax.process_count() > 1
    err = None
    if data.split is None:
        # replicated arrays are addressable everywhere — direct fetch
        if write is not None:
            try:
                _faults().preempt_point("save-slab")
                write(tuple(slice(0, s) for s in data.shape), np.asarray(data.larray))
            except Exception as e:  # noqa: BLE001 — deferred to the caller
                err = e
        return err
    for r in range(data.comm.size):
        _, _, slices = data.comm.chunk(data.shape, data.split, rank=r)
        if any(s.stop <= s.start for s in slices):
            continue
        block = data.larray[slices]
        if multihost:
            from jax.experimental import multihost_utils

            block = multihost_utils.process_allgather(block, tiled=True)
        if write is not None and err is None:
            try:
                # the simulated-preemption seam sits INSIDE the deferred-
                # error block: a writer killed between two slab writes
                # still reaches the barrier, the staged temp file is
                # discarded, and the previous file survives untouched
                _faults().preempt_point("save-slab")
                write(slices, np.asarray(block))
            except Exception as e:  # noqa: BLE001 — deferred to the caller
                err = e
    return err


def _finish_save(err: Optional[BaseException]) -> None:
    """End a cross-process save: allgather a per-process
    failure flag so a writer-side error raises on EVERY process.  Without
    the flag only process 0 learns of a failed save — the other processes
    return success and march into the next collective (e.g. a load of the
    file that was never written) while the writer has died, hanging the
    cluster.  The flag allgather is itself a full rendezvous (no process
    passes it until every process has finished its slab collectives and
    the writer has closed the file), so it IS the end-of-save barrier —
    a separate sync_global_devices on top would just double the
    cross-process latency.  Every process must reach this call exactly
    once per save."""
    any_err = err is not None
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        flags = multihost_utils.process_allgather(
            np.asarray([1 if err is not None else 0], np.int32)
        )
        any_err = bool(np.asarray(flags).sum())
    if err is not None:
        raise err
    if any_err:
        raise RuntimeError(
            "save failed on the writer process (process 0); see its traceback"
        )


def _writer_save(data: DNDarray, prepare, path: str, mode: str = "w") -> None:
    """Writer-side half of a cross-process save.  ``prepare(target)``
    returns ``(write, close)`` for the staged temp file ``target``; any
    error — open, dataset creation, or a slab write — is DEFERRED until
    the slab fetches and the barrier have run, because those are
    collectives the other processes are already executing (an early raise
    on the writer would hang the cluster in the next allgather).  The
    temp is committed over ``path`` only after a clean close; on any
    error it is discarded and the previous file survives."""
    err, write, close, tmp = None, None, None, None
    try:
        _faults().io_open(path)
        tmp = _atomic_begin(path, mode)
        write, close = prepare(tmp)
    except Exception as e:  # noqa: BLE001 — deferred past the collectives
        err = e
    werr = _emit_slabs(data, write)
    err = err or werr
    if close is not None:
        try:
            close()
        except Exception as e:  # noqa: BLE001
            err = err or e
    if tmp is not None:
        if err is None:
            try:
                _atomic_commit(tmp, path)
            except Exception as e:  # noqa: BLE001
                err = e
        else:
            _atomic_abort(tmp)
    _finish_save(err)


def _save_hdf5_many(path: str, datasets, attrs=None, mode: str = "w") -> None:
    """Write several datasets plus file attributes in ONE file open and
    ONE cross-process failure barrier.  ``datasets`` is an ordered
    sequence of (key, DNDarray); every process must pass the same
    sequence (the slab fetches are collectives executed in order).  This
    is the multi-dataset generalization of :func:`_writer_save` — the
    deferred-error choreography lives here once, shared by
    :func:`save_hdf5` (via that helper) and estimator checkpointing."""
    datasets = list(datasets)
    if jax.process_index() == 0:
        err, f, tmp = None, None, None
        try:
            _faults().io_open(path)
            tmp = _atomic_begin(path, mode)
            f = h5py.File(tmp, mode)
        except Exception as e:  # noqa: BLE001 — deferred past the collectives
            err = e
        for key, arr in datasets:
            write = None
            if f is not None and err is None:
                try:
                    dset = f.create_dataset(
                        key, arr.shape, dtype=np.dtype(arr.dtype._np_type)
                    )
                    write = dset.__setitem__
                except Exception as e:  # noqa: BLE001
                    err = e
            werr = _emit_slabs(arr, write)
            err = err or werr
        if f is not None:
            if err is None and attrs:
                try:
                    for k, v in attrs.items():
                        f.attrs[k] = v
                except Exception as e:  # noqa: BLE001
                    err = e
            try:
                f.close()
            except Exception as e:  # noqa: BLE001
                err = err or e
        if tmp is not None:
            if err is None:
                try:
                    _atomic_commit(tmp, path)
                except Exception as e:  # noqa: BLE001
                    err = e
            else:
                _atomic_abort(tmp)
        _finish_save(err)
    else:
        for _, arr in datasets:
            _emit_slabs(arr, None)
        _finish_save(None)


def save_hdf5(data: DNDarray, path: str, dataset: str, mode: str = "w", **kwargs) -> None:
    """Save to HDF5 (reference io.py:129-234 — rank-0 metadata + ordered
    per-rank slab writes; here process 0 writes each shard slab)."""
    if not supports_hdf5():
        raise RuntimeError("h5py is required for HDF5 support")
    if not isinstance(data, DNDarray):
        raise TypeError(f"data must be a DNDarray, not {type(data)}")
    if not isinstance(path, str):
        raise TypeError(f"path must be str, not {type(path)}")

    def prepare(target):
        f = h5py.File(target, mode)
        try:
            dset = f.create_dataset(
                dataset, data.shape, dtype=np.dtype(data.dtype._np_type), **kwargs
            )
        except Exception:
            f.close()
            raise
        return dset.__setitem__, f.close

    if jax.process_index() == 0:
        _writer_save(data, prepare, path, mode)
    else:
        _emit_slabs(data, None)
        _finish_save(None)


def load_netcdf(
    path: str,
    variable: str,
    dtype=types.float32,
    split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Load a NetCDF variable (reference io.py:235-311)."""
    if not supports_netcdf():
        raise RuntimeError("a NetCDF backend (netCDF4 or scipy) is required")
    dtype = types.canonical_heat_type(dtype)
    np_dtype = np.dtype(dtype._np_type)

    if nc is not None:
        def _probe():
            _faults().io_open(path)
            with nc.Dataset(path, "r") as handle:
                return tuple(
                    _named_member(path, handle.variables, variable, "variable").shape
                )

        def read_slices(index):
            with nc.Dataset(path, "r") as f:
                return np.asarray(f.variables[variable][index], dtype=np_dtype)

    else:
        def _probe():
            _faults().io_open(path)
            with _scipy_nc(path, "r", mmap=False) as handle:
                return tuple(
                    _named_member(path, handle.variables, variable, "variable").shape
                )

        def read_slices(index):
            with _scipy_nc(path, "r", mmap=False) as f:
                return np.array(f.variables[variable][index], dtype=np_dtype)

    gshape = _retry_open(_probe, "io.load_netcdf")

    return _sharded_from_reader(gshape, dtype, split, device, comm, read_slices)


def save_netcdf(
    data: DNDarray, path: str, variable: str, mode: str = "w", dimension_names=None, **kwargs
) -> None:
    """Save to NetCDF (reference io.py:312-621 — rank-ordered slab writes;
    here the controller writes each shard slab, bounding host memory by one
    shard exactly like :func:`save_hdf5`)."""
    if not supports_netcdf():
        raise RuntimeError("a NetCDF backend (netCDF4 or scipy) is required")
    if not isinstance(data, DNDarray):
        raise TypeError(f"data must be a DNDarray, not {type(data)}")
    if dimension_names is None:
        dimension_names = [f"dim_{i}" for i in range(data.ndim)]
    np_dtype = np.dtype(data.dtype._np_type)

    if nc is None:
        if kwargs:
            raise TypeError(
                f"NetCDF-3 (scipy backend) does not support createVariable "
                f"options {sorted(kwargs)}; install netCDF4 for them"
            )
        # classic NetCDF-3 typecodes: int8/int16/int32, float32/float64
        classic_ok = (np_dtype.kind == "i" and np_dtype.itemsize <= 4) or (
            np_dtype.kind == "f" and np_dtype.itemsize in (4, 8)
        )
        if not classic_ok:
            raise TypeError(
                f"NetCDF-3 (scipy backend) cannot store dtype {np_dtype}; "
                "cast to a signed int <= 32 bits or float32/float64, or "
                "install netCDF4"
            )

    def prepare(target):
        f = (
            nc.Dataset(target, mode)
            if nc is not None
            else _scipy_nc(target, "w" if mode == "w" else "a")
        )
        try:
            for name, length in zip(dimension_names, data.shape):
                if name not in f.dimensions:
                    f.createDimension(name, length)
            if nc is not None:
                var = f.createVariable(variable, np_dtype, tuple(dimension_names), **kwargs)
            else:
                var = f.createVariable(variable, np_dtype, tuple(dimension_names))
        except Exception:
            f.close()
            raise
        return var.__setitem__, f.close

    if jax.process_index() == 0:
        _writer_save(data, prepare, path, mode)
    else:
        _emit_slabs(data, None)
        _finish_save(None)


def load_csv(
    path: str,
    header_lines: int = 0,
    sep: str = ",",
    dtype=types.float32,
    encoding: str = "utf-8",
    split: Optional[int] = None,
    device=None,
    comm=None,
) -> DNDarray:
    """Load a CSV file (reference io.py:665-885 — byte-range partitioning by
    rank with line-boundary fixup).  The partitioning runs in the native
    threaded scanner (:mod:`heat_tpu.native`, C++ over mmap'd byte ranges
    with the same line-ownership rule); the numpy parser is the fallback
    for exotic encodings, ragged rows, or toolchain-less hosts."""
    if not isinstance(path, str):
        raise TypeError(f"path must be str, not {type(path)}")
    if not isinstance(sep, str):
        raise TypeError(f"separator must be str, not {type(sep)}")
    if not isinstance(header_lines, int):
        raise TypeError(f"header_lines must be int, not {type(header_lines)}")
    dtype = types.canonical_heat_type(dtype)
    data = None
    if encoding in ("utf-8", "ascii", "utf8"):
        from .. import native

        data = native.fastcsv_parse(path, header_lines=header_lines, sep=sep)
        if data is not None:
            data = data.astype(np.dtype(dtype._np_type), copy=False)
    if data is None:
        data = np.genfromtxt(
            path,
            delimiter=sep,
            skip_header=header_lines,
            dtype=np.dtype(dtype._np_type),
            encoding=encoding,
        )
    return factories.array(data, dtype=dtype, split=split, device=device, comm=comm)


def save_csv(
    data: DNDarray,
    path: str,
    header_lines: Optional[str] = None,
    sep: str = ",",
    decimals: int = -1,
    encoding: str = "utf-8",
    **kwargs,
) -> None:
    """Save a 1-D/2-D DNDarray to CSV (reference io.py adds this in later
    versions; provided for round-trip completeness)."""
    if data.ndim > 2:
        raise ValueError("save_csv supports 1-D and 2-D arrays")
    # the allgather is a collective every process joins BEFORE the
    # writer-only (fallible) file write, so a write error cannot desync it
    if jax.process_count() > 1 and data.split is not None:
        from jax.experimental import multihost_utils

        arr = np.asarray(multihost_utils.process_allgather(data.larray, tiled=True))
    else:
        arr = np.asarray(data.larray)
    fmt = f"%.{decimals}f" if decimals >= 0 else "%s"
    err = None
    if jax.process_index() == 0:
        tmp = None
        try:
            _faults().io_open(path)
            tmp = _atomic_begin(path)
            _faults().preempt_point("save-slab")
            np.savetxt(
                tmp, arr, delimiter=sep, header=header_lines or "", fmt=fmt, encoding=encoding
            )
            _atomic_commit(tmp, path)
        except Exception as e:  # noqa: BLE001 — deferred past the collectives
            err = e
            _atomic_abort(tmp)
    _finish_save(err)


def load(path: str, *args, **kwargs) -> DNDarray:
    """Extension-dispatched load (reference io.py:622-664)."""
    if _tel.enabled:
        _tel.inc("io.loads")
        with _tel.span("io:load", "io", path=str(path)):
            return _load_impl(path, *args, **kwargs)
    return _load_impl(path, *args, **kwargs)


def _load_impl(path: str, *args, **kwargs) -> DNDarray:
    if not isinstance(path, str):
        raise TypeError(f"Expected path to be str, but was {type(path)}")
    ext = os.path.splitext(path)[-1].strip().lower()
    if ext in __HDF5_EXTENSIONS:
        if not supports_hdf5():
            raise RuntimeError(f"hdf5 is required for file extension {ext}")
        return load_hdf5(path, *args, **kwargs)
    if ext in __NETCDF_EXTENSIONS:
        if not supports_netcdf():
            raise RuntimeError(f"netcdf is required for file extension {ext}")
        return load_netcdf(path, *args, **kwargs)
    if ext in __CSV_EXTENSIONS:
        return load_csv(path, *args, **kwargs)
    raise ValueError(f"Unsupported file extension {ext}")


def save(data: DNDarray, path: str, *args, **kwargs) -> None:
    """Extension-dispatched save (reference io.py:886-923).  Estimators
    dispatch to :func:`heat_tpu.save_estimator` (extension): one call
    saves data or a fitted model alike."""
    if _tel.enabled:
        _tel.inc("io.saves")
        with _tel.span("io:save", "io", path=str(path)):
            return _save_impl(data, path, *args, **kwargs)
    return _save_impl(data, path, *args, **kwargs)


def _save_impl(data: DNDarray, path: str, *args, **kwargs) -> None:
    from .base import BaseEstimator

    if isinstance(data, BaseEstimator):
        if args or kwargs:
            raise TypeError(
                "estimator checkpoints take no dataset/option arguments: "
                "use ht.save(estimator, path)"
            )
        from .checkpoint import save_estimator

        # path/extension validation lives in save_estimator so est.save()
        # and ht.save() enforce the same contract
        return save_estimator(data, path)
    if not isinstance(path, str):
        raise TypeError(f"Expected path to be str, but was {type(path)}")
    ext = os.path.splitext(path)[-1].strip().lower()
    if ext in __HDF5_EXTENSIONS:
        if not supports_hdf5():
            raise RuntimeError(f"hdf5 is required for file extension {ext}")
        return save_hdf5(data, path, *args, **kwargs)
    if ext in __NETCDF_EXTENSIONS:
        if not supports_netcdf():
            raise RuntimeError(f"netcdf is required for file extension {ext}")
        return save_netcdf(data, path, *args, **kwargs)
    if ext in __CSV_EXTENSIONS:
        return save_csv(data, path, *args, **kwargs)
    raise ValueError(f"Unsupported file extension {ext}")
