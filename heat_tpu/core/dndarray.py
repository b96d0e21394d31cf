"""The DNDarray: a global n-D array sharded over a TPU device mesh.

Reference: heat/core/dndarray.py:53-3962 — there, a ``DNDarray`` is an SPMD
illusion: every MPI process stores only its slab (``lshape``) of the global
array (``gshape``), split along at most one axis, and ~130 methods hand-roll
the communication to maintain the illusion.

Here the illusion is real: the backing store **is** a single global
:class:`jax.Array` whose shards live distributed across the mesh with a
:class:`~jax.sharding.NamedSharding`; ``split`` records which axis is
sharded.  Every operation is expressed on the global array and XLA/GSPMD
inserts the collectives — so the reference's per-method communication logic
(e.g. the 250-line distributed ``__getitem__``, dndarray.py:1476-1726)
collapses into plain ``jnp`` indexing plus split bookkeeping.  Sharding in
this model is a *performance annotation*: a mis-placed shard costs time,
never correctness — the exact inversion of the MPI design, where layout
errors corrupt results.

Design invariants:

* the at-rest backing store (``self._buffer``) is a global jax.Array whose
  split axis is **canonically padded**: an axis of true length ``n`` over a
  ``p``-device mesh is stored zero-padded to ``p * ceil(n/p)`` and committed
  SHARDED, so per-device memory is O(n/p) for *any* n — the TPU-first
  equivalent of the reference invariant that each rank's torch tensor
  matches its ``chunk()`` slice (reference communication.py:82-137,
  dndarray.py:93).  Divisible axes (and replicated arrays) store exactly
  ``gshape``;
* ``self.larray`` is the true-shape view: ``larray.shape == gshape``
  always.  For padded arrays it is a lazily-cached slice — cheap inside
  compiled programs, but committing it at a program boundary materializes
  a ragged (hence replicated) array, so scale paths consume ``_buffer``;
* pad rows hold *unspecified* values after ops (elementwise garbage-in/
  garbage-out is confined to the pad): every non-elementwise consumer
  must go through ``larray``/masking.  The op wrappers in
  ``_operations.py`` do this centrally;
* ``split ∈ {None, 0..ndim-1}``; ``None`` = replicated on all devices;
* shard layout is *canonical* (GSPMD ceil-division): arrays are always
  balanced, so ``balance_``/``redistribute_`` (reference dndarray.py:900,
  2560) are no-ops kept for API parity.
"""

from __future__ import annotations

import math
import operator
import warnings
from typing import List, Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map

from ..telemetry import _core as _tel
from . import types
from ._compile import jitted
from ._tracing import require_concrete
from .communication import Communication, sanitize_comm
from .devices import Device
from .stride_tricks import sanitize_axis

__all__ = ["DNDarray", "LocalIndex"]

#: Minimum element count of the operand before array-key indexing along the
#: split axis routes through the bounded-memory ring gather/scatter
#: (:mod:`heat_tpu.parallel.take`) instead of the GSPMD gather (which
#: REPLICATES the operand for data-dependent cross-shard indexing).  Small
#: operands keep the plain jnp path — the ring's p rounds only pay off once
#: per-device memory is at stake.  Override with HEAT_TPU_RING_INDEX_MIN.
import os as _os

_item = operator.methodcaller("item")  # the host read of a 0-d device array

_RING_INDEX_MIN = int(_os.environ.get("HEAT_TPU_RING_INDEX_MIN", str(1 << 22)))


def _fit_index_array(k, n: int):
    """Normalize an integer index array for axis length ``n`` so jax's
    documented clamp (gather) / drop (scatter) semantics hold WITHOUT the
    silent int32 truncation jax applies to wide keys (an int64 index of
    2**32+3 otherwise reads/writes row 3), and without the OverflowError
    narrow keys (int8 on an axis longer than their range) trigger.

    Values are mapped into int32-safe sentinels that jax post-processes to
    its own semantics: OOB-high → ``n`` (gather clamps to n-1, scatter
    drops), OOB-low → ``-(n+1)`` (one wrap later still ``-1`` < 0: gather
    clamps to 0, scatter drops).  Both sentinels fit int32 for every
    ``n < 2**31`` (``n`` ≤ int32 max, ``-(n+1)`` ≥ int32 min), i.e. for
    every axis jax itself can index with int32 — there is no unguarded
    large-``n`` regime (the r4 advisor found the previous ``2n``-based
    sentinel silently skipped normalization for n ≥ 2**30).  Host numpy
    arrays normalize for free;
    device arrays pay two elementwise ops only for risky dtypes.
    """
    if n <= 0 or n >= 2**31:
        return k  # n itself no longer fits int32; jax must gather in int64
    if isinstance(k, np.ndarray):
        if np.issubdtype(k.dtype, np.unsignedinteger):
            return np.minimum(k, np.asarray(n, np.uint64)).astype(np.int32)
        kk = k.astype(np.int64)
        return np.where(kk >= n, n, np.where(kk < -n, -(n + 1), kk)).astype(np.int32)
    dt = k.dtype
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if np.dtype(dt).itemsize <= 2:
            return k  # uint8/16 fit int32; jax clamps/drops them natively
        return jnp.minimum(k, jnp.asarray(n, dt)).astype(jnp.int32)
    if np.dtype(dt).itemsize <= 2:
        return k.astype(jnp.int32)  # widen int8/16 past their own range
    if np.dtype(dt).itemsize == 4:
        return k  # int32 cannot out-range int32
    kk = jnp.where(k >= n, n, jnp.where(k < -n, -(n + 1), k))
    return kk.astype(jnp.int32)


class LocalIndex:
    """Indexing proxy over the raw backing array
    (reference dndarray.py:37-50, exposed as ``x.lloc``).

    In the single-controller model the "local" array is the global one; this
    proxy indexes it directly, without split bookkeeping, and supports
    assignment (functionally, via ``.at[].set``).
    """

    __slots__ = ("__obj",)

    def __init__(self, obj: "DNDarray"):
        self.__obj = obj

    def __getitem__(self, key):
        return self.__obj.larray[key]

    def __setitem__(self, key, value):
        arr = self.__obj.larray.at[key].set(jnp.asarray(value, self.__obj.larray.dtype))
        self.__obj.larray = arr


class DNDarray:
    """Distributed N-Dimensional array over a JAX device mesh.

    Parameters mirror the reference constructor (dndarray.py:79-93):

    array : jax.Array
        The **global** array (reference stores the local chunk instead).
        On a ragged split axis this may be either the true-length array
        (it will be padded to the at-rest form) or an already canonically
        padded buffer (``comm.padded_size`` long on the split axis, pad
        rows arbitrary) — anything else raises ``ValueError``.
    gshape : tuple of int
        TRUE global shape (``gshape[split]`` is the real length even when
        ``array`` arrives padded); equals ``array.shape`` otherwise.
    dtype : heat type
        Element type (:mod:`heat_tpu.core.types`).
    split : int or None
        Sharded axis; None = replicated.
    device : Device
        Platform the mesh lives on.
    comm : Communication
        The device-mesh communicator.
    balanced : bool
        Kept for API parity; canonical GSPMD layout is always balanced.
    """

    def __init__(
        self,
        array: jax.Array,
        gshape: Tuple[int, ...],
        dtype,
        split: Optional[int],
        device: Device,
        comm: Communication,
        balanced: bool = True,
    ):
        self.__gshape = tuple(int(s) for s in gshape)
        self.__dtype = dtype
        self.__device = device
        self.__comm = comm
        ndim = len(self.__gshape)
        if isinstance(split, (tuple, list)):
            # splits-tuple spelling: splits[d] = mesh axis sharding dim d.
            # The legacy `split` int becomes the exact compat view (the dim
            # mesh axis 0 shards — lossless on a 1-D mesh).
            splits = comm.normalize_splits(ndim, split)
            split = comm.split_view(splits)
        else:
            if split is not None and self.__gshape:
                if not -ndim <= split < ndim:
                    raise ValueError(
                        f"split axis {split} out of range for {ndim}-dimensional "
                        f"shape {self.__gshape}"
                    )
                split = int(split) % ndim  # normalize negatives only
            splits = (
                comm.normalize_splits(ndim, split)
                if (self.__gshape or split is None)
                else (None,) * ndim
            )
        self.__split = split
        self.__splits = splits
        self.__balanced = True if balanced is None else bool(balanced)
        self.__true_view = None
        self.__halo_prev = None
        self.__halo_next = None
        self.__halo_size = 0
        self.__array = self.__commit(array)

    def __commit(self, array) -> jax.Array:
        """Normalize ``array`` to the at-rest invariant: every ragged
        sharded dim (gshape[d] not divisible by its mesh axis) is
        zero-padded to the canonical length and committed sharded.  Accepts
        either the true-shape array or an already-padded buffer, per dim;
        divisible/replicated arrays pass through untouched (sharding them
        stays the caller's job, as before)."""
        splits = self.__splits
        if not self.__gshape or all(g is None for g in splits):
            return array
        comm = self.__comm
        needs_pad = False
        for d, g in enumerate(splits):
            if g is None:
                continue
            n = self.__gshape[d]
            pn = comm.padded_size(n, mesh_axis=g)
            if pn == n:
                continue
            have = int(array.shape[d])
            if have == pn:
                continue  # this dim is already at rest
            if have != n:
                raise ValueError(
                    f"backing array axis {d} has length {have}; expected the "
                    f"true length {n} or the padded length {pn} for gshape "
                    f"{self.__gshape} over mesh {comm.mesh_shape}"
                )
            needs_pad = True
        if not needs_pad:
            return array
        return comm.pad_to_shards(array, splits=splits)

    # ------------------------------------------------------------------ #
    # metadata properties (reference dndarray.py:95-360)                  #
    # ------------------------------------------------------------------ #
    @property
    def balanced(self) -> bool:
        """Always True under the canonical GSPMD layout
        (reference dndarray.py:95-106 tracks this lazily)."""
        return self.__balanced

    @property
    def comm(self) -> Communication:
        return self.__comm

    @comm.setter
    def comm(self, comm):
        self.__comm = sanitize_comm(comm)

    @property
    def device(self) -> Device:
        return self.__device

    @property
    def dtype(self):
        return self.__dtype

    @property
    def gshape(self) -> Tuple[int, ...]:
        """Global shape (reference dndarray.py:186)."""
        return self.__gshape

    @property
    def shape(self) -> Tuple[int, ...]:
        """Global shape — numpy-compatible alias (reference dndarray.py:286)."""
        return self.__gshape

    @property
    def larray(self) -> jax.Array:
        """The global array at its TRUE shape (``larray.shape == gshape``).

        Semantic shift from the reference (dndarray.py:123-135): there this
        is the rank-local torch tensor; here it is the *global* device array
        whose shards are distributed — the natural "local" object of
        single-controller SPMD.  When the at-rest buffer is padded (ragged
        split axis), this is a cached slice of the buffer; committing that
        slice at a program boundary materializes a ragged array (GSPMD
        replicates those), so scale pipelines consume :attr:`_buffer`.
        """
        arr = self.__array
        splits = self.__splits
        if not self.__gshape or all(g is None for g in splits):
            return arr
        padded_dims = tuple(
            d
            for d, g in enumerate(splits)
            if g is not None and int(arr.shape[d]) != self.__gshape[d]
        )
        if not padded_dims:
            return arr
        if self.__true_view is None:
            view = arr
            for d in padded_dims:
                view = self.__comm.unpad(view, self.__gshape[d], d)
            self.__true_view = view
        return self.__true_view

    @larray.setter
    def larray(self, array: jax.Array):
        """Rebind the backing data.  ``array`` is interpreted at its TRUE
        shape (adopted as the new gshape); a ragged split axis is re-padded
        to the at-rest invariant."""
        if tuple(array.shape) != self.__gshape:
            self.__gshape = tuple(int(s) for s in array.shape)
        self.__array = self.__commit(array)
        self._invalidate_halos()

    @property
    def _buffer(self) -> jax.Array:
        """The at-rest backing buffer: the split axis canonically padded to
        ``comm.padded_size(gshape[split])`` (== gshape for divisible axes).
        Pad-row values are unspecified; mask or :meth:`larray` before any
        non-elementwise use."""
        return self.__array

    @property
    def padshape(self) -> Tuple[int, ...]:
        """Shape of the at-rest buffer (gshape with the split axis padded)."""
        return tuple(int(s) for s in self.__array.shape)

    def _zeroed_buffer(self) -> jax.Array:
        """The at-rest buffer with pad rows forced to zero — still padded
        and sharded (no boundary crossing).  For consumers that assume the
        canonical zero fill (halo exchange, SUMMA's contraction-axis
        operands).  Zeroes every padded sharded dim, so grid layouts with
        two ragged dims come back fully masked."""
        arr = self.__array
        splits = self.__splits
        if not self.__gshape or all(g is None for g in splits):
            return arr
        dims = tuple(
            (d, self.__gshape[d])
            for d, g in enumerate(splits)
            if g is not None and int(arr.shape[d]) != self.__gshape[d]
        )
        if not dims:
            return arr
        comm = self.__comm

        def make():
            def _z(x):
                mask = None
                for d, n in dims:
                    m = jax.lax.broadcasted_iota(jnp.int32, x.shape, d) < n
                    mask = m if mask is None else mask & m
                return jnp.where(mask, x, jnp.zeros((), x.dtype))

            return _z

        key = ("dnd.zeropad", comm, splits, dims, tuple(int(s) for s in arr.shape))
        return jitted(key, make)(arr)

    @property
    def lloc(self) -> LocalIndex:
        """Raw (split-unaware) indexer (reference dndarray.py:259)."""
        return LocalIndex(self)

    @property
    def lshape(self) -> Tuple[int, ...]:
        """Shape of the calling process's first shard (reference
        dndarray.py:205: the calling rank's chunk).  Single-host this is
        mesh position 0; on multihost (init_multihost) it is the first
        position owned by THIS process."""
        _, lshape, _ = self.__comm.chunk(
            self.__gshape, self._layout, rank=self.__comm.local_position()
        )
        return lshape

    @property
    def lshape_map(self) -> np.ndarray:
        """(size, ndim) table of every mesh position's shard shape
        (reference ``create_lshape_map``, dndarray.py:1117 — built there via
        Allreduce; here computed from the canonical layout)."""
        return self.create_lshape_map()

    @property
    def ndim(self) -> int:
        return len(self.__gshape)

    @property
    def size(self) -> int:
        """Total number of elements (reference ``gnumel``)."""
        return int(np.prod(self.__gshape)) if self.__gshape else 1

    @property
    def gnumel(self) -> int:
        return self.size

    @property
    def lnumel(self) -> int:
        """Elements in the calling process's first shard (reference
        dndarray.py:231)."""
        return int(np.prod(self.lshape)) if self.lshape else 1

    @property
    def nbytes(self) -> int:
        """Global memory footprint in bytes (reference ``gnbytes``)."""
        return self.size * np.dtype(self.__dtype._np_type).itemsize

    @property
    def gnbytes(self) -> int:
        return self.nbytes

    @property
    def lnbytes(self) -> int:
        return self.lnumel * np.dtype(self.__dtype._np_type).itemsize

    @property
    def itemsize(self) -> int:
        return np.dtype(self.__dtype._np_type).itemsize

    @property
    def split(self) -> Optional[int]:
        """The sharded axis, or None when replicated (reference dndarray.py:321).

        On an N-D grid comm this is the exact *compat view* of
        :attr:`splits`: the array dim mesh axis 0 shards.  Every layout a
        1-D mesh can express round-trips through it losslessly."""
        return self.__split

    @property
    def splits(self) -> Tuple[Optional[int], ...]:
        """Mesh-axis layout tuple: ``splits[d]`` is the mesh axis sharding
        array dim ``d`` (None = unsharded).  ``(0, 1)`` on a 2-D grid comm
        is the SUMMA block layout — dim 0 over mesh rows, dim 1 over mesh
        columns.  On the default 1-D mesh this is the one-hot spelling of
        :attr:`split`."""
        return self.__splits

    @property
    def _layout(self):
        """The layout in the spelling comm methods historically expect:
        the legacy int on a 1-D mesh (exact), the splits tuple on a grid."""
        if getattr(self.__comm, "mesh_ndim", 1) > 1:
            return self.__splits
        return self.__split

    @property
    def stride(self) -> Tuple[int, ...]:
        """C-order element strides (reference dndarray.py:333 — torch-style)."""
        strides = []
        acc = 1
        for s in reversed(self.__gshape):
            strides.append(acc)
            acc *= s
        return tuple(reversed(strides))

    @property
    def strides(self) -> Tuple[int, ...]:
        """C-order byte strides (reference dndarray.py:345 — numpy-style)."""
        return tuple(s * self.itemsize for s in self.stride)

    @property
    def T(self) -> "DNDarray":
        from .linalg import basics

        return basics.transpose(self, None)

    @property
    def real(self) -> "DNDarray":
        return self

    @property
    def imag(self) -> "DNDarray":
        from . import factories

        return factories.zeros_like(self)

    @property
    def sharding(self):
        """The semantic NamedSharding of this array over its comm's mesh
        (TPU-native introspection; no reference analog).

        Derived from (comm, split) rather than read off the backing array:
        on a single-device comm the backing array may carry a plain
        SingleDeviceSharding (the apply_sharding fast path skips the
        device_put), but the NamedSharding contract — ``.spec`` access,
        mesh introspection — holds either way."""
        return self.__comm.sharding(self.ndim, self._layout)

    # ------------------------------------------------------------------ #
    # conversion / export                                                #
    # ------------------------------------------------------------------ #
    def astype(self, dtype, copy: bool = True) -> "DNDarray":
        """Cast to ``dtype`` (reference dndarray.py:540-575)."""
        dtype = types.canonical_heat_type(dtype)
        casted = self.__array.astype(dtype.jax_type())
        if copy:
            return DNDarray(
                casted, self.shape, dtype, self._layout, self.device, self.comm, self.balanced
            )
        self.__array = casted
        self.__dtype = dtype
        self._invalidate_halos()
        return self

    def numpy(self) -> np.ndarray:
        """Gather to a host numpy array (reference dndarray.py: ``numpy`` —
        there an implicit resplit(None) + .numpy())."""
        require_concrete(".numpy()")
        return _tel.host_read("sync:dndarray.numpy", self.larray, np.asarray)

    def copy(self) -> "DNDarray":
        """An independent copy of this array (reference dndarray.py: ``copy``
        → memory.copy)."""
        from . import memory

        return memory.copy(self)

    def is_distributed(self) -> bool:
        """True when data lives split across more than one mesh position
        (reference dndarray.py:1771-1779)."""
        return self.__split is not None and self.__comm.is_distributed()

    @property
    def numdims(self) -> int:
        """Deprecated alias of :attr:`ndim` (reference dndarray.py:245)."""
        warnings.warn("numdims is deprecated, use ndim instead", DeprecationWarning, stacklevel=2)
        return self.ndim

    def save(self, path: str, *args, **kwargs) -> None:
        """Save to HDF5/NetCDF/CSV by file extension (reference
        dndarray.py:3104)."""
        require_concrete(".save()")
        from . import io

        io.save(self, path, *args, **kwargs)

    def save_hdf5(self, path: str, dataset: str, mode: str = "w", **kwargs) -> None:
        """Save to an HDF5 dataset (reference dndarray.py:3132)."""
        require_concrete(".save_hdf5()")
        from . import io

        io.save_hdf5(self, path, dataset, mode, **kwargs)

    def save_netcdf(self, path: str, variable: str, mode: str = "w", **kwargs) -> None:
        """Save to a NetCDF variable (reference dndarray.py:3162)."""
        require_concrete(".save_netcdf()")
        from . import io

        io.save_netcdf(self, path, variable, mode, **kwargs)

    def __array__(self, dtype=None):
        require_concrete("np.asarray()")
        arr = _tel.host_read("sync:dndarray.asarray", self.larray, np.asarray)
        return arr.astype(dtype) if dtype is not None else arr

    def tolist(self, keepsplit: bool = False) -> list:
        """Nested python lists of the global data (reference dndarray.py:3718)."""
        require_concrete(".tolist()")
        return _tel.host_read("sync:dndarray.tolist", self.larray, np.asarray).tolist()

    def item(self):
        """The single element of a size-1 array as a python scalar
        (reference dndarray.py:1754)."""
        require_concrete(".item()")
        if self.size != 1:
            raise ValueError("only one-element DNDarrays can be converted to Python scalars")
        return _tel.host_read("sync:dndarray.item", self.larray.reshape(()), _item)

    def __bool__(self) -> bool:
        require_concrete("bool()")
        return bool(self.item())

    def __int__(self) -> int:
        require_concrete("int()")
        return int(self.item())

    def __float__(self) -> float:
        require_concrete("float()")
        return float(self.item())

    def __complex__(self) -> complex:
        require_concrete("complex()")
        return complex(self.item())

    def __len__(self) -> int:
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.__gshape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    # ------------------------------------------------------------------ #
    # device / layout movement                                           #
    # ------------------------------------------------------------------ #
    def cpu(self) -> "DNDarray":
        """Move to the CPU mesh (reference dndarray.py:1006)."""
        return self.to_device("cpu")

    def to_device(self, device) -> "DNDarray":
        """Move the array to another platform's mesh (no reference analog as
        a general method; subsumes the reference's ``cpu()``/gpu pattern)."""
        from .devices import sanitize_device
        from .communication import comm_for_device

        device = sanitize_device(device)
        if device == self.__device:
            return self
        comm = comm_for_device(device.platform)
        arr = jax.device_put(np.asarray(self.larray), comm.sharding(self.ndim, None))
        arr = comm.apply_sharding(arr, self.__split)
        return DNDarray(arr, self.shape, self.dtype, self.split, device, comm, True)

    def create_lshape_map(self, force_check: bool = False) -> np.ndarray:
        """Table of all shard shapes (reference dndarray.py:1117-1160)."""
        size = self.__comm.size
        ndim = max(self.ndim, 1)
        out = np.zeros((size, ndim), dtype=np.int64)
        for r in range(size):
            _, lshape, _ = self.__comm.chunk(self.__gshape, self._layout, rank=r)
            out[r, : len(lshape)] = lshape
        return out

    def is_balanced(self, force_check: bool = False) -> bool:
        """Canonical layout ⇒ always balanced (reference dndarray.py:1781-1806
        needs an Allreduce to find out)."""
        return True

    def balance_(self) -> None:
        """No-op: the canonical GSPMD layout is always balanced
        (reference dndarray.py:900-1004 moves data with Send/Recv chains)."""
        self.__balanced = True

    def redistribute_(self, lshape_map=None, target_map=None) -> None:
        """Arbitrary per-rank shard sizes are not representable in XLA's
        sharding model; the canonical equal layout is maintained by the
        compiler (reference dndarray.py:2560-2746 implements a pairwise
        Isend/Recv shuffle).

        A ``target_map`` equal to the canonical layout is accepted as the
        no-op it is; any *other* map asks for a layout this framework
        cannot represent, and raises rather than silently returning the
        wrong distribution (see docs/migration.md)."""
        if target_map is None:
            return
        target = np.asarray(target_map)
        canonical = self.create_lshape_map()
        if target.size != canonical.size:
            raise ValueError(
                f"target_map must have shape {canonical.shape} "
                f"(one lshape row per shard), got {target.shape}"
            )
        # a flat (size,) map for a 1-D array is the natural spelling of
        # the same (size, 1) canonical table — normalize before comparing
        target = target.reshape(canonical.shape)
        if np.array_equal(target, canonical):
            return  # already the layout we maintain
        raise NotImplementedError(
            "redistribute_: non-canonical per-rank shard sizes are not "
            "representable in XLA's GSPMD sharding model; heat_tpu always "
            "maintains the canonical equal-chunk layout "
            f"({canonical.tolist()}). Requested {target.tolist()}. "
            "See docs/migration.md for the layout contract."
        )

    def resplit_(self, axis: Optional[int] = None) -> "DNDarray":
        """In-place re-shard along ``axis`` (reference dndarray.py:2801-2921:
        split→None = Allgatherv, None→split = local slicing, split→split =
        tile shuffle; here one XLA reshard covers all three).

        ``axis`` also accepts a splits tuple: on a grid comm this is the
        native spelling (e.g. ``(0, 1)`` = block layout), routed through
        the 2-D redistribution planner; on a 1-D mesh it collapses to its
        exact ``split`` compat int first."""
        comm = self.__comm
        grid = getattr(comm, "mesh_ndim", 1) > 1
        if isinstance(axis, (tuple, list)) or grid:
            if not isinstance(axis, (tuple, list)):
                axis = sanitize_axis(self.shape, axis)
            splits = comm.normalize_splits(self.ndim, axis)
            if not grid:
                axis = comm.split_view(splits)  # exact on 1-D: legacy path below
            else:
                if splits == self.__splits:
                    return self
                true = self.larray
                self.__splits = splits
                self.__split = comm.split_view(splits)
                self.__array = comm.commit_split(true, splits)
                self.__balanced = True
                self._invalidate_halos()
                return self
        axis = sanitize_axis(self.shape, axis)
        if axis == self.__split:
            return self
        true = self.larray
        self.__split = axis
        self.__splits = comm.normalize_splits(self.ndim, axis)
        # commit_split pads+shards ragged target axes in one step
        self.__array = self.__comm.commit_split(true, axis)
        self.__balanced = True
        self._invalidate_halos()
        return self

    def resplit(self, axis: Optional[int] = None) -> "DNDarray":
        """Out-of-place resplit (reference manipulations.py:2969)."""
        from . import manipulations

        return manipulations.resplit(self, axis)

    # ------------------------------------------------------------------ #
    # halo exchange (reference dndarray.py:390-483)                       #
    # ------------------------------------------------------------------ #
    def get_halo(self, halo_size: int) -> None:
        """Fetch every shard's neighbor boundary strips via one ppermute
        pair (:func:`heat_tpu.parallel.halo_exchange`).

        The reference posts Isend/Irecv pairs with prev/next ranks and
        stores the received strips per rank (dndarray.py:390-463).  Here
        :attr:`halo_prev` / :attr:`halo_next` become *global sharded*
        arrays whose split axis has length ``size * halo_size``: position
        p's block holds the strip it received from its predecessor /
        successor.  Strips reaching past the global edges are zero-filled
        (the reference leaves them absent — a per-rank None; equal-shard
        layouts need a uniform shape, and zeros are the natural stencil
        boundary).
        """
        if not isinstance(halo_size, int):
            raise TypeError(f"halo_size needs to be an integer, but was {type(halo_size)}")
        if halo_size < 0:
            raise ValueError(f"halo_size needs to be a non-negative integer, but was {halo_size}")
        if self.__split is None or halo_size == 0:
            self._invalidate_halos()
            return
        from ..parallel.primitives import halo_exchange

        arr = self._zeroed_buffer()
        if self.__split != 0:
            arr = jnp.moveaxis(arr, self.__split, 0)
        # halo_exchange validates halo_size <= shard_width (raising before
        # any state here changes)
        prev, nxt = halo_exchange(arr, halo_size, comm=self.__comm)
        if self.__split != 0:
            prev = jnp.moveaxis(prev, 0, self.__split)
            nxt = jnp.moveaxis(nxt, 0, self.__split)
        self.__halo_prev = prev
        self.__halo_next = nxt
        self.__halo_size = halo_size

    def _invalidate_halos(self) -> None:
        """Drop cached derived views (halo strips, the true-shape slice);
        called whenever the backing array or layout changes."""
        self.__true_view = None
        self.__halo_prev = None
        self.__halo_next = None
        self.__halo_size = 0

    @property
    def halo_prev(self):
        return self.__halo_prev

    @property
    def halo_next(self):
        return self.__halo_next

    @property
    def array_with_halos(self) -> jax.Array:
        """Every shard extended by its neighbor strips
        (reference dndarray.py:363-365, 465-483).

        A global sharded array whose split axis has length
        ``size * (shard_width + 2 * halo_size)``: position p's block is
        ``[prev strip | shard p (zero-padded to shard_width) | next
        strip]``.  Stencil consumers map over the blocks and keep rows
        ``[halo_size, halo_size + shard_width)``, then unpad with
        ``comm.valid_counts`` — see tests/test_extended_dndarray.py for
        the pattern.  Without halos (or replicated) this is the plain
        backing array.
        """
        h = self.__halo_size
        if self.__split is None or not h:
            return self.larray  # no halos: the plain (true-shape) array
        comm = self.__comm
        split = self.__split
        arr = self._zeroed_buffer()
        prev, nxt = self.__halo_prev, self.__halo_next
        if split != 0:
            arr = jnp.moveaxis(arr, split, 0)
            prev = jnp.moveaxis(prev, split, 0)
            nxt = jnp.moveaxis(nxt, split, 0)
        arr = comm.pad_to_shards(arr, axis=0)
        from jax.sharding import PartitionSpec

        from ._compile import jitted

        def make():
            spec = PartitionSpec(comm.axis_name)

            def kernel(p, b, nx):
                return jnp.concatenate([p, b, nx], axis=0)

            def _f(p, b, nx):
                return shard_map(
                    kernel,
                    mesh=comm.mesh,
                    in_specs=(spec, spec, spec),
                    out_specs=spec,
                )(p, b, nx)

            return _f

        out = jitted(("dnd.halo_concat", comm), make)(prev, arr, nxt)
        if split != 0:
            out = jnp.moveaxis(out, 0, split)
        return out

    # ------------------------------------------------------------------ #
    # indexing (reference dndarray.py:1476-1726, 3190-3339)               #
    # ------------------------------------------------------------------ #
    def __process_key(self, key):
        """Convert DNDarray (and numpy-style list) keys to jax arrays, pass
        everything else through.  Lists are advanced-index arrays in
        numpy/reference semantics (dndarray.py:1476) but rejected raw by
        jax, so they are wrapped here.

        Plain integer keys are bounds-checked on the host: jnp's ``.at``
        semantics silently CLIP out-of-range indices, so without the check
        ``x[99] = 1`` on a 5-row array would no-op instead of raising the
        numpy/reference ``IndexError``."""

        def pre(k):
            # one-time normalization (lists/DNDarrays convert exactly once,
            # before both the dim counting and the per-dim pass)
            if isinstance(k, DNDarray):
                return k.larray
            if isinstance(k, list):
                return np.asarray(k)
            return k

        def one(k, dim):
            if isinstance(k, np.ndarray) and k.ndim == 0 and np.issubdtype(k.dtype, np.integer):
                # numpy semantics: a host 0-d integer array key behaves like
                # the scalar int — route it through the same bounds check
                # (jnp's .at clips silently otherwise).  Device (jnp) 0-d
                # keys pass through: converting them would force a blocking
                # device→host sync per index.
                k = int(k)
            if isinstance(k, (int, np.integer)) and not isinstance(k, (bool, np.bool_)):
                if dim is not None and dim < self.ndim:
                    n = self.__gshape[dim]
                    if not -n <= k < n:
                        raise IndexError(
                            f"index {k} is out of bounds for axis {dim} with size {n}"
                        )
                return k
            if isinstance(k, np.ndarray):
                if k.size == 0:  # numpy: a[[]] selects nothing, not float64
                    k = k.astype(np.int32)
                if (
                    np.issubdtype(k.dtype, np.integer)
                    and dim is not None
                    and dim < self.ndim
                ):
                    k = _fit_index_array(k, self.__gshape[dim])
                return jnp.asarray(k)
            if (
                isinstance(k, (jnp.ndarray, jax.Array))
                and jnp.issubdtype(k.dtype, jnp.integer)
                and dim is not None
                and dim < self.ndim
            ):
                return _fit_index_array(k, self.__gshape[dim])
            return k

        def consumed(k):
            # how many array dims key element k consumes (keys are
            # pre-normalized: no lists or DNDarrays reach here)
            if k is None or isinstance(k, (bool, np.bool_)):
                return 0  # newaxis / scalar-bool mask: adds an axis instead
            if isinstance(k, (np.ndarray, jnp.ndarray)) and k.dtype == bool:
                return k.ndim
            return 1

        if isinstance(key, tuple):
            key = tuple(pre(k) for k in key)
            dims: List[Optional[int]] = []
            # `Ellipsis in key` would run elementwise == on array keys
            if any(k is Ellipsis for k in key):
                e = next(i for i, k in enumerate(key) if k is Ellipsis)
                dim = 0
                for k in key[:e]:
                    dims.append(dim if consumed(k) == 1 else None)
                    dim += consumed(k)
                dims.append(None)  # the ellipsis itself
                tail = key[e + 1 :]
                dim = self.ndim - sum(consumed(k) for k in tail)
                for k in tail:
                    dims.append(dim if consumed(k) == 1 else None)
                    dim += consumed(k)
            else:
                dim = 0
                for k in key:
                    dims.append(dim if consumed(k) == 1 else None)
                    dim += consumed(k)
            return tuple(one(k, d) for k, d in zip(key, dims))
        return one(pre(key), 0)

    def __result_split(self, key, result_ndim: int) -> Optional[int]:
        """Split bookkeeping for indexing results.

        For BASIC keys (ints, slices, None, Ellipsis, scalar bools) the
        output axis of the split is computed exactly: slices preserve it,
        ints drop axes before it, None/bool insert axes, and an Ellipsis
        expands to the full slices it stands for.  Advanced (array) keys
        keep the nearest-shardable-axis heuristic — a performance hint
        only, since layout never affects values (pinned by
        tests/test_setitem_matrix.py)."""
        if self.__split is None or result_ndim == 0:
            return None
        split = self.__split
        keyt = key if isinstance(key, tuple) else (key,)

        def is_basic(k):
            return (
                k is Ellipsis
                or k is None
                or isinstance(k, (bool, np.bool_, slice))
                or (isinstance(k, (int, np.integer)) and not isinstance(k, (bool, np.bool_)))
            )

        if all(is_basic(k) for k in keyt):
            consumed = sum(
                1
                for k in keyt
                if isinstance(k, (int, np.integer, slice))
                and not isinstance(k, (bool, np.bool_))
            )
            expanded: List = []
            for k in keyt:
                if k is Ellipsis:
                    expanded.extend([slice(None)] * (self.ndim - consumed))
                else:
                    expanded.append(k)
            dim = 0  # input axis cursor
            out = 0  # output axis cursor
            for k in expanded:
                if k is None or isinstance(k, (bool, np.bool_)):
                    out += 1  # newaxis / scalar-bool mask inserts an axis
                    continue
                if isinstance(k, slice):
                    if dim == split:
                        return min(out, result_ndim - 1)
                    dim += 1
                    out += 1
                else:  # integer: drops this input axis
                    if dim == split:
                        # split axis consumed: nearest shardable axis
                        return min(out, result_ndim - 1)
                    dim += 1
            # key exhausted before the split axis: the rest map one-to-one
            return min(out + (split - dim), result_ndim - 1)

        # advanced keys: nearest-shardable heuristic (as before)
        dim = 0
        dropped_before = 0
        split_key = slice(None)
        for k in keyt:
            if k is Ellipsis:
                return min(split, result_ndim - 1)
            if k is None:
                continue
            if dim == split:
                split_key = k
                break
            if isinstance(k, (int, np.integer)):
                dropped_before += 1
            dim += 1
        if isinstance(split_key, (int, np.integer)):
            return min(max(split - dropped_before, 0), result_ndim - 1)
        return min(split - dropped_before, result_ndim - 1)

    def __ring_index_plan(self, jkey):
        """Detect the scale-sensitive fancy-indexing pattern: ONE 1-D
        integer-array key on the split axis, every other axis untouched,
        on a distributed operand big enough that GSPMD's replicate-the-
        operand gather would hurt (≥ ``_RING_INDEX_MIN`` elements).
        Returns the index array, or None for the plain jnp path."""
        s = self.__split
        if s is None or not self.__comm.is_distributed():
            return None
        if self.size < _RING_INDEX_MIN:
            return None

        def is_idx(k):
            return (
                isinstance(k, (jnp.ndarray, jax.Array))
                and k.ndim == 1
                and k.shape[0] > 0
                and jnp.issubdtype(k.dtype, jnp.integer)
            )

        if isinstance(jkey, tuple):
            if len(jkey) > self.ndim:
                return None
            idx = None
            for d, k in enumerate(jkey):
                if isinstance(k, slice):
                    if k != slice(None):
                        return None
                elif is_idx(k):
                    if d != s or idx is not None:
                        return None
                    idx = k
                else:
                    return None
            return idx
        return jkey if s == 0 and is_idx(jkey) else None

    def __ring_getitem(self, idx) -> "DNDarray":
        """Fancy gather along the split axis via the bounded-memory ring
        (reference dndarray.py:1476-1726 exchanges per-rank key
        intersections; GSPMD would replicate the operand instead —
        parallel/take.py).  The operand's at-rest buffer feeds the ring
        directly; the result commits padded+sharded at rest."""
        from ..parallel.take import ring_take

        s, comm = self.__split, self.__comm
        n = self.__gshape[s]
        m = int(idx.shape[0])
        buf = self.__array
        if s != 0:
            buf = jnp.moveaxis(buf, s, 0)
        # oob='clip': jnp gather clamp semantics (wrap negatives, clip to
        # range).  The key arrives already sentinel-mapped by
        # _fit_index_array (__process_key); ring_take's own _sanitize_index
        # composes with those sentinels (n stays a drop, -(n+1) wraps to -1
        # and still clamps/drops) — two cheap passes on the index vector,
        # each safe alone
        out = ring_take(buf, idx, comm=comm, n=n, padded_out=True, oob="clip")
        if s != 0:
            out = jnp.moveaxis(out, 0, s)
        gshape = self.__gshape[:s] + (m,) + self.__gshape[s + 1 :]
        return DNDarray(out, gshape, self.__dtype, s, self.__device, comm, True)

    def __ring_setitem(self, idx, value) -> None:
        """Fancy scatter along the split axis via the ring dual
        (reference dndarray.py:3190-3339).  Out-of-range indices drop and
        duplicate destinations resolve in unspecified order — the same
        contract as jnp's ``.at[].set`` scatter.  The new buffer replaces
        the at-rest store without any boundary materialization."""
        from ..parallel.take import ring_put

        s, comm = self.__split, self.__comm
        n = self.__gshape[s]
        m = int(idx.shape[0])
        vshape = self.__gshape[:s] + (m,) + self.__gshape[s + 1 :]
        if (
            isinstance(value, DNDarray)
            and value.split == s
            and value.gshape == vshape
            and value._buffer.dtype == self.__array.dtype
        ):
            # aligned at-rest operand (e.g. the gather round-trip): its
            # padded buffer feeds the ring directly — pad rows align with
            # the masked pad queries and are never written.  Going through
            # .larray here would materialize the ragged view REPLICATED at
            # the boundary, the exact spike this path exists to avoid.
            value = value._buffer
        else:
            if isinstance(value, DNDarray):
                value = value.larray
            value = jnp.asarray(value, dtype=self.__array.dtype)
            # numpy setitem layout: the advanced axis stays in place (axis s)
            value = jnp.broadcast_to(value, vshape)
        buf = self.__array
        if s != 0:
            value = jnp.moveaxis(value, s, 0)
            buf = jnp.moveaxis(buf, s, 0)
        out = ring_put(n, idx, value, comm=comm, base=buf, padded_out=True)
        if s != 0:
            out = jnp.moveaxis(out, 0, s)
        self.__array = out
        self._invalidate_halos()

    def __getitem__(self, key) -> "DNDarray":
        """Global-semantics indexing (reference dndarray.py:1476-1726 — there
        each rank intersects the key with its chunk; here plain jnp indexing
        on the global array, with big split-axis array keys routed through
        the bounded-memory ring gather)."""
        jkey = self.__process_key(key)
        ridx = self.__ring_index_plan(jkey)
        if ridx is not None:
            return self.__ring_getitem(ridx)
        result = self.larray[jkey]
        if result.ndim == 0:
            return DNDarray(
                result, (), self.__dtype, None, self.__device, self.__comm, True
            )
        split = self.__result_split(jkey, result.ndim)
        result = self.__comm.apply_sharding(result, split)
        return DNDarray(
            result, tuple(result.shape), self.__dtype, split, self.__device, self.__comm, True
        )

    def __setitem__(self, key, value):
        """Global-semantics assignment (reference dndarray.py:3190-3339),
        expressed functionally via ``.at[key].set`` and a rebind."""
        jkey = self.__process_key(key)
        ridx = self.__ring_index_plan(jkey)
        if ridx is not None:
            self.__ring_setitem(ridx, value)
            return
        if isinstance(value, DNDarray):
            value = value.larray
        value = jnp.asarray(value, dtype=self.__array.dtype)
        updated = self.larray.at[jkey].set(value)
        if updated.shape == self.__array.shape:
            updated = self.__comm.apply_sharding(updated, self.__split)
        self.__array = self.__commit(updated)
        self._invalidate_halos()

    def fill_diagonal(self, value) -> "DNDarray":
        """Fill the main diagonal in place (reference dndarray.py:1161)."""
        if self.ndim != 2:
            raise ValueError("fill_diagonal requires a 2-D DNDarray")
        n = min(self.shape)
        idx = jnp.arange(n)
        self.__array = self.__comm.apply_sharding(
            self.__array.at[idx, idx].set(jnp.asarray(value, self.__array.dtype)), self.__split
        )
        self._invalidate_halos()
        return self

    # ------------------------------------------------------------------ #
    # string representations                                             #
    # ------------------------------------------------------------------ #
    def __repr__(self) -> str:
        require_concrete("repr()")
        from . import printing

        return printing.__str__(self)

    def __str__(self) -> str:
        require_concrete("print()/str()")
        from . import printing

        return printing.__str__(self)

    # ------------------------------------------------------------------ #
    # operator / method delegation (reference dndarray.py — ~130 methods) #
    # All following methods delegate to the ops modules, mirroring the    #
    # reference's delegation pattern.                                     #
    # ------------------------------------------------------------------ #
    # -- arithmetics ---------------------------------------------------- #
    def __add__(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    __radd__ = __add__

    def __iadd__(self, other):
        from . import arithmetics

        res = arithmetics.add(self, other)
        if tuple(res.shape) != self.__gshape:
            # numpy semantics: in-place ops may not grow the array
            raise ValueError(
                f"non-broadcastable output operand with shape {self.__gshape} "
                f"doesn't match the broadcast shape {tuple(res.shape)}"
            )
        self.__array, self.__dtype, self.__split = res._buffer, res.dtype, res.split
        self.__splits = res.splits
        self._invalidate_halos()
        return self

    def __sub__(self, other):
        from . import arithmetics

        return arithmetics.sub(self, other)

    def __rsub__(self, other):
        from . import arithmetics

        return arithmetics.sub(other, self)

    def __mul__(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    __rmul__ = __mul__

    def __truediv__(self, other):
        from . import arithmetics

        return arithmetics.div(self, other)

    def __rtruediv__(self, other):
        from . import arithmetics

        return arithmetics.div(other, self)

    def __floordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(self, other)

    def __rfloordiv__(self, other):
        from . import arithmetics

        return arithmetics.floordiv(other, self)

    def __mod__(self, other):
        from . import arithmetics

        return arithmetics.mod(self, other)

    def __rmod__(self, other):
        from . import arithmetics

        return arithmetics.mod(other, self)

    def __pow__(self, other):
        from . import arithmetics

        return arithmetics.pow(self, other)

    def __rpow__(self, other):
        from . import arithmetics

        return arithmetics.pow(other, self)

    def __matmul__(self, other):
        from .linalg import basics

        return basics.matmul(self, other)

    def __neg__(self):
        from . import arithmetics

        return arithmetics.mul(self, -1)

    def __pos__(self):
        return self

    def __abs__(self):
        from . import rounding

        return rounding.abs(self)

    def __invert__(self):
        from . import arithmetics

        return arithmetics.invert(self)

    def __lshift__(self, other):
        from . import arithmetics

        return arithmetics.left_shift(self, other)

    def __rshift__(self, other):
        from . import arithmetics

        return arithmetics.right_shift(self, other)

    def __and__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_and(self, other)

    def __or__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_or(self, other)

    def __xor__(self, other):
        from . import arithmetics

        return arithmetics.bitwise_xor(self, other)

    # -- relational ----------------------------------------------------- #
    def __eq__(self, other):
        from . import relational

        return relational.eq(self, other)

    def __ne__(self, other):
        from . import relational

        return relational.ne(self, other)

    def __lt__(self, other):
        from . import relational

        return relational.lt(self, other)

    def __le__(self, other):
        from . import relational

        return relational.le(self, other)

    def __gt__(self, other):
        from . import relational

        return relational.gt(self, other)

    def __ge__(self, other):
        from . import relational

        return relational.ge(self, other)

    __hash__ = None  # mutable container, like the reference

    # -- named arithmetics methods -------------------------------------- #
    def add(self, other):
        from . import arithmetics

        return arithmetics.add(self, other)

    def sub(self, other):
        from . import arithmetics

        return arithmetics.sub(self, other)

    def mul(self, other):
        from . import arithmetics

        return arithmetics.mul(self, other)

    def div(self, other):
        from . import arithmetics

        return arithmetics.div(self, other)

    def fmod(self, other):
        from . import arithmetics

        return arithmetics.fmod(self, other)

    def pow(self, other):
        from . import arithmetics

        return arithmetics.pow(self, other)

    def prod(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import arithmetics

        return arithmetics.prod(self, axis, out, keepdims, keepdim)

    def sum(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import arithmetics

        return arithmetics.sum(self, axis, out, keepdims, keepdim)

    def cumsum(self, axis=0):
        from . import arithmetics

        return arithmetics.cumsum(self, axis)

    def cumprod(self, axis=0):
        from . import arithmetics

        return arithmetics.cumprod(self, axis)

    # -- exponential / trig / rounding ---------------------------------- #
    def exp(self, out=None):
        from . import exponential

        return exponential.exp(self, out)

    def expm1(self, out=None):
        from . import exponential

        return exponential.expm1(self, out)

    def exp2(self, out=None):
        from . import exponential

        return exponential.exp2(self, out)

    def log(self, out=None):
        from . import exponential

        return exponential.log(self, out)

    def log2(self, out=None):
        from . import exponential

        return exponential.log2(self, out)

    def log10(self, out=None):
        from . import exponential

        return exponential.log10(self, out)

    def log1p(self, out=None):
        from . import exponential

        return exponential.log1p(self, out)

    def sqrt(self, out=None):
        from . import exponential

        return exponential.sqrt(self, out)

    def sin(self, out=None):
        from . import trigonometrics

        return trigonometrics.sin(self, out)

    def cos(self, out=None):
        from . import trigonometrics

        return trigonometrics.cos(self, out)

    def tan(self, out=None):
        from . import trigonometrics

        return trigonometrics.tan(self, out)

    def sinh(self, out=None):
        from . import trigonometrics

        return trigonometrics.sinh(self, out)

    def cosh(self, out=None):
        from . import trigonometrics

        return trigonometrics.cosh(self, out)

    def tanh(self, out=None):
        from . import trigonometrics

        return trigonometrics.tanh(self, out)

    def arcsin(self, out=None):
        from . import trigonometrics

        return trigonometrics.arcsin(self, out)

    def arccos(self, out=None):
        from . import trigonometrics

        return trigonometrics.arccos(self, out)

    def arctan(self, out=None):
        from . import trigonometrics

        return trigonometrics.arctan(self, out)

    def abs(self, out=None, dtype=None):
        from . import rounding

        return rounding.abs(self, out, dtype)

    def absolute(self, out=None, dtype=None):
        """Alias of :meth:`abs` (reference heat/core/dndarray.py:506)."""
        return self.abs(out, dtype)

    def fabs(self, out=None):
        from . import rounding

        return rounding.fabs(self, out)

    def ceil(self, out=None):
        from . import rounding

        return rounding.ceil(self, out)

    def floor(self, out=None):
        from . import rounding

        return rounding.floor(self, out)

    def clip(self, a_min, a_max, out=None):
        from . import rounding

        return rounding.clip(self, a_min, a_max, out)

    def modf(self, out=None):
        from . import rounding

        return rounding.modf(self, out)

    def round(self, decimals=0, out=None, dtype=None):
        from . import rounding

        return rounding.round(self, decimals, out, dtype)

    def trunc(self, out=None):
        from . import rounding

        return rounding.trunc(self, out)

    # -- logical -------------------------------------------------------- #
    def all(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import logical

        return logical.all(self, axis, out, keepdims, keepdim)

    def any(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import logical

        return logical.any(self, axis, out, keepdims, keepdim)

    def allclose(self, other, rtol=1e-05, atol=1e-08, equal_nan=False):
        from . import logical

        return logical.allclose(self, other, rtol, atol, equal_nan)

    def isclose(self, other, rtol=1e-05, atol=1e-08, equal_nan=False):
        from . import logical

        return logical.isclose(self, other, rtol, atol, equal_nan)

    # -- statistics ----------------------------------------------------- #
    def argmax(self, axis=None, out=None, **kwargs):
        from . import statistics

        return statistics.argmax(self, axis, out, **kwargs)

    def argmin(self, axis=None, out=None, **kwargs):
        from . import statistics

        return statistics.argmin(self, axis, out, **kwargs)

    def max(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import statistics

        return statistics.max(self, axis, out, keepdims, keepdim)

    def min(self, axis=None, out=None, keepdims=None, keepdim=None):
        from . import statistics

        return statistics.min(self, axis, out, keepdims, keepdim)

    def mean(self, axis=None, keepdims=None, keepdim=None):
        from . import statistics

        return statistics.mean(self, axis, keepdims=keepdims, keepdim=keepdim)

    def median(self, axis=None, keepdim=None, keepdims=None):
        from . import statistics

        return statistics.median(self, axis, keepdim, keepdims=keepdims)

    def var(self, axis=None, ddof=0, **kwargs):
        from . import statistics

        return statistics.var(self, axis, ddof=ddof, **kwargs)

    def std(self, axis=None, ddof=0, **kwargs):
        from . import statistics

        return statistics.std(self, axis, ddof=ddof, **kwargs)

    def skew(self, axis=None, unbiased=True):
        from . import statistics

        return statistics.skew(self, axis, unbiased)

    def kurtosis(self, axis=None, unbiased=True, Fischer=True):
        from . import statistics

        return statistics.kurtosis(self, axis, unbiased, Fischer)

    def average(self, axis=None, weights=None, returned=False):
        from . import statistics

        return statistics.average(self, axis=axis, weights=weights, returned=returned)

    def percentile(self, q, axis=None, out=None, interpolation="linear", keepdims=False):
        from . import statistics

        return statistics.percentile(self, q, axis, out, interpolation, keepdims)

    # -- manipulations -------------------------------------------------- #
    def expand_dims(self, axis):
        from . import manipulations

        return manipulations.expand_dims(self, axis)

    def flatten(self):
        from . import manipulations

        return manipulations.flatten(self)

    def ravel(self):
        from . import manipulations

        return manipulations.flatten(self)

    def reshape(self, *shape, **kwargs):
        from . import manipulations

        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        return manipulations.reshape(self, shape, **kwargs)

    def squeeze(self, axis=None):
        from . import manipulations

        return manipulations.squeeze(self, axis)

    def unique(self, sorted=False, return_inverse=False, axis=None):
        from . import manipulations

        return manipulations.unique(self, sorted, return_inverse, axis)

    def flip(self, axis=None):
        from . import manipulations

        return manipulations.flip(self, axis)

    def sort(self, axis=-1, descending=False, out=None):
        from . import manipulations

        return manipulations.sort(self, axis, descending, out)

    def repeat(self, repeats, axis=None):
        from . import manipulations

        return manipulations.repeat(self, repeats, axis)

    def nonzero(self):
        from . import indexing

        return indexing.nonzero(self)

    # -- linalg --------------------------------------------------------- #
    def transpose(self, axes=None):
        from .linalg import basics

        return basics.transpose(self, axes)

    def tril(self, k=0):
        from .linalg import basics

        return basics.tril(self, k)

    def triu(self, k=0):
        from .linalg import basics

        return basics.triu(self, k)

    def dot(self, other, out=None):
        from .linalg import basics

        return basics.dot(self, other, out=out)

    def matmul(self, other, out=None, precision=None):
        from .linalg import basics

        return basics.matmul(self, other, out=out, precision=precision)

    def qr(self, tiles_per_proc=1, calc_q=True, overwrite_a=False):
        from .linalg.qr import qr as _qr

        return _qr(self, tiles_per_proc, calc_q, overwrite_a)

    def norm(self):
        from .linalg import basics

        return basics.norm(self)
