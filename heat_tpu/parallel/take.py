"""Distributed take/put: gather/scatter rows of an axis-0-sharded array
by GLOBAL indices, with bounded per-device memory.

Reference: the MPI code resolves global fancy indexing with Alltoallv of
request/response buffers (heat/core/dndarray.py:1476-1726 getitem and
:3190-3339 setitem route per-rank index intersections through ragged
collectives).  GSPMD's answer to a data-dependent cross-shard gather is
to REPLICATE the operand (tests/test_hlo_ragged.py pins this), which
caps scale at per-device HBM.

TPU formulation (**ring take**): rotate the DATA blocks around the mesh
with ``ppermute``; in round r every device sees the block of global rows
``[src*w, (src+1)*w)`` and answers the subset of its queries that land
in that range with a LOCAL gather.  After p rounds every query has met
its row.  Total bytes moved equal one all-gather, but only two blocks
are ever resident per device — O(N/p) memory instead of O(N) — and
every shape is static.

``ring_put`` is the dual (scatter by global index): the OUTPUT blocks
rotate, and each device deposits the subset of its values whose
destination lands in the visiting block.  Duplicate destinations resolve
in unspecified order (see :func:`ring_put`).
"""

from __future__ import annotations

from functools import partial
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast

from ..core.communication import XlaCommunication, get_comm

__all__ = ["ring_take", "ring_put"]


def _pad_rows(comm, arr):
    return comm.pad_to_shards(arr, axis=0) if arr.shape[0] % comm.size else comm.apply_sharding(arr, 0)


def _sanitize_index(idx: jax.Array, n: int, clip: bool = False) -> jax.Array:
    """Wrap negatives (numpy semantics) and resolve anything still out of
    ``[0, n)`` — to the drop/fill sentinel ``n`` by default, or clamped
    into range with ``clip=True`` (jnp gather semantics).  All range
    logic runs BEFORE any narrowing cast: truncating first would fold an
    out-of-range 64-bit (or, with x64 off, uint32) index into a valid row
    and silently read/write the wrong data.  Unsigned indices range-check
    in their own domain for the same reason.  The result is int32
    (``n < 2**31`` is enforced by the callers)."""
    dt = idx.dtype
    if jnp.issubdtype(dt, jnp.unsignedinteger):
        if np.dtype(dt).itemsize <= 2:
            idx = idx.astype(jnp.int32)  # lossless widen
        else:
            # uint32/uint64: compare against n IN the unsigned dtype, then
            # cast — every surviving value is <= n < 2**31, so lossless
            idx = jnp.minimum(idx, jnp.asarray(n, dt)).astype(jnp.int32)
    else:
        wide = jnp.int64 if jax.config.jax_enable_x64 else jnp.int32
        idx = idx.astype(wide)  # widen BEFORE arithmetic: int8 + n would wrap
        idx = jnp.where(idx < 0, idx + n, idx)
    if clip:
        return jnp.clip(idx, 0, n - 1).astype(jnp.int32)
    idx = jnp.where((idx < 0) | (idx >= n), n, idx)
    return idx.astype(jnp.int32)


def ring_take(
    arr: jax.Array,
    idx: jax.Array,
    comm: Optional[XlaCommunication] = None,
    fill=0,
    n: Optional[int] = None,
    padded_out: bool = False,
    oob: str = "fill",
):
    """``out[i] = arr[idx[i]]`` over the mesh: ``arr`` (N, ...) and
    ``idx`` (M,) both shard along axis 0; the result is (M, ...) sharded
    like ``idx``.  Negative indices wrap (numpy semantics); out-of-range
    indices produce ``fill`` (drop-mode semantics, matching the
    framework's scatter convention), or clamp into range with
    ``oob='clip'`` (jnp gather semantics — what ``DNDarray.__getitem__``
    uses).

    ``arr`` may already be the canonically PADDED buffer of a shorter
    axis — pass its true length as ``n`` (pad rows are never read: the
    kernel masks queries ``>= n``).  ``padded_out=True`` returns the
    padded (``padded_size(M)``, ...) at-rest buffer instead of slicing
    back to M — the form a DNDarray stores directly, avoiding a ragged
    boundary materialization of the result."""
    comm = get_comm() if comm is None else comm
    if n is None:
        n = arr.shape[0]
    m = idx.shape[0]
    if max(comm.padded_size(n), comm.padded_size(m)) > 2**31 - 1:
        # indices ride as int32; silently truncating would return wrong
        # rows — the same bound the ring sort enforces
        raise ValueError("ring_take: axis length exceeds int32 index range")
    if oob not in ("fill", "clip"):
        raise ValueError(f"ring_take: oob must be 'fill' or 'clip', got {oob!r}")
    idx = _sanitize_index(idx, n, clip=(oob == "clip"))
    arr_p = _pad_rows(comm, arr)
    idx_p = _pad_rows(comm, idx)
    out = _ring_take(arr_p, idx_p, n, comm, float(fill))
    return out if padded_out else comm.unpad(out, m, 0)


@partial(jax.jit, static_argnames=("n", "comm", "fill"))
def _ring_take(arr, idx, n: int, comm: XlaCommunication, fill: float):
    p = comm.size
    w = arr.shape[0] // p
    mesh, name = comm.mesh, comm.axis_name
    perm = [(i, (i + 1) % p) for i in range(p)]  # forward ring rotation
    trail = arr.shape[1:]

    def kernel(block, q):
        s = jax.lax.axis_index(name).astype(jnp.int32)
        # pcast-to-varying: a fresh constant is 'unvarying' in shard_map's
        # axis typing, but the loop writes per-device values into it
        out0 = pcast(
            jnp.full(q.shape + trail, jnp.asarray(fill, arr.dtype)), name, to="varying"
        )

        def body(r, carry):
            vis, out = carry
            src = (s - r) % p  # whose rows are visiting this round
            base = src * jnp.int32(w)
            mask = (q >= base) & (q < base + w) & (q < jnp.int32(n))
            local = jnp.clip(q - base, 0, w - 1)
            vals = jnp.take(vis, local, axis=0)
            out = jnp.where(
                mask.reshape(mask.shape + (1,) * len(trail)), vals, out
            )
            return jax.lax.ppermute(vis, name, perm), out

        _, out = jax.lax.fori_loop(0, p, body, (block, out0))
        return out

    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=(comm.spec(arr.ndim, 0), comm.spec(1, 0)),
        out_specs=comm.spec(len(trail) + 1, 0),
    )(arr, idx)


def ring_put(
    n: int,
    idx: jax.Array,
    vals: jax.Array,
    comm: Optional[XlaCommunication] = None,
    base: Optional[jax.Array] = None,
    padded_out: bool = False,
):
    """``out[idx[i]] = vals[i]`` over the mesh; ``idx`` (M,) and ``vals``
    (M, ...) shard along axis 0, the result is (n, ...) axis-0 sharded.
    Without ``base`` the destination is a fresh zero array; with ``base``
    (an (n, ...) array, true-length or already canonically padded) the
    un-indexed rows keep their base values — numpy setitem semantics.
    Negative indices wrap (numpy semantics); out-of-range indices drop.
    Duplicate destinations resolve in UNSPECIFIED order (XLA scatter
    makes no ordering promise for repeated indices, and the ring visit
    order adds a cross-shard dimension on top) — callers needing a
    tie-break must disambiguate indices first; the framework's own
    callers pass permutations.  ``padded_out=True`` returns the padded
    at-rest buffer (pad rows carry base garbage/zeros)."""
    comm = get_comm() if comm is None else comm
    m = idx.shape[0]
    if max(comm.padded_size(n), comm.padded_size(m)) > 2**31 - 1:
        raise ValueError("ring_put: axis length exceeds int32 index range")
    idx = _sanitize_index(idx, n)
    idx_p = _pad_rows(comm, idx)
    if base is not None:
        vals = vals.astype(base.dtype)
        if base.shape[0] not in (n, comm.padded_size(n)):
            raise ValueError(
                f"ring_put: base axis 0 is {base.shape[0]}, expected {n} or "
                f"the padded {comm.padded_size(n)}"
            )
        base = _pad_rows(comm, base)
    vals_p = _pad_rows(comm, vals)
    out = _ring_put(idx_p, vals_p, n, m, comm, base)
    return out if padded_out else comm.unpad(out, n, 0)


@partial(jax.jit, static_argnames=("n", "m", "comm"))
def _ring_put(idx, vals, n: int, m: int, comm: XlaCommunication, base=None):
    p = comm.size
    wq = idx.shape[0] // p
    wo = comm.padded_size(n) // p
    mesh, name = comm.mesh, comm.axis_name
    perm = [(i, (i + 1) % p) for i in range(p)]
    trail = vals.shape[1:]

    def kernel(q, v, *b):
        s = jax.lax.axis_index(name).astype(jnp.int32)
        j = jnp.arange(wq, dtype=jnp.int32)
        valid = (s * wq + j) < jnp.int32(m)  # padded queries never write
        if b:
            # each block starts at home (round 0 writes into shard s's own
            # block) and returns home after p rotations — seeding it with
            # the local base shard gives update-in-place semantics
            block = b[0]
        else:
            block = pcast(
                jnp.zeros((wo,) + trail, vals.dtype), name, to="varying"
            )

        def body(r, blk):
            # the block visiting me in round r belongs to shard (s - r) % p
            owner = (s - r) % p
            base_row = owner * jnp.int32(wo)
            mask = valid & (q >= base_row) & (q < base_row + wo) & (q < jnp.int32(n))
            local = jnp.where(mask, q - base_row, wo)  # wo = drop sink
            blk = blk.at[local].set(v, mode="drop")
            return jax.lax.ppermute(blk, name, perm)

        # after p write+rotate rounds every block has visited every shard
        # and returned to its origin, which is exactly its home position
        return jax.lax.fori_loop(0, p, body, block)

    operands = (idx, vals) if base is None else (idx, vals, base)
    in_specs = (comm.spec(1, 0), comm.spec(vals.ndim, 0))
    if base is not None:
        in_specs = in_specs + (comm.spec(base.ndim, 0),)
    return shard_map(
        kernel,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=comm.spec(len(trail) + 1, 0),
    )(*operands)
