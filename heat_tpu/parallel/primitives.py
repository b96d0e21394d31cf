"""Sequence/context-parallel communication primitives.

See package docstring for the reference-mechanism mapping.  Every function
accepts either a DNDarray (uses its communicator) or a raw jax.Array (uses
the default communicator).
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast
from jax.sharding import NamedSharding, PartitionSpec

from ..comm.overlap import overlap_enabled, timed_dispatch
from ..core._compile import cache_stable, jitted
from ..core.communication import XlaCommunication, get_comm
from ..core.dndarray import DNDarray

__all__ = [
    "all_to_all_resplit",
    "halo_exchange",
    "prefix_scan",
    "prefix_sum",
    "ring_map",
    "ring_source",
    "zigzag_chunk_owner",
    "zigzag_inverse_perms",
    "zigzag_merge",
    "zigzag_perms",
    "zigzag_split",
]


def _unpack(x, comm: Optional[XlaCommunication]):
    if isinstance(x, DNDarray):
        return x.larray, x.comm
    return x, (comm or get_comm())


def ring_source(position: int, round: int, size: int) -> int:
    """Origin of the rotating block seen by ``position`` at ``round``.

    With the +1 rotation used by :func:`ring_map`, after ``round`` hops the
    block at mesh position p started at ``(p - round) % size``.  Consumers
    of ragged inputs combine this with ``comm.valid_counts(n)`` to know how
    many rows of the rotating block are real data — the analog of the
    reference's per-rank Probe'd recv sizes (spatial/distance.py:271-287).
    """
    return (position - round) % size


def ring_map(
    fn: Callable,
    x,
    comm: Optional[XlaCommunication] = None,
    axis: int = 0,
) -> jax.Array:
    """Apply ``fn(stationary_block, rotating_block, round)`` over a full
    ring rotation and stack the per-round results.

    The communication shape of the reference's pairwise-distance ring
    (spatial/distance.py:261-345) and of ring attention: each mesh position
    keeps its stationary block while the rotating copy moves one hop per
    round via ``ppermute``; after ``size`` rounds every position has seen
    every block.

    Returns an array with a leading ``size`` axis of per-round results,
    sharded like ``x``.  Any axis length is accepted: non-divisible axes
    are zero-padded to the canonical layout (``comm.pad_to_shards``), so
    ``fn`` sees equal ``shard_width``-row blocks whose trailing rows may be
    padding — mask with ``comm.valid_counts`` + :func:`ring_source` when
    the computation isn't padding-invariant.
    """
    arr, comm = _unpack(x, comm)
    size = comm.size
    if axis != 0:
        arr = jnp.moveaxis(arr, axis, 0)
    if size == 1:
        out = fn(arr, arr, 0)
        return out[None]
    if arr.shape[0] % size != 0:
        arr = comm.pad_to_shards(arr, axis=0)

    mesh, name = comm.mesh, comm.axis_name
    perm = [(i, (i + 1) % size) for i in range(size)]
    overlapped = overlap_enabled(size)

    def kernel(block):
        stationary = block

        def fold(r, rotating, acc):
            res = fn(stationary, rotating, r)
            return acc.at[r].set(res)

        probe = fn(stationary, stationary, 0)
        acc0 = jnp.zeros((size,) + probe.shape, probe.dtype)
        # freshly-created carries are axis-invariant; the loop makes them
        # varying over the mesh axis — align the types up front
        acc0 = pcast(acc0, (name,), to="varying")
        if overlapped:
            # double-buffered: round r issues the hop that produces
            # operand r+2 while the fold consumes operand r, so the DMA
            # runs behind the math.  Same ppermute chain applied to the
            # same operands, same fold order — bitwise equal to the
            # serial body (design.md §18); costs one extra in-flight slab
            # and one extra (unconsumed) hop.
            def body(r, carry):
                cur, inflight, acc = carry
                nxt = jax.lax.ppermute(inflight, name, perm)
                acc = fold(r, cur, acc)
                return inflight, nxt, acc

            inflight0 = jax.lax.ppermute(stationary, name, perm)
            _, _, acc = jax.lax.fori_loop(
                0, size, body, (stationary, inflight0, acc0)
            )
        else:
            def body(r, carry):
                rotating, acc = carry
                acc = fold(r, rotating, acc)
                rotating = jax.lax.ppermute(rotating, name, perm)
                return rotating, acc

            _, acc = jax.lax.fori_loop(0, size, body, (stationary, acc0))
        if probe.ndim == 0:
            # scalar per round: materialize the per-position axis so the
            # global result is (rounds, positions)
            acc = acc[:, None]
        return acc

    def make():
        return shard_map(
            kernel,
            mesh=mesh,
            in_specs=PartitionSpec(name),
            out_specs=PartitionSpec(None, name),
        )

    # cached per (comm, fn) — but only for cache-STABLE fns: a
    # module-level plain function repeats its identity across calls, so
    # the compiled ring program is reused.  Everything else — lambdas,
    # closures, bound methods — gets a transient jit (the old behavior):
    # keying on per-call identities would grow the global cache by one
    # dead entry per call without ever hitting
    if cache_stable(fn):
        ring = jitted(("ring_map", comm, fn), make)  # spmdlint: disable=SPMD401
    else:
        ring = jax.jit(make())
    if isinstance(arr, jax.core.Tracer):  # inside fuse/jit: no host timing
        return ring(arr)
    return timed_dispatch("ring_map", overlapped, lambda: ring(arr))


def halo_exchange(
    x,
    halo_size: int,
    comm: Optional[XlaCommunication] = None,
) -> Tuple[jax.Array, jax.Array]:
    """Fetch each shard's neighbor boundary strips via one ppermute pair.

    The reference's ``get_halo`` (dndarray.py:390-463) posts Isend/Irecv
    with prev/next ranks; here both directions are a single
    ``shard_map``-wrapped pair of collective-permutes over ICI.  Returns
    ``(prev_halos, next_halos)`` where each is sharded like ``x`` and holds,
    per shard, the strip received from the neighbor (first/last shard
    receive zeros, mirroring the reference's absent-neighbor behavior).

    Any axis-0 length is accepted via canonical zero-padding: with the
    ceil-division layout, the predecessor of every non-empty shard is a
    *full* shard, so the plain block-edge strips remain exact, and strips
    that reach past the global end come back zero-filled — the natural
    boundary semantics for stencils.  Requires ``halo_size ≤ shard_width``.
    """
    arr, comm = _unpack(x, comm)
    size = comm.size
    if halo_size < 0:
        raise ValueError(f"halo_size needs to be non-negative, got {halo_size}")
    if halo_size and comm.shard_width(arr.shape[0]) < halo_size:
        raise ValueError(
            f"halo_size ({halo_size}) exceeds the shard width "
            f"({comm.shard_width(arr.shape[0])})"
        )
    if size == 1 or halo_size == 0:
        z = jnp.zeros((halo_size,) + arr.shape[1:], arr.dtype)
        return z, z
    if arr.shape[0] % size != 0:
        arr = comm.pad_to_shards(arr, axis=0)

    mesh, name = comm.mesh, comm.axis_name
    fwd = [(i, i + 1) for i in range(size - 1)]  # my tail → next's halo_prev
    bwd = [(i + 1, i) for i in range(size - 1)]  # my head → prev's halo_next

    def kernel(block):
        tail = block[-halo_size:]
        head = block[:halo_size]
        prev_halo = jax.lax.ppermute(tail, name, fwd)  # zeros at position 0
        next_halo = jax.lax.ppermute(head, name, bwd)  # zeros at last position
        return prev_halo, next_halo

    prev, nxt = jitted(
        ("halo_exchange", comm, halo_size),
        lambda: shard_map(
            kernel,
            mesh=mesh,
            in_specs=PartitionSpec(name),
            out_specs=(PartitionSpec(name), PartitionSpec(name)),
        ),
    )(arr)
    return prev, nxt


#: op name -> (local cumulative fn, identity, axis reduction)
_SCAN_OPS = {
    "sum": (jnp.cumsum, 0, jnp.sum),
    "prod": (jnp.cumprod, 1, jnp.prod),
}


def prefix_scan(
    x,
    op: str = "sum",
    comm: Optional[XlaCommunication] = None,
    axis: int = 0,
) -> jax.Array:
    """Element-wise cumulative ``op`` along a SHARDED axis as a real
    two-level scan: parallel local cum-op per shard + one all-gather of
    the p shard totals, combined below the caller's position for the
    cross-shard offset.

    The engine under distributed cumulative ops (the data-axis analog of
    the reference's ``Scan`` collective, communication.py:524-567): asking
    GSPMD to partition ``jnp.cumsum`` along a sharded axis produces a
    sequential program across the shards, where this formulation runs
    the two passes over the data it actually needs plus one all-gather
    of p totals (its time on the chip: not measured; no cell).  Any axis
    length is accepted: the
    canonical padding is filled with the op identity, so it is invisible
    to the scan.
    """
    if op not in _SCAN_OPS:
        raise ValueError(f"unsupported prefix_scan op {op!r}")
    arr, comm = _unpack(x, comm)
    if comm.size == 1 or arr.shape[axis] == 0:
        # empty: shards would index local[-1] of size 0
        return _SCAN_OPS[op][0](arr, axis=axis)
    # one compiled program (pad + shard_map + unpad); the eager per-phase
    # dispatch costs more than the scan itself at 1M elements
    return _prefix_scan_jit(arr, op, comm, axis)


@partial(jax.jit, static_argnames=("op", "comm", "axis"))
def _prefix_scan_jit(arr, op: str, comm: XlaCommunication, axis: int):
    cum, ident, reduce_fn = _SCAN_OPS[op]
    size = comm.size
    if axis != 0:
        arr = jnp.moveaxis(arr, axis, 0)
    n = arr.shape[0]
    if n % size != 0:
        arr = comm.pad_to_shards(arr, axis=0)
        if ident != 0:  # zero-padding must become the op's identity
            pos = jnp.arange(arr.shape[0]).reshape((-1,) + (1,) * (arr.ndim - 1))
            arr = jnp.where(pos < n, arr, jnp.asarray(ident, arr.dtype))

    mesh, name = comm.mesh, comm.axis_name

    def kernel(block):
        local = cum(block, axis=0)
        totals = jax.lax.all_gather(local[-1], name)  # (p, ...)
        s = jax.lax.axis_index(name)
        mask = (jnp.arange(size) < s).reshape((size,) + (1,) * (block.ndim - 1))
        offset = jnp.where(mask, totals, jnp.asarray(ident, totals.dtype))
        acc = reduce_fn(offset, axis=0)  # one vectorized fold of the p totals
        if op == "sum":
            return local + acc.astype(local.dtype)
        return local * acc.astype(local.dtype)

    spec = comm.spec(arr.ndim, 0)
    out = shard_map(kernel, mesh=mesh, in_specs=spec, out_specs=spec)(arr)
    out = comm.unpad(out, n, axis=0)
    return jnp.moveaxis(out, 0, axis) if axis != 0 else out


def prefix_sum(
    x,
    comm: Optional[XlaCommunication] = None,
    axis: int = 0,
) -> jax.Array:
    """Cumulative sum along a sharded axis — ``prefix_scan(x, "sum")``."""
    return prefix_scan(x, "sum", comm=comm, axis=axis)


def all_to_all_resplit(
    x,
    from_axis: int,
    to_axis: int,
    comm: Optional[XlaCommunication] = None,
) -> jax.Array:
    """Swap the sharded axis: split at ``from_axis`` → split at ``to_axis``.

    The Ulysses sequence-parallel primitive (heads↔sequence swap) and the
    reference's axis-permuted ``Alltoallv`` (communication.py:764-881).
    Expressed as a sharding transformation; XLA lowers it to one
    all-to-all over ICI when both axis sizes divide the mesh.
    """
    arr, comm = _unpack(x, comm)
    del from_axis  # the array's current sharding already encodes it
    return comm.apply_sharding(arr, to_axis)


def zigzag_chunk_owner(c: int, size: int) -> int:
    """Zig-zag home device of sequence half-chunk ``c`` (0 <= c < 2*size):
    device ``i`` holds the mirrored pair ``(i, 2*size-1-i)``.  Under a
    causal mask this pairing gives every device the same attention work
    per ring round — contiguous sharding instead gives device 0 one
    non-empty round and device size-1 all of them."""
    return c if c < size else 2 * size - 1 - c


def zigzag_perms(size: int):
    """Forward resplit schedule, contiguous → zig-zag, as two ppermute
    permutations.  Contiguous device ``i`` holds half-chunks (2i, 2i+1);
    the first stream carries every device's first half, the second its
    second half, each to the chunk's zig-zag home — both are bijections
    because ``zigzag_chunk_owner`` maps evens and odds one-to-one."""
    first = [(i, zigzag_chunk_owner(2 * i, size)) for i in range(size)]
    second = [(i, zigzag_chunk_owner(2 * i + 1, size)) for i in range(size)]
    return first, second


def zigzag_inverse_perms(size: int):
    """Inverse resplit schedule, zig-zag → contiguous.  Zig-zag device
    ``d`` holds chunks (d, 2*size-1-d) — exactly one even, one odd.  The
    even-chunk stream lands as its receiver's first local half (chunk 2i
    → device i), the odd-chunk stream as the second half."""
    even = [(d, (d if d % 2 == 0 else 2 * size - 1 - d) // 2)
            for d in range(size)]
    odd = [(d, ((2 * size - 1 - d) if d % 2 == 0 else d) // 2)
           for d in range(size)]
    return even, odd


def zigzag_split(x, axis: int, axis_name: str, size: int):
    """Contiguous local block → zig-zag ``(lo, hi)`` half-chunks.

    Traced INSIDE shard_map: ``x`` is device ``i``'s contiguous local
    block whose ``axis`` covers global rows [i*L, (i+1)*L); the result is
    the device's zig-zag pair — ``lo`` = half-chunk ``i`` (global rows
    [i*Lh, (i+1)*Lh)), ``hi`` = half-chunk ``2*size-1-i`` — moved with
    two ppermutes (one per local half).  ``axis`` length must be even.
    """
    L = x.shape[axis]
    lh = L // 2
    first = jax.lax.slice_in_dim(x, 0, lh, axis=axis)
    second = jax.lax.slice_in_dim(x, lh, L, axis=axis)
    pf, ps = zigzag_perms(size)
    a = jax.lax.ppermute(first, axis_name, pf)
    b = jax.lax.ppermute(second, axis_name, ps)
    # chunk i arrived on the stream matching its parity: even chunks ride
    # the first-half stream (2i' is even), odd ones the second
    even = jax.lax.axis_index(axis_name) % 2 == 0
    lo = jnp.where(even, a, b)
    hi = jnp.where(even, b, a)
    return lo, hi


def zigzag_merge(lo, hi, axis: int, axis_name: str, size: int):
    """Inverse of :func:`zigzag_split`: the zig-zag pair back to the
    contiguous local block (traced inside shard_map)."""
    even = jax.lax.axis_index(axis_name) % 2 == 0
    # device d's even-indexed chunk is d itself when d is even, else its
    # mirror 2*size-1-d
    even_chunk = jnp.where(even, lo, hi)
    odd_chunk = jnp.where(even, hi, lo)
    pe, po = zigzag_inverse_perms(size)
    first = jax.lax.ppermute(even_chunk, axis_name, pe)
    second = jax.lax.ppermute(odd_chunk, axis_name, po)
    return jnp.concatenate([first, second], axis=axis)
