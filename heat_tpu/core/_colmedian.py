"""Exact medians down the columns of a matrix, one for each group of its rows,
from ONE read of it and with nothing of its size beside it — a Pallas TPU
kernel.

``KMedians``' centre update asks, for every feature column ``j`` and every
cluster ``c``, for numpy's median of ``{x[i, j] : label[i] == c}``.  The labels
belong to the rows, so every column splits its rows the same way.  The kernel
walks the operand by tiles of columns, ALL rows of a tile brought into VMEM
once (the layout of ``core/_colvar.py``), and selects there:

1. *Lay the tile out by rows and by cluster.*  A tile arrives as the chip
   tiles it, 8 rows to a register.  Each row is copied into a slab of its own,
   ``(8, W)`` with ``8 * W`` the tile's columns, at the place the row has in the
   order of the labels (``dest``, from the caller), as an int32 **key** whose
   signed order is the float's (NaN, either sign, last).  A register of a
   row's slab is one sublane each of 8 registers of its row group: the 8 are
   transposed in registers and stored whole.  A cluster's members are then
   the slabs ``starts[c] .. starts[c + 1]``, and everything below is
   elementwise between whole registers of ONE row: no reduction across
   sublanes or lanes anywhere.
2. *Select the two middle members*, by the method the cluster's own member
   count ``m`` takes (a scalar the kernel reads; :data:`_NETWORK_MAX`):

   - up to ``_NETWORK_MAX`` members, *by a comparison network in registers*:
     register by register of the slab, the members' registers (the missing
     ones ``_INT_MAX``, which sorts last) go through the exchanges of Batcher's
     merge exchange for ``_NETWORK_MAX`` items (Knuth, TAOCP 5.2.2, Algorithm
     M) that the lower half's outputs can hear of: a ``min`` and a ``max`` an
     exchange, nothing loaded or stored between them.  The middles are the
     outputs at their ranks.
   - more members, *by radix*: the ``t``-th smallest key of a cluster is the
     largest ``v`` with ``|{key < v}| <= t - 1``: built bit by bit from the
     top, 32 counts over the cluster's slabs (a load, a compare, a select and
     an add a register), the same 32 whatever the values.  One more pass
     finds the upper middle of an even count: the lower one again if it is a
     duplicate, else the smallest key above it.
3. The median is the member's own value, or ``(a + b) / 2`` of the two middle
   members in float32, as ``numpy.median`` makes it: exact.  A cluster whose
   median position lies among NaN members gets NaN (they sort last); an empty
   cluster gets an unspecified value, the caller keeps its centre.

The result leaves the kernel slab by slab, ``(k, tiles, 8, W)``, which read
row-major is ``(k, columns)``.

- The rows need not divide by 8 (the last row group is copied row by row up
  to the operand's own last row), nor the columns by the tile: the last
  tile's padding columns make medians of their own that are cut off.  The
  operand itself is never padded, copied, sorted or laid out anew in HBM.
- Columns are independent: the grid's axis is parallel.

Falls back to nothing: ``cluster/kmedians.py`` gates on :func:`conforms` and
keeps the rank bisection over a sorted copy for every other operand.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["group_medians", "conforms", "by_network"]

_SUBLANES = 8
_LANES = 128
#: lanes of a row's slab: the tile is ``8 * _SLAB`` columns wide.  Eight
#: registers a row; the threshold, the count and the answer of the selection
#: stay in registers beside them.  At 300 x 6 291 456 on a v5e, a pass of
#: counting alone (my chip run, PR 36, on that PR's lay-out, 12 ms slower than
#: this one): 160 ms at 256 lanes, 96 at 512, 69-71 at 1024: what a trip of
#: the count's loop costs beside its compares is shared by more lanes
_SLAB = 1024
#: rows one trip of the count's loop takes (8: 69 ms, 4: 71, at 1024 lanes, there)
_UNROLL = 8
#: most members of a cluster whose middles come from the comparison network;
#: a larger cluster keeps the counting passes.  The network is made for this
#: many items whatever the cluster has, and all of them are in registers at
#: once (the chip has 64).  A pass over 300 x 6 291 456 on a v5e
#: (``scripts/time_colmedian.py``; my chip runs, PR 37), counting / 40 / 48 /
#: 56: the cell's 8 blobs of 37-38 rows 55.6 / 28.0 / 32.7 / 37.6 ms (258,
#: 334 and 418 exchanges a cluster, 1.9 cycles each, against 33 x 37 counting
#: steps of 0.9); 6 clusters of 50: 51.7 / 52.8 / - / 37.8; 113 + 38 + 38 + 37 +
#: 37 + 14 + 14 + 9 (a random start's merged and split blobs): 55.0 / 40.5 /
#: 45.7 / 50.2; 4 x 75, 2 x 150 and 1 x 300 count at 50.7, 49.6 and 48.4
#: whichever it is.  The cell's clusters are blobs (at most 38), pieces of one
#: or several merged (74 and more): 40 serves the first two at the least cost
_NETWORK_MAX = 40
#: bytes of VMEM the kernel may be granted: two tiles of the operand in
#: flight, the tile again as keys, the results (30 MB of it at the cell's 300
#: rows)
_VMEM_LIMIT = 48 * 1024 * 1024
#: most rows the kernel is used with: all of them, three times, in a tile of
#: the narrowest slab (128 lanes) must fit :data:`_VMEM_LIMIT` with room
MAX_ROWS = 2048
#: smallest operand the kernel is used for, in bytes: under it a fit is a
#: few launches' worth of work on either route (not measured apart)
MIN_BYTES = 64 * 1024 * 1024

_INT_MAX = 0x7FFFFFFF
_SIGN = -0x80000000


def _interpret() -> bool:
    """Whether the kernel runs in the Pallas interpreter.  Never in the
    program: the CPU tests patch it to drive the route through ``KMedians``."""
    return False


def _slab(rows: int, cols: int) -> int:
    """Lanes of a row's slab: :data:`_SLAB`, halved while three copies of all
    ``rows`` of the tile (two in flight, one as keys) pass the VMEM the kernel
    states or the tile is wider than the operand; 0 where none fits."""
    padded = pl.cdiv(rows, _SUBLANES) * _SUBLANES
    slab = _SLAB
    while slab >= _LANES and (
        3 * padded * _SUBLANES * slab * 4 > _VMEM_LIMIT - (8 << 20) or _SUBLANES * slab > cols
    ):
        slab //= 2
    return slab if slab >= _LANES else 0


def conforms(arr, k: int) -> bool:
    """True where the per-cluster medians of ``arr``'s columns take this
    kernel: a float32 matrix of at most :data:`MAX_ROWS` rows and at least
    :data:`MIN_BYTES`, a tile of all its rows fitting VMEM, in a process that
    drives ONE TPU (what ``_colvar.conforms`` asks, for its reasons: read at
    trace time, a process with one device cannot hold a sharded operand).
    THE one predicate: ``KMedians`` branches on it and names the route in its
    launch span by it."""
    return (
        arr.ndim == 2
        and arr.dtype == jnp.float32
        and 0 < arr.shape[0] <= MAX_ROWS
        and 0 < k
        and arr.size * 4 >= MIN_BYTES
        and _slab(*arr.shape) > 0
        and (jax.default_backend() == "tpu" or _interpret())
        and jax.device_count() == 1
    )


def _flip(bits):
    """Float32 bits <-> a key whose signed int32 order is the float's: the
    magnitude bits of a negative are inverted.  Its own inverse."""
    return bits ^ ((bits >> 31) & _INT_MAX)


def _middle_ranks(m):
    """The ranks (from 1, in ascending order among a cluster's ``m`` members)
    of the two members whose mean is numpy's median; the same rank twice at
    an odd count.  Both routes of ``KMedians``' medians ask here, and it is
    where a probe plants its faults (``perf/tools/limits_probe_kmedians.py``)."""
    return (m + 1) // 2, m // 2 + 1


def by_network(counts):
    """How many of the clusters with these member counts take the comparison
    network in :func:`group_medians`: those of 1 to :data:`_NETWORK_MAX`
    members (an empty one selects nothing)."""
    return jnp.sum((counts > 0) & (counts <= _NETWORK_MAX), dtype=jnp.int32)


def _merge_exchange(n: int):
    """Batcher's merge exchange for ``n`` items (Knuth, TAOCP 5.2.2, Algorithm
    M) as its exchanges ``(i, j)``, ``i < j``, in order: after them item ``i``
    is the ``i``-th smallest, whatever ``n``."""
    pairs = []
    top = 1 << ((n - 1).bit_length() - 1) if n > 1 else 0  # the largest power of two under n
    p = top
    while p > 0:
        q, r, d = top, 0, p
        while True:
            pairs += [(i, i + d) for i in range(n - d) if i & p == r]
            if q == p:
                break
            d, q, r = q - p, q >> 1, p
        p >>= 1
    return pairs


def _selection(n: int, outputs):
    """The exchanges of :func:`_merge_exchange` that ``outputs`` can hear of,
    in order: the others move no value into those places."""
    heard, kept = set(outputs), []
    for i, j in reversed(_merge_exchange(n)):
        if i in heard or j in heard:
            kept.append((i, j))
            heard |= {i, j}
    return kept[::-1]


def _kernel(dest_ref, starts_ref, x_ref, out_ref, keys_ref, *, n, k, slab):
    full, ragged = divmod(n, _SUBLANES)

    def lay_out(g, rows):
        """Row group ``g``'s first ``rows`` rows, each to its own slab."""
        first = g * _SUBLANES
        if not isinstance(g, int):
            first = pl.multiple_of(first, _SUBLANES)
        to = [dest_ref[first + u] for u in range(rows)]

        def registers(v, carry):
            """The ``v``-th register of each row's slab: its sublane ``s`` is
            the row's 128 lanes at ``s * slab + v * 128`` of the tile, which
            arrive as one sublane each of 8 registers of the row group."""
            at = pl.multiple_of(v * _LANES, _LANES)
            x = jnp.stack(
                [x_ref[pl.ds(first, _SUBLANES), pl.ds(s * slab + at, _LANES)] for s in range(_SUBLANES)]
            )
            key = _flip(jax.lax.bitcast_convert_type(x, jnp.int32))
            key = jnp.swapaxes(jnp.where(x != x, _INT_MAX, key), 0, 1)  # (row, s, lane)
            for u in range(rows):
                keys_ref[to[u], :, pl.ds(at, _LANES)] = key[u]
            return carry

        jax.lax.fori_loop(0, slab // _LANES, registers, 0)

    def groups(g, carry):
        lay_out(g, _SUBLANES)
        return carry

    jax.lax.fori_loop(0, full, groups, 0)
    if ragged:  # the padding rows hold anything: never copied
        lay_out(full, ragged)

    zeros = jnp.zeros((_SUBLANES, slab), jnp.int32)

    def median(lower, higher):
        a = jax.lax.bitcast_convert_type(_flip(lower), jnp.float32)
        b = jax.lax.bitcast_convert_type(_flip(higher), jnp.float32)
        return jnp.where(lower == higher, a, (a + b) * 0.5)

    def by_a_network(c, lo, m):
        """The medians of a cluster of at most ``_NETWORK_MAX`` members."""
        lower_rank, upper_rank = _middle_ranks(m)
        ranks = _NETWORK_MAX // 2 + 1  # the middles of that many members lie under it
        exchanges = _selection(_NETWORK_MAX, range(ranks))

        def registers(v, carry):
            at = pl.multiple_of(v * _LANES, _LANES)
            key = [  # a member past the cluster's last reads some slab of the tile, and is not taken
                jnp.where(j < m, keys_ref[jnp.minimum(lo + j, n - 1), :, pl.ds(at, _LANES)], _INT_MAX)
                for j in range(_NETWORK_MAX)
            ]
            for i, j in exchanges:
                key[i], key[j] = jnp.minimum(key[i], key[j]), jnp.maximum(key[i], key[j])
            lower = higher = key[0]
            for j in range(1, ranks):
                lower = jnp.where(lower_rank - 1 == j, key[j], lower)
                higher = jnp.where(upper_rank - 1 == j, key[j], higher)
            out_ref[c, 0, :, pl.ds(at, _LANES)] = median(lower, higher)
            return carry

        jax.lax.fori_loop(0, slab // _LANES, registers, 0)

    def by_counting(c, lo, m):
        """The medians of a larger cluster, by radix."""
        hi = lo + m
        trips = m // _UNROLL

        def over_members(term, init):
            """``term(keys of a member, state)`` folded over the cluster."""

            def some(i, state):
                r = lo + i * _UNROLL
                for u in range(_UNROLL):
                    state = term(keys_ref[r + u], state)
                return state

            state = jax.lax.fori_loop(0, trips, some, init)
            return jax.lax.fori_loop(
                lo + trips * _UNROLL, hi, lambda r, st: term(keys_ref[r], st), state
            )

        # the lower middle, the t-th smallest: the largest v (in the keys'
        # unsigned order, ``key ^ _SIGN``) that fewer than t members lie
        # under, bit by bit from the top
        lower_rank, upper_rank = _middle_ranks(m)
        below_t = jnp.maximum(lower_rank, 1) - 1

        def bit(b, ans):
            trial = ans | (jnp.int32(1) << (31 - b))
            under = over_members(
                lambda key, cnt: cnt + jnp.where(key < (trial ^ _SIGN), 1, 0), zeros
            )
            return jnp.where(under <= below_t, trial, ans)

        lower = jax.lax.fori_loop(0, 32, bit, zeros) ^ _SIGN

        # the upper middle: the lower again where as many members as its rank
        # are no larger (an odd count, a duplicate), else the next key
        def upper(key, state):
            upto, above = state
            return (
                upto + jnp.where(key <= lower, 1, 0),
                jnp.minimum(above, jnp.where(key > lower, key, _INT_MAX)),
            )

        upto, above = over_members(upper, (zeros, jnp.full_like(zeros, _INT_MAX)))
        out_ref[c, 0] = median(lower, jnp.where(upto >= upper_rank, lower, above))

    def cluster(c, carry):
        lo = starts_ref[c]
        m = starts_ref[c + 1] - lo

        @pl.when(m > 0)  # an empty cluster's row is unspecified: nothing is selected for it
        def _():
            jax.lax.cond(m <= _NETWORK_MAX, by_a_network, by_counting, c, lo, m)

        return carry

    jax.lax.fori_loop(0, k, cluster, 0)


@functools.partial(jax.jit, static_argnames=("k", "interpret", "slab"))
def group_medians(arr, labels, k: int, interpret: bool = False, slab: int | None = None):
    """``(medians, counts)``: ``medians[c, j]`` is numpy's median of
    ``arr[labels == c, j]`` (float32, exact: a member's value or the mean of
    the two middle members; NaN members sort last), ``counts[c]`` the members
    of cluster ``c``.  ``labels`` are whole numbers in ``[0, k)``, one a row.
    A cluster without members gets an unspecified row.

    ``interpret`` runs the Pallas interpreter (CPU test suite); ``slab`` is the
    lanes of a row's slab, a multiple of 128 (:func:`_slab`'s unless given:
    tests use narrow ones), a tile being 8 slabs wide."""
    n, cols = arr.shape
    slab = slab or _slab(n, cols)
    tile = _SUBLANES * slab
    tiles = pl.cdiv(cols, tile)
    member = labels.astype(jnp.int32)[:, None] == jnp.arange(k, dtype=jnp.int32)[None, :]
    counts = jnp.sum(member, axis=0, dtype=jnp.int32)
    starts = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts, dtype=jnp.int32)])
    # the place of each row in the order of the labels (ties by row number):
    # where its cluster starts, and how many of the cluster's rows lie before it
    before = jnp.cumsum(member, axis=0, dtype=jnp.int32) - 1
    dest = jnp.sum(jnp.where(member, starts[None, :k] + before, 0), axis=1, dtype=jnp.int32)
    # x64 off for index arithmetic — see flash_attention
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_kernel, n=n, k=k, slab=slab),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(tiles,),
                in_specs=[
                    pl.BlockSpec(
                        (pl.cdiv(n, _SUBLANES) * _SUBLANES, tile), lambda j, dest, starts: (0, j)
                    )
                ],
                out_specs=pl.BlockSpec(
                    (k, 1, _SUBLANES, slab), lambda j, dest, starts: (0, j, 0, 0)
                ),
                scratch_shapes=[pltpu.VMEM((n, _SUBLANES, slab), jnp.int32)],
            ),
            out_shape=jax.ShapeDtypeStruct((k, tiles, _SUBLANES, slab), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="colmedian",
        )(dest, starts, arr)
    return out.reshape(k, tiles * tile)[:, :cols], counts
