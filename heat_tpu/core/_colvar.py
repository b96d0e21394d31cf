"""Centred sums of squares down the columns of a matrix, from ONE read of it —
a Pallas TPU kernel.

The variance along axis 0 as ``jnp.var`` makes it is two streaming passes over
the operand: the column means, then the sums of ``(x - mean)**2``.  On an
operand that fills a chip both are bound by that stream.  A column needs all of
its rows for its mean before the first centred square can be taken, but it needs
nothing of any other column, so this kernel walks the operand by tiles of
columns, ALL rows of a tile brought into VMEM once, and makes both sums of the
two-pass algorithm from the resident tile::

    mean[j] = sum_i x[i, j] / n
    m2[j]   = sum_i (x[i, j] - mean[j])**2

The arithmetic is the two-pass form's own (the mean is taken off before the
squares are summed, in float32, every row), in another order of the same ``n``
additions; it cannot cancel where a mean is large beside its deviation.  The
raw form ``E[x**2] - mean**2`` does, and so does a shift by a data row on a
column whose first row is an outlier: neither is here.  Nor are merged
single-pass moments (Welford's and Chan's updates, which XLA compiles to one
pass as fast as this one): their running mean is rounded at every merge, so
their error grows with the mean over the deviation (3e-5 of the deviation at a
thousand, 300 rows of float32) where the two sums' stays at 1e-7.

On a v5e, 300 x 6 291 456 float32 (7.55 GB): 10.18 ms, 742 GB/s, the rate of a
plain column sum, against the two passes' 20.29 ms (chip runs of PR 33,
``PERF.md`` §6).

Layout: a one-dimensional grid over tiles of ``tile`` columns; a grid step's
block is ``(rows up to a multiple of 8, tile)``, two in flight.  Within a tile
the columns go by chunks of :data:`_CHUNK` lanes, one after the other; a
chunk's sum runs over its row groups of 8 into an ``(8, chunk)``
sublane-partial accumulator in registers, reduced across sublanes once.

- The rows need not divide by 8: what lies in the last row group's padding is
  unspecified, so that group takes a masked fold.  Nor the columns by the tile:
  the last tile's padding columns make sums of their own that are never
  written back.  The operand itself is never padded, copied or laid out anew.
- Columns are independent: the grid's axis is parallel, and a NaN or an inf
  stays in its own column.

Falls back to nothing: ``statistics._var`` gates on :func:`conforms` and keeps
``jnp.var``'s two passes for every other operand.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["centred_squares", "conforms"]

_SUBLANES = 8
#: lanes a chunk: 4 registers a row group, 4 accumulators and 4 of the mean
#: live across the fold's loop (1024 measured the same, and 2 or 8 row groups a
#: trip of the loop: the kernel waits for its tiles at any of them).  The
#: chunks of a tile are a loop too, not unrolled: unrolled, the 16 chunks of
#: the cell's tile cost every process 0.9 s of tracing and lowering
_CHUNK = 512
#: row groups of 8 one trip of the fold's loop takes
_UNROLL = 4
#: narrowest tile the kernel is used with, one chunk: all rows of it must fit
#: :data:`_TILE_BYTES`.  An operand of more rows (5 120) is tall, not wide; a
#: merge over row tiles would serve it, ``statistics._var`` keeps two passes
MIN_TILE = _CHUNK
#: bytes one tile may hold in VMEM (two are in flight): 8192 columns of the
#: cell's 300 rows (1024 to 8192 measured the same there to 0.05 %), the more
#: columns the fewer the rows, so that a grid step moves enough for its fixed
#: cost (8 rows: 2.26 ms a GB at 327 680 columns a tile, 2.77 at 8192)
_TILE_BYTES = 10 * 1024 * 1024
#: VMEM the compiler may use: two tiles in flight and the (1, tile) results
_VMEM_LIMIT = 32 * 1024 * 1024
#: smallest operand the kernel is used for, in bytes.  Measured on the chip at
#: 300 rows (PR 33): at 4.9 and 19.7 MB both forms take the 0.19 ms the host
#: needs to issue a call; at 78.6 MB the kernel 0.18-0.19 ms against 0.23, at
#: 315 MB 0.45 against 0.87.  Under this the two passes stay
MIN_BYTES = 64 * 1024 * 1024


def _interpret() -> bool:
    """Whether the kernel runs in the Pallas interpreter.  Never in the
    program: the CPU tests patch it to drive the route through ``ht.var``."""
    return False


def _tile(rows: int, cols: int) -> int:
    """Columns a tile: as many whole chunks as :data:`_TILE_BYTES` hold of all
    ``rows`` (in whole row groups), no wider than the operand."""
    padded = pl.cdiv(rows, _SUBLANES) * _SUBLANES
    return min(_TILE_BYTES // (4 * padded), cols) // _CHUNK * _CHUNK


def conforms(arr, axis) -> bool:
    """True where the variance of ``arr`` along ``axis`` takes this kernel: a
    float32 matrix reduced over its rows (axis 0: the rows are the major axis,
    the columns the lanes), all rows of at least :data:`MIN_TILE` columns
    fitting a tile, at least :data:`MIN_BYTES` in all, in a process that drives
    ONE TPU.

    Read at trace time, where an operand's own sharding cannot be seen; a
    process with one device cannot hold a sharded operand, which is what the
    kernel must never meet (GSPMD would all-gather it around the custom call,
    and the chips' partial moments would need merging).  THE one predicate:
    ``statistics._var`` branches on it and ``statistics._moment2`` names the
    form in its launch spans by it."""
    return (
        arr.ndim == 2
        and axis in (0, (0,))
        and arr.dtype == jnp.float32
        and arr.size * 4 >= MIN_BYTES
        and _tile(*arr.shape) >= MIN_TILE
        and (jax.default_backend() == "tpu" or _interpret())
        and jax.device_count() == 1
    )


def _kernel(x_ref, m2_ref, *, n, chunk):
    full, ragged = divmod(n, _SUBLANES)
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, chunk), 0)

    def fold(lanes, term):
        """``sum_i term(x[i])`` down the chunk's columns, every row once."""

        def group(g, acc):
            rows = pl.ds(pl.multiple_of(g * _SUBLANES, _SUBLANES), _SUBLANES)
            return acc + term(x_ref[rows, lanes])

        def body(i, acc):  # unrolled by hand: Mosaic's fori_loop takes unroll=1
            for u in range(_UNROLL):
                acc = group(i * _UNROLL + u, acc)
            return acc

        acc = jnp.zeros((_SUBLANES, chunk), jnp.float32)
        acc = jax.lax.fori_loop(0, full // _UNROLL, body, acc)
        for g in range(full - full % _UNROLL, full):
            acc = acc + term(x_ref[g * _SUBLANES:(g + 1) * _SUBLANES, lanes])
        if ragged:  # the padding rows hold anything, NaN too: selected away
            last = term(x_ref[full * _SUBLANES:(full + 1) * _SUBLANES, lanes])
            acc = acc + jnp.where(sub < ragged, last, 0.0)
        return jnp.sum(acc, axis=0, keepdims=True)

    def columns(c, carry):
        lanes = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        mean = jnp.broadcast_to(fold(lanes, lambda x: x) / n, (_SUBLANES, chunk))
        m2_ref[:, lanes] = fold(lanes, lambda x: jnp.square(x - mean))
        return carry

    jax.lax.fori_loop(0, x_ref.shape[1] // chunk, columns, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "tile"))
def centred_squares(arr, interpret: bool = False, tile: int | None = None):
    """``sum_i (arr[i, j] - mean_j)**2`` for every column ``j`` of a float32
    matrix, ``mean_j`` the column's own mean over all rows: shape ``(cols,)``,
    from one read of ``arr``.  Divided by ``rows - ddof`` it is the variance.

    ``interpret`` runs the Pallas interpreter (CPU test suite); ``tile`` is the
    tile's width in columns, a multiple of 128 (:func:`_tile`'s unless given:
    tests use small ones, which go by narrower chunks)."""
    n, cols = arr.shape
    tile = tile or _tile(n, cols)
    chunk = math.gcd(_CHUNK, tile)
    # x64 off for index arithmetic — see flash_attention
    with jax.enable_x64(False):
        m2 = pl.pallas_call(
            functools.partial(_kernel, n=n, chunk=chunk),
            grid=(pl.cdiv(cols, tile),),
            in_specs=[pl.BlockSpec((pl.cdiv(n, _SUBLANES) * _SUBLANES, tile), lambda j: (0, j))],
            out_specs=pl.BlockSpec((1, tile), lambda j: (0, j)),
            out_shape=jax.ShapeDtypeStruct((1, cols), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="colvar",
        )(arr)
    return m2.reshape(cols)
