"""Symmetric matrix-vector product from the upper block-triangle — a Pallas
TPU kernel.

A dense ``A @ w`` on a symmetric float32 ``A`` of 40 000 rows streams all
6.4 GB from HBM and is bound by that stream (8.50 ms at 753 GB/s on a v5e, 92 %
of the chip's bandwidth).  The entries below the diagonal repeat those above
it, so this kernel walks only the tiles ``(I, J)`` with ``J >= I`` and uses
each tile, while it is in VMEM, for both products (4.42 ms: 3.44 GB at 778
GB/s; 0.228 ms for 0.381 at 8 192 rows; chip runs of PR 29, ``PERF.md`` §6)::

    y[I] += A[I, J] @ w[J]          the tile's rows
    y[J] += A[I, J]^T @ w[I]        the same tile standing in for A[J, I]

The operator it applies is ``triu(A) + triu(A, 1)^T`` whatever the tile size:
a diagonal tile gives its upper triangle to the first product and its strict
upper triangle to the second.  Nothing below the diagonal tiles is read at all.

Layout: a one-dimensional grid over the ``nb (nb + 1) / 2`` tiles in row-major
order of the block triangle (their ``(I, J)`` come as scalar-prefetched index
arrays, so no grid step is spent on a skipped tile); ``w``, ``y`` and the
column product's accumulator stay in VMEM for the whole call.  All arithmetic
is float32 multiply and add on the vector units: no MXU pass, nothing rounded
to bfloat16, so the result does not depend on a matmul precision.

- Row product: a block row accumulates ``(rows, 128)`` lane-partial sums over
  its tiles and reduces across lanes once, when its last tile is done (one
  transpose a 128 x 128 chunk: the reduced sums leave along the lanes, as
  ``y`` is laid out).
- Column product: ``(8, block)`` sublane-partial sums a column block, reduced
  across sublanes once, in the last grid step.
- ``A``'s size need not divide into tiles: the last tile in each direction is
  ragged and what lies in its padding is unspecified, so diagonal and ragged
  tiles take a masked fold.  ``A`` itself is never padded or copied.
- The accumulation into ``y[J]`` crosses grid steps: the axis is sequential.

Falls back to nothing: callers gate on :func:`conforms` and keep their own
dense product for every other operand (see ``solver._matvec``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["symv", "conforms"]

#: tile edge: 4 MB of float32 a tile, two in flight; 820 of 1600 tiles at
#: n = 40 000 (3.44 GB of 6.41 GB).  A row group of the tile is 8 registers,
#: and 8 of ``w[J]`` and 8 column accumulators live across the fold's loop;
#: 512 and 2048 measured the same to 2 % on the chip.
BLOCK = 1024

#: smallest order the kernel is used for.  At 8 192 rows the operator is 268
#: MB, twice a v5e's whole VMEM, so every product streams it from HBM and is
#: bound by that stream, and with 8 block rows the triangle is 36 of 64 tiles:
#: measured there, 0.228 ms against the dense product's 0.381.  Smaller
#: operands were not measured and keep the dense product.
MIN_N = 8192

#: VMEM the compiler may use: two tiles in flight (8 MB), the column
#: accumulator (1.3 MB at n = 40 000), the padded ``w`` and ``y``, two
#: (block, 128) scratches (0.5 MB each); what is left of the chip's 128 MB
#: stays the compiler's (it keeps Lanczos' 48 MB basis there, ``PERF.md`` §5)
_VMEM_LIMIT = 32 * 1024 * 1024

_LANES, _SUBLANES = 128, 8
#: row groups of 8 one trip of the fold's loop takes (1 % over one a trip)
_UNROLL = 4


def _interpret() -> bool:
    """Whether the kernel runs in the Pallas interpreter.  Never in the
    program: the CPU tests patch it to drive the route through ``lanczos``."""
    return False


def conforms(arr) -> bool:
    """True where a product with ``arr`` takes this kernel: a square float32
    operand of at least :data:`MIN_N` rows, in a process that drives ONE TPU.

    Read at trace time, where an operand's own sharding cannot be seen; a
    process with one device cannot hold a sharded operand, which is what the
    kernel must never meet (GSPMD would all-gather it around the custom
    call).  An operand that sits on one chip of several takes the dense
    product: slower, not wrong.  THE one predicate: ``solver._matvec``
    branches on it and ``lanczos`` names the route in its launch spans by it."""
    return (
        arr.ndim == 2
        and arr.shape[0] == arr.shape[1]
        and arr.dtype == jnp.float32
        and arr.shape[0] >= MIN_N
        and (jax.default_backend() == "tpu" or _interpret())
        and jax.device_count() == 1
    )


def _tiles(nb: int):
    """``(I, J)`` of the block triangle's tiles, row-major."""
    ii, jj = np.triu_indices(nb)
    return ii.astype(np.int32), jj.astype(np.int32)


def _kernel(ii_ref, jj_ref, a_ref, w_ref, y_ref, wcol_ref, racc_ref, cacc_ref, *, n, block):
    t = pl.program_id(0)
    bi, bj = ii_ref[t], jj_ref[t]
    last = pl.cdiv(n, block) - 1
    chunks = block // _LANES

    @pl.when(t == 0)
    def _():
        cacc_ref[...] = jnp.zeros_like(cacc_ref)

    @pl.when(bj == bi)  # a block row starts at its diagonal tile
    def _():
        racc_ref[...] = jnp.zeros_like(racc_ref)
        # w[I] down the sublanes, one value a row across all lanes: the
        # transpose of its lane-major chunk broadcast down the sublanes
        for c in range(chunks):
            lanes = slice(c * _LANES, (c + 1) * _LANES)
            wcol_ref[lanes, :] = jnp.broadcast_to(w_ref[bi, :, lanes], (_LANES, _LANES)).T

    def fold(masked: bool):
        """Both products of the tile, a row group of 8 at a time.  ``masked``
        (diagonal and ragged tiles): the row product keeps ``row <= col < n``
        and the column product ``col > row``.  A padding ROW needs no mask of
        its own: only the corner tile has any, its row product lands in
        ``y``'s padding and its column product, kept where ``col > row >= n``,
        too; the caller cuts the padding off."""
        sub = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, _LANES), 1)
        spans = [slice(c * _LANES, (c + 1) * _LANES) for c in range(chunks)]
        wj = [jnp.broadcast_to(w_ref[bj, :, s], (_SUBLANES, _LANES)) for s in spans]
        cols = [bj * block + s.start + lane for s in spans] if masked else None

        def group(r, acc):
            rows = pl.ds(pl.multiple_of(r * _SUBLANES, _SUBLANES), _SUBLANES)
            wi = wcol_ref[rows, :]
            row = bi * block + r * _SUBLANES + sub if masked else None
            out, s = [], None
            for k, span in enumerate(spans):
                a = a_ref[rows, span]
                a_row = a_col = a
                if masked:
                    a_row = jnp.where((cols[k] >= row) & (cols[k] < n), a, 0.0)
                    a_col = jnp.where(cols[k] > row, a, 0.0)
                out.append(acc[k] + a_col * wi)
                p = a_row * wj[k]
                s = p if s is None else s + p
            racc_ref[rows, :] += s
            return tuple(out)

        def body(i, acc):  # unrolled by hand: Mosaic's fori_loop takes unroll=1
            for u in range(_UNROLL):
                acc = group(i * _UNROLL + u, acc)
            return acc

        zeros = tuple(jnp.zeros((_SUBLANES, _LANES), jnp.float32) for _ in spans)
        acc = jax.lax.fori_loop(0, block // (_SUBLANES * _UNROLL), body, zeros)
        for k, span in enumerate(spans):
            cacc_ref[bj, :, span] += acc[k]

    edge = bj == bi
    if n % block:
        edge = edge | (bj == last)
    pl.when(edge)(functools.partial(fold, True))
    pl.when(jnp.logical_not(edge))(functools.partial(fold, False))

    @pl.when(bj == last)  # the block row is whole: reduce its lane partials
    def _():
        for c in range(chunks):
            lanes = slice(c * _LANES, (c + 1) * _LANES)
            y_ref[bi, :, lanes] = jnp.sum(racc_ref[lanes, :].T, axis=0, keepdims=True)

    @pl.when(t == pl.num_programs(0) - 1)
    def _():
        def add(b, _):
            y_ref[b] += jnp.sum(cacc_ref[b], axis=0, keepdims=True)
            return _

        jax.lax.fori_loop(0, last + 1, add, 0)


@functools.partial(jax.jit, static_argnames=("interpret", "block"))
def symv(arr, w, interpret: bool = False, block: int | None = None):
    """``(triu(arr) + triu(arr, 1)^T) @ w`` for a square float32 ``arr`` and a
    vector ``w``, reading only the tiles of ``arr`` on and above the block
    diagonal.  For a symmetric ``arr`` that is ``arr @ w``.

    ``interpret`` runs the Pallas interpreter (CPU test suite); ``block`` is
    the tile edge, a multiple of 128 (:data:`BLOCK` unless given: tests use
    small ones)."""
    n = arr.shape[0]
    block = block or BLOCK
    nb = pl.cdiv(n, block)
    ii, jj = _tiles(nb)
    w3 = jnp.pad(w.astype(jnp.float32), (0, nb * block - n)).reshape(nb, 1, block)
    whole = lambda t, ii, jj: (0, 0, 0)
    # x64 off for index arithmetic — see flash_attention
    with jax.enable_x64(False):
        y3 = pl.pallas_call(
            functools.partial(_kernel, n=n, block=block),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2,
                grid=(len(ii),),
                in_specs=[
                    pl.BlockSpec((block, block), lambda t, ii, jj: (ii[t], jj[t])),
                    pl.BlockSpec((nb, 1, block), whole),
                ],
                out_specs=pl.BlockSpec((nb, 1, block), whole),
                scratch_shapes=[
                    pltpu.VMEM((block, _LANES), jnp.float32),  # w[I], lane-broadcast
                    pltpu.VMEM((block, _LANES), jnp.float32),  # row product, lane partials
                    pltpu.VMEM((nb, _SUBLANES, block), jnp.float32),  # column product
                ],
            ),
            out_shape=jax.ShapeDtypeStruct((nb, 1, block), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT,
            ),
            interpret=interpret,
            name="symv",
        )(jnp.asarray(ii), jnp.asarray(jj), arr, w3)
    return y3.reshape(nb * block)[:n]
