"""Distributed QR decomposition.

Reference: heat/core/linalg/qr.py:10-988 — a tiled CAQR over
``SquareDiagTiles`` with per-tile Householder factorizations, pairwise tile
row merges, async Q-factor shipping, and a column-cyclic split=1 loop.

TPU-first design (per SURVEY.md §7 build plan, item 8):

* **split=0 (row-sharded) over a 1-D mesh of several devices, m ≥ n:** an
  operand :func:`rows_route` admits (float32, every shard at least n rows
  and :data:`MIN_BYTES`, a process on a TPU) takes the **row-sharded
  CholeskyQR2** (``cholqr2_rows``): the one-device program below run on
  each shard inside ``shard_map``, each of its two Grams summed over the
  chips by one all-reduce of its (n, n) (:func:`_summed`); the Choleskys,
  R⁻¹, the soundness check and R's SVD run replicated, and Q, where asked
  for, is ``A_local·R⁻¹`` made shard by shard, so nothing of A's size is
  made beside A and Q on any chip.  Its fallback, in the same program, is
  the blocked TSQR of each shard with the shards' R factors all-gathered
  and factored once more (``rows_tsqr``, :func:`_across`).  Every other
  row-sharded operand takes **TSQR** (communication-avoiding
  tall-skinny QR).  Each shard computes a local QR; the stacked R factors
  are QR'd again; one all-gather replaces the reference's point-to-point
  tile choreography.  Non-divisible row counts go through the canonical
  zero-padding (``comm.pad_to_shards``), on both routes: zero rows leave
  the Grams and R untouched and —
  because the stage-2 Q's rows matching zero R-stack rows vanish — drop
  out of Q exactly, so ragged TSQR is exact for full-column-rank inputs
  (the same caveat any QR has for deficient ones).
* **split=1 (column-sharded), m ≥ n: blocked CGS2** — a panel loop in the
  spirit of the reference's column-cyclic ``__split1_qr_loop``
  (qr.py:817-988): each panel is orthogonalized against the accumulated Q
  by two classical Gram-Schmidt projections (MXU matmuls; provably stable
  for κ(A) ≲ 1/√ε) and factored locally.  ``tiles_per_proc`` subdivides
  each mesh position's panel, matching the reference's latency/parallelism
  knob (qr.py:31-36).
* **held whole on a device (one device, or replicated), m ≥ n: one cached
  program** (``jitted:linalg.qr``; reference split=None, qr.py:70-94) on
  the route :func:`tall_route` names from the shape, the dtype and the
  backend: a float32 operand of :data:`MIN_BYTES` or more on a TPU takes
  **blocked CholeskyQR2** — two Gram passes over blocks of rows (the second
  over the implicit ``Q1 = A·R1⁻¹``, formed a block at a time and never
  stored), two (n, n) Cholesky factorizations, ``R = R2·R1``; Q, where asked
  for, is ``A·R⁻¹`` written once in a third pass.  The Grams are made on
  and below their diagonal tiles of :data:`MXU_COLS` columns and mirrored,
  and ``A·R⁻¹`` without the zero tiles under R⁻¹'s first diagonal block, so
  the MXU skips tiles the symmetry mirrors or the triangle makes zero.
  Nothing of A's size is made beside A and Q, which is what lets a chip
  factor an operand that fills half of it.  Q made so is orthonormal to about u·κ(A), so the
  program checks its factor: where R is not finite (the Gram's Cholesky
  broke down: κ(A)² near 1/u, a rank-deficient A) or κ(R) passes
  :data:`KAPPA_MAX`, the same program factors by the blocked TSQR instead
  (Householder QRs of blocks of rows, the blocks' Q written into the
  output's buffer), orthonormal to rounding.  Every other operand takes
  XLA's Householder QR of the whole operand (``householder``), which needs
  a working copy of A.  The launch span states ``route``, ``a_passes``
  (reads of A), ``precision`` (of the tall products) and, on ``cholqr2``
  and ``cholqr2_rows``, ``fallback`` and ``col_blocks``; on
  ``cholqr2_rows`` also ``shards`` and ``collective_bytes``.
* wide (m < n) inputs use on-device ``jnp.linalg.qr``.

The one remaining distributed fallback — split=0 with more than
``m / n`` devices, where shards are wider than tall and TSQR's local QR
does not reduce — gathers with a ``UserWarning`` (the R stack would be as
large as the matrix itself, so gathering is also the bandwidth-optimal
choice there).
"""

from __future__ import annotations

import collections
import contextlib
import threading
import warnings
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from .. import factories, types
from .._compile import jitted
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from .._split_semantics import split_semantics as _split_semantics
from ...telemetry import _core as _tel

__all__ = ["qr"]

QR = collections.namedtuple("QR", "Q, R")

# compiled replicated-golden twins, keyed on (mesh, shape, dtype, tiles,
# arm) — a plain dict, NOT the production jit cache: twin runs must not
# record dispatches (tests gate the kernel's count at exactly one)
_REFERENCE_CACHE: dict = {}


def _tsqr_program(comm):
    """The two-stage TSQR pipeline as a traceable ``f(x) -> (q, r)`` over
    a shard-padded row-split operand: per-shard local QR inside shard_map,
    a second QR of the small (size·n, n) R stack, and the Q-correction
    matmul.  :func:`_tsqr` wraps it in the keyed-jit cache.  A mesh of
    one device never comes here: :func:`qr` factors an operand held whole
    on a device by :func:`_one_device_qr`."""
    mesh = comm.mesh
    axis = comm.axis_name

    from .basics import _precision

    def _local_qr(block):
        q, r = jnp.linalg.qr(block)
        return q, r  # plain tuple: QRResult confuses shard_map out_specs

    local_qr = shard_map(
        _local_qr,
        mesh=mesh,
        in_specs=PartitionSpec(axis, None),
        out_specs=(PartitionSpec(axis, None), PartitionSpec(axis, None)),
    )

    def _combine(q1_blk, q2_blk):
        return jnp.matmul(q1_blk, q2_blk, precision=_precision())

    combine = shard_map(
        _combine,
        mesh=mesh,
        in_specs=(PartitionSpec(axis, None), PartitionSpec(axis, None)),
        out_specs=PartitionSpec(axis, None),
    )

    def _f(x):
        q1, r1 = local_qr(x)  # q1: (padded_m, n) row-split; r1: (size*n, n)
        # stage 2 on the R stack (size*n × n — small, replicated)
        r1_full = jax.lax.with_sharding_constraint(r1, comm.sharding(2, None))
        q2, r = jnp.linalg.qr(r1_full)  # q2: (size*n, n)
        q = combine(q1, q2)
        return q, r

    return _f


#: bytes from which a tall float32 operand held whole by a TPU takes the
#: blocked CholeskyQR2 route (:func:`tall_route`): from the smallest size timed
#: on a v5e, 4 MB, it was the faster at 64 and at 300 columns, 1.44 against
#: 2.12 ms at 16 384 x 64 and 6.7 against 6.9 ms at 4 096 x 300, 14 against
#: 180 ms at 262 144 x 300 (``scripts/time_tall_svd.py``; PERF.md, section 6).
#: Nothing smaller was timed, so the whole operand's Householder QR keeps it
MIN_BYTES = 4 << 20
#: rows of A a step of the blocked passes reads (78.6 MB at 300 columns)
BLOCK_ROWS = 1 << 16
#: MXU precision of the CholeskyQR2 route's tall products (the Grams, the
#: implicit Q1 = A·R1⁻¹, and U = A·W or Q = A·R⁻¹), fixed here and not taken
#: from ``basics.set_matmul_precision``: on the benchmark's 6 291 456 x 300
#: operand (κ about 70) three passes leave U orthonormal only to 1.3e-4 and one
#: pass to 2.5e-2, where six read 5e-6 (PERF.md, section 2)
TALL_PRECISION = "highest"
#: columns of a tile of the MXU: the tall products of the CholeskyQR2 route are
#: planned on tiles of this many columns (:func:`_tiles`) and skip tiles
#: that the Grams' symmetry mirrors or R⁻¹'s triangle makes zero
MXU_COLS = 128
#: the largest κ(R) = S[0] / S[-1] for which the CholeskyQR2 route forms Q (or
#: U) from A in one product.  ``A·R⁻¹`` is orthonormal only to about u·κ(A)
#: (u = 2⁻²⁴), so this keeps the loss under 6e-5, the order of what the blocked
#: TSQR itself reads at the benchmark's size on a v5e, 1.2e-5 to 3.0e-5
#: (PERF.md, section 6); an operand past it, or one whose Cholesky broke down,
#: takes the blocked TSQR
KAPPA_MAX = 1e3
#: ``.axis``: the mesh axis over which the Grams of the program this thread is
#: tracing are summed, set by :func:`_grams_over` (none: a program of the whole
#: operand).  It reaches :func:`_cholqr2` and :func:`_second_pass` without a
#: parameter, so that the seams a planted fault replaces keep their signatures
#: on both routes (``perf/tools/limits_probe_svd.py``)
_GRAMS = threading.local()


@contextlib.contextmanager
def _grams_over(axis):
    """The Grams traced inside the block summed over the mesh axis ``axis``
    (None: left as they are)."""
    was = getattr(_GRAMS, "axis", None)
    _GRAMS.axis = axis
    try:
        yield
    finally:
        _GRAMS.axis = was


def _summed(g):
    """A Gram of the rows this program holds made the whole operand's: one
    all-reduce of the (n, n) over the axis :func:`_grams_over` set, inside
    the row-sharded program; the Gram itself in a program of the whole
    operand."""
    axis = getattr(_GRAMS, "axis", None)
    return g if axis is None else jax.lax.psum(g, axis)


def whole_on_each_device(a: DNDarray) -> bool:
    """True where every device of ``a``'s mesh holds all of ``a``: one device,
    or a replicated operand.  A program over the raw array is then the whole
    factorization, with no collective."""
    return a.comm.size == 1 or a.split is None


def tall_route(shape, dtype) -> str:
    """The factorization a tall operand held whole on a device takes:
    ``cholqr2`` for a float32 matrix of at least :data:`MIN_BYTES` in a
    process on a TPU, ``householder`` (XLA's QR of the whole operand, which
    needs an (m, n) working copy beside its outputs) for anything else.

    The blocked route holds nothing of A's size beside A and its output, so it
    is what fits a chip at a size that fills it; it also reads A only two or
    three times where the Householder QR of XLA sweeps its copy once a column,
    which is why it is the faster on the chip from :data:`MIN_BYTES`.  It is
    sound at any κ(A): its program checks its own factor and takes the blocked
    TSQR where CholeskyQR2 is not (:func:`_sound`).  Off the TPU nothing was
    timed, and the Householder form stays."""
    m, n = shape
    if (
        m >= n
        and jnp.dtype(dtype) == jnp.float32
        and m * n * 4 >= MIN_BYTES
        and jax.default_backend() == "tpu"
    ):
        return "cholqr2"
    return "householder"


def rows_route(shape, dtype, split, comm) -> bool:
    """True where a tall operand split by rows over a 1-D mesh of several
    devices takes the row-sharded CholeskyQR2 (``cholqr2_rows``): float32,
    m >= n, every shard at least n rows and :data:`MIN_BYTES`, a process on
    a TPU.  It is :func:`tall_route`'s ``cholqr2`` run on each shard, the
    Grams summed over the chips, so a shard must be an operand that route
    takes; a shard of fewer rows than columns has no n x n R of its own for
    the fallback to stack.  Anything else keeps the TSQR."""
    m, n = shape
    rows = comm.shard_width(m)
    return (
        split == 0
        and comm.mesh_ndim == 1
        and comm.size > 1
        and m >= n
        and jnp.dtype(dtype) == jnp.float32
        and rows >= n
        and rows * n * 4 >= MIN_BYTES
        and jax.default_backend() == "tpu"
    )


def _tall_dot(a, b, precision):
    """One tall product of the CholeskyQR2 route (the seam its planted faults
    replace, ``perf/tools/limits_probe_svd.py``)."""
    return jnp.matmul(a, b, precision=precision)


def _tiles(n):
    """How many tiles of :data:`MXU_COLS` columns (the last ragged) the
    structured tall products plan n columns on; one, the dense products,
    where ``n <= MXU_COLS``."""
    return -(-n // MXU_COLS)


def _lower_gram(q, precision):
    """``qᵀq`` on and below its diagonal tiles, in two products: the columns
    before the last tile against all of q, the last tile against itself.  The
    MXU skips the tiles above the last tile's diagonal block, which the
    symmetry mirrors.  A product whose kernel is one tile wide runs the MXU at
    about half its rate (XLA's cost model of the v5e), so the columns before
    the last tile stay one product.  One tile: the dense ``qᵀq``."""
    tiles = _tiles(q.shape[1])
    if tiles == 1:
        return _tall_dot(q.T, q, precision)
    s = MXU_COLS * (tiles - 1)
    last = _tall_dot(q[:, s:].T, q[:, s:], precision)
    return jnp.concatenate([_tall_dot(q.T, q[:, :s], precision), jnp.pad(last, ((s, 0), (0, 0)))], axis=1)


def _upper_parts(rows, rinv, precision):
    """``rows·triu(rinv)`` as ``[(first column, product)]``, the products side
    by side: the first tile's columns from the first tile of ``rows`` alone,
    the other columns from all of it, so the MXU skips the zero tiles under
    the first tile's diagonal block.  One tile: the dense product."""
    rinv = jnp.triu(rinv)
    if _tiles(rinv.shape[1]) == 1:
        return [(0, _tall_dot(rows, rinv, precision))]
    w = MXU_COLS
    return [(0, _tall_dot(rows[:, :w], rinv[:w, :w], precision)), (w, _tall_dot(rows, rinv[:, w:], precision))]


def _upper_product(a, rinv, precision):
    """``A·triu(rinv)`` of the whole operand by :func:`_upper_parts`, a block
    of :data:`BLOCK_ROWS` rows at a time, each product written into the
    output's own buffer: products of whole columns would each make a
    temporary of A's height.  One tile: the dense product of A."""
    m, n = a.shape
    if _tiles(n) == 1:
        return _tall_dot(a, jnp.triu(rinv), precision)
    block = min(BLOCK_ROWS, m)
    full, tail = divmod(m, block)

    def put(out, start, rows):
        for col, part in _upper_parts(rows, rinv, precision):
            out = jax.lax.dynamic_update_slice(out, part, (start, col))
        return out

    def step(i, out):
        return put(out, i * block, jax.lax.dynamic_slice_in_dim(a, i * block, block, 0))

    out = jax.lax.fori_loop(0, full, step, jnp.zeros((m, n), a.dtype))
    return put(out, full * block, a[full * block :]) if tail else out


def _gram(a, rinv, precision):
    """``Σ_b (A_b·rinv)ᵀ(A_b·rinv)`` over blocks of :data:`BLOCK_ROWS` rows,
    ``A_bᵀA_b`` where ``rinv`` is None: ONE read of A, nothing of its size
    made (the block's ``A_b·rinv`` is the implicit Q1's rows, never stored).
    ``rinv`` is upper triangular; both products skip MXU tiles that the
    triangles make zero or mirrored (:func:`_upper_parts`, :func:`_lower_gram`),
    and the lower triangle is mirrored once at the end: the Gram is exactly
    symmetric."""
    m, n = a.shape
    block = min(BLOCK_ROWS, m)
    full, tail = divmod(m, block)

    def part(rows):
        q = rows if rinv is None else jnp.concatenate([p for _, p in _upper_parts(rows, rinv, precision)], axis=1)
        return _lower_gram(q, precision)

    def step(i, g):
        return g + part(jax.lax.dynamic_slice_in_dim(a, i * block, block, 0))

    g = jax.lax.fori_loop(0, full, step, jnp.zeros((n, n), a.dtype))
    if tail:
        g = g + part(a[full * block :])
    return jnp.tril(g) + jnp.tril(g, -1).T


def _cholesky_r(g):
    """The upper factor R of a Gram ``g = RᵀR``."""
    return jnp.linalg.cholesky(g).T


def _upper_inverse(r):
    return jax.scipy.linalg.solve_triangular(r, jnp.eye(r.shape[0], dtype=r.dtype), lower=False)


def _second_pass(a, r1, r1inv, precision):
    """CholeskyQR's second pass over ``Q1 = A·R1⁻¹``: ``(R, R⁻¹)`` of
    ``A = Q·R`` with ``R = R2·R1``.  The first pass's Q1 is orthogonal only to
    ``u·κ(A)²``; its own Gram has κ near 1, so R is the factor of a Q
    orthogonal to rounding.  That Q is never formed: ``A·R⁻¹``, made in one
    product, is orthonormal to about ``u·κ(A)`` (:data:`KAPPA_MAX`)."""
    r2 = _cholesky_r(_summed(_gram(a, r1inv, precision)))
    r = jnp.matmul(r2, r1, precision="highest")
    rinv = jnp.matmul(r1inv, _upper_inverse(r2), precision="highest")
    return r, rinv


def _cholqr2(a, precision):
    """``(R, R⁻¹)`` of the tall ``a`` by CholeskyQR2 (Fukaya et al., 2014):
    two blocked Gram passes over A, two (n, n) Cholesky factorizations.  Q is
    not built: a caller that needs it forms ``A·R⁻¹`` in one more pass.  A
    Gram whose Cholesky breaks down (κ(A)² near 1/u, a rank-deficient A)
    leaves NaN in both.  Inside the row-sharded program ``a`` is a shard and
    each Gram is summed over the chips (:func:`_summed`)."""
    r1 = _cholesky_r(_summed(_gram(a, None, precision)))
    return _second_pass(a, r1, _upper_inverse(r1), precision)


def _r_svd(r):
    """``(U_R, S, V)`` of the (n, n) factor, on the device."""
    ur, s, vt = jnp.linalg.svd(r, full_matrices=False)
    return ur, s, vt.T


def _sound(r, rinv, compute_uv: bool):
    """``(sound, R, R⁻¹, (U_R, S, V))``: whether the CholeskyQR2 factor may
    form Q or U from A — finite, and κ(R) at most :data:`KAPPA_MAX` — and
    R's SVD (S alone, the others None, where not ``compute_uv``).  A factor
    that is not finite is replaced by the identity first, so that the small
    SVD runs on numbers."""
    finite = jnp.isfinite(r).all() & jnp.isfinite(rinv).all()
    eye = jnp.eye(r.shape[0], dtype=r.dtype)
    r, rinv = jnp.where(finite, r, eye), jnp.where(finite, rinv, eye)
    small = _r_svd(r) if compute_uv else (None, jnp.linalg.svd(r, compute_uv=False), None)
    s = small[1]
    return finite & (s[-1] * KAPPA_MAX >= s[0]), r, rinv, small


def _tsqr_blocks(m, n):
    """``(rows a block, full blocks, tail rows)`` of the blocked TSQR: blocks
    of :data:`BLOCK_ROWS`, never fewer rows than columns."""
    block = min(m, max(BLOCK_ROWS, n))
    return (block,) + divmod(m, block)


def _blocked_tsqr(a, keep_q: bool):
    """``(R, Q_s, buffer)`` of the tall ``a`` by TSQR over blocks of rows, ONE
    read of A: each block's Householder QR ``A_b = Q_b·R_b``, then the QR of
    the stacked ``R_b``, ``Q_s·R``.  Where ``keep_q``, the blocks' ``Q_b`` are
    written into an (m, n) buffer, the caller's output (a tail of fewer rows
    than columns fills its first columns), and :func:`_tsqr_apply` makes Q or
    U in it; else the buffer is None.  R's diagonal is made non-negative, as
    CholeskyQR's is."""
    m, n = a.shape
    block, full, tail = _tsqr_blocks(m, n)
    k = min(tail, n)
    stack = jnp.zeros((full * n + k, n), a.dtype)
    buf = jnp.zeros((m, n), a.dtype) if keep_q else None

    def factor(rows):
        return jnp.linalg.qr(rows) if keep_q else (None, jnp.linalg.qr(rows, mode="r"))

    def step(i, carry):
        stack, buf = carry
        q, r = factor(jax.lax.dynamic_slice_in_dim(a, i * block, block, 0))
        if keep_q:
            buf = jax.lax.dynamic_update_slice_in_dim(buf, q, i * block, 0)
        return jax.lax.dynamic_update_slice_in_dim(stack, r, i * n, 0), buf

    stack, buf = jax.lax.fori_loop(0, full, step, (stack, buf))
    if tail:
        q, r = factor(a[full * block :])
        stack = stack.at[full * n :].set(r)
        if keep_q:
            buf = buf.at[full * block :, :k].set(q)
    return (*_positive_qr(stack, keep_q), buf)


def _positive_qr(stack, keep_q: bool):
    """``(R, Q)`` of a stack of R factors, R's diagonal made non-negative (Q
    None where not ``keep_q``)."""
    qs, r = jnp.linalg.qr(stack) if keep_q else (None, jnp.linalg.qr(stack, mode="r"))
    sign = jnp.where(jnp.diagonal(r) < 0, -1.0, 1.0).astype(r.dtype)
    return sign[:, None] * r, (None if qs is None else qs * sign)


def _across(r, qs, axis):
    """The blocked TSQR of a shard made the whole operand's: the shards' R
    all-gathered over ``axis`` (one (n, n) a chip) and factored once more,
    replicated, and ``Q_s`` times this shard's block of that Q.  ``(r, qs)``
    as they are where ``axis`` is None."""
    if axis is None:
        return r, qs
    n = r.shape[0]
    r, q = _positive_qr(jax.lax.all_gather(r, axis, tiled=True), qs is not None)
    if qs is None:
        return r, None
    mine = jax.lax.dynamic_slice_in_dim(q, jax.lax.axis_index(axis) * n, n, 0)
    return r, jnp.matmul(qs, mine, precision="highest")


def _tsqr_apply(buf, qs, right, precision):
    """Q (``right`` None) or ``Q·right`` in place of the blocks' ``Q_b`` in
    ``buf``: ``Q_b·(Q_s,b·right)`` a block, ONE read and one write of the
    buffer."""
    m, n = buf.shape
    block, full, tail = _tsqr_blocks(m, n)
    w = qs if right is None else jnp.matmul(qs, right, precision="highest")

    def step(i, buf):
        rows = jax.lax.dynamic_slice_in_dim(buf, i * block, block, 0)
        wi = jax.lax.dynamic_slice_in_dim(w, i * n, n, 0)
        return jax.lax.dynamic_update_slice_in_dim(buf, _tall_dot(rows, wi, precision), i * block, 0)

    buf = jax.lax.fori_loop(0, full, step, buf)
    if tail:
        buf = buf.at[full * block :].set(_tall_dot(buf[full * block :, : min(tail, n)], w[full * n :], precision))
    return buf


def _cholqr2_qr(x, calc_q: bool, axis=None):
    """``(Q or None, R)`` of the program on the ``cholqr2`` route: Q = A·R⁻¹
    where :func:`_sound`, else the blocked TSQR.  With ``axis``, of the
    shard ``x`` on ``cholqr2_rows``: the Grams summed over it, R
    replicated, the fallback's R factored across the shards (:func:`_across`)."""

    def direct():
        return (_upper_product(x, rinv, TALL_PRECISION) if calc_q else None), r

    def fallback():
        r2, qs, buf = _blocked_tsqr(x, calc_q)
        r2, qs = _across(r2, qs, axis)
        return (_tsqr_apply(buf, qs, None, TALL_PRECISION) if calc_q else None), r2

    with _grams_over(axis):
        sound, r, rinv, _ = _sound(*_cholqr2(x, TALL_PRECISION), False)
    return jax.lax.cond(sound, direct, fallback)


def _cholqr2_svd(x, compute_uv: bool, axis=None):
    """``(U, S, V)`` (or S) of the program on the ``cholqr2`` route: R's SVD
    ``R = U_R·S·Vᵀ`` on the device and ``U = A·W``, ``W = R⁻¹·U_R``, in ONE
    pass over A, where :func:`_sound`; else the blocked TSQR and
    ``U = Q·U_R`` in its buffer.  With ``axis``, of the shard ``x`` on
    ``cholqr2_rows``, as :func:`_cholqr2_qr`: U keeps the shard's rows."""
    with _grams_over(axis):
        sound, r, rinv, (ur, s, v) = _sound(*_cholqr2(x, TALL_PRECISION), compute_uv)
    if not compute_uv:
        return jax.lax.cond(
            sound, lambda: s, lambda: jnp.linalg.svd(_across(_blocked_tsqr(x, False)[0], None, axis)[0], compute_uv=False)
        )

    def direct():
        return _tall_dot(x, jnp.matmul(rinv, ur, precision="highest"), TALL_PRECISION), s, v

    def fallback():
        r2, qs, buf = _blocked_tsqr(x, True)
        r2, qs = _across(r2, qs, axis)
        ur2, s2, v2 = _r_svd(r2)
        return _tsqr_apply(buf, qs, ur2, TALL_PRECISION), s2, v2

    return jax.lax.cond(sound, direct, fallback)


#: reads of A each route's program makes, by what it returns (``q``: Q or U
#: is formed).  The Householder form copies A once into its working buffer
#: and sweeps that copy, never A again.  ``cholqr2`` (and ``cholqr2_rows``,
#: each chip its shard) counts its sound branch: the blocked TSQR an operand
#: that is not :func:`_sound` takes reads A once more, and its (m, n) buffer
#: once where Q or U is formed
A_PASSES = {
    ("cholqr2", False): 2,
    ("cholqr2", True): 3,
    ("cholqr2_rows", False): 2,
    ("cholqr2_rows", True): 3,
    ("householder", False): 1,
    ("householder", True): 1,
}
#: the branch an operand that is not :func:`_sound` takes, by route
FALLBACK = {"cholqr2": "blocked_tsqr", "cholqr2_rows": "rows_tsqr"}


def _precision_name(route: str) -> str:
    """The precision of a route's tall products, as its launch span states it."""
    if route in FALLBACK:
        return TALL_PRECISION
    from .basics import get_matmul_precision

    return get_matmul_precision()


def route_fields(route: str, formed: bool, n: int, shards: int = 1) -> dict:
    """The launch span's fields of a tall QR or SVD program of n columns:
    ``route``, ``a_passes`` and ``precision``; on ``cholqr2`` and
    ``cholqr2_rows`` also ``fallback``, the branch an operand that is not
    :func:`_sound` takes, and ``col_blocks``, the tiles of :data:`MXU_COLS`
    columns its structured tall products are planned on (1: the dense
    products); on ``cholqr2_rows`` also ``shards``, the devices the rows are
    split over, and ``collective_bytes``, what a chip hands its collectives on
    the sound branch: the two (n, n) float32 Grams it sums (the fallback
    all-gathers one (n, n) R more)."""
    fields = {"route": route, "a_passes": A_PASSES[route, formed], "precision": _precision_name(route)}
    if route in FALLBACK:
        fields["fallback"] = FALLBACK[route]
        fields["col_blocks"] = _tiles(n)
    if route == "cholqr2_rows":
        fields["shards"] = int(shards)
        fields["collective_bytes"] = 2 * n * n * 4
    return fields


def _program(route: str, comm, body, row_outputs):
    """``body(x, axis)`` as the traceable program of ``route``: of the whole
    operand (``axis`` None), or on ``cholqr2_rows`` of each row shard inside
    ``shard_map`` over the comm's axis, the outputs ``row_outputs`` marks
    True split by rows as A is, the others replicated."""
    if route != "cholqr2_rows":
        return lambda x: body(x, None)
    rows = comm.spec(2, 0)
    return shard_map(
        lambda x: body(x, comm.axis_name),
        mesh=comm.mesh,
        in_specs=rows,
        out_specs=jax.tree.map(lambda t: rows if t else PartitionSpec(), row_outputs),
        check_vma=False,
    )


def run_tall(program, arr, comm, route: str):
    """Run a tall factorization's cached ``program`` (:func:`_program`,
    under the fields of :func:`route_fields`) on ``arr``.  On
    ``cholqr2_rows`` the rows are first zero-padded to a whole number a shard
    (``comm.pad_to_shards``); the caller cuts what it returns of A's height
    back.  A program on the CholeskyQR2 routes holds an SVD of R, so it is
    lowered with x64 off, as ``svd``'s is."""
    if route not in FALLBACK:
        return program(arr)
    if route == "cholqr2_rows":
        arr = comm.pad_to_shards(arr, axis=0)
    with jax.enable_x64(False):
        return program(arr)


def _tall_qr(arr, comm, calc_q: bool, route: str):
    """``(Q or None, R)`` of a tall operand on ``route`` (``tall_route``'s, for
    one held whole on each device, or ``cholqr2_rows``), one cached program
    launched as ``jitted:linalg.qr``.  On the CholeskyQR2 routes Q is written
    once, as ``A·R⁻¹``, split as A is; ``calc_q=False`` makes the two Gram
    passes alone."""
    m, n = map(int, arr.shape)
    calc_q = bool(calc_q)

    def body(x, axis):
        if route in FALLBACK:
            return _cholqr2_qr(x, calc_q, axis)
        return tuple(jnp.linalg.qr(x)) if calc_q else (None, jnp.linalg.qr(x, mode="r"))

    fields = route_fields(route, calc_q, n, comm.size)
    key = ("linalg.qr", comm, (m, n), str(arr.dtype), route, calc_q, fields["precision"])
    program = jitted(key, lambda: _program(route, comm, body, (calc_q or None, False)), fields=fields)
    q, r = run_tall(program, arr, comm, route)
    return (None if q is None else comm.unpad(q, m, 0)), r


def _tsqr(a: DNDarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Two-stage TSQR on the mesh (replaces reference qr.py:303-816).

    Stage 1: per-shard local QR inside shard_map (runs on every device in
    parallel).  Stage 2: the (size·n, n) stack of R factors — tiny — is
    QR'd again, and local Qs are corrected by the matching R-block.
    Handles any row count via canonical zero-padding.
    """
    comm = a.comm
    m, n = a.shape
    size = comm.size
    arr = a.larray

    if comm.shard_width(m) < n:
        # shards wider than tall: local QR would not reduce and the R
        # stack would match the full matrix — gather and factor once
        warnings.warn(
            f"qr: {m}x{n} split=0 over {size} devices leaves shards with "
            f"fewer rows ({comm.shard_width(m)}) than columns ({n}); "
            "gathering for a single on-device QR (use fewer devices or a "
            "taller matrix for distributed TSQR)",
            stacklevel=3,
        )
        return jnp.linalg.qr(arr)

    arr_p = comm.pad_to_shards(arr, axis=0)
    q, r = jitted(("qr.tsqr", comm), lambda: _tsqr_program(comm))(arr_p)
    return comm.unpad(q, m, 0), r


def _cgs2_split1(a: DNDarray, tiles_per_proc: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Blocked classical Gram-Schmidt with reorthogonalization over column
    panels (the TPU formulation of the reference's column-cyclic split=1
    loop, qr.py:817-988).

    Panels follow the mesh layout (one per position, subdivided by
    ``tiles_per_proc``), so each projection is a large MXU matmul whose
    collectives GSPMD schedules over ICI; no panel is ever gathered.
    """
    comm = a.comm
    m, n = a.shape
    arr = a.larray

    # panel plan: each position's column block, split into tiles_per_proc
    c = comm.shard_width(n)
    bounds = []
    for r in range(comm.size):
        start, stop = r * c, min((r + 1) * c, n)
        if start >= stop:
            continue
        width = stop - start
        t = max(1, min(int(tiles_per_proc), width))
        tw = -(-width // t)
        for j in range(t):
            s2 = start + j * tw
            e2 = min(s2 + tw, stop)
            if s2 < e2:
                bounds.append((s2, e2))

    def make():
        from .basics import _precision

        def _f(x):
            q_panels = []
            rows = []
            q_acc = None  # (m, k) accumulated orthonormal columns
            for (s, e) in bounds:
                panel = x[:, s:e]
                if q_acc is None:
                    y = jnp.zeros((0, e - s), x.dtype)
                else:
                    # CGS2: project out the accumulated basis twice
                    y1 = jnp.matmul(q_acc.T, panel, precision=_precision())
                    panel = panel - jnp.matmul(q_acc, y1, precision=_precision())
                    y2 = jnp.matmul(q_acc.T, panel, precision=_precision())
                    panel = panel - jnp.matmul(q_acc, y2, precision=_precision())
                    y = y1 + y2
                qk, rkk = jnp.linalg.qr(panel)
                q_panels.append(qk)
                # R rows for this panel: [Y; Rkk; 0] padded to n rows later
                rows.append((s, e, y, rkk))
                q_acc = qk if q_acc is None else jnp.concatenate([q_acc, qk], axis=1)
                q_acc = jax.lax.with_sharding_constraint(
                    q_acc, comm.sharding(2, 1 if q_acc.shape[1] % comm.size == 0 else None)
                )
            q = jnp.concatenate(q_panels, axis=1)
            r_full = jnp.zeros((n, n), x.dtype)
            for (s, e, y, rkk) in rows:
                if y.shape[0]:
                    r_full = r_full.at[: y.shape[0], s:e].set(y)
                r_full = r_full.at[s:e, s:e].set(rkk)
            return q, r_full

        return _f

    key = ("qr.cgs2", comm, tuple(bounds), (m, n), str(arr.dtype))
    return jitted(key, make)(arr)


def _mm(a, b):
    """Matmul pinned behind an optimization barrier — the grid QR/SVD
    twin discipline's determinism primitive.  XLA CPU decides a dot's
    emission (library GEMM vs inlined fusion loop, with different
    accumulation orders) from its fusion CONTEXT, so the same matmul can
    produce different bits inside the shard_map kernel and the
    replicated golden simulation.  Barriers on the operands and the
    result pin every twin-sensitive dot as a standalone op in BOTH
    programs, making the pair bitwise-reproducible (without them the
    ragged-panel shapes in tests/test_linalg2d.py diverge by 1 ulp)."""
    a, b = jax.lax.optimization_barrier((a, b))
    return jax.lax.optimization_barrier(jnp.matmul(a, b))


def _sumsq(x):
    """Sum of squares pinned behind optimization barriers — same
    rationale as :func:`_mm`, for reductions: XLA CPU's reduce emission
    also depends on fusion context, and the QDWH convergence scalars
    (norm scale, delta) feed every subsequent bit of the iteration."""
    t = jax.lax.optimization_barrier(x * x)
    return jax.lax.optimization_barrier(jnp.sum(t))


def _grid_panel_schedule(n: int, c: int, tiles_per_proc: int):
    """Enrich :func:`heat_tpu.comm._costs.grid_panel_bounds` with each
    panel's padded-global column start and the per-mesh-column valid
    counts — the static facts the kernel, the wire model, and the
    replicated golden all iterate in lock-step."""
    from ...comm._costs import grid_panel_bounds

    nloc = -(-n // c)
    bounds = tuple(
        (jc, lo, nb, jc * nloc + lo)
        for (jc, lo, nb) in grid_panel_bounds(n, c, tiles_per_proc)
    )
    vcs = tuple(min(nloc, max(0, n - jc * nloc)) for jc in range(c))
    return nloc, bounds, vcs


def _caqr_shard_body(a_loc, *, ax0, ax1, r, c, nloc, bounds, vcs, overlapped):
    """Per-device body of the grid blocked/CAQR QR — called inside a
    shard_map over the r×c mesh (axes ``ax0``/``ax1`` bound), and reused
    verbatim by the QDWH SVD's inner factorization (svd.py).

    Panel ownership algebra (docs/design.md §23): columns live
    block-distributed along the mesh columns in chunks of ``nloc``;
    ``bounds`` holds ``(owner, local offset, width, global start)`` per
    panel over REAL columns only (pad columns are never factored — a
    factored zero column would produce garbage orthonormal directions
    that corrupt every trailing real column).  Per panel:

    1. masked-psum broadcast of the owner's panel along the mesh columns
       (owner block + zero blocks — any-order exact);
    2. BCGS2 reorthogonalization against the accumulated basis (skipped
       on the first panel): the projection coefficients are reduced down
       the mesh rows and the correction/coefficient bundle combined
       along the columns, both via all-gather + index-ordered local sums
       (a psum's internal reduction order is unspecified and would break
       the bitwise twin);
    3. TSQR down the mesh rows: local QR, all-gather of the small R
       stack, second QR, Q-correction matmul;
    4. trailing update via the W = Qpᵀ·A coefficients (reduced down the
       rows in index order), applied as TWO column-disjoint masked
       subtracts — next panel, then the rest — in BOTH arms, so the
       overlap arm can factor panel ``p+1`` between them (distance-2
       lookahead) while every column still sees the identical op
       sequence, keeping the two arms bitwise-equal.

    Q columns and the panel's R diagonal block are written at factor
    time (the lookahead factor of ``p+1`` must see the basis including
    panel ``p``); R's trailing rows get the W coefficients and R's
    second-projection rows the BCGS2 coefficients via ``.add`` — each R
    entry receives at most two addends from zero, and two-term IEEE
    addition commutes, so the arms' different write orders agree
    bitwise.  Returns ``(q_loc, r_loc)`` with ``r_loc`` of padded shape
    ``(c*nloc, nloc)``, bit-identical down the mesh rows.
    """
    mloc = a_loc.shape[0]
    Np = c * nloc
    dt = a_loc.dtype
    i = jax.lax.axis_index(ax0)
    j = jax.lax.axis_index(ax1)
    ids = jnp.arange(nloc)
    col_gids = j * nloc + ids
    valid = ids < jnp.asarray(vcs)[j]
    row_valid = np.zeros((Np,), dtype=bool)
    for jc in range(c):
        row_valid[jc * nloc : jc * nloc + vcs[jc]] = True
    row_valid = jnp.asarray(row_valid)
    zero = jnp.zeros((), dt)

    def bcast_cols(x, owner):
        return jax.lax.psum(jnp.where(owner == j, x, zero), ax1)

    def rowsum(x):
        g = jax.lax.all_gather(x, ax0)
        acc = g[0]
        for b in range(1, r):
            acc = acc + g[b]
        return acc

    def factor(p, a_cur, q_acc, r_acc):
        jc, lo, nb, gstart = bounds[p]
        pan = bcast_cols(jax.lax.slice_in_dim(a_cur, lo, lo + nb, axis=1), jc)
        if p:
            z_loc = rowsum(_mm(q_acc.T, pan))
            prev = valid & (col_gids < gstart)
            z_loc = jnp.where(prev[:, None], z_loc, zero)
            bundle = jnp.concatenate([_mm(q_acc, z_loc), z_loc], axis=0)
            g = jax.lax.all_gather(bundle, ax1)  # (c, mloc+nloc, nb)
            corr = g[0, :mloc]
            for b in range(1, c):
                corr = corr + g[b, :mloc]
            z_full = jnp.reshape(g[:, mloc:], (Np, nb))
            pan = pan - corr
            zmask = (row_valid & (jnp.arange(Np) < gstart))[:, None]
            r_add = jnp.zeros_like(r_acc).at[:, lo : lo + nb].set(
                jnp.where(zmask, z_full, zero)
            )
            r_acc = r_acc + jnp.where(jc == j, r_add, zero)
        q1, r1 = jnp.linalg.qr(pan)
        st = jax.lax.all_gather(r1, ax0, tiled=True)  # (r*nb, nb)
        q2, rp = jnp.linalg.qr(st)
        qp = _mm(q1, jax.lax.dynamic_slice_in_dim(q2, i * nb, nb, 0))
        q_acc = jnp.where(jc == j, q_acc.at[:, lo : lo + nb].set(qp), q_acc)
        r_blk = jnp.zeros_like(r_acc).at[gstart : gstart + nb, lo : lo + nb].set(rp)
        r_acc = r_acc + jnp.where(jc == j, r_blk, zero)
        return qp, q_acc, r_acc

    def masks(p):
        _jc, _lo, nb, gstart = bounds[p]
        trail = valid & (col_gids >= gstart + nb)
        if p + 1 < len(bounds):
            _, _, nbn, gsn = bounds[p + 1]
            nxt = valid & (col_gids >= gsn) & (col_gids < gsn + nbn)
        else:
            nxt = jnp.zeros_like(trail)
        return trail, nxt, trail & ~nxt

    a_cur = a_loc
    q_acc = jnp.zeros_like(a_loc)
    r_acc = jnp.zeros((Np, nloc), dt)
    P = len(bounds)
    if not overlapped:
        for p in range(P):
            qp, q_acc, r_acc = factor(p, a_cur, q_acc, r_acc)
            _jc, _lo, nb, gstart = bounds[p]
            trail, nxt, rest = masks(p)
            w = rowsum(_mm(qp.T, a_cur))
            a_cur = a_cur - _mm(qp, jnp.where(nxt[None, :], w, zero))
            a_cur = a_cur - _mm(qp, jnp.where(rest[None, :], w, zero))
            r_acc = r_acc.at[gstart : gstart + nb, :].add(
                jnp.where(trail[None, :], w, zero)
            )
    else:
        qp, q_acc, r_acc = factor(0, a_cur, q_acc, r_acc)
        for p in range(P):
            _jc, _lo, nb, gstart = bounds[p]
            trail, nxt, rest = masks(p)
            w = rowsum(_mm(qp.T, a_cur))
            a_cur = a_cur - _mm(qp, jnp.where(nxt[None, :], w, zero))
            if p + 1 < P:
                qn, q_acc, r_acc = factor(p + 1, a_cur, q_acc, r_acc)
            a_cur = a_cur - _mm(qp, jnp.where(rest[None, :], w, zero))
            r_acc = r_acc.at[gstart : gstart + nb, :].add(
                jnp.where(trail[None, :], w, zero)
            )
            if p + 1 < P:
                qp = qn
    return q_acc, r_acc


def _caqr_sim(blocks, *, r, c, nloc, bounds, vcs, overlapped):
    """Lockstep replicated simulation of :func:`_caqr_shard_body` — the
    bitwise golden twin (PR 11 discipline).  ``blocks[(i, j)]`` holds the
    ``(mloc, nloc)`` shard of mesh position ``(i, j)``; every collective
    is replayed op-for-op: the masked psum as an index-ordered sum of
    the owner block plus explicit zero blocks (mirroring psum's ``-0 +
    +0 = +0`` normalization), all-gathers as index-ordered stacks.
    Returns ``(q_blocks, r_blocks)`` matching the kernel bit-for-bit."""
    mloc = blocks[(0, 0)].shape[0]
    Np = c * nloc
    dt = blocks[(0, 0)].dtype
    zero = jnp.zeros((), dt)
    col_gids = {j: j * nloc + jnp.arange(nloc) for j in range(c)}
    valid = {j: jnp.arange(nloc) < jnp.asarray(vcs)[j] for j in range(c)}
    row_valid = np.zeros((Np,), dtype=bool)
    for jc in range(c):
        row_valid[jc * nloc : jc * nloc + vcs[jc]] = True
    row_valid = jnp.asarray(row_valid)

    def bcast_cols(vals_row, owner):
        acc = vals_row[0] if owner == 0 else jnp.where(False, vals_row[0], zero)
        for jp in range(1, c):
            acc = acc + (
                vals_row[jp] if owner == jp else jnp.where(False, vals_row[jp], zero)
            )
        return acc

    def rowsum(vals_col):
        acc = vals_col[0]
        for b in range(1, r):
            acc = acc + vals_col[b]
        return acc

    def factor(p, a_cur, q_acc, r_acc):
        jc, lo, nb, gstart = bounds[p]
        pan = {}
        for i in range(r):
            row = [
                jax.lax.slice_in_dim(a_cur[(i, jp)], lo, lo + nb, axis=1)
                for jp in range(c)
            ]
            p_i = bcast_cols(row, jc)
            for j in range(c):
                pan[(i, j)] = p_i
        qp = {}
        if p:
            z = {}
            for j in range(c):
                for i in range(r):
                    z[(i, j)] = rowsum(
                        [
                            _mm(q_acc[(b, j)].T, pan[(b, j)])
                            for b in range(r)
                        ]
                    )
            for j in range(c):
                prev = valid[j] & (col_gids[j] < gstart)
                for i in range(r):
                    z[(i, j)] = jnp.where(prev[:, None], z[(i, j)], zero)
            for i in range(r):
                bundles = [
                    jnp.concatenate(
                        [_mm(q_acc[(i, jp)], z[(i, jp)]), z[(i, jp)]],
                        axis=0,
                    )
                    for jp in range(c)
                ]
                g = jnp.stack(bundles)  # all_gather along the mesh columns
                corr = g[0, :mloc]
                for b in range(1, c):
                    corr = corr + g[b, :mloc]
                z_full = jnp.reshape(g[:, mloc:], (Np, nb))
                for j in range(c):
                    pan[(i, j)] = pan[(i, j)] - corr
                zmask = (row_valid & (jnp.arange(Np) < gstart))[:, None]
                r_add = jnp.zeros((Np, nloc), dt).at[:, lo : lo + nb].set(
                    jnp.where(zmask, z_full, zero)
                )
                for j in range(c):
                    r_acc[(i, j)] = r_acc[(i, j)] + (
                        r_add if jc == j else jnp.where(False, r_add, zero)
                    )
        for j in range(c):
            q1s, r1s = {}, {}
            for i in range(r):
                q1s[i], r1s[i] = jnp.linalg.qr(pan[(i, j)])
            st = jnp.concatenate([r1s[b] for b in range(r)], axis=0)
            q2, rp = jnp.linalg.qr(st)
            for i in range(r):
                qp[(i, j)] = _mm(
                    q1s[i], jax.lax.dynamic_slice_in_dim(q2, i * nb, nb, 0)
                )
                if jc == j:
                    q_acc[(i, j)] = q_acc[(i, j)].at[:, lo : lo + nb].set(qp[(i, j)])
                r_blk = jnp.zeros((Np, nloc), dt).at[
                    gstart : gstart + nb, lo : lo + nb
                ].set(rp)
                r_acc[(i, j)] = r_acc[(i, j)] + (
                    r_blk if jc == j else jnp.where(False, r_blk, zero)
                )
        return qp

    def masks(p, j):
        _jc, _lo, nb, gstart = bounds[p]
        trail = valid[j] & (col_gids[j] >= gstart + nb)
        if p + 1 < len(bounds):
            _, _, nbn, gsn = bounds[p + 1]
            nxt = valid[j] & (col_gids[j] >= gsn) & (col_gids[j] < gsn + nbn)
        else:
            nxt = jnp.zeros_like(trail)
        return trail, nxt, trail & ~nxt

    def wcoeffs(qp, a_cur):
        w = {}
        for j in range(c):
            for i in range(r):
                w[(i, j)] = rowsum(
                    [_mm(qp[(b, j)].T, a_cur[(b, j)]) for b in range(r)]
                )
        return w

    def update(qp, a_cur, r_acc, p, which):
        for j in range(c):
            trail, nxt, rest = masks(p, j)
            mask = {"next": nxt, "rest": rest}[which]
            for i in range(r):
                a_cur[(i, j)] = a_cur[(i, j)] - _mm(
                    qp[(i, j)], jnp.where(mask[None, :], w[(i, j)], zero)
                )

    a_cur = dict(blocks)
    q_acc = {k: jnp.zeros_like(v) for k, v in blocks.items()}
    r_acc = {k: jnp.zeros((Np, nloc), dt) for k in blocks}
    P = len(bounds)
    if not overlapped:
        for p in range(P):
            qp = factor(p, a_cur, q_acc, r_acc)
            _jc, _lo, nb, gstart = bounds[p]
            w = wcoeffs(qp, a_cur)
            update(qp, a_cur, r_acc, p, "next")
            update(qp, a_cur, r_acc, p, "rest")
            for j in range(c):
                trail = masks(p, j)[0]
                for i in range(r):
                    r_acc[(i, j)] = r_acc[(i, j)].at[gstart : gstart + nb, :].add(
                        jnp.where(trail[None, :], w[(i, j)], zero)
                    )
    else:
        qp = factor(0, a_cur, q_acc, r_acc)
        for p in range(P):
            _jc, _lo, nb, gstart = bounds[p]
            w = wcoeffs(qp, a_cur)
            update(qp, a_cur, r_acc, p, "next")
            if p + 1 < P:
                qn = factor(p + 1, a_cur, q_acc, r_acc)
            update(qp, a_cur, r_acc, p, "rest")
            for j in range(c):
                trail = masks(p, j)[0]
                for i in range(r):
                    r_acc[(i, j)] = r_acc[(i, j)].at[gstart : gstart + nb, :].add(
                        jnp.where(trail[None, :], w[(i, j)], zero)
                    )
            if p + 1 < P:
                qp = qn
    return q_acc, r_acc


def _grid_qr_reference(arr, mesh_shape, *, tiles_per_proc=1, overlapped=False):
    """Replicated golden twin of the grid CAQR: runs the exact panel
    schedule of :func:`_grid_qr_fn` on an unsharded operand via
    :func:`_caqr_sim` and reassembles the padded global ``(q, r)`` —
    bitwise-equal to the kernel's outputs (tests/test_linalg2d.py pins
    this).

    The whole simulation runs as ONE jitted program: eager per-op
    execution changes XLA CPU's fusion context and with it the emission
    of small dots, so an unjitted twin diverges by 1 ulp on ragged
    panels even with :func:`_mm`'s barriers in place."""
    r, c = mesh_shape
    m, n = arr.shape
    mloc = -(-m // r)
    nloc, bounds, vcs = _grid_panel_schedule(n, c, tiles_per_proc)
    Mp, Np = r * mloc, c * nloc

    def run(x):
        x = jnp.pad(x, ((0, Mp - m), (0, Np - n)))
        blocks = {
            (i, j): x[i * mloc : (i + 1) * mloc, j * nloc : (j + 1) * nloc]
            for i in range(r)
            for j in range(c)
        }
        qb, rb = _caqr_sim(
            blocks, r=r, c=c, nloc=nloc, bounds=bounds, vcs=vcs,
            overlapped=overlapped,
        )
        q = jnp.concatenate(
            [
                jnp.concatenate([qb[(i, j)] for j in range(c)], axis=1)
                for i in range(r)
            ],
            axis=0,
        )
        r_full = jnp.concatenate([rb[(0, j)] for j in range(c)], axis=1)
        return q, r_full[:n]

    key = (mesh_shape, (m, n), str(arr.dtype), tiles_per_proc, overlapped)
    fn = _REFERENCE_CACHE.get(key)
    if fn is None:
        fn = _REFERENCE_CACHE[key] = jax.jit(run)
    return fn(arr)


def _grid_qr_fn(comm, bounds, vcs, overlapped, nloc, n, shape, dtype_str):
    """The grid CAQR as ONE cached shard_map program ``f(a_padded) ->
    (q, r)``: Q on the ``(ax0, ax1)`` grid, R column-sharded with true
    row count (replicated down the mesh rows bit-identically)."""
    key = ("qr.grid", comm, bounds, vcs, shape, dtype_str, overlapped)

    def make():
        ax0, ax1 = comm.axis_names
        r, c = comm.mesh_shape

        def kern(a_loc):
            q_loc, r_loc = _caqr_shard_body(
                a_loc,
                ax0=ax0,
                ax1=ax1,
                r=r,
                c=c,
                nloc=nloc,
                bounds=bounds,
                vcs=vcs,
                overlapped=overlapped,
            )
            return q_loc, r_loc[:n]

        return shard_map(
            kern,
            mesh=comm.mesh,
            in_specs=(PartitionSpec(ax0, ax1),),
            out_specs=(PartitionSpec(ax0, ax1), PartitionSpec(None, ax1)),
            check_vma=False,
        )

    return jitted(key, make)


def _grid_qr(a: DNDarray, jt, tiles_per_proc: int):
    """Dispatch wrapper of the grid blocked/CAQR QR (operand splits
    ``(0, 1)``, ``m >= n``): ships the ZEROED buffer (pad rows/columns
    must be exact zeros — pads in a factored panel would corrupt the
    basis), launches the one cached program, credits the telemetry
    ledger with figures straight from
    :func:`heat_tpu.comm._costs.grid_qr_model` (delegation keeps
    accounted and modeled bytes byte-identical), and times the dispatch
    under the overlap policy."""
    from ...comm import _costs
    from ...comm.overlap import overlap_enabled, timed_dispatch

    comm = a.comm
    m, n = a.shape
    r, c = comm.mesh_shape
    mloc = -(-m // r)
    nloc, bounds, vcs = _grid_panel_schedule(n, c, int(tiles_per_proc))
    nb_max = max(b[2] for b in bounds)
    if mloc < nb_max:
        raise ValueError(
            f"qr: grid CAQR needs row shards at least as tall as the widest "
            f"column panel: {m}x{n} over the {r}x{c} mesh leaves "
            f"({mloc}, {nloc}) shards with {mloc} rows < panel width "
            f"{nb_max}; use a taller matrix, a flatter mesh, or raise "
            f"tiles_per_proc"
        )
    arr = a._zeroed_buffer()
    if arr.dtype != jt:
        arr = arr.astype(jt)
    ov = overlap_enabled(len(bounds))
    fn = _grid_qr_fn(
        comm, bounds, vcs, ov, nloc, n, tuple(map(int, arr.shape)), str(arr.dtype)
    )
    if _tel.enabled:
        model = _costs.grid_qr_model(
            m, n, (r, c), tiles_per_proc=int(tiles_per_proc), overlap=ov
        )
        _tel.account_bytes(
            "qr2d", "f32", model["exact_wire_bytes"], model["wire_bytes"]
        )
        with _tel.span(
            "comm:qr2d", "comm", mesh=f"{r}x{c}", panels=len(bounds), overlap=ov
        ):
            return timed_dispatch("qr2d", ov, lambda: fn(arr))
    return timed_dispatch("qr2d", ov, lambda: fn(arr))


@_split_semantics("entry_qr")
def qr(
    a: DNDarray,
    tiles_per_proc: int = 1,
    calc_q: bool = True,
    overwrite_a: bool = False,
) -> QR:
    """Reduced QR factorization ``a = Q @ R`` (reference qr.py:10-302).

    ``tiles_per_proc`` subdivides each mesh position's column panel in the
    split=1 path (the reference's latency/parallelism knob, qr.py:31-36);
    the split=0 TSQR formulation has no tile-count knob and ignores it.
    """
    sanitize_in(a)
    if not isinstance(tiles_per_proc, (int, np.integer)):
        raise TypeError(f"tiles_per_proc must be an int, got {type(tiles_per_proc)}")
    if tiles_per_proc < 1:
        raise ValueError(f"tiles_per_proc must be >= 1, got {tiles_per_proc}")
    if a.ndim != 2:
        raise ValueError(f"qr requires a 2-D DNDarray, got {a.ndim}-d")

    dtype = a.dtype if types.heat_type_is_inexact(a.dtype) else types.float32

    comm = a.comm
    if comm.mesh_ndim == 2 and comm.size > 1 and a.splits == (0, 1):
        # grid blocked/CAQR QR on the r×c mesh (arXiv 2112.09017's dense
        # QR at pod scale): panel TSQR down the mesh columns + trailing
        # update, one cached dispatch, bitwise-pinned overlap arm
        m, n = a.shape
        if m < n:
            r_m, c_m = comm.mesh_shape
            raise ValueError(
                f"qr: wide inputs have no grid formulation: {m}x{n} with "
                f"splits (0, 1) on the {r_m}x{c_m} mesh — factor the "
                f"transpose (resplit its layout to (0, 1)) and transpose "
                f"back, or use svd for the spectral path"
            )
        q_arr, r_arr = _grid_qr(a, dtype.jax_type(), int(tiles_per_proc))
        r_nd = DNDarray(r_arr, (n, n), dtype, (None, 1), a.device, comm, True)
        if not calc_q:
            return QR(None, r_nd)
        q_nd = DNDarray(q_arr, (m, n), dtype, (0, 1), a.device, comm, True)
        return QR(q_nd, r_nd)

    arr = a.larray.astype(dtype.jax_type())
    aa = a if (a.dtype is dtype and arr is a.larray) else DNDarray(
        arr, a.shape, dtype, a.split, a.device, a.comm, True
    )

    if whole_on_each_device(a) and a.shape[0] >= a.shape[1]:
        q_g, r_g = _tall_qr(arr, comm, calc_q, tall_route(a.shape, arr.dtype))
    elif rows_route(a.shape, arr.dtype, a.split, comm):
        q_g, r_g = _tall_qr(arr, comm, calc_q, "cholqr2_rows")
    elif a.split == 0 and a.shape[0] >= a.shape[1]:
        q_g, r_g = _tsqr(aa)
    elif a.split == 1 and a.shape[0] >= a.shape[1] and a.comm.size > 1:
        q_g, r_g = _cgs2_split1(aa, int(tiles_per_proc))
    else:
        # replicated or wide matrices: on-device QR, XLA plans the
        # distribution (reference split=None, qr.py:70-94)
        q_g, r_g = jnp.linalg.qr(arr)

    comm, device = a.comm, a.device
    if not calc_q:
        r_split = a.split if a.split == 1 else None
        r = DNDarray(comm.apply_sharding(r_g, r_split), tuple(r_g.shape), dtype, r_split, device, comm, True)
        return QR(None, r)

    q_split = a.split
    q = DNDarray(comm.apply_sharding(q_g, q_split), tuple(q_g.shape), dtype, q_split, device, comm, True)
    r_split = None if a.split != 1 else 1
    r = DNDarray(comm.apply_sharding(r_g, r_split), tuple(r_g.shape), dtype, r_split, device, comm, True)
    return QR(q, r)
