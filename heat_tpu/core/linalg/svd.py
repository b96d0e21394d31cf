"""Singular value decomposition.

Reference: heat/core/linalg/svd.py:1 — a **stub** (one commented line); SVD
does not exist in HeAT 0.5.1.  Implemented here because the rebuild's
baseline configs exercise it (BASELINE.md target 5: "linalg.qr + SVD on
tall-skinny split DNDarray").

Algorithm: reduce via QR first, then factor the small triangular R **on
device** — the standard communication-avoiding SVD.  Only the (n, n) R ever
reaches the SVD kernel, so the MXU carries the real work (the factor and
U's product) and the decomposition adds zero host syncs.  float64 operands
are the one exception: the TPU has no f64 hardware, so their R factors on
the host through LAPACK (one tiny transfer) and the chain runs eagerly.
Wide matrices factor transposed and swap U/V.

A float32 operand on a TPU takes one cached program,
:func:`_tall_svd` (``jitted:linalg.svd``), on one of two routes: held whole
on a device (one device, or replicated) that ``qr.tall_route`` sends on the
``cholqr2`` route, or split by rows over a 1-D mesh of several devices with
every shard at least n rows and ``qr.MIN_BYTES`` (``qr.rows_route``,
``cholqr2_rows``: the same program on each shard inside ``shard_map``, the
two Grams summed over the chips by one all-reduce of their (n, n) each, the
n x n work replicated, U made shard by shard and split as A is).  R comes
from two blocked Gram passes, its SVD ``R = U_R·S·Vᵀ`` is taken on the
device, and U is ``A·W`` with ``W = R⁻¹·U_R`` (n x n), in ONE more pass over
A: **Q is never built**.  Q and U are each as large as A; at 6 291 456 x 300
float32 A on one chip (or 6 291 456 x 1 200 over four), Q, U and the
Householder QR's working copy of A need about 30 GB a chip, A and U alone
15.3 GB, which one 16 GB chip holds.  Forming U from A also reads A once
where ``Q·U_R`` would read Q once, after Q was written.  U made so is
orthonormal to about u·κ(A); the program takes it only where R is finite
and κ(R) is at most ``qr.KAPPA_MAX``, and else factors by the blocked TSQR
(of each shard, the shards' R factored once more), its blocks' Q written
into U's own buffer (``qr._cholqr2_svd``).  Every other operand takes the
fused chain below: the TSQR of ``qr.py`` where row-split, then ``Q·U_R``.

The on-device chain is traced, lowered and compiled with x64 **off**
(:func:`svd` enters ``jax.enable_x64(False)`` around the fused program):
lowered under the package's x64-on default, ``jnp.linalg.svd`` with
singular vectors aborts the TPU compiler — a CHECK in XLA's
TransposeFolding pass that kills the process, reproduced against libtpu
0.0.34 both on the chip and for a described one (PERF.md, PR 21).  It is
the lowering that matters, not the trace: an inner x64-off context around
the small SVD does not help once an outer program is lowered with x64 on.
The operands are f32 either way, so only internal index dtypes change.
Where :func:`svd` does not own the lowering — called inside a caller's
``ht.fuse`` or ``jax.jit``, or re-lowered by ``aot.export_programs`` — and
x64 is on, :func:`_refuse_x64_lowering` raises on a TPU instead of letting
the compiler kill the process.
"""

from __future__ import annotations

import collections
import sys

import numpy as np
import jax as _jax
import jax.numpy as jnp


@_jax.jit
def _jitted_svd(a):
    # one persistent jit: a fresh lambda per call would recompile the SVD
    # every invocation (~1.2 s each on the TPU)
    return jnp.linalg.svd(a, full_matrices=False)


@_jax.jit
def _jitted_singvals(a):
    return jnp.linalg.svd(a, compute_uv=False)

from .. import types
from .._tracing import FuseTraceError
from ..dndarray import DNDarray
from ..fuse import fuse
from ..sanitation import sanitize_in
from .qr import qr as _qr

#: the module itself (the package's ``qr`` attribute is the function): the
#: one-device route's helpers are looked up through it when a program is
#: traced
_qr_mod = sys.modules[_qr.__module__]

__all__ = ["svd"]

#: element cap for the silent wide-shard pre-resplit below — 1M elements
#: (4 MB f32) replicates harmlessly; anything larger keeps qr's gather
#: warning as the memory signal
_SMALL_RESPLIT_MAX = 1 << 20

SVD = collections.namedtuple("SVD", "U, S, V")


def _refuse_x64_lowering(x) -> None:
    """Raise where an SVD with singular vectors is being traced into a
    program that will be lowered for a TPU with x64 on (see module
    docstring): that lowering aborts the compiler and the process."""
    if (
        isinstance(x, _jax.core.Tracer)
        and _jax.config.jax_enable_x64
        and _jax.default_backend() == "tpu"
    ):
        raise FuseTraceError(
            "ht.linalg.svd with singular vectors cannot be traced into an "
            "enclosing program while x64 is on: lowering it aborts the TPU "
            "compiler. Call svd outside the ht.fuse / jax.jit function, or "
            "trace and compile that function under jax.enable_x64(False)."
        )


def _small_svd(r: jnp.ndarray):
    """SVD of the reduced (n, n) triangular factor: on device, except a
    float64 R, which factors on the host (see module docstring)."""
    _refuse_x64_lowering(r)
    if r.dtype == jnp.float64:
        # float64 R factors on the host: the x64-off context the device
        # chain runs under would silently downcast it, and the TPU has no
        # f64 hardware — LAPACK on an (n, n) triangle is the right tool
        # (one tiny transfer)
        ur, s, vt = np.linalg.svd(np.asarray(r), full_matrices=False)
        return jnp.asarray(ur, r.dtype), jnp.asarray(s, r.dtype), jnp.asarray(vt, r.dtype)
    return _jitted_svd(r)


def _small_singvals(r: jnp.ndarray):
    """Singular values of the reduced factor, same device/host policy as
    :func:`_small_svd`."""
    if r.dtype == jnp.float64:
        return jnp.asarray(np.linalg.svd(np.asarray(r), compute_uv=False), r.dtype)
    return _jitted_singvals(r)


def _svd_pipeline(a: DNDarray, osplit, dtype, compute_uv: bool):
    """The tall (m ≥ n) QR-first SVD chain over a sanitized operand.

    Module-level so :func:`heat_tpu.fuse` can compile the whole thing —
    resplit heuristic, (TS)QR, small SVD, Q·Ur correction, layout commits —
    into one program per (shape, split, dtype) signature; :func:`svd`
    routes float64 operands through it eagerly instead (their R factors
    round-trip through LAPACK on the host, which cannot trace).
    """
    comm, device = a.comm, a.device
    m, n = a.shape

    if (
        a.split == 0
        and comm.size > 1
        and comm.shard_width(m) < n
        and m * n <= _SMALL_RESPLIT_MAX
    ):
        # small-intermediate rule (ML callers: spectral embeddings, tiny
        # covariance factors): shards would be wider than tall, so TSQR
        # would gather behind a warning per fit.  Make the layout call
        # HERE, once and silently — but ONLY for genuinely small matrices
        # (the element cap): replication is the plan either way, and a
        # LARGE wide-shard matrix must keep qr's gather warning as the
        # memory signal.  U is re-sharded to the caller's split below, so
        # the public contract is unchanged
        a = a.resplit(None)

    if not compute_uv:
        _, r = _qr(a if a.dtype is dtype else a.astype(dtype))
        s_arr = _small_singvals(r.larray).astype(dtype.jax_type())
        return DNDarray(s_arr, tuple(s_arr.shape), dtype, None, device, comm, True)

    q, r = _qr(a if a.dtype is dtype else a.astype(dtype))
    ur, s, vt = _small_svd(r.larray)
    from .basics import _precision

    u = jnp.matmul(q.larray, ur.astype(dtype.jax_type()), precision=_precision())
    u_split = osplit if osplit == 0 else None  # caller's layout, even after
    u = comm.apply_sharding(u, u_split)        # the small-matrix resplit
    U = DNDarray(u, (m, n), dtype, u_split, device, comm, True)
    s_arr = s.astype(dtype.jax_type())
    S = DNDarray(s_arr, (n,), dtype, None, device, comm, True)
    v = jnp.transpose(vt).astype(dtype.jax_type())
    V = DNDarray(v, (n, n), dtype, None, device, comm, True)
    return SVD(U, S, V)


_fused_svd_pipeline = fuse(_svd_pipeline)


def _tall_svd(a: DNDarray, dtype, compute_uv: bool, route: str):
    """The SVD of a tall operand on a CholeskyQR2 route: ``cholqr2``
    (``qr.tall_route``, held whole on each device) or ``cholqr2_rows``
    (``qr.rows_route``, split by rows over several devices), one cached
    program launched as ``jitted:linalg.svd`` (``qr._cholqr2_svd``), with the
    fields of ``qr.route_fields`` and, where U is formed, ``u: direct``.

    R from two blocked Gram passes (on ``cholqr2_rows`` each shard's, summed
    over the chips), its SVD ``R = U_R·S·Vᵀ`` on the device, and ``U = A·W``
    with ``W = R⁻¹·U_R`` (n x n) in ONE more pass, shard by shard: Q is never
    built, so A and U are the only arrays of A's size on a chip, and U is
    split as A is.  An operand whose factor is not sound takes the blocked
    TSQR inside the same program."""
    comm, device = a.comm, a.device
    m, n = a.shape
    arr = a.larray
    compute_uv = bool(compute_uv)
    fields = _qr_mod.route_fields(route, compute_uv, n, comm.size)
    if compute_uv:
        fields["u"] = "direct"
    key = ("linalg.svd", comm, (m, n), str(arr.dtype), route, compute_uv, fields["precision"])

    def body(x, axis):
        return _qr_mod._cholqr2_svd(x, compute_uv, axis)

    outputs = (True, False, False) if compute_uv else False  # which are split by rows as A
    program = _jitted(key, lambda: _qr_mod._program(route, comm, body, outputs), fields=fields)
    out = _qr_mod.run_tall(program, arr, comm, route)
    if not compute_uv:
        return DNDarray(out, (n,), dtype, None, device, comm, True)
    u, s, v = out
    u_split = a.split if a.split == 0 else None
    U = DNDarray(comm.unpad(u, m, 0), (m, n), dtype, u_split, device, comm, True)
    S = DNDarray(s, (n,), dtype, None, device, comm, True)
    V = DNDarray(v, (n, n), dtype, None, device, comm, True)
    return SVD(U, S, V)


# ---------------------------------------------------------------------------
# grid (2-D mesh) QDWH polar-decomposition SVD — arXiv 2112.09017's route
# to record-scale SVD: a dynamically-weighted Halley iteration built on the
# grid blocked QR, then an eigendecomposition of the small symmetric factor
# ---------------------------------------------------------------------------

import jax

from .._compile import jitted as _jitted
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as _P
from ...telemetry import _core as _tel
from .qr import (
    _caqr_shard_body as _caqr_body,
    _caqr_sim,
    _grid_panel_schedule,
    _mm,
    _sumsq,
)

#: static trip cap of the QDWH while_loop — the cubic ``l`` recurrence
#: reaches ``1 - eps`` from any f64 floor in <= 9 iterations, so 12 bounds
#: both dtypes with margin; the telemetry model is credited for exactly
#: this worst case (``qdwh_svd_model(iterations=_QDWH_MAXIT)``)
_QDWH_MAXIT = 12


def _qdwh_coeffs(l):
    """The dynamically-weighted Halley coefficients ``(a, b, c, l')`` from
    the lower bound ``l`` on the current polar iterate's smallest singular
    value (Nakatsukasa/Bai/Gygi's closed form).  Shared verbatim by the
    kernel and the replicated golden — the convergence decision must be
    bitwise-identical in both programs (docs/design.md §23)."""
    l2 = l * l
    d = jnp.cbrt((4.0 * (1.0 - l2)) / (l2 * l2))
    a = jnp.sqrt(1.0 + d) + 0.5 * jnp.sqrt(
        8.0 - 4.0 * d + (8.0 * (2.0 - l2)) / (l2 * jnp.sqrt(1.0 + d))
    )
    b = (a - 1.0) ** 2 / 4.0
    c = a + b - 1.0
    ln = jnp.minimum(l * (a + b * l2) / (1.0 + c * l2), 1.0)
    return a, b, c, ln


def _qdwh_tols(n, np_dtype):
    """Static convergence tolerances: iterate while the lower bound is
    measurably below 1 OR successive polar iterates still move more than
    rounding at the ``sqrt(n)``-element Frobenius scale."""
    eps = float(np.finfo(np_dtype).eps)
    return eps / n, 10.0 * eps, 10.0 * eps * float(n) ** 0.5


def _grid_svd_fn(comm, shape, n, dtype_str, overlapped):
    """The QDWH polar SVD as ONE cached shard_map program ``f(a_padded)
    -> (u, s, v)`` over a ``(0, 1)``-laid-out tall operand.

    Per device: scale by the Frobenius norm (scalar all-gathers + ordered
    sums down both mesh axes — deterministic, unlike a bare psum), then a
    ``jax.lax.while_loop`` whose carry holds ``(X, l, k, delta)`` — the
    ``l`` lower-bound recurrence rides the carry, convergence is decided
    ON DEVICE (no host syncs, SPMD202-clean), and the static trip cap
    ``_QDWH_MAXIT`` bounds the program.  Each iteration stacks
    ``[sqrt(c)·X; I]`` (the identity block INCLUDES the pad diagonal —
    pad unit columns keep every panel full rank and provably wash out of
    the combine: their Q1 columns are exactly zero), runs the grid CAQR
    body (:func:`heat_tpu.core.linalg.qr._caqr_shard_body` — the same
    code the public grid QR dispatches), and combines ``X' = (b/c)·X +
    ((a - b/c)/sqrt(c))·Q1·Q2ᵀ`` in ``c`` panel-ordered steps of masked
    column broadcasts.  Epilogue: ``H = UpᵀA`` assembled via ordered
    gathers, symmetrized, eigendecomposed per device (replicated inputs
    give replicated outputs bit-for-bit), and ``U = Up·V`` reduced in
    mesh-column order."""
    key = ("svd.qdwh", comm, shape, n, dtype_str, _QDWH_MAXIT, overlapped)

    def make():
        ax0, ax1 = comm.axis_names
        r, c = comm.mesh_shape
        mloc = shape[0] // r
        nloc = shape[1] // c
        Np = c * nloc
        nploc = -(-Np // r)
        Npr = r * nploc
        qnloc, qbounds, qvcs = _grid_panel_schedule(Np, c, 1)
        l0, ltol, dtol = _qdwh_tols(n, np.dtype(dtype_str))

        def kern(a_loc):
            dt = a_loc.dtype
            i = jax.lax.axis_index(ax0)
            j = jax.lax.axis_index(ax1)
            zero = jnp.zeros((), dt)

            def scalar_reduce(v):
                g0 = jax.lax.all_gather(v, ax0)
                acc = g0[0]
                for b in range(1, r):
                    acc = acc + g0[b]
                g1 = jax.lax.all_gather(acc, ax1)
                acc = g1[0]
                for b in range(1, c):
                    acc = acc + g1[b]
                return acc

            def bcast_cols(x, owner):
                return jax.lax.psum(jnp.where(owner == j, x, zero), ax1)

            def colsum(x):
                g = jax.lax.all_gather(x, ax1)
                acc = g[0]
                for b in range(1, c):
                    acc = acc + g[b]
                return acc

            def gather_cols(x):
                g = jax.lax.all_gather(x, ax1)  # (c, rows, cols)
                return jnp.reshape(
                    jnp.moveaxis(g, 0, 1), (x.shape[0], c * x.shape[1])
                )

            alpha = jnp.sqrt(scalar_reduce(_sumsq(a_loc)))
            alpha = jnp.where(alpha > 0, alpha, jnp.ones((), dt))
            x0 = a_loc / alpha
            row_gid = i * nploc + jnp.arange(nploc)[:, None]
            col_gid = j * nloc + jnp.arange(nloc)[None, :]
            eye_block = (row_gid == col_gid).astype(dt)

            def cond(carry):
                _x, l, k, delta = carry
                return (k < _QDWH_MAXIT) & (
                    (delta > dtol) | (jnp.abs(1.0 - l) > ltol)
                )

            def body(carry):
                x, l, k, _delta = carry
                ca, cb, cc, ln = _qdwh_coeffs(l)
                sc = jnp.sqrt(cc).astype(dt)
                stacked = jnp.concatenate([sc * x, eye_block], axis=0)
                q_loc, _r_loc = _caqr_body(
                    stacked,
                    ax0=ax0,
                    ax1=ax1,
                    r=r,
                    c=c,
                    nloc=qnloc,
                    bounds=qbounds,
                    vcs=qvcs,
                    overlapped=overlapped,
                )
                q1 = q_loc[:mloc]
                q2f = jax.lax.all_gather(q_loc[mloc:], ax0, tiled=True)
                acc = jnp.zeros((mloc, Npr), dt)
                for t in range(c):
                    acc = acc + _mm(
                        bcast_cols(q1, t), bcast_cols(q2f, t).T
                    )
                m_loc = jax.lax.dynamic_slice_in_dim(acc, j * nloc, nloc, 1)
                ca = ca.astype(dt)
                cb = cb.astype(dt)
                cc = cc.astype(dt)
                x_new = (cb / cc) * x + ((ca - cb / cc) / sc) * m_loc
                delta = jnp.sqrt(scalar_reduce(_sumsq(x_new - x)))
                return x_new, ln.astype(l.dtype), k + 1, delta

            init = (
                x0,
                jnp.asarray(l0, x0.dtype),
                jnp.zeros((), jnp.int32),
                jnp.asarray(jnp.inf, x0.dtype),
            )
            up_loc, _l, _k, _delta = jax.lax.while_loop(cond, body, init)

            a_full = gather_cols(a_loc)  # (mloc, Np)
            g = jax.lax.all_gather(_mm(up_loc.T, a_full), ax0)
            h_rows = g[0]
            for b in range(1, r):
                h_rows = h_rows + g[b]  # (nloc, Np)
            h_full = jnp.reshape(
                jax.lax.all_gather(h_rows, ax1, tiled=True), (Np, Np)
            )
            h = h_full[:n, :n]
            hs = 0.5 * (h + h.T)
            evals, evecs = jnp.linalg.eigh(hs)
            s = evals[::-1]
            v = evecs[:, ::-1]
            vp = jnp.zeros((Np, Np), dt).at[:n, :n].set(v)
            u_part = _mm(
                up_loc, jax.lax.dynamic_slice_in_dim(vp, j * nloc, nloc, 0)
            )
            u_full = colsum(u_part)  # (mloc, Np)
            u_loc = jax.lax.dynamic_slice_in_dim(u_full, j * nloc, nloc, 1)
            return u_loc, s, v

        return _shard_map(
            kern,
            mesh=comm.mesh,
            in_specs=(_P(ax0, ax1),),
            out_specs=(_P(ax0, ax1), _P(), _P()),
            check_vma=False,
        )

    return _jitted(key, make)


def _grid_svd(a: DNDarray, dtype, compute_uv: bool):
    """Dispatch wrapper of the grid QDWH SVD: early guard with shapes and
    mesh in the message, zeroed buffer, one cached program, telemetry
    credited straight from :func:`heat_tpu.comm._costs.qdwh_svd_model`
    (op ``svd2d``), timed under the overlap policy."""
    from ...comm import _costs
    from ...comm.overlap import overlap_enabled, timed_dispatch

    comm, device = a.comm, a.device
    m, n = a.shape
    r, c = comm.mesh_shape
    mloc = -(-m // r)
    nloc = -(-n // c)
    Np = c * nloc
    nploc = -(-Np // r)
    if mloc + nploc < nloc:
        raise ValueError(
            f"svd: grid QDWH needs stacked shards at least as tall as a "
            f"column panel: {m}x{n} over the {r}x{c} mesh stacks "
            f"({mloc} + {nploc}) rows against panel width {nloc}; use a "
            f"taller matrix or a flatter mesh"
        )
    arr = a._zeroed_buffer()
    jt = dtype.jax_type()
    if arr.dtype != jt:
        arr = arr.astype(jt)
    ov = overlap_enabled(c)
    fn = _grid_svd_fn(comm, tuple(map(int, arr.shape)), n, str(arr.dtype), ov)
    if _tel.enabled:
        model = _costs.qdwh_svd_model(m, n, (r, c), iterations=_QDWH_MAXIT)
        _tel.account_bytes(
            "svd2d", "f32", model["exact_wire_bytes"], model["wire_bytes"]
        )
        with _tel.span(
            "comm:svd2d",
            "comm",
            mesh=f"{r}x{c}",
            iterations=_QDWH_MAXIT,
            overlap=ov,
        ):
            u_arr, s_arr, v_arr = timed_dispatch("svd2d", ov, lambda: fn(arr))
    else:
        u_arr, s_arr, v_arr = timed_dispatch("svd2d", ov, lambda: fn(arr))
    S = DNDarray(s_arr, (n,), dtype, None, device, comm, True)
    if not compute_uv:
        return S
    U = DNDarray(u_arr, (m, n), dtype, (0, 1), device, comm, True)
    V = DNDarray(v_arr, (n, n), dtype, None, device, comm, True)
    return SVD(U, S, V)


def _qdwh_svd_reference(arr, mesh_shape):
    """Replicated golden twin of the grid QDWH SVD: simulates the mesh's
    blocks in lockstep — the while_loop (same carry, same tolerances,
    same coefficient math, so the trip decisions agree bitwise), the
    stacked CAQR via :func:`heat_tpu.core.linalg.qr._caqr_sim`, the
    panel-ordered combine with explicit zero-block additions mirroring
    the masked psums, and the eigh epilogue.  One jitted program (eager
    execution changes XLA CPU's dot emission — see ``_mm``).  Returns
    ``(u_padded, s, v)`` bitwise-equal to the kernel's outputs.

    The golden replays the SERIAL panel order only: the kernel's overlap
    arm is pinned bitwise to its serial arm (asserted directly in
    tests/test_linalg2d.py), so one canonical golden covers both.  Simulating the
    reordered overlap schedule inside this much larger program trips
    XLA CPU's fusion-context sensitivity in ops beyond the barriered
    matmuls/reductions — the two sim arms match bitwise in a minimal
    program but not embedded here, so we don't embed the second arm."""
    from .qr import _REFERENCE_CACHE

    r, c = mesh_shape
    m, n = arr.shape
    mloc = -(-m // r)
    nloc = -(-n // c)
    Mp, Np = r * mloc, c * nloc
    nploc = -(-Np // r)
    Npr = r * nploc
    qnloc, qbounds, qvcs = _grid_panel_schedule(Np, c, 1)
    l0, ltol, dtol = _qdwh_tols(n, np.dtype(arr.dtype.name))

    def run(x):
        dt = x.dtype
        zero = jnp.zeros((), dt)
        x = jnp.pad(x, ((0, Mp - m), (0, Np - n)))
        blocks = {
            (i, j): x[i * mloc : (i + 1) * mloc, j * nloc : (j + 1) * nloc]
            for i in range(r)
            for j in range(c)
        }

        def scalar_reduce(parts):
            # parts[(i, j)] -> the same gather order as the kernel: down
            # the mesh rows first, then along the columns
            col_acc = {}
            for j in range(c):
                acc = parts[(0, j)]
                for b in range(1, r):
                    acc = acc + parts[(b, j)]
                col_acc[j] = acc
            acc = col_acc[0]
            for b in range(1, c):
                acc = acc + col_acc[b]
            return acc

        def bcast_cols(vals_row, owner):
            acc = vals_row[0] if owner == 0 else jnp.where(False, vals_row[0], zero)
            for jp in range(1, c):
                acc = acc + (
                    vals_row[jp]
                    if owner == jp
                    else jnp.where(False, vals_row[jp], zero)
                )
            return acc

        alpha = jnp.sqrt(
            scalar_reduce({k: _sumsq(v) for k, v in blocks.items()})
        )
        alpha = jnp.where(alpha > 0, alpha, jnp.ones((), dt))
        x0 = {k: v / alpha for k, v in blocks.items()}
        eye = {
            (i, j): (
                (i * nploc + jnp.arange(nploc)[:, None])
                == (j * nloc + jnp.arange(nloc)[None, :])
            ).astype(dt)
            for i in range(r)
            for j in range(c)
        }

        def cond(carry):
            _x, l, k, delta = carry
            return (k < _QDWH_MAXIT) & (
                (delta > dtol) | (jnp.abs(1.0 - l) > ltol)
            )

        def body(carry):
            xb, l, k, _delta = carry
            ca, cb, cc, ln = _qdwh_coeffs(l)
            sc = jnp.sqrt(cc).astype(dt)
            stacked = {
                k2: jnp.concatenate([sc * xb[k2], eye[k2]], axis=0)
                for k2 in xb
            }
            qb, _rb = _caqr_sim(
                stacked,
                r=r,
                c=c,
                nloc=qnloc,
                bounds=qbounds,
                vcs=qvcs,
                overlapped=False,
            )
            q2f = {
                j: jnp.concatenate(
                    [qb[(b, j)][mloc:] for b in range(r)], axis=0
                )
                for j in range(c)
            }
            ca = ca.astype(dt)
            cb = cb.astype(dt)
            cc = cc.astype(dt)
            x_new = {}
            for i in range(r):
                acc = jnp.zeros((mloc, Npr), dt)
                for t in range(c):
                    q1_pan = bcast_cols(
                        [qb[(i, jp)][:mloc] for jp in range(c)], t
                    )
                    q2f_pan = bcast_cols([q2f[jp] for jp in range(c)], t)
                    acc = acc + _mm(q1_pan, q2f_pan.T)
                for j in range(c):
                    m_loc = jax.lax.dynamic_slice_in_dim(
                        acc, j * nloc, nloc, 1
                    )
                    x_new[(i, j)] = (cb / cc) * xb[(i, j)] + (
                        (ca - cb / cc) / sc
                    ) * m_loc
            delta = jnp.sqrt(
                scalar_reduce({k2: _sumsq(x_new[k2] - xb[k2]) for k2 in xb})
            )
            return x_new, ln.astype(l.dtype), k + 1, delta

        init = (
            x0,
            jnp.asarray(l0, dt),
            jnp.zeros((), jnp.int32),
            jnp.asarray(jnp.inf, dt),
        )
        up, _l, _k, _delta = jax.lax.while_loop(cond, body, init)

        a_full = {
            i: jnp.concatenate([blocks[(i, j)] for j in range(c)], axis=1)
            for i in range(r)
        }
        h_rows = {}
        for j in range(c):
            acc = _mm(up[(0, j)].T, a_full[0])
            for b in range(1, r):
                acc = acc + _mm(up[(b, j)].T, a_full[b])
            h_rows[j] = acc
        h_full = jnp.concatenate([h_rows[j] for j in range(c)], axis=0)
        h = h_full[:n, :n]
        hs = 0.5 * (h + h.T)
        evals, evecs = jnp.linalg.eigh(hs)
        s = evals[::-1]
        v = evecs[:, ::-1]
        vp = jnp.zeros((Np, Np), dt).at[:n, :n].set(v)
        u_rows = []
        for i in range(r):
            parts = [
                _mm(
                    up[(i, j)],
                    jax.lax.dynamic_slice_in_dim(vp, j * nloc, nloc, 0),
                )
                for j in range(c)
            ]
            acc = parts[0]
            for b in range(1, c):
                acc = acc + parts[b]
            u_rows.append(acc)
        u = jnp.concatenate(u_rows, axis=0)  # (Mp, Np)
        return u, s, v

    key = ("qdwh", mesh_shape, (m, n), str(arr.dtype))
    fn = _REFERENCE_CACHE.get(key)
    if fn is None:
        fn = _REFERENCE_CACHE[key] = _jax.jit(run)
    return fn(arr)


from .._split_semantics import split_semantics as _split_semantics


@_split_semantics("entry_svd")
def svd(a: DNDarray, full_matrices: bool = False, compute_uv: bool = True):
    """Reduced SVD ``a = U @ diag(S) @ V.T``.

    Returns the namedtuple ``SVD(U, S, V)``; with ``compute_uv=False`` only
    ``S`` (as a DNDarray).  The on-device configurations compile the whole
    QR→SVD→correction chain into one fused program (one device dispatch
    per call after warmup); float64 operands keep the eager chain, since
    their small factor visits LAPACK on the host mid-pipeline.
    """
    sanitize_in(a)
    if a.ndim != 2:
        raise ValueError(f"svd requires a 2-D DNDarray, got {a.ndim}-d")
    if full_matrices:
        raise NotImplementedError("full_matrices=True is not supported (reduced SVD only)")

    dtype = a.dtype if types.heat_type_is_inexact(a.dtype) else types.float32
    m, n = a.shape

    comm = a.comm
    if comm.mesh_ndim == 2 and comm.size > 1 and a.splits in ((0, 1), (1, 0)):
        # grid QDWH polar SVD (arXiv 2112.09017): wide inputs factor the
        # transpose — its (1, 0) layout is re-committed to (0, 1) by one
        # planned redistribution — and swap U with V; the generic wide
        # recursion below cannot do this (a.T's tuple layout would fall
        # into the 1-D tall chain and gather)
        if m < n:
            res = svd(a.T.resplit((0, 1)), compute_uv=compute_uv)
            if not compute_uv:
                return res
            return SVD(res.V, res.S, res.U)
        if a.splits == (1, 0):
            a = a.resplit((0, 1))
        return _grid_svd(a, dtype, compute_uv)

    if m < n:
        # wide: factor the transpose, swap U and V
        if not compute_uv:
            return svd(a.T, compute_uv=False)
        res = svd(a.T, compute_uv=True)
        return SVD(res.V, res.S, res.U)

    if dtype is types.float64:
        return _svd_pipeline(a, a.split, dtype, compute_uv)
    if compute_uv:
        _refuse_x64_lowering(a.larray)
    if a.dtype is not dtype:
        a = a.astype(dtype)  # an integer operand must not meet the context below
    with _jax.enable_x64(False):  # see module docstring: the LOWERING must be x64-off
        if _qr_mod.whole_on_each_device(a) and _qr_mod.tall_route(a.shape, a.larray.dtype) == "cholqr2":
            return _tall_svd(a, dtype, compute_uv, "cholqr2")
        if _qr_mod.rows_route(a.shape, a.larray.dtype, a.split, comm):
            return _tall_svd(a, dtype, compute_uv, "cholqr2_rows")
        return _fused_svd_pipeline(a, a.split, dtype, compute_uv)
