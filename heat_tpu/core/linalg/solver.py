"""Iterative solvers: conjugate gradients and Lanczos.

Reference: heat/core/linalg/solver.py:8-184 — pure compositions of matmul
and reductions; the distributed work all happens inside those primitives,
which is equally true here.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .. import types
from .._compile import launch
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from . import _symv, basics

__all__ = ["cg", "lanczos"]


@jax.jit
def _cg_loop(arr, bv, xv):
    """Full conjugate-gradient iteration on device; jitted once at module
    level so repeat solves of the same shape replay the cached program."""
    # stable carry dtype: promote all operands to one inexact type up front
    ctype = jnp.result_type(arr.dtype, bv.dtype, xv.dtype, jnp.float32)
    arr, bv, xv = arr.astype(ctype), bv.astype(ctype), xv.astype(ctype)
    r0 = bv - arr @ xv
    init = (jnp.int32(0), xv, r0, r0, jnp.dot(r0, r0))

    def cond(s):
        it, _, _, _, rsold = s
        # ~(x < tol) rather than x >= tol: NaN must keep iterating so bad
        # inputs propagate instead of silently returning x0
        return jnp.logical_and(it < bv.shape[0], ~(jnp.sqrt(rsold) < 1e-10))

    def body(s):
        it, x, r, p, rsold = s
        Ap = arr @ p
        alpha = rsold / jnp.dot(p, Ap)
        x = x + alpha * p
        r = r - alpha * Ap
        rsnew = jnp.dot(r, r)
        p = r + (rsnew / rsold) * p
        return it + 1, x, r, p, rsnew

    _, x, _, _, _ = jax.lax.while_loop(cond, body, init)
    return x


def cg(A: DNDarray, b: DNDarray, x0: DNDarray, out: Optional[DNDarray] = None) -> DNDarray:
    """Conjugate gradients for SPD ``A`` (reference solver.py:8-73)."""
    sanitize_in(A)
    sanitize_in(b)
    sanitize_in(x0)
    if A.ndim != 2:
        raise RuntimeError("A needs to be a 2D matrix")
    if b.ndim != 1:
        raise RuntimeError("b needs to be a 1D vector")
    if x0.ndim != 1:
        raise RuntimeError("c needs to be a 1D vector")

    # the whole iteration as ONE device while_loop (the reference,
    # solver.py:39-52, pays three host round-trips per step for the
    # .item() reductions; here the convergence test stays on device)
    xres = _cg_loop(A.larray, b.larray, x0.larray)
    x = DNDarray(
        x0.comm.apply_sharding(xres, x0.split),
        tuple(xres.shape),
        types.canonical_heat_type(xres.dtype),
        x0.split,
        x0.device,
        x0.comm,
        True,
    )
    if out is not None:
        out.larray = x.larray
        return out
    return x


def _matvec_route(arr) -> str:
    """Which product ``_matvec`` compiles for ``arr``, as the launch spans
    name it: ``symmetric_half`` or ``dense``."""
    return "symmetric_half" if _symv.conforms(arr) else "dense"


def _matvec(arr, w, precision):
    """``arr @ w``: the one pass over the (n, n) operator a step makes.

    Lanczos' operator is symmetric, so where ``_symv.conforms(arr)`` (float32,
    at least ``_symv.MIN_N`` rows, one TPU) the pass reads only the upper
    block-triangle and uses each tile for both products; every other operand
    takes the dense product, at ``precision``."""
    with jax.named_scope("lanczos.matvec"):
        if _matvec_route(arr) == "symmetric_half":
            return _symv.symv(arr, w, interpret=_symv._interpret())
        return jnp.matmul(arr, w, precision=precision)


def _reorth(V, w, precision):
    """``w`` less its projection on the columns of ``V`` (two thin products)."""
    with jax.named_scope("lanczos.reorth"):
        return w - jnp.matmul(V, jnp.matmul(V.T, w, precision=precision), precision=precision)


@functools.partial(jax.jit, static_argnames=("m", "precision"))
def _lanczos_start(arr, v, m, precision=None):
    """Step 0: the normalised start vector as V's first column, its image
    and T's first entry — the carry ``_lanczos_segment`` enters with."""
    v = (v / jnp.linalg.norm(v)).astype(arr.dtype)
    w0 = _matvec(arr, v, precision)
    alpha0 = jnp.dot(w0, v, precision=precision)
    V = jnp.zeros((arr.shape[0], m), dtype=arr.dtype).at[:, 0].set(v)
    T = jnp.zeros((m, m), dtype=arr.dtype).at[0, 0].set(alpha0)
    return V, T, w0 - alpha0 * v, v


@functools.partial(jax.jit, static_argnames=("precision",))
def _lanczos_segment(arr, R, start, stop, carry, precision=None):
    """Lanczos steps ``[start, stop)`` as ONE device program.

    The reference (solver.py:74-184) — and this module until the fuse PR —
    decided breakdown-restart on the host with ``float(beta)``, a blocking
    device→host sync per iteration.  Here the decision is a ``jnp.where``
    select between the normal step and a restart candidate drawn from the
    pre-generated random matrix ``R`` (one column per iteration), so the
    steps run as a single ``fori_loop`` with zero host syncs.

    Re-enterable: the carry ``(V, T, w, v_prev)`` comes in explicitly and
    the ``fori_loop`` bounds are dynamic — a plain call runs one segment
    with ``(1, m)``; a checkpointed call replays THIS program segment by
    segment (snapshotting the carry plus the restart matrix ``R`` between
    segments), which is what makes resume bitwise-exact.

    The full re-orthogonalization projects against ALL m columns of V:
    columns ≥ i are still zero, so their coefficients vanish and the
    projection equals the reference's ``V[:, :i]`` slice — this is what
    lets the loop body stay shape-static inside ``fori_loop``.

    ``precision`` is the products' (static): ``lanczos`` passes the
    library's linalg policy, ``highest`` unless set otherwise.  On a TPU
    jax's default is one bf16 pass, which bounds the basis' orthogonality
    and the Ritz pairs' residuals at about 1e-3; the matvec streams the
    operator from memory either way, so float32 products cost it nothing.
    """

    def body(i, state):
        V, T, w, v_prev = state
        beta = jnp.linalg.norm(w)
        breakdown = beta < 1e-10
        # restart candidate: random column re-orthogonalized against V
        # (reference :120-130); computed unconditionally — a lax.cond would
        # re-trace both branches anyway and the two thin products are noise
        # next to the matvec
        with jax.named_scope("lanczos.restart"):
            vr = _reorth(V, jnp.take(R, i, axis=1).astype(arr.dtype), precision)
            vr_nrm = jnp.linalg.norm(vr)
            vr = jnp.where(vr_nrm > 0, vr / vr_nrm, vr)
            w = jnp.where(breakdown, vr, w / jnp.where(breakdown, 1.0, beta))
        # full re-orthogonalization (reference :140-152)
        w = _reorth(V, w, precision)
        nrm = jnp.linalg.norm(w)
        w = jnp.where(nrm > 0, w / nrm, w)
        V = V.at[:, i].set(w)
        wnew = _matvec(arr, w, precision)
        alpha = jnp.dot(wnew, w, precision=precision)
        w_next = wnew - alpha * w - beta * v_prev
        T = T.at[i, i].set(alpha)
        T = T.at[i - 1, i].set(beta)
        T = T.at[i, i - 1].set(beta)
        return V, T, w_next, w

    return jax.lax.fori_loop(start, stop, body, carry)


def lanczos(
    A: DNDarray,
    m: int,
    v0: Optional[DNDarray] = None,
    V_out: Optional[DNDarray] = None,
    T_out: Optional[DNDarray] = None,
    checkpoint_every: int = 0,
    checkpoint_path: Optional[str] = None,
    resume=False,
) -> Tuple[DNDarray, DNDarray]:
    """Lanczos tridiagonalization with full re-orthogonalization
    (reference solver.py:74-184).  Returns (V, T) with ``T = V.T A V``
    tridiagonal, ``V`` the (n, m) orthonormal Krylov basis.

    ``A`` must be symmetric (the reference documents it as symmetric
    positive definite): ``T`` is tridiagonal only then, and the step's
    product with ``A`` relies on it.  That product takes one of two routes,
    chosen from what the operand shows, never by a switch.  A float32 ``A`` of
    at least ``_symv.MIN_N`` (8 192) rows in a process that drives one TPU is
    bound by the stream of its n x n entries from memory, so a Pallas kernel
    (``_symv.symv``) reads only the tiles on and above the block diagonal and
    uses each for both halves of the product, in float32 on the vector units:
    the operator applied is ``triu(A) + triu(A, 1)^T``, exactly symmetric, and
    differs from a stored ``A`` by no more than ``A[j, i] - A[i, j]`` (the last
    bit, where ``Laplacian`` computed the two from ``(-a * d_i) * d_j``).
    Every other operand (row-sharded over several chips, float64 or bfloat16,
    smaller, on the CPU) takes the dense ``A @ w`` at the linalg precision.
    The launch spans ``jit:lanczos.start`` and ``jit:lanczos.segment`` name
    the route in their ``matvec`` field (``symmetric_half`` or ``dense``).

    The reference re-orthogonalizes rank-locally and Allreduces dot
    products (:140-152); here the inner products on the sharded vectors
    compile to all-reduces automatically, and the whole m-step iteration —
    including the breakdown-restart decision, formerly a ``float(beta)``
    host sync per step — runs as one compiled device loop.

    With ``checkpoint_every=N`` the iteration runs in N-step segments of
    the same compiled program, snapshotting the carry (and the
    breakdown-restart matrix, so restart draws replay too) to
    ``checkpoint_path`` between segments; ``resume=True`` restarts from
    the snapshot and finishes bitwise-identical to an uninterrupted run.
    ``resume="elastic"`` additionally accepts a snapshot taken at a
    different mesh size (the Lanczos carry is replicated, so migration
    is a pass-through).
    """
    sanitize_in(A)
    if A.ndim != 2 or A.shape[0] != A.shape[1]:
        raise RuntimeError("A needs to be a square matrix")
    if not isinstance(m, int) or m <= 0:
        raise RuntimeError("m must be a positive integer")

    n = A.shape[0]
    arr = A.larray.astype(jnp.float32 if types.heat_type_is_exact(A.dtype) else A.larray.dtype)
    precision = basics._precision()
    route = _matvec_route(arr)

    from .. import random
    from ...resilience import elastic as _elastic
    from ...resilience.resume import LoopCheckpointer

    ckpt = LoopCheckpointer(
        checkpoint_path, checkpoint_every, "lanczos",
        {"n": int(n), "m": int(m)}, comm=A.comm,
        splits={"i": None, "V": None, "T": None, "w": None,
                "v_prev": None, "R": None},
    )
    if resume:
        state, _ = ckpt.load(elastic=resume == "elastic")
        R = jnp.asarray(state["R"], jnp.float32)
        carry = (
            jnp.asarray(state["V"], arr.dtype),
            jnp.asarray(state["T"], arr.dtype),
            jnp.asarray(state["w"], arr.dtype),
            jnp.asarray(state["v_prev"], arr.dtype),
        )
        it = int(state["i"])
    else:
        if v0 is None:
            # draws land on A's communicator so sub-mesh fits (elastic
            # recovery on a shrunk device set) don't mix device sets
            v = random.rand(
                n, dtype=types.float32, device=A.device, comm=A.comm
            ).larray
        else:
            sanitize_in(v0)
            v = v0.larray
        # breakdown-restart candidates, one per iteration (drawn per fit,
        # used on device only when the matching step actually breaks down)
        R = random.rand(
            n, m, dtype=types.float32, device=A.device, comm=A.comm
        ).larray
        carry = launch(
            "jit:lanczos.start", _lanczos_start, (arr, v), {"m": m, "precision": precision},
            n=n, m=m, matvec=route,
        )
        it = 1

    while it < m:
        stop = ckpt.stop(it, m)
        with _elastic.dispatch_guard("lanczos.seg", A.comm):
            carry = launch(
                "jit:lanczos.segment", _lanczos_segment,
                (arr, R, jnp.int32(it), jnp.int32(stop), carry), {"precision": precision},
                steps=stop - it, n=n, m=m, matvec=route,
            )
        it = stop
        if it >= m:
            break
        ckpt.tick(
            it,
            {"i": jnp.int32(it), "V": carry[0], "T": carry[1],
             "w": carry[2], "v_prev": carry[3], "R": R},
        )
    V, T = carry[0], carry[1]

    comm, device = A.comm, A.device
    V_nd = DNDarray(comm.apply_sharding(V, 0 if A.split is not None else None), (n, m),
                    types.canonical_heat_type(V.dtype), 0 if A.split is not None else None,
                    device, comm, True)
    T_nd = DNDarray(T, (m, m), types.canonical_heat_type(T.dtype), None, device, comm, True)
    if V_out is not None:
        V_out.larray = V_nd.larray
        T_out.larray = T_nd.larray
        return V_out, T_out
    return V_nd, T_nd
