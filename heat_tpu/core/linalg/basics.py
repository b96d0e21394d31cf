"""Distributed linear algebra basics.

Reference: heat/core/linalg/basics.py:16-1269.  The centerpiece there is a
780-line hand-written block-distributed SUMMA ``matmul`` covering all four
split combinations with Isend/Irecv block exchanges (:285-787), whose point
is an O(n²/p) per-rank memory guarantee.  GSPMD does NOT honor that
guarantee: measured on an 8-device mesh, its plan for splits 00/01/11
all-gathers one full operand per device (f32[1024,1024] at m=k=n=1024) —
fine at laptop scale, an OOM at pod scale.  So 2-D matmuls on those combos
run an explicit ring SUMMA (``_summa``: shard_map + ppermute, p rounds,
one visiting shard at a time — the reference's schedule re-expressed as an
ICI ring program), pinned by HLO assertions in tests/test_hlo_matmul.py.
Split 10 and everything else (vectors, batched) keep the compiler plan:
there GSPMD's single result all-reduce IS the right schedule.  The module
keeps the reference's *semantics* throughout: dtype promotion, the
vector/matrix edge cases, and the result-split rules for every split
combination (basics.py:168-283).
"""

from __future__ import annotations

from typing import List, Optional, Tuple, Union

import numpy as np

import jax.numpy as jnp
from jax import shard_map
from jax.lax import pcast

from jax.sharding import PartitionSpec

from .. import factories, types
from .._compile import jitted
from .._tracing import record_dispatch
from ..communication import sanitize_comm
from ..dndarray import DNDarray
from ..sanitation import sanitize_in
from ..stride_tricks import sanitize_axis
from ...telemetry import _core as _tel

__all__ = [
    "dot",
    "get_matmul_precision",
    "matmul",
    "matrix_norm",
    "norm",
    "outer",
    "projection",
    "set_matmul_precision",
    "transpose",
    "tril",
    "triu",
    "vector_norm",
]

# On TPU the MXU's default matmul precision is bfloat16-accumulate, which is
# far below the reference's float32 torch numerics (observed: ||QR - A||
# ~0.3 instead of ~1e-5 on a 1024×16 factorization).  This framework is a
# numerics-parity analytics stack first, so linalg defaults to 'highest'
# (fp32 accumulation via multiple MXU passes); benchmarks that want raw MXU
# throughput can switch to 'default' (bf16) or 'float32' (3-pass).
_MATMUL_PRECISION = "highest"


def set_matmul_precision(precision: str) -> None:
    """Set the MXU precision for all linalg matmuls:
    'default' (bf16 inputs), 'float32', or 'highest'."""
    global _MATMUL_PRECISION
    if precision not in ("default", "float32", "highest"):
        raise ValueError(f"invalid precision {precision!r}")
    _MATMUL_PRECISION = precision


def get_matmul_precision() -> str:
    """The current MXU matmul precision for linalg ops."""
    return _MATMUL_PRECISION


def _precision():
    return None if _MATMUL_PRECISION == "default" else _MATMUL_PRECISION


def _result_split_matmul(a: DNDarray, b: DNDarray, out_ndim: int) -> Optional[int]:
    """Result-split rule for matmul, mirroring reference basics.py:168-283:
    split=0 @ anything → row-split result; anything @ split=1 → col-split;
    a.split=1 @ b.split=0 contracts the split axis → split=None (the
    all-reduce case)."""
    if out_ndim == 0:
        return None
    if a.split == 0 and a.ndim > 1:
        return 0
    if b.split is not None and b.ndim > 1 and b.split == b.ndim - 1:
        return out_ndim - 1
    if a.split is not None or b.split is not None:
        # contraction over the split axis (or vector operands): replicate,
        # XLA will have inserted the psum
        return None
    return None


def _summa_fn(sa: int, sb: int, comm, precision, chunk: int):
    """The jitted shard_map ring-matmul program for one split combo —
    cached per (combo, comm, precision, chunk), and exposed so the HLO
    tests lower the EXACT production program (tests/test_hlo_matmul.py).
    ``chunk`` is the rotating operand's shard width along its split axis;
    the padded global widths are ``chunk * comm.size``."""
    import jax
    from jax.sharding import PartitionSpec as P

    key = (sa, sb, comm, precision, chunk)
    cached = _SUMMA_CACHE.get(key)
    if cached is not None:
        return cached

    p, mesh, axis = comm.size, comm.mesh, comm.axis_name
    perm = [(i, (i + 1) % p) for i in range(p)]

    if (sa, sb) == (0, 0):
        # A (Mp/p, Kp) stationary; B's k-shards (Kp/p, N) rotate — chunk
        # r of A's columns multiplies the shard that originated at r
        def kern(a_loc, b_blk):
            my = jax.lax.axis_index(axis)

            def body(r, carry):
                b_blk, acc = carry
                origin = (my - r) % p
                a_chunk = jax.lax.dynamic_slice_in_dim(
                    a_loc, origin * chunk, chunk, 1
                )
                acc = acc + jnp.matmul(a_chunk, b_blk, precision=precision)
                return jax.lax.ppermute(b_blk, axis, perm), acc

            acc0 = pcast(
                jnp.zeros((a_loc.shape[0], b_blk.shape[1]), a_loc.dtype),
                (axis,), to="varying",
            )
            _, acc = jax.lax.fori_loop(0, p, body, (b_blk, acc0))
            return acc

        ins, outs = (P(axis, None), P(axis, None)), P(axis, None)
    elif (sa, sb) == (0, 1):
        # A (Mp/p, K) stationary; B's column shards (K, Np/p) rotate,
        # each landing in its own slice of the (Mp/p, Np) result columns
        def kern(a_loc, b_blk):
            my = jax.lax.axis_index(axis)

            def body(r, carry):
                b_blk, acc = carry
                origin = (my - r) % p
                prod = jnp.matmul(a_loc, b_blk, precision=precision)
                col = origin * chunk  # axis_index dtype; zero must match
                acc = jax.lax.dynamic_update_slice(
                    acc, prod, (jnp.zeros((), col.dtype), col)
                )
                return jax.lax.ppermute(b_blk, axis, perm), acc

            acc0 = pcast(
                jnp.zeros((a_loc.shape[0], chunk * p), a_loc.dtype),
                (axis,), to="varying",
            )
            _, acc = jax.lax.fori_loop(0, p, body, (b_blk, acc0))
            return acc

        ins, outs = (P(axis, None), P(None, axis)), P(axis, None)
    else:
        # (1, 1): B (Kp, Np/p) stationary; A's k-shards (M, Kp/p) rotate,
        # each contracting against its slice of B's rows
        def kern(a_blk, b_loc):
            my = jax.lax.axis_index(axis)

            def body(r, carry):
                a_blk, acc = carry
                origin = (my - r) % p
                b_chunk = jax.lax.dynamic_slice_in_dim(
                    b_loc, origin * chunk, chunk, 0
                )
                acc = acc + jnp.matmul(a_blk, b_chunk, precision=precision)
                return jax.lax.ppermute(a_blk, axis, perm), acc

            acc0 = pcast(
                jnp.zeros((a_blk.shape[0], b_loc.shape[1]), a_blk.dtype),
                (axis,), to="varying",
            )
            _, acc = jax.lax.fori_loop(0, p, body, (a_blk, acc0))
            return acc

        ins, outs = (P(None, axis), P(None, axis)), P(None, axis)

    fn = jax.jit(shard_map(kern, mesh=mesh, in_specs=ins, out_specs=outs))
    _SUMMA_CACHE[key] = fn
    return fn


#: (sa, sb, comm, precision, chunk) -> jitted program; comm objects are
#: long-lived singletons, so this never grows past a handful of entries
_SUMMA_CACHE: dict = {}


def _summa(aa, ba, sa: int, sb: int, comm, precision):
    """Ring (SUMMA-style) matmul for the split combinations where GSPMD
    chooses to ALL-GATHER a full operand — split 00, 01 and 11 (verified
    in HLO: a `f32[m,k]`/`f32[k,n]` all-gather per device, i.e. O(n²)
    per-device memory; the reference's hand-written SUMMA,
    basics.py:285-787, guarantees O(n²/p)).

    One operand stays stationary; the other's shards rotate around the
    mesh ring with ``ppermute`` (p rounds), each round contributing one
    block product.  Per-device memory: own shards + one visiting shard +
    the local result block — the reference's guarantee, on ICI.

    ``aa``/``ba`` are the PADDED buffers (split axes at canonical width);
    non-split contraction axes are zero-padded here when ragged, and the
    pad region always multiplies those zeros, so the at-rest buffers'
    unspecified pad values never reach the result.  Returns the padded
    sharded result and its split.
    """
    p = comm.size
    if (sa, sb) == (0, 0):
        Kp = comm.padded_size(aa.shape[1])
        if Kp != aa.shape[1]:
            aa = jnp.pad(aa, ((0, 0), (0, Kp - aa.shape[1])))
            aa = comm.apply_sharding(aa, 0)
        chunk = Kp // p
        out_split = 0
    elif (sa, sb) == (0, 1):
        chunk = ba.shape[1] // p  # ba padded on its split axis already
        out_split = 0
    else:  # (1, 1)
        Kp = aa.shape[1]
        if ba.shape[0] != Kp:
            ba = jnp.pad(ba, ((0, Kp - ba.shape[0]), (0, 0)))
            ba = comm.apply_sharding(ba, 1)
        chunk = Kp // p
        out_split = 1
    out = _summa_fn(sa, sb, comm, precision, chunk)(aa, ba)
    return out, out_split


def _summa_grid_fn(comm, precision, w: int, overlapped: bool, layout: str = "grid"):
    """The jitted grid-SUMMA program for an r×c mesh — cached per
    (comm, precision, panel width, overlap arm, layout) like
    :func:`_summa_fn`.

    ``layout="grid"``: both operands carry splits ``(0, 1)``: local A is
    ``(Mp/r, Kp/c)`` and local B ``(Kp/r, Np/c)`` with ``Kp = r*c*w``.
    Panel ``t`` of the k axis lives on mesh column ``t // r`` of A (local
    offset ``(t % r) * w``) and on mesh row ``t // c`` of B (offset
    ``(t % c) * w``); each of the ``L = r*c`` steps broadcasts the two
    panels with a masked psum (exact: one owner's values plus zeros) and
    accumulates one ``(Mp/r, w) @ (w, Np/c)`` block product — per-device
    memory O(mn/rc) plus two panels.  The overlap arm issues panel
    ``t+1``'s broadcasts before consuming panel ``t`` (the
    double-buffering discipline of docs/design.md §18); the accumulation
    order is identical, so the two arms are bitwise-equal.

    ``layout="rowcol"``: A splits ``(0, None)`` — local ``(Mp/r, Kp)`` —
    against B splits ``(None, 1)`` — local ``(Kp, Np/c)``.  Every device
    already holds the full contraction extent for its output block, so
    the SAME L-panel accumulation runs rank-local with ZERO collectives;
    keeping the panel order (rather than one monolithic matmul) is what
    pins the result bitwise to the shared replicated twin.

    ``layout="colrow"``: A splits ``(None, 1)`` — local ``(Mp, Kp/c)``
    (the k axis sharded along the mesh columns) — against B splits
    ``(0, None)`` — local ``(Kp/r, Np)``.  The owner of panel ``t``
    slices its own row/column block of the panel before the masked psum,
    so the broadcasts ship exactly the grid schedule's bytes and the
    accumulation order is again panel-identical."""
    import jax
    from jax.sharding import PartitionSpec as P

    key = ("2d", comm, precision, w, overlapped, layout)
    cached = _SUMMA_CACHE.get(key)
    if cached is not None:
        return cached

    r, c = comm.mesh_shape
    ax0, ax1 = comm.axis_names
    L = r * c

    if layout == "rowcol":

        def panels(a_loc, b_loc, t):
            a_pan = jax.lax.dynamic_slice_in_dim(a_loc, t * w, w, 1)
            b_pan = jax.lax.dynamic_slice_in_dim(b_loc, t * w, w, 0)
            return a_pan, b_pan

    elif layout == "colrow":

        def panels(a_loc, b_loc, t):
            mloc = a_loc.shape[0] // r
            nloc = b_loc.shape[1] // c
            i = jax.lax.axis_index(ax0)
            j = jax.lax.axis_index(ax1)
            a_cand = jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_slice_in_dim(a_loc, i * mloc, mloc, 0),
                (t % r) * w, w, 1,
            )
            a_pan = jax.lax.psum(
                jnp.where(t // r == j, a_cand, jnp.zeros((), a_cand.dtype)),
                ax1,
            )
            b_cand = jax.lax.dynamic_slice_in_dim(
                jax.lax.dynamic_slice_in_dim(b_loc, j * nloc, nloc, 1),
                (t % c) * w, w, 0,
            )
            b_pan = jax.lax.psum(
                jnp.where(t // c == i, b_cand, jnp.zeros((), b_cand.dtype)),
                ax0,
            )
            return a_pan, b_pan

    else:

        def panels(a_loc, b_loc, t):
            a_cand = jax.lax.dynamic_slice_in_dim(a_loc, (t % r) * w, w, 1)
            a_pan = jax.lax.psum(
                jnp.where(t // r == jax.lax.axis_index(ax1), a_cand,
                          jnp.zeros((), a_cand.dtype)),
                ax1,
            )
            b_cand = jax.lax.dynamic_slice_in_dim(b_loc, (t % c) * w, w, 0)
            b_pan = jax.lax.psum(
                jnp.where(t // c == jax.lax.axis_index(ax0), b_cand,
                          jnp.zeros((), b_cand.dtype)),
                ax0,
            )
            return a_pan, b_pan

    def kern(a_loc, b_loc):
        if layout == "colrow":
            out_shape = (a_loc.shape[0] // r, b_loc.shape[1] // c)
        else:
            out_shape = (a_loc.shape[0], b_loc.shape[1])
        acc0 = pcast(
            jnp.zeros(out_shape, a_loc.dtype),
            (ax0, ax1), to="varying",
        )
        if overlapped:

            def body(t, carry):
                a_pan, b_pan, acc = carry
                nxt = panels(a_loc, b_loc, jnp.minimum(t + 1, L - 1))
                acc = acc + jnp.matmul(a_pan, b_pan, precision=precision)
                return nxt + (acc,)

            first = panels(a_loc, b_loc, 0)
            _, _, acc = jax.lax.fori_loop(0, L, body, first + (acc0,))
        else:

            def body(t, acc):
                a_pan, b_pan = panels(a_loc, b_loc, t)
                return acc + jnp.matmul(a_pan, b_pan, precision=precision)

            acc = jax.lax.fori_loop(0, L, body, acc0)
        return acc

    in_specs = {
        "grid": (P(ax0, ax1), P(ax0, ax1)),
        "rowcol": (P(ax0, None), P(None, ax1)),
        "colrow": (P(None, ax1), P(ax0, None)),
    }[layout]
    fn = jax.jit(
        shard_map(
            kern, mesh=comm.mesh,
            in_specs=in_specs,
            out_specs=P(ax0, ax1),
            check_vma=False,
        )
    )
    _SUMMA_CACHE[key] = fn
    return fn


def _summa_grid(aa, ba, dims, comm, precision, layout: str = "grid"):
    """Dispatch wrapper of the grid SUMMA: pads both operands' k axes to
    the panel grid ``Kp = r*c*w`` (``w = ceil(k / (r*c))``; ``Kp`` is >=
    both at-rest padded k extents, so the pad only grows and stays
    divisible), commits the layout's splits, and launches the ONE
    compiled program — explicitly counted via :func:`record_dispatch`,
    credited to the telemetry ledger with figures straight from
    :func:`heat_tpu.comm._costs.summa_grid_model` (delegation keeps the
    accounted and modeled bytes byte-identical), and timed under the
    overlap policy.

    ``layout`` picks the operand schedule (see :func:`_summa_grid_fn`):
    ``"grid"`` for ``(0,1)×(0,1)``, ``"rowcol"`` for ``(0,None)×(None,1)``
    (rank-local, zero wire — the overlap policy is moot, so the serial
    arm always runs), ``"colrow"`` for ``(None,1)×(0,None)``."""
    import jax

    from ...comm import _costs
    from ...comm.overlap import overlap_enabled, timed_dispatch

    m, k, n = dims
    r, c = comm.mesh_shape
    L = r * c
    w = -(-k // L)
    Kp = L * w
    if aa.shape[1] != Kp:
        aa = jnp.pad(aa, ((0, 0), (0, Kp - aa.shape[1])))
    if ba.shape[0] != Kp:
        ba = jnp.pad(ba, ((0, Kp - ba.shape[0]), (0, 0)))
    if layout == "colrow":
        # the unsharded result axes must land on the r×c output grid
        Mp = r * (-(-m // r))
        Np = c * (-(-n // c))
        if aa.shape[0] != Mp:
            aa = jnp.pad(aa, ((0, Mp - aa.shape[0]), (0, 0)))
        if ba.shape[1] != Np:
            ba = jnp.pad(ba, ((0, 0), (0, Np - ba.shape[1])))
    splits_a, splits_b = {
        "grid": ((0, 1), (0, 1)),
        "rowcol": ((0, None), (None, 1)),
        "colrow": ((None, 1), (0, None)),
    }[layout]
    aa = comm.apply_sharding(aa, splits_a)
    ba = comm.apply_sharding(ba, splits_b)
    ov = overlap_enabled(L) if layout != "rowcol" else False
    fn = _summa_grid_fn(comm, precision, w, ov, layout)
    if isinstance(aa, jax.core.Tracer) or isinstance(ba, jax.core.Tracer):
        return fn(aa, ba)
    record_dispatch()
    if _tel.enabled:
        model = _costs.summa_grid_model(m, k, n, (r, c), overlap=ov, layout=layout)
        _tel.account_bytes(
            "summa2d", "f32", model["exact_wire_bytes"], model["wire_bytes"]
        )
        with _tel.span("comm:summa2d", "comm", mesh=f"{r}x{c}", panels=L, layout=layout):
            return timed_dispatch("summa2d", ov, lambda: fn(aa, ba))
    return timed_dispatch("summa2d", ov, lambda: fn(aa, ba))


def matmul(
    a: DNDarray,
    b: DNDarray,
    out: Optional[DNDarray] = None,
    precision: Optional[str] = None,
) -> DNDarray:
    """Matrix product of two DNDarrays (reference basics.py:71-787).

    All four split combinations are supported.  For 2-D operands with
    splits 00/01/11 on a 1-D mesh a ring SUMMA (shard_map + ppermute)
    keeps per-device memory at O(1/p) — GSPMD's plan for those combos
    all-gathers a full operand (see _summa).  Split 10 contracts the
    shared axis: GSPMD's single result all-reduce IS the right schedule
    there, and the other cases (vectors, batched) keep the compiler plan
    too.  On a 2-D (grid) mesh, operands both laid out splits ``(0, 1)``
    run the grid SUMMA (:func:`_summa_grid_fn`): k-panel broadcasts on
    the row/column sub-rings, one compiled dispatch, per-device memory
    O(mn/rc + panels) — the payoff workload of arXiv 2112.09017.

    ``out`` receives the result values in place.  ``precision`` overrides
    the process-wide matmul precision for this call (``'default'`` |
    ``'float32'`` | ``'highest'``, see :func:`set_matmul_precision`).
    """
    sanitize_in(a)
    sanitize_in(b)
    if precision is None:
        prec = _precision()
    elif precision in ("default", "float32", "highest"):
        prec = None if precision == "default" else precision
    else:
        raise ValueError(f"invalid precision {precision!r}")
    if a.ndim == 0 or b.ndim == 0:
        raise ValueError("matmul does not accept 0-d operands (use mul)")
    # numpy contraction rule: last axis of a against b's second-to-last
    # (or only) axis — mismatches are the reference's ValueError contract
    # (basics.py:83-96), not a backend TypeError
    k_a = a.shape[-1]
    k_b = b.shape[-2] if b.ndim >= 2 else b.shape[0]
    if k_a != k_b:
        raise ValueError(
            f"matmul shape mismatch: {a.shape} @ {b.shape} "
            f"(contracting {k_a} vs {k_b})"
        )
    # batched operands: leading dims must broadcast, same ValueError contract
    if a.ndim > 2 or b.ndim > 2:
        batch_a = a.shape[:-2] if a.ndim > 2 else ()
        batch_b = b.shape[:-2] if b.ndim > 2 else ()
        for da, db in zip(reversed(batch_a), reversed(batch_b)):
            if da != db and da != 1 and db != 1:
                raise ValueError(
                    f"matmul batch dimensions do not broadcast: "
                    f"{a.shape} @ {b.shape} ({da} vs {db})"
                )
    promoted = types.promote_types(a.dtype, b.dtype)
    jt = promoted.jax_type()
    comm = a.comm
    grid_layout = None
    if a.ndim == 2 and b.ndim == 2 and comm.mesh_ndim == 2 and comm.size > 1:
        if a.splits == (0, 1) and b.splits == (0, 1):
            grid_layout = "grid"
        elif a.splits == (0, None) and b.splits == (None, 1):
            grid_layout = "rowcol"
        elif a.splits == (None, 1) and b.splits == (0, None):
            grid_layout = "colrow"
    if grid_layout is not None:
        # grid SUMMA on the r×c mesh — "grid" for (0,1)×(0,1) operands,
        # plus the rank-local schedules: "rowcol" (0,None)×(None,1) runs
        # the same panel accumulation with ZERO wire, "colrow"
        # (None,1)×(0,None) ships the grid schedule's bytes while eliding
        # the two planned redistributions.  BOTH operands ship the ZEROED
        # buffer — at-rest pad values are unspecified and can be
        # non-finite, and 0 * inf = NaN would poison the k-sum (the same
        # discipline as the 1-D combos below)
        aa = a._zeroed_buffer()
        ba = b._zeroed_buffer()
        aa = aa.astype(jt) if aa.dtype != jt else aa
        ba = ba.astype(jt) if ba.dtype != jt else ba
        garr = _summa_grid(
            aa, ba, (a.shape[0], a.shape[1], b.shape[1]), comm, prec,
            grid_layout,
        )
        result = DNDarray(
            garr, (a.shape[0], b.shape[1]), promoted, (0, 1), a.device, comm, True
        )
    elif (
        a.ndim == 2
        and b.ndim == 2
        and comm.mesh_ndim == 1
        and comm.size > 1
        and (a.split, b.split) in ((0, 0), (0, 1), (1, 1))
    ):
        # ring SUMMA: O(1/p) per-device memory where GSPMD would
        # all-gather a full operand (tests/test_hlo_matmul.py pins this)
        # the operand whose SPLIT axis is the contraction axis ships the
        # ZEROED buffer: at-rest pad values are unspecified and can be
        # non-finite (ht.log leaves -inf pad rows), and 0 * inf = NaN
        # would poison every real output element through the k-sum
        zero_a = (a.split, b.split) == (1, 1)  # a's axis 1 == k
        zero_b = (a.split, b.split) == (0, 0)  # b's axis 0 == k
        aa = (a._zeroed_buffer() if zero_a else a._buffer).astype(jt)
        ba = (b._zeroed_buffer() if zero_b else b._buffer).astype(jt)
        garr, split = _summa(aa, ba, a.split, b.split, comm, prec)
        if (a.split, b.split) == (0, 1):
            garr = garr[:, : b.shape[1]]  # drop B's column padding
        result = DNDarray(
            garr, (a.shape[0], b.shape[1]), promoted, split, a.device, comm, True
        )
    else:
        aa = a.larray.astype(jt)
        ba = b.larray.astype(jt)
        garr = jnp.matmul(aa, ba, precision=prec)
        split = _result_split_matmul(a, b, garr.ndim)
        garr = comm.apply_sharding(garr, split)
        result = DNDarray(
            garr, tuple(garr.shape), promoted, split, a.device, comm, True
        )
    if out is not None:
        sanitize_in(out)
        out.larray = result.larray
        return out
    return result


def dot(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None):
    """Dot product (reference basics.py:16-70: 1-D = local dot + Allreduce;
    2-D delegates to matmul; scalars multiply)."""
    if isinstance(a, DNDarray) and isinstance(b, DNDarray):
        if a.ndim == 0 or b.ndim == 0:
            from .. import arithmetics

            return arithmetics.mul(a, b)
        if a.ndim == 1 and b.ndim == 1:
            res = jnp.dot(a.larray, b.larray, precision=_precision())
            result = DNDarray(
                res, (), types.promote_types(a.dtype, b.dtype), None, a.device, a.comm, True
            )
            if out is not None:
                out.larray = result.larray
                return out
            return result
        return matmul(a, b, out=out)
    from .. import arithmetics

    return arithmetics.mul(a, b)


def matrix_norm(a: DNDarray, ord=None) -> DNDarray:
    """Frobenius norm of a matrix (numpy-parity helper over the reference's
    single ``norm``, basics.py:788-811)."""
    sanitize_in(a)
    res = jnp.linalg.norm(a.larray.astype(jnp.float32) if types.heat_type_is_exact(a.dtype) else a.larray, ord=ord)
    return DNDarray(res, (), types.canonical_heat_type(res.dtype), None, a.device, a.comm, True)


def _psum_scalar(s, axes):
    """Allreduce a scalar partial over every sharded mesh axis.

    Pass-through collective helper: ``axes`` is bound at the call site
    from the comm's ``axis_names`` for exactly the mesh axes the
    enclosing shard_map shards over, so the call site carries the
    axis-name proof (the spec itself comes from ``comm.spec`` and is not
    statically visible to the linter)."""
    import jax

    return jax.lax.psum(s, axes)


def norm(a: DNDarray) -> DNDarray:
    """Frobenius/2-norm of the whole array
    (reference basics.py:788-811: sqrt of distributed dot).

    Returns a 0-d DNDarray.  Sharded inputs (any 1-D split or grid splits
    tuple) reduce via an exact psum of per-shard partial sums of squares
    inside ONE jitted program — no host round trip and no device-wide
    gather.  The old implementation coerced the traced value through
    ``float(jnp.sqrt(...))``, the SPMD202 host-sync shape
    (tests/test_spmdlint.py pins the regression fixture); callers that
    want a python scalar apply ``float()`` to the returned 0-d array,
    which is then an explicit, caller-chosen sync point."""
    sanitize_in(a)
    comm = a.comm
    dtype = a.dtype if types.heat_type_is_inexact(a.dtype) else types.float32
    jt = dtype.jax_type()
    splits = a.splits
    sharded = comm.size > 1 and a.ndim > 0 and any(g is not None for g in splits)
    if not sharded:
        arr = a.larray
        key = ("linalg.norm", comm, a.ndim, str(arr.dtype), str(jt))

        def make():
            def _f(x):
                x = x.astype(jt) if x.dtype != jt else x
                return jnp.sqrt(jnp.sum(x * x))

            return _f

        res = jitted(key, make)(arr)
    else:
        # pads of every sharded dim are forced to zero so the local
        # sum-of-squares is exact over real elements only
        arr = a._zeroed_buffer()
        spec = comm.spec(a.ndim, splits)
        axes = tuple(
            comm.axis_names[g] for g in splits if g is not None
        )
        key = (
            "linalg.norm", comm, splits,
            tuple(int(s) for s in arr.shape), str(arr.dtype), str(jt),
        )

        def make():
            def kern(x):
                x = x.astype(jt) if x.dtype != jt else x
                return jnp.sqrt(_psum_scalar(jnp.sum(x * x), axes))

            return shard_map(
                kern, mesh=comm.mesh, in_specs=(spec,),
                out_specs=PartitionSpec(), check_vma=False,
            )

        res = jitted(key, make)(arr)
    return DNDarray(res, (), dtype, None, a.device, comm, True)


def vector_norm(a: DNDarray, ord=2) -> DNDarray:
    """Vector p-norm (numpy-parity helper)."""
    sanitize_in(a)
    arr = a.larray
    if types.heat_type_is_exact(a.dtype):
        arr = arr.astype(jnp.float32)
    res = jnp.linalg.norm(arr.reshape(-1), ord=ord)
    return DNDarray(res, (), types.canonical_heat_type(res.dtype), None, a.device, a.comm, True)


def outer(a: DNDarray, b: DNDarray, out: Optional[DNDarray] = None, split: Optional[int] = None) -> DNDarray:
    """Outer product of two vectors (reference basics.py:812-1050 — a ring
    exchange of the smaller operand; here one sharded jnp.outer, with the
    requested result split applied)."""
    sanitize_in(a)
    sanitize_in(b)
    promoted = types.promote_types(a.dtype, b.dtype)
    garr = jnp.outer(a.larray.astype(promoted.jax_type()), b.larray.astype(promoted.jax_type()))
    if split is None:
        split = 0 if (a.split is not None or b.split is not None) else None
    split = sanitize_axis(garr.shape, split)
    garr = a.comm.apply_sharding(garr, split)
    result = DNDarray(garr, tuple(garr.shape), promoted, split, a.device, a.comm, True)
    if out is not None:
        out.larray = result.larray
        return out
    return result


def projection(a: DNDarray, b: DNDarray) -> DNDarray:
    """Projection of vector a onto vector b (reference basics.py:1051-1077)."""
    sanitize_in(a)
    sanitize_in(b)
    if a.ndim != 1 or b.ndim != 1:
        raise RuntimeError(f"projection requires 1-D vectors, got {a.ndim}-d and {b.ndim}-d")
    from .. import arithmetics

    scale = dot(a, b).item() / dot(b, b).item()
    return arithmetics.mul(b, scale)


def transpose(a: DNDarray, axes: Optional[List[int]] = None) -> DNDarray:
    """Permute axes (reference basics.py:1078-1146: local permute + split
    remap)."""
    sanitize_in(a)
    if axes is None:
        axes = tuple(reversed(range(a.ndim)))
    else:
        axes = tuple(int(ax) % a.ndim for ax in axes)
        if len(axes) != a.ndim or len(set(axes)) != a.ndim:
            raise ValueError("axes do not match array")
    garr = jnp.transpose(a.larray, axes)
    split = axes.index(a.split) if a.split is not None else None
    garr = a.comm.apply_sharding(garr, split)
    return DNDarray(garr, tuple(garr.shape), a.dtype, split, a.device, a.comm, a.balanced)


def __tri_op(m: DNDarray, k: int, op) -> DNDarray:
    """Shared tril/triu core (reference basics.py:1147-1221 — per-rank
    diagonal offsets; here one global masked op)."""
    sanitize_in(m)
    if m.ndim < 2:
        # numpy semantics: a 1-D input becomes a 2-D matrix replicating the vector
        garr = op(jnp.vstack([m.larray] * m.shape[0]), k=k)
        split = m.split
        garr = m.comm.apply_sharding(garr, split)
        return DNDarray(garr, tuple(garr.shape), m.dtype, split, m.device, m.comm, True)
    garr = op(m.larray, k=k)
    garr = m.comm.apply_sharding(garr, m.split)
    return DNDarray(garr, tuple(garr.shape), m.dtype, m.split, m.device, m.comm, m.balanced)


def tril(m: DNDarray, k: int = 0) -> DNDarray:
    """Lower-triangular part (reference basics.py:1222-1246)."""
    return __tri_op(m, k, jnp.tril)


def triu(m: DNDarray, k: int = 0) -> DNDarray:
    """Upper-triangular part (reference basics.py:1247-1269)."""
    return __tri_op(m, k, jnp.triu)


# split semantics for heat_tpu.analysis.splitflow (see core/_split_semantics.py)
from .._split_semantics import declare_split_semantics_table  # noqa: E402

declare_split_semantics_table(
    __name__,
    {
        "matmul": ("matmul", "dot"),
        "transpose": ("transpose",),
        "elementwise": ("tril", "triu"),
    },
)
