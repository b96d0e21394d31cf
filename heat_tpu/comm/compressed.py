"""Compressed collectives: block-scaled quantized ring allreduce/allgather.

Every collective in :mod:`heat_tpu.core.communication` ships full-precision
words over the interconnect.  For bandwidth-bound paths (moment reductions,
Lasso/GaussianNB fit loops, wide all-gathers) that is 4x the bytes the
algorithm needs: EQuARX-style block-scaled quantization (arXiv 2506.17615)
recovers most of the wire time at negligible accuracy cost.  This module is
the plannable compressed layer under the comm seam:

``allreduce_q`` / ``allgather_q``
    Drop-in compressed twins of :meth:`XlaCommunication.allreduce` /
    :meth:`XlaCommunication.allgather`.  Both are two-stage ring programs
    inside ``shard_map`` — reduce-scatter then all-gather, one
    :func:`jax.lax.ppermute` hop per step — whose payloads are block-scaled
    int8 (one f32 scale per :data:`BLOCK` values) or bf16.  Quantize /
    dequantize is fused into each ring step via a Pallas kernel
    (interpret-mode on CPU); each call is ONE compiled dispatch, the bytes
    never round-trip through the host.

``ring_allreduce_q`` / ``ring_allgather_q``
    The in-kernel forms, callable inside an existing ``shard_map`` body
    (axis name passed explicitly, like ``jax.lax.psum``).  The ``*_ef``
    variant threads an **error-feedback accumulator**: the residual
    ``e' = (x + e) - deQ(Q(x + e))`` is exactly the part of the local
    contribution that was never transmitted, so iterative algorithms
    (Lasso proximal-gradient, k-means centroid updates) re-inject it next
    round and compression error does not bias convergence.

Precision policy
    Mirrors ``set_matmul_precision``: a process-wide mode
    (``"f32"`` | ``"bf16"`` | ``"int8_block"`` | ``"auto"``) consulted by
    the comm layer and the fused reduce paths, so ML modules pick up
    compression with **no call-site changes**.  ``"f32"`` (the default)
    keeps every existing numeric bit-identical; ``"auto"`` compresses only
    payloads at least :func:`get_collective_threshold` bytes.  The policy
    is part of every program cache key (:func:`heat_tpu.core._compile.jitted`
    and the ``ht.fuse`` cache), so flipping it retraces rather than
    replaying a stale program.

Wire format (int8_block): a payload of n f32 values is padded to a
multiple of ``BLOCK`` = 128 (the TPU lane width) and sent as
``(n_blocks, 128) int8`` plus ``(n_blocks, 1) float32`` scales, where
``scale = max(|block|) / 127`` and ``q = round(x / scale)``.  That is
``(1 + 4/128)/4 ~ 0.258x`` the exact-f32 bytes.  Per-element roundtrip
error is at most ``scale/2 = max|block|/254``; across a p-device ring the
reduce-scatter re-quantizes each partial sum once per hop, so the
documented worst-case bound on the reduced value is
``p * max_k(absmax_k) / 254`` per element (k ranging over the blocks that
position contributed to) — in practice far smaller, and zero for all-zero
blocks (exact zeros survive quantization exactly).

Non-finite payloads: a block containing NaN/±Inf has a non-finite absmax;
that absmax itself is transmitted as the block scale (with q == 1), so the
decoded block is uniformly that non-finite value — deterministic
propagation instead of an implementation-defined int8 pattern.  Likewise a
reduce-scatter partial sum that overflows f32 propagates as ±Inf.  The
numerical health guards (:mod:`heat_tpu.resilience.guards`) detect both at
the host boundary and can degrade the affected call to the exact f32 path.
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec

from ..core._compile import jitted, register_key_context
from ..core.communication import sanitize_comm
from ..telemetry import _core as _tel
from . import _costs
from .overlap import overlap_enabled, timed_dispatch

__all__ = [
    "BLOCK",
    "allgather_q",
    "allreduce_q",
    "collective_precision",
    "dequantize_blocks",
    "get_collective_precision",
    "get_collective_threshold",
    "quantize_blocks",
    "reduce_mode",
    "ring_allgather_q",
    "ring_allreduce_q",
    "ring_allreduce_q_ef",
    "set_collective_precision",
    "set_collective_threshold",
    "wire_model",
]

#: Quantization block length: one f32 scale per this many payload values.
#: 128 is the TPU lane width, so every block is one register row and the
#: scale overhead is 4/128 bytes/value (wire ratio ~0.258x of exact f32).
#: Canonically defined in the shared jax-free cost model (comm/_costs.py)
#: so the static analyzer and the kernels agree by construction.
BLOCK = _costs.BLOCK

_MODES = ("f32", "bf16", "int8_block", "auto")
_PRECISION = "f32"
#: "auto" compresses only payloads of at least this many bytes (small
#: control messages — shapes, counts, scalars — stay exact).
_AUTO_THRESHOLD = 1 << 16

#: Pallas quantize path: int8 stores tile as (32, 128) on TPU, so the
#: fused kernel only engages when the block-rows divide the sublane tile;
#: other shapes take the identical jnp formulation (XLA fuses it anyway).
_PALLAS_ROWS = 32
#: ... and up to the largest payload the kernel is compiled for against the
#: chip's compiler (tests/test_tpu_compile.py).
_PALLAS_MAX_ELEMS = 1 << 22
#: Row-grid tile of the fused kernels: one (tile, BLOCK) slab and its
#: (tile, 1) scales — which VMEM pads to 128 lanes — live on chip at a time,
#: never the whole payload.  Rows conform to ``_PALLAS_ROWS``, so a
#: power-of-two tile between the two always divides them.
_PALLAS_TILE_ROWS = 1024


# --------------------------------------------------------------------- #
# precision policy (mirrors core.linalg.set_matmul_precision)           #
# --------------------------------------------------------------------- #
def set_collective_precision(precision: str) -> None:
    """Set the process-wide collective compression mode.

    ``"f32"``
        Exact collectives (the default) — bit-identical to the seed.
    ``"bf16"``
        Payloads cast to bfloat16 on the wire (2x fewer bytes).
    ``"int8_block"``
        Block-scaled int8 payloads (~0.26x the bytes, see module docs).
    ``"auto"``
        ``int8_block`` for payloads >= :func:`get_collective_threshold`
        bytes, exact below.

    Only float32/bfloat16 payloads are ever compressed; float64 and
    integer/exact dtypes always go exact regardless of the policy (the
    static analog is spmdlint rule SPMD203).
    """
    global _PRECISION
    if precision not in _MODES:
        raise ValueError(
            f"unknown collective precision {precision!r}: expected one of {_MODES}"
        )
    _PRECISION = precision


def get_collective_precision() -> str:
    """The current process-wide collective compression mode."""
    return _PRECISION


@contextlib.contextmanager
def collective_precision(precision: str):
    """Context manager form of :func:`set_collective_precision`."""
    prev = _PRECISION
    set_collective_precision(precision)
    try:
        yield
    finally:
        set_collective_precision(prev)


def set_collective_threshold(nbytes: int) -> None:
    """Minimum payload size (bytes) that ``"auto"`` mode compresses."""
    global _AUTO_THRESHOLD
    nbytes = int(nbytes)
    if nbytes < 0:
        raise ValueError("threshold must be non-negative")
    _AUTO_THRESHOLD = nbytes


def get_collective_threshold() -> int:
    """Current ``"auto"``-mode payload-size threshold in bytes."""
    return _AUTO_THRESHOLD


@register_key_context
def _policy_token() -> Tuple:
    """The policy's contribution to every compiled-program cache key.

    Registered with :func:`heat_tpu.core._compile.register_key_context`,
    so a policy flip can never replay a program traced under a different
    wire format — it keys a fresh entry instead (ISSUE: "the policy
    becomes part of the program cache key").
    """
    return ("commq", _PRECISION, _AUTO_THRESHOLD)


def _compressible(dtype) -> bool:
    dt = jnp.dtype(dtype)
    return dt == jnp.dtype(jnp.float32) or dt == jnp.dtype(jnp.bfloat16)


def reduce_mode(dtype, payload_nbytes: int, precision: Optional[str] = None):
    """Resolve the wire mode for a payload: ``"bf16"`` / ``"int8_block"``,
    or ``None`` when the collective must stay exact.

    ``None`` comes back for the default ``"f32"`` policy, for ``"auto"``
    payloads under the size threshold, and for non-compressible dtypes
    (f64, integers, bool) — those always ride exact.  An *explicit*
    compressed ``precision`` on an exact dtype is a contract violation and
    raises (runtime twin of spmdlint SPMD203).
    """
    p = precision if precision is not None else _PRECISION
    if p not in _MODES:
        raise ValueError(
            f"unknown collective precision {p!r}: expected one of {_MODES}"
        )
    if p != "f32" and not _compressible(dtype) and precision is not None:
        raise TypeError(
            f"quantized collective requested on exact dtype "
            f"{jnp.dtype(dtype).name}: only float32/bfloat16 payloads "
            "compress (SPMD203)"
        )
    return _costs.resolve_mode(
        jnp.dtype(dtype).name, payload_nbytes, p, _AUTO_THRESHOLD
    )


# --------------------------------------------------------------------- #
# block-scaled quantization (Pallas-fused, jnp fallback)                #
# --------------------------------------------------------------------- #
@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
    """Pallas interpret mode everywhere but real TPU hardware."""
    return jax.default_backend() != "tpu"


def _q_kernel(x_ref, q_ref, s_ref):
    x = x_ref[:]
    absmax = jnp.max(jnp.abs(x), axis=1, keepdims=True)
    finite = jnp.isfinite(absmax)
    # Non-finite payloads must quantize DETERMINISTICALLY: casting a NaN
    # (round(NaN/scale)) to int8 is implementation-defined, so a block
    # whose absmax is NaN/Inf instead emits q == 1 with the non-finite
    # absmax itself as the scale — dequantize yields the whole block as
    # that non-finite value (propagation, not silent garbage).  Finite
    # blocks take the exact pre-existing formula, bit for bit.
    scale = jnp.where(
        jnp.logical_and(finite, absmax > 0.0),
        absmax / 127.0,
        jnp.where(finite, jnp.float32(1.0), absmax),
    )
    q_ref[:] = jnp.where(finite, jnp.round(x / scale), jnp.float32(1.0)).astype(jnp.int8)
    s_ref[:] = scale


def _dq_kernel(q_ref, s_ref, o_ref):
    o_ref[:] = q_ref[:].astype(jnp.float32) * s_ref[:]


def _use_pallas(rows: int, block: int) -> bool:
    return (
        rows > 0
        and block == BLOCK
        and rows % _PALLAS_ROWS == 0
        and rows * block <= _PALLAS_MAX_ELEMS
    )


def _row_tiled(kernel, operands, out_widths, out_dtypes):
    """Run a row-independent kernel over a 1-D grid of row tiles: every
    operand and output is ``(rows, width)``, blocked ``(tile, width)``."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    rows = operands[0].shape[0]
    tile = _PALLAS_TILE_ROWS
    while rows % tile:
        tile //= 2
    spec = lambda width: pl.BlockSpec((tile, width), lambda i: (i, 0))
    # x64 off: python-int literals in the index maps would otherwise trace
    # as i64, which Mosaic rejects (same guard as parallel/flash_attention)
    with jax.enable_x64(False):
        return pl.pallas_call(
            kernel,
            grid=(rows // tile,),
            in_specs=[spec(x.shape[1]) for x in operands],
            out_specs=tuple(spec(w) for w in out_widths),
            out_shape=tuple(
                jax.ShapeDtypeStruct((rows, w), dt)
                for w, dt in zip(out_widths, out_dtypes)
            ),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=_interpret(),
        )(*operands)


def quantize_blocks(x, block: int = BLOCK):
    """Block-scale a flat f32 payload: ``(rows, block) int8`` +
    ``(rows, 1) float32`` scales, ``rows = len(x) / block`` (x must be
    1-D f32 with length a multiple of ``block``).  Dispatches the fused
    Pallas kernel when the shape conforms to the int8 tile grid, the
    identical jnp formulation otherwise."""
    rows = x.shape[0] // block
    x2 = x.reshape(rows, block)
    if _use_pallas(rows, block):
        return _row_tiled(
            _q_kernel, (x2,), (block, 1), (jnp.int8, jnp.float32)
        )
    # identical formulation to _q_kernel, including the deterministic
    # non-finite propagation (Pallas/jnp bit-parity is load-bearing)
    absmax = jnp.max(jnp.abs(x2), axis=1, keepdims=True)
    finite = jnp.isfinite(absmax)
    scale = jnp.where(
        jnp.logical_and(finite, absmax > 0.0),
        absmax / 127.0,
        jnp.where(finite, jnp.float32(1.0), absmax),
    )
    return jnp.where(finite, jnp.round(x2 / scale), jnp.float32(1.0)).astype(jnp.int8), scale


def dequantize_blocks(q, scales):
    """Inverse of :func:`quantize_blocks`: flat f32 payload of length
    ``q.size``."""
    rows, block = q.shape
    if _use_pallas(rows, block):
        (out,) = _row_tiled(_dq_kernel, (q, scales), (block,), (jnp.float32,))
        return out.reshape(rows * block)
    return (q.astype(jnp.float32) * scales).reshape(rows * block)


def _encode(flat, mode: str, block: int):
    """Flat f32 (length multiple of ``block``) -> tuple of wire leaves."""
    if mode == "bf16":
        return (flat.astype(jnp.bfloat16),)
    return quantize_blocks(flat, block)


def _decode(payload, mode: str):
    """Wire leaves -> flat f32."""
    if mode == "bf16":
        return payload[0].astype(jnp.float32)
    return dequantize_blocks(*payload)


def _roundtrip(flat, mode: str, block: int):
    """``deQ(Q(flat))`` — what the first ring hop actually transmits."""
    return _decode(_encode(flat, mode, block), mode)


def _padded_len(n: int, block: int) -> int:
    return max(block, -(-n // block) * block)


# --------------------------------------------------------------------- #
# in-kernel ring primitives (call inside shard_map, like lax.psum)      #
# --------------------------------------------------------------------- #
def ring_allreduce_q(value, axis_name, *, size: int, mode: str, block: int = BLOCK):
    """Compressed ring all-reduce (sum) of ``value`` over ``axis_name``;
    call inside a ``shard_map`` body spanning ``size`` devices.

    Two stages, ``size - 1`` ``ppermute`` hops each: a reduce-scatter in
    which every hop re-quantizes the running partial sum of one chunk,
    then an all-gather in which each fully-reduced chunk is quantized
    exactly ONCE and the same bytes are forwarded around the ring — all
    devices decode identical payloads, so the result is bit-identical
    across positions (safe to declare replicated).

    Under the overlap policy (:mod:`heat_tpu.comm.overlap`) each chunk is
    split at a block-aligned boundary into two independent streams whose
    encode → ppermute → decode chains interleave, so one stream's wire
    time hides behind the other's quantization math.  The reduce-scatter
    hops themselves are data-dependent (hop ``s+1`` ships what hop ``s``
    produced), which is why the latency hiding lives WITHIN each hop
    rather than across iterations.  Per-``block`` quantization is
    row-independent, so the split streams carry bit-identical payloads
    and the result is bitwise-equal to the serial body.
    """
    if size == 1:
        return value
    shape, dtype = value.shape, value.dtype
    n = int(math.prod(shape)) if shape else 1
    flat = value.reshape(-1).astype(jnp.float32)
    chunk = _padded_len(-(-n // size), block)
    total = size * chunk
    flat = jnp.pad(flat, (0, total - n))
    chunks = flat.reshape(size, chunk)
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]
    # both stream halves must be non-empty block multiples
    overlapped = overlap_enabled(size) and chunk >= 2 * block
    h = (chunk // block // 2) * block

    # stage 1 — reduce-scatter: position i accumulates chunk (i+1) mod size
    cur = jnp.take(chunks, idx, axis=0)
    if overlapped:
        for s in range(size - 1):
            add = jnp.take(chunks, (idx - s - 1) % size, axis=0)
            pa = _encode(cur[:h], mode, block)
            pa = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pa)
            pb = _encode(cur[h:], mode, block)
            pb = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pb)
            cur = jnp.concatenate(
                [_decode(pa, mode) + add[:h], _decode(pb, mode) + add[h:]]
            )
    else:
        for s in range(size - 1):
            payload = _encode(cur, mode, block)
            payload = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in payload)
            cur = _decode(payload, mode) + jnp.take(chunks, (idx - s - 1) % size, axis=0)

    # stage 2 — all-gather: quantize each reduced chunk once, forward the
    # bytes verbatim so every device decodes the same values
    out = jnp.zeros((size, chunk), jnp.float32)
    if overlapped:
        pa = _encode(cur[:h], mode, block)
        pb = _encode(cur[h:], mode, block)
        dec = jnp.concatenate([_decode(pa, mode), _decode(pb, mode)])
        out = jax.lax.dynamic_update_slice_in_dim(
            out, dec[None], (idx + 1) % size, axis=0
        )
        for s in range(size - 1):
            pa = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pa)
            pb = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pb)
            dec = jnp.concatenate([_decode(pa, mode), _decode(pb, mode)])
            out = jax.lax.dynamic_update_slice_in_dim(
                out, dec[None], (idx - s) % size, axis=0
            )
    else:
        payload = _encode(cur, mode, block)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, _decode(payload, mode)[None], (idx + 1) % size, axis=0
        )
        for s in range(size - 1):
            payload = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in payload)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, _decode(payload, mode)[None], (idx - s) % size, axis=0
            )
    return out.reshape(total)[:n].reshape(shape).astype(dtype)


def ring_allreduce_q_ef(value, error, axis_name, *, size: int, mode: str, block: int = BLOCK):
    """Error-feedback form: returns ``(reduced, new_error)``.

    The ring input is ``x + e`` (this round's value plus last round's
    untransmitted residual); the new residual is exactly the part of that
    the first quantization drops, ``(x + e) - deQ(Q(x + e))``, carried by
    the caller into the next iteration.  The quantization therefore
    introduces no accumulating bias into iterative algorithms.
    """
    xc = value.astype(jnp.float32) + error.astype(jnp.float32)
    if size == 1:
        return xc.astype(value.dtype), jnp.zeros_like(error)
    n = int(math.prod(xc.shape)) if xc.shape else 1
    flat = xc.reshape(-1)
    flat = jnp.pad(flat, (0, _padded_len(n, block) - n))
    vhat = _roundtrip(flat, mode, block)[:n].reshape(xc.shape)
    reduced = ring_allreduce_q(xc, axis_name, size=size, mode=mode, block=block)
    return reduced.astype(value.dtype), (xc - vhat).astype(error.dtype)


def ring_allgather_q(value, axis_name, *, size: int, mode: str, block: int = BLOCK):
    """Compressed ring all-gather: each position quantizes its ``value``
    once, the bytes make ``size - 1`` ``ppermute`` hops, and every
    position decodes the identical payloads into a stacked
    ``(size,) + value.shape`` result (row r = position r's value),
    bit-identical across devices.

    Under the overlap policy the payload is split into two block-aligned
    streams (see :func:`ring_allreduce_q`): each hop's two half-size
    ppermutes interleave with the halves' decodes, and decode-of-halves
    concatenated equals the serial decode bit for bit."""
    shape, dtype = value.shape, value.dtype
    if size == 1:
        return value[None]
    n = int(math.prod(shape)) if shape else 1
    flat = value.reshape(-1).astype(jnp.float32)
    padded = _padded_len(n, block)
    flat = jnp.pad(flat, (0, padded - n))
    idx = jax.lax.axis_index(axis_name)
    perm = [(i, (i + 1) % size) for i in range(size)]
    overlapped = overlap_enabled(size) and padded >= 2 * block
    h = (padded // block // 2) * block

    out = jnp.zeros((size, padded), jnp.float32)
    if overlapped:
        pa = _encode(flat[:h], mode, block)
        pb = _encode(flat[h:], mode, block)
        dec = jnp.concatenate([_decode(pa, mode), _decode(pb, mode)])
        out = jax.lax.dynamic_update_slice_in_dim(out, dec[None], idx, axis=0)
        for s in range(size - 1):
            pa = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pa)
            pb = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in pb)
            dec = jnp.concatenate([_decode(pa, mode), _decode(pb, mode)])
            out = jax.lax.dynamic_update_slice_in_dim(
                out, dec[None], (idx - s - 1) % size, axis=0
            )
    else:
        payload = _encode(flat, mode, block)
        out = jax.lax.dynamic_update_slice_in_dim(
            out, _decode(payload, mode)[None], idx, axis=0
        )
        for s in range(size - 1):
            payload = tuple(jax.lax.ppermute(leaf, axis_name, perm) for leaf in payload)
            out = jax.lax.dynamic_update_slice_in_dim(
                out, _decode(payload, mode)[None], (idx - s - 1) % size, axis=0
            )
    return out[:, :n].reshape((size,) + shape).astype(dtype)


# --------------------------------------------------------------------- #
# host-level collectives (XlaCommunication calling conventions)         #
# --------------------------------------------------------------------- #
def _resilience():
    """The fault-injection and health-guard seams.  Imported lazily: the
    resilience package sits ABOVE the comm layer in the import graph, and
    with no plans armed and guards off the seams cost two truthiness
    checks per call."""
    from ..resilience import faults, guards

    return faults, guards


def allreduce_q(
    array,
    op: str = "sum",
    comm=None,
    precision: Optional[str] = None,
    error=None,
    block: Optional[int] = None,
    axis_name: Optional[str] = None,
    size: Optional[int] = None,
):
    """Compressed twin of :meth:`XlaCommunication.allreduce`.

    ``array`` has shape ``(comm.size, ...)`` — one block per mesh
    position; the blocks are summed with the compressed ring and the
    result, shape ``(...)``, comes back replicated.  One compiled
    dispatch; the quantized bytes never visit the host.

    ``error`` (optional, same shape as ``array``) switches on error
    feedback: the call returns ``(result, new_error)`` with ``new_error``
    sharded like the input, to be passed back next iteration.

    Passing ``axis_name`` (and static ``size``) instead selects the
    in-kernel form for use inside an existing ``shard_map`` body, where
    ``array`` is the local contribution.  Only ``op="sum"`` compresses;
    other ops (and payloads the policy leaves exact) fall back to the
    exact collective.
    """
    mode = reduce_mode(
        getattr(array, "dtype", jnp.float32),
        _payload_nbytes(array, stacked=axis_name is None),
        precision,
    )
    if axis_name is not None:  # in-kernel form
        if size is None:
            raise ValueError("in-kernel allreduce_q needs the static mesh size")
        blk = int(block or BLOCK)
        if error is not None:
            return ring_allreduce_q_ef(
                array, error, axis_name, size=size, mode=mode or "bf16", block=blk
            )
        if mode is None:
            return jax.lax.psum(array, axis_name)
        return ring_allreduce_q(array, axis_name, size=size, mode=mode, block=blk)

    comm = sanitize_comm(comm)
    if op != "sum":
        if error is not None:
            raise ValueError(f"error feedback requires op='sum', got {op!r}")
        return comm.allreduce(array, op)
    if mode is None and error is None:
        # pin the ambient policy: comm.allreduce re-consults it, and an
        # explicit precision="f32" here (the guard's degrade path) must
        # stay exact even under a compressed ambient policy
        with collective_precision("f32"):
            return comm.allreduce(array, op)
    p = comm.size
    if int(array.shape[0]) != p:
        raise ValueError(
            f"allreduce_q expects one block per mesh position: leading axis "
            f"{array.shape[0]} != mesh size {p}"
        )
    if p == 1:
        if error is None:
            return jnp.squeeze(array, axis=0)
        return (
            jnp.squeeze(array, axis=0) + jnp.squeeze(error, axis=0).astype(array.dtype),
            jnp.zeros_like(error),
        )
    mesh, name = comm._mesh, comm.axis_name
    blk = int(block or BLOCK)
    shape = tuple(int(s) for s in array.shape)
    has_err = error is not None
    dt = jnp.dtype(array.dtype).name
    edt = jnp.dtype(error.dtype).name if has_err else None
    wire = mode  # None + error: exact transmission, residual is zero

    def make():
        def kernel(x, e=None):
            v = jnp.squeeze(x, axis=0)
            if e is None:
                return ring_allreduce_q(v, name, size=p, mode=wire, block=blk)
            ev = jnp.squeeze(e, axis=0)
            if wire is None:
                r = jax.lax.psum(v + ev.astype(v.dtype), name)
                return r, jnp.zeros_like(ev)[None]
            r, enew = ring_allreduce_q_ef(
                v, ev, name, size=p, mode=wire, block=blk
            )
            return r, enew[None]

        spec = PartitionSpec(name)
        if has_err:
            def _f(x, e):
                return shard_map(
                    kernel,
                    mesh=mesh,
                    in_specs=(spec, spec),
                    out_specs=(PartitionSpec(), spec),
                    check_vma=False,
                )(x, e)
        else:
            def _f(x):
                return shard_map(
                    kernel,
                    mesh=mesh,
                    in_specs=spec,
                    out_specs=PartitionSpec(),
                    check_vma=False,
                )(x)

        return _f

    fn = jitted(("commq.allreduce", comm, wire, blk, shape, dt, edt), make)
    faults, guards = _resilience()
    # the seams only exist at the eager host boundary: under a trace
    # (ht.fuse / user jit) injection would bake faults into the compiled
    # program and the health check cannot concretize — there the fused
    # program's own health output covers the call
    eager = not isinstance(array, jax.core.Tracer)
    payload = faults.comm_input("allreduce_q", array) if eager and faults.any_active() else array
    if _tel.enabled and eager:
        n_res = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        _account_wire("allreduce", wire, n_res, p)
        # whether THIS dispatch traced the two-stream latency-hiding body
        ring_ov = (
            wire is not None
            and overlap_enabled(p)
            and _padded_len(-(-n_res // p), blk) >= 2 * blk
        )
        with _tel.span("commq:allreduce", "comm", mode=wire or "f32", mesh=p):
            out = timed_dispatch(
                "allreduce_q", ring_ov,
                (lambda: fn(payload, error)) if has_err else (lambda: fn(payload)),
            )
    else:
        out = fn(payload, error) if has_err else fn(payload)
    if eager and faults.any_active():
        if has_err:
            out = (faults.comm_output("allreduce_q", out[0]), out[1])
        else:
            out = faults.comm_output("allreduce_q", out)
    if eager and wire is not None and guards.active():
        values = out if has_err else (out,)
        if not guards.is_healthy(*values):
            def _exact():
                # bit-identical to what set_collective_precision("f32")
                # would have produced for THIS call; uses the original
                # (pre-injection) operands
                return allreduce_q(
                    array, op, comm, precision="f32", error=error, block=block
                )

            return guards.handle("allreduce_q", out, _exact)
    return out


def _payload_nbytes(array, stacked: bool) -> int:
    """Wire bytes per ring payload: the result-sized block, i.e. the
    stacked input's bytes divided by its leading axis.  Computed from
    shape/dtype so tracers (fuse programs) size identically to arrays."""
    shape = tuple(getattr(array, "shape", ()) or ())
    elems = int(np.prod(shape)) if shape else 1
    nbytes = elems * jnp.dtype(getattr(array, "dtype", jnp.float32)).itemsize
    if stacked and shape:
        nbytes //= max(int(shape[0]), 1)
    return nbytes


def wire_model(n_elems: int, size: int, mode: Optional[str], *,
               block: int = BLOCK, op: str = "allreduce") -> dict:
    """Bytes-moved model for one ring collective, per device.

    The single source of the 0.258x claim: exact f32 ships 4 B/element,
    ``int8_block`` ships 1 B/element plus one f32 scale per ``block``
    elements (132/512 per 128-block), ``bf16`` 2 B/element.  ``op="
    allreduce"`` models the reduce-scatter + all-gather ring (each device
    sends ``2*(size-1)`` chunks of ``ceil(n/size)`` elements padded to
    the block grid); ``op="allgather"`` the one-way ring (``size-1`` hops
    of the ``n_elems``-element local shard).  The telemetry layer's live
    exact-vs-wire byte accounting (:func:`_account_wire`) is credited from
    this same model, so the accounted ratio and the tested exact-byte math
    cannot drift apart.  The arithmetic itself lives
    in the shared jax-free model (:mod:`heat_tpu.comm._costs`), which the
    static analyzer loads by file path."""
    return _costs.ring_wire_model(n_elems, size, mode, block=block, op=op)


def _account_wire(op: str, mode: Optional[str], n_elems: int, size: int,
                  reps: int = 1) -> None:
    """Credit ``reps`` ring invocations to the telemetry byte ledger
    (no-op unless telemetry is enabled; callers pre-check the flag)."""
    wm = wire_model(n_elems, size, mode, op=op)
    _tel.account_bytes(
        op, mode or "f32", wm["exact_wire_bytes"] * reps, wm["wire_bytes"] * reps
    )


def allgather_q(
    array,
    axis: int = 0,
    comm=None,
    precision: Optional[str] = None,
    block: Optional[int] = None,
):
    """Compressed twin of :meth:`XlaCommunication.allgather`: replicate an
    ``axis``-split global array, shipping each shard as block-scaled int8
    (or bf16) exactly once around the ring.  All devices decode the same
    bytes, so the replicated result is bit-identical across positions.
    Payloads the policy leaves exact — and ragged axes, where the shard
    layout is not canonical — fall back to the exact all-gather."""
    comm = sanitize_comm(comm)
    p = comm.size
    ndim = int(getattr(array, "ndim", 0))
    mode = reduce_mode(
        getattr(array, "dtype", jnp.float32), _payload_nbytes(array, stacked=False), precision
    )
    if mode is None or p == 1 or ndim == 0:
        # pin the policy for the same reason as allreduce_q: an explicit
        # precision="f32" must not bounce back through comm.allgather's
        # policy seam onto the quantized ring
        with collective_precision("f32"):
            return comm.allgather(array, axis=axis)
    axis = int(axis) % ndim
    if int(array.shape[axis]) % p != 0:
        with collective_precision("f32"):
            return comm.allgather(array, axis=axis)
    mesh, name = comm._mesh, comm.axis_name
    blk = int(block or BLOCK)
    shape = tuple(int(s) for s in array.shape)
    dt = jnp.dtype(array.dtype).name

    def make():
        def kernel(shard):
            moved = jnp.moveaxis(shard, axis, 0)
            stacked = ring_allgather_q(moved, name, size=p, mode=mode, block=blk)
            full = stacked.reshape((p * moved.shape[0],) + moved.shape[1:])
            return jnp.moveaxis(full, 0, axis)

        def _f(x):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=comm.spec(len(shape), axis),
                out_specs=PartitionSpec(),
                check_vma=False,
            )(x)

        return _f

    fn = jitted(("commq.allgather", comm, mode, blk, axis, shape, dt), make)
    faults, guards = _resilience()
    eager = not isinstance(array, jax.core.Tracer)  # see allreduce_q
    payload = faults.comm_input("allgather_q", array) if eager and faults.any_active() else array
    if _tel.enabled and eager:
        n_loc = int(np.prod(shape)) // p
        _account_wire("allgather", mode, n_loc, p)
        ring_ov = overlap_enabled(p) and _padded_len(n_loc, blk) >= 2 * blk
        with _tel.span("commq:allgather", "comm", mode=mode, mesh=p):
            out = timed_dispatch("allgather_q", ring_ov, lambda: fn(payload))
    else:
        out = fn(payload)
    if eager and faults.any_active():
        out = faults.comm_output("allgather_q", out)
    if eager and guards.active() and not guards.is_healthy(out):
        # the exact all-gather is precisely the "f32" policy's path
        return guards.handle(
            "allgather_q",
            out,
            lambda: allgather_q(array, axis=axis, comm=comm, precision="f32"),
        )
    return out


# --------------------------------------------------------------------- #
# fused reduction engines (the no-call-site-changes hooks)              #
# --------------------------------------------------------------------- #
def reduce_q(
    buffer,
    *,
    comm,
    split: int,
    axes: Tuple[int, ...],
    keepdims: bool,
    mode: str,
    mean_n: Optional[int] = None,
    out_dtype=None,
    block: Optional[int] = None,
    fields: Optional[dict] = None,
):
    """Compressed engine for ``sum``/``mean`` over axes covering the split.

    ``buffer`` is the canonically sharded (padded) global array split at
    ``split``; pad rows are zeros, so the local partial sum over ``axes``
    is exact and the cross-device combine rides the compressed ring.
    ``mean_n`` (the TRUE element count, pads excluded) turns the sum into
    a mean.  One compiled dispatch; result comes back replicated.
    ``fields`` joins the launch spans of the entry (:func:`jitted`).
    """
    p = comm.size
    mesh, name = comm._mesh, comm.axis_name
    blk = int(block or BLOCK)
    shape = tuple(int(s) for s in buffer.shape)
    dt = jnp.dtype(buffer.dtype).name
    odt = jnp.dtype(out_dtype or buffer.dtype)

    def make():
        def kernel(b):
            part = jnp.sum(b.astype(jnp.float32), axis=axes, keepdims=keepdims)
            red = ring_allreduce_q(part, name, size=p, mode=mode, block=blk)
            if mean_n is not None:
                red = red / jnp.float32(mean_n)
            return red.astype(odt)

        def _f(x):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=comm.spec(len(shape), split),
                out_specs=PartitionSpec(),
                check_vma=False,
            )(x)

        return _f

    key = ("commq.reduce", comm, mode, blk, split, axes, keepdims, mean_n, shape, dt, odt.name)
    return jitted(key, make, fields=fields)(buffer)


def moments_q(
    buffer,
    *,
    comm,
    split: int,
    axes: Tuple[int, ...],
    keepdims: bool,
    mode: str,
    true_n: int,
    split_valid: int,
    ddof: int = 0,
    finalize: str = "var",
    out_dtype=None,
    block: Optional[int] = None,
    fields: Optional[dict] = None,
):
    """Compressed var/std engine with CENTERED second moments.

    ``var = E[x^2] - E[x]^2`` is a catastrophic cancellation for
    non-centered data (``E[x^2] ~ mu^2 + var``): a block-scaled
    quantization error that is tiny *relative to the raw second moment*
    can exceed the variance outright.  So the first moment combines EXACT
    (a plain ``psum`` — it is also what centers the data), and only the
    centered sum of squared deviations rides the quantized ring, computed
    locally through the shifted-data identity

        sum_local (x - mu)^2 = sum x^2 - 2 mu sum_local x + c_local mu^2

    whose ring payload has magnitude ``~ var * n`` instead of
    ``~ mu^2 * n``.  ``c_local`` is the per-shard count of REAL (un-padded)
    elements — canonical zero pads would each contribute ``mu^2`` to the
    centered sum, so they are excluded via the shard's valid count.
    ``true_n`` is the real global element count of the reduction and
    ``split_valid`` the un-padded extent of the split axis."""
    p = comm.size
    mesh, name = comm._mesh, comm.axis_name
    blk = int(block or BLOCK)
    shape = tuple(int(s) for s in buffer.shape)
    dt = jnp.dtype(buffer.dtype).name
    odt = jnp.dtype(out_dtype or buffer.dtype)
    # real elements reduced per output element, per shard: the shard's
    # valid split-axis rows times the extent of the other reduced axes
    other = true_n // max(int(split_valid), 1)
    vcounts = tuple(c * other for c in comm.valid_counts(split_valid))

    def make():
        def kernel(b):
            b32 = b.astype(jnp.float32)
            s1 = jnp.sum(b32, axis=axes, keepdims=keepdims)
            s2 = jnp.sum(b32 * b32, axis=axes, keepdims=keepdims)
            gs1 = jax.lax.psum(s1, name)  # exact first moment
            mu = gs1 / jnp.float32(true_n)
            c_local = jnp.asarray(vcounts, jnp.float32)[jax.lax.axis_index(name)]
            ssd_local = s2 - 2.0 * mu * s1 + c_local * mu * mu
            ssd = ring_allreduce_q(ssd_local, name, size=p, mode=mode, block=blk)
            var = jnp.maximum(ssd, 0.0) / jnp.float32(true_n - ddof)
            out = jnp.sqrt(var) if finalize == "std" else var
            return out.astype(odt)

        def _f(x):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=comm.spec(len(shape), split),
                out_specs=PartitionSpec(),
                check_vma=False,
            )(x)

        return _f

    key = (
        "commq.moments", comm, mode, blk, split, axes, keepdims, true_n,
        split_valid, ddof, finalize, shape, dt, odt.name,
    )
    return jitted(key, make, fields=fields)(buffer)


def class_moments_q(arr, member, *, comm, mode: str, block: Optional[int] = None):
    """Per-class ``(counts, sums, ssd)`` for GaussianNB's ``partial_fit``
    in ONE program.  Counts and first moments combine EXACT via ``psum``:
    counts divide every statistic, and the class means are what CENTER the
    second moments — ``sqsum/n - mu^2`` is a catastrophic cancellation for
    non-centered data, so shipping raw sums-of-squares over a quantized
    ring destroys the variance.  Only the centered sum of squared
    deviations rides the compressed ring, each shard computing its partial
    through the weighted shifted-data identity

        sum_i m_ik (x_i - mu_k)^2
            = sq_k - 2 mu_k s_k + (sum_i m_ik) mu_k^2

    (exact per shard in f32; ring payload magnitude ``~ var_k * n_k``
    instead of ``~ mu_k^2 * n_k``).  ``arr`` is ``(n, f)`` and ``member``
    ``(n, k)``, both row-split with ``n`` divisible by the mesh; returns
    replicated f32 ``(k,)`` counts, ``(k, f)`` sums, ``(k, f)`` ssd."""
    p = comm.size
    mesh, name = comm._mesh, comm.axis_name
    blk = int(block or BLOCK)
    nshape = tuple(int(s) for s in arr.shape)
    k = int(member.shape[1])
    f = nshape[1]
    dt = jnp.dtype(arr.dtype).name

    def make():
        def kernel(a, m):
            a32 = a.astype(jnp.float32)
            m32 = m.astype(jnp.float32)
            c_local = jnp.sum(m32, axis=0)  # (k,)
            s_local = m32.T @ a32  # (k, f)
            sq_local = m32.T @ (a32 * a32)  # (k, f)
            counts = jax.lax.psum(c_local, name)
            sums = jax.lax.psum(s_local, name)
            mu = sums / jnp.maximum(counts, 1.0)[:, None]
            ssd_local = sq_local - 2.0 * mu * s_local + c_local[:, None] * mu * mu
            ssd = ring_allreduce_q(ssd_local, name, size=p, mode=mode, block=blk)
            return counts, sums, jnp.maximum(ssd, 0.0)

        def _f(a, m):
            return shard_map(
                kernel,
                mesh=mesh,
                in_specs=(comm.spec(2, 0), comm.spec(2, 0)),
                out_specs=(PartitionSpec(), PartitionSpec(), PartitionSpec()),
                check_vma=False,
            )(a, m)

        return _f

    key = ("commq.class_moments", comm, mode, blk, nshape, k, dt)
    return jitted(key, make)(arr, member)
