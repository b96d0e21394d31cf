"""Statistical reductions and order statistics.

Reference: heat/core/statistics.py:41-1705.  The reference's hardest
machinery — custom MPI reduction ops over packed (value‖index) buffers for
``argmax``/``argmin`` (:1124-1168) and Bennett-style pairwise moment merging
for ``mean``/``var``/``skew``/``kurtosis`` (:870-945) — is left to XLA's
reduction lowering (variadic reduce with value/index pairs; tree reductions
over shards; the centred moments as a mean pass and a pass over the centred
values), so every function here is its jnp formulation plus the reference's
split/keepdims/ddof semantics.  One exception: the variance of a wide float32
matrix along axis 0 on one TPU reads its operand once (:func:`_var`,
:mod:`._colvar`).
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple, Union

import numpy as np

import jax
import jax.numpy as jnp

from . import _colvar, _operations, factories, types
from ._compile import entry as _entry, jitted
from .dndarray import DNDarray
from .fuse import fuse
from .sanitation import merge_keepdims, sanitize_in
from .stride_tricks import sanitize_axis

__all__ = [
    "argmax",
    "argmin",
    "average",
    "bincount",
    "cov",
    "histc",
    "histogram",
    "kurtosis",
    "max",
    "maximum",
    "mean",
    "median",
    "min",
    "minimum",
    "percentile",
    "skew",
    "std",
    "var",
]


def _argmax_op(a, axis=None, keepdims=False):
    return jnp.argmax(a, axis=axis, keepdims=keepdims)


def _argmin_op(a, axis=None, keepdims=False):
    return jnp.argmin(a, axis=axis, keepdims=keepdims)


def argmax(x, axis=None, out=None, keepdims=None, keepdim=None, **kwargs):
    """Index of the global maximum (reference statistics.py:41-112; the
    MPI_ARGMAX packed-buffer reduction :1124-1168 is XLA's variadic
    reduce)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    return _operations.__reduce_op(
        _argmax_op, x, axis, out, keepdims=keepdims, dtype=types.int64
    )


def argmin(x, axis=None, out=None, keepdims=None, keepdim=None, **kwargs):
    """Index of the global minimum (reference statistics.py:113-185)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    return _operations.__reduce_op(
        _argmin_op, x, axis, out, keepdims=keepdims, dtype=types.int64
    )


def average(x: DNDarray, axis=None, weights: Optional[DNDarray] = None, returned: bool = False):
    """Weighted average (reference statistics.py:186-319)."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    if weights is None:
        result = mean(x, axis)
        if returned:
            n = x.size if axis is None else np.prod([x.shape[a] for a in (axis if isinstance(axis, tuple) else (axis,))])
            wsum = factories.full_like(result, float(n))
            return result, wsum
        return result
    w = weights.larray if isinstance(weights, DNDarray) else jnp.asarray(weights)
    arr = x.larray
    if w.ndim == 1 and axis is not None and not isinstance(axis, tuple) and w.shape[0] == arr.shape[axis]:
        bshape = [1] * arr.ndim
        bshape[axis] = -1
        wb = w.reshape(bshape)
    elif w.shape == arr.shape:
        wb = w
    else:
        raise ValueError("weights differ in shape from a and do not match the axis length")
    wsum = jnp.sum(wb * jnp.ones_like(arr), axis=axis)
    if bool(jnp.any(wsum == 0)):
        raise ZeroDivisionError("Weights sum to zero, can't be normalized")
    res = jnp.sum(arr * wb, axis=axis) / wsum
    result = _wrap_reduced(x, res, axis)
    if returned:
        wret = _wrap_reduced(x, jnp.broadcast_to(wsum, res.shape), axis)
        return result, wret
    return result


def _wrap_reduced(x: DNDarray, garr, axis, keepdims: bool = False) -> DNDarray:
    split = x.split
    if split is not None:
        axes = (
            tuple(range(x.ndim))
            if axis is None
            else ((axis,) if isinstance(axis, int) else tuple(axis))
        )
        if split in axes:
            split = None
        elif not keepdims:
            split = split - sum(1 for a in axes if a < split)
    if garr.ndim == 0:
        split = None
    garr = x.comm.apply_sharding(garr, split)
    return DNDarray(
        garr,
        tuple(garr.shape),
        types.canonical_heat_type(garr.dtype),
        split,
        x.device,
        x.comm,
        True,
    )


def _compressed_moment(x: DNDarray, axis, keepdims: bool, kind: str, ddof: int = 0):
    """Collective-precision policy seam for mean/var/std whose axes cover
    the split: local partials + the block-scaled quantized ring in one
    program (:mod:`heat_tpu.comm.compressed`), instead of GSPMD's exact
    all-reduce.  Returns the replicated result, or None when the policy
    (or the geometry) keeps the exact path.  var/std combine the first
    moment exactly and compress only the centered second moment (see
    :func:`heat_tpu.comm.compressed.moments_q`)."""
    if x.split is None or x.comm.size <= 1 or types.heat_type_is_exact(x.dtype):
        return None
    axes = (
        tuple(range(x.ndim))
        if axis is None
        else ((axis,) if isinstance(axis, int) else tuple(axis))
    )
    if x.split not in axes:
        return None
    from ..comm import compressed as _cq

    buf = x._buffer
    out_elems = 1
    for d, s in enumerate(x.gshape):
        if d not in axes:
            out_elems *= int(s)
    payload = out_elems * 4
    mode = _cq.reduce_mode(buf.dtype, payload)
    if mode is None:
        return None
    true_n = 1
    for a in axes:
        true_n *= int(x.gshape[a])
    fields = {"route": "compressed", "axis": axes}  # axes, as the engines' keys have them
    if kind == "mean":
        return _cq.reduce_q(
            buf, comm=x.comm, split=x.split, axes=axes, keepdims=keepdims,
            mode=mode, mean_n=true_n, out_dtype=buf.dtype, fields=fields,
        )
    return _cq.moments_q(
        buf, comm=x.comm, split=x.split, axes=axes, keepdims=keepdims,
        mode=mode, true_n=true_n, split_valid=int(x.gshape[x.split]),
        ddof=ddof, finalize=kind, out_dtype=buf.dtype, fields=fields,
    )


def bincount(x: DNDarray, weights=None, minlength: int = 0) -> DNDarray:
    """Occurrence counts of non-negative ints (reference statistics.py:320-385).

    Data-dependent output size ⇒ computed with a fixed global length
    (max+1), the XLA-friendly formulation of a distributed histogram."""
    sanitize_in(x)
    arr = x.larray
    if arr.ndim != 1:
        raise ValueError("bincount expects a 1-d array")
    length = int(builtins_max(int(jnp.max(arr)) + 1 if arr.size else 0, minlength))
    w = weights.larray if isinstance(weights, DNDarray) else weights
    res = jnp.bincount(arr, weights=w, length=length)
    dtype = types.int64 if w is None else types.canonical_heat_type(res.dtype)
    return factories.array(res, dtype=dtype, split=None, device=x.device, comm=x.comm)


import builtins as _builtins

builtins_max = _builtins.max
builtins_min = _builtins.min


def cov(m: DNDarray, y: Optional[DNDarray] = None, rowvar: bool = True, bias: bool = False, ddof=None) -> DNDarray:
    """Covariance matrix estimate (reference statistics.py:386-459)."""
    sanitize_in(m)
    if ddof is not None and not isinstance(ddof, int):
        raise TypeError("ddof must be integer")
    arr = m.larray
    if arr.ndim > 2:
        raise ValueError("m has more than 2 dimensions")
    if arr.ndim == 1:
        arr = arr.reshape(1, -1)
    if not rowvar and arr.shape[0] != 1:
        arr = arr.T
    if y is not None:
        sanitize_in(y)
        ya = y.larray
        if ya.ndim > 2:
            raise ValueError("y has more than 2 dimensions")
        if ya.ndim == 1:
            ya = ya.reshape(1, -1)
        if not rowvar and ya.shape[0] != 1:
            ya = ya.T
        arr = jnp.concatenate([arr, ya], axis=0)
    if ddof is None:
        ddof = 0 if bias else 1
    n = arr.shape[1]
    avg = jnp.mean(arr, axis=1, keepdims=True)
    fact = n - ddof
    xc = arr - avg
    res = (xc @ xc.T) / fact
    return factories.array(res, split=m.split if m.split in (0, 1) else None, device=m.device, comm=m.comm)


def histc(input: DNDarray, bins: int = 100, min: float = 0.0, max: float = 0.0, out=None) -> DNDarray:
    """torch-style histogram (reference statistics.py:460-520)."""
    sanitize_in(input)
    arr = input.larray
    lo, hi = float(min), float(max)
    if lo == 0.0 and hi == 0.0:
        lo, hi = float(jnp.min(arr)), float(jnp.max(arr))
    hist, _ = jnp.histogram(arr, bins=bins, range=(lo, hi))
    result = factories.array(
        hist.astype(input.dtype.jax_type()), dtype=input.dtype, device=input.device, comm=input.comm
    )
    if out is not None:
        out.larray = result.larray
        return out
    return result


def histogram(a: DNDarray, bins: int = 10, range=None, normed=None, weights=None, density=None):
    """numpy-style histogram (reference statistics.py:521-565)."""
    sanitize_in(a)
    hist, edges = jnp.histogram(
        a.larray,
        bins=bins,
        range=range,
        weights=weights.larray if isinstance(weights, DNDarray) else weights,
        density=density,
    )
    return (
        factories.array(hist, device=a.device, comm=a.comm),
        factories.array(edges, device=a.device, comm=a.comm),
    )


def _kurtosis_program(x: DNDarray, axis, unbiased: bool, Fischer: bool) -> DNDarray:
    arr = x.larray.astype(jnp.float64 if x.dtype is types.float64 else jnp.float32)
    mu = jnp.mean(arr, axis=axis, keepdims=True)
    diff = arr - mu
    m2 = jnp.mean(diff**2, axis=axis)
    m4 = jnp.mean(diff**4, axis=axis)
    n = arr.size if axis is None else arr.shape[axis]
    g2 = m4 / jnp.where(m2 == 0, 1, m2**2)
    if unbiased:
        g2 = ((n - 1) / ((n - 2) * (n - 3))) * ((n + 1) * g2 - 3 * (n - 1)) + 3
    res = g2 - 3 if Fischer else g2
    return _wrap_reduced(x, res, axis)


_fused_kurtosis = fuse(_kurtosis_program)


def kurtosis(x: DNDarray, axis=None, unbiased: bool = True, Fischer: bool = True):
    """Fourth standardized moment (reference statistics.py:566-615; pairwise
    moment merging :870-945 happens inside XLA's tree reduction).  The whole
    moment chain compiles into one program via :func:`heat_tpu.fuse`."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    return _fused_kurtosis(x, axis, unbiased, Fischer)


def _skew_program(x: DNDarray, axis, unbiased: bool) -> DNDarray:
    arr = x.larray.astype(jnp.float64 if x.dtype is types.float64 else jnp.float32)
    mu = jnp.mean(arr, axis=axis, keepdims=True)
    diff = arr - mu
    m2 = jnp.mean(diff**2, axis=axis)
    m3 = jnp.mean(diff**3, axis=axis)
    n = arr.size if axis is None else arr.shape[axis]
    g1 = m3 / jnp.where(m2 == 0, 1, m2**1.5)
    if unbiased and n > 2:
        g1 = g1 * jnp.sqrt(n * (n - 1.0)) / (n - 2.0)
    return _wrap_reduced(x, g1, axis)


_fused_skew = fuse(_skew_program)


def skew(x: DNDarray, axis=None, unbiased: bool = True):
    """Third standardized moment (reference statistics.py:1423-1465), one
    fused program per (shape, axis, flags) signature."""
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    return _fused_skew(x, axis, unbiased)


def _nan_propagating(redfn):
    """NaN-propagating min/max reduction: XLA's cross-shard all-reduce
    min/max follows IEEE minNum/maxNum (NaN silently loses to any
    number), so a SHARDED array with a NaN reduced like numpy's min/max
    would drop it — jnp.min on a single device propagates, the
    partitioned collective does not.  One extra fused isnan any-reduce
    restores numpy/reference semantics."""

    def f(a, axis=None, keepdims=False):
        r = redfn(a, axis=axis, keepdims=keepdims)
        if jnp.issubdtype(a.dtype, jnp.floating):
            bad = jnp.any(jnp.isnan(a), axis=axis, keepdims=keepdims)
            r = jnp.where(bad, jnp.nan, r)
        return r

    return f


_nanprop_min = _nan_propagating(jnp.min)
_nanprop_max = _nan_propagating(jnp.max)


def max(x, axis=None, out=None, keepdims=None, keepdim=None):
    """Maximum along axes (reference statistics.py:616-727)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    return _operations.__reduce_op(_nanprop_max, x, axis, out, keepdims=keepdims)


def maximum(x1, x2, out=None):
    """Elementwise maximum of two arrays (reference statistics.py:958-1057)."""
    return _operations.__binary_op(jnp.maximum, x1, x2, out)


def _mean(a, axis, keepdims):
    """The program of :func:`mean`: one read of the operand."""
    with jax.named_scope("stat.mean"):
        return jnp.mean(a, axis=axis, keepdims=keepdims)


@_entry("stat:mean")
def mean(x, axis=None, keepdims=None, keepdim=None):
    """Arithmetic mean (reference statistics.py:728-869; cross-shard moment
    combination is XLA's).  ``axis`` may be an int or a tuple of ints;
    ``keepdims``/``keepdim`` follow numpy/torch spelling like every other
    reduction here (the reference's mean lacks it — kept for oracle
    conformance)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    cast = jnp.float32 if types.heat_type_is_exact(x.dtype) else None
    res = _compressed_moment(x, axis, keepdims, kind="mean")
    if res is None:
        fn = jitted(
            ("stat.mean", axis, cast, keepdims),
            lambda: lambda a: _mean(a.astype(cast) if cast else a, axis, keepdims),
            fields={"reads": 1, "route": "exact", "axis": axis},
        )
        res = fn(x.larray)
    return _wrap_reduced(x, res, axis, keepdims=keepdims)


def median(x: DNDarray, axis=None, keepdim=None, out=None, keepdims=None):
    """Median = 50th percentile (reference statistics.py:845-877 —
    signature there is ``median(x, axis, keepdim)``, so ``keepdim`` keeps
    the third positional slot)."""
    if isinstance(keepdim, DNDarray):
        # a numpy-style positional caller passing an output buffer third
        # would silently get keepdim truthiness — fail loudly instead
        raise TypeError(
            "median()'s third positional parameter is keepdim (reference "
            "signature); pass the output buffer as out=..."
        )
    keepdims = merge_keepdims(keepdims, keepdim)
    return percentile(x, 50.0, axis=axis, out=out, keepdims=keepdims)


def min(x, axis=None, out=None, keepdims=None, keepdim=None):
    """Minimum along axes (reference statistics.py:1058-1123)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    return _operations.__reduce_op(_nanprop_min, x, axis, out, keepdims=keepdims)


def minimum(x1, x2, out=None):
    """Elementwise minimum (reference statistics.py:1253-1351)."""
    return _operations.__binary_op(jnp.minimum, x1, x2, out)


def percentile(x: DNDarray, q, axis=None, out=None, interpolation: str = "linear", keepdims=None, keepdim=None):
    """q-th percentile(s) along an axis (reference statistics.py:1171-1422 —
    distributed via resplit + partition gather; here XLA's global sort)."""
    keepdims = merge_keepdims(keepdims, keepdim)
    sanitize_in(x)
    axis = sanitize_axis(x.shape, axis)
    method = {"linear": "linear", "lower": "lower", "higher": "higher", "midpoint": "midpoint", "nearest": "nearest"}[interpolation]
    # interpolation dtype follows the x64 state: requesting float64 with
    # x64 off silently downcasts to f32 AND trips jax's dtype warning —
    # ask for what the backend can actually represent
    wide = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    qa = jnp.asarray(q, dtype=wide)
    reduced_empty = (
        x.size == 0 if axis is None else any(x.shape[a] == 0 for a in (
            (axis,) if isinstance(axis, int) else axis
        ))
    )
    # interpolation dtype only — materializing the (possibly ragged) true
    # view or an f64 copy up front would defeat the padded fast paths below
    idt = wide if types.heat_type_is_exact(x.dtype) else x._buffer.dtype

    def _cast_view():
        arr = x.larray
        return arr.astype(wide) if types.heat_type_is_exact(x.dtype) else arr

    from ..parallel import sort as _parallel_sort  # lazy: parallel imports core

    if reduced_empty:
        # numpy: percentile of an empty region is nan (np.median([]) is
        # nan; numpy 2.x percentile IndexErrors — we take the nan
        # contract), never a backend gather error.  res flows into the
        # common wrap/out tail like every other branch
        if axis is None:
            tail = (1,) * x.ndim if keepdims else ()
        else:
            axes = (axis,) if isinstance(axis, int) else tuple(axis)
            tail = tuple(
                (1 if d in axes else s) if keepdims or d not in axes else None
                for d, s in enumerate(x.shape)
            )
            tail = tuple(s for s in tail if s is not None)
        res = jnp.full(tuple(qa.shape) + tail, jnp.nan, dtype=idt)
    elif (
        axis is None
        and x.split is not None
        and _parallel_sort.supports(x._buffer.dtype, x.size, x.comm)
    ):
        # global percentile of a sharded array: jnp.percentile's internal
        # sort is the pathological GSPMD global sort — rank-sort over the
        # ring instead, then interpolate locally on the sorted output
        # 1-D padded arrays feed their at-rest buffer straight in (the ring
        # sort masks rows past the true length); n-D must ravel the true
        # view — pad rows would interleave the flattened order
        flat = x._buffer if x.ndim == 1 else jnp.ravel(x.larray)
        svals, _ = _parallel_sort.ring_rank_sort(
            flat, x.size, comm=x.comm, want_indices=False
        )
        res = _interp_sorted(svals.astype(idt), qa, method)
        if keepdims:
            res = jnp.reshape(res, qa.shape + (1,) * x.ndim)
    elif (
        isinstance(axis, int)
        and axis == x.split
        and _parallel_sort.supports_axis(x._buffer.dtype, x.shape, axis, x.comm)
    ):
        # axis-quantile ALONG the split axis: the reference resolves this
        # with a distributed partition gather (statistics.py:1171-1422);
        # here the explicit distributed sort orders every fiber along the
        # split axis, then interpolation is a local gather.
        # sort in the original (sortable) dtype, interpolate in the cast
        moved = jnp.moveaxis(x.larray, axis, 0) if axis != 0 else x.larray
        svals, _ = _parallel_sort.sort_axis0(
            moved, x.shape[axis], comm=x.comm, want_indices=False
        )
        res = _interp_sorted(svals.astype(idt), qa, method)
        # res: qa.shape + (dims of x without `axis`, original order) —
        # exactly jnp.percentile's layout; keepdims re-inserts the axis
        if keepdims:
            res = jnp.expand_dims(res, axis=qa.ndim + axis)
    elif qa.ndim > 1:
        # jnp.percentile only takes rank-<=1 q; numpy allows any shape —
        # flatten, compute, and fold the q axes back in front
        flat = jnp.percentile(
            _cast_view(), qa.reshape(-1), axis=axis, method=method, keepdims=keepdims
        )
        res = flat.reshape(qa.shape + flat.shape[1:])
    else:
        res = jnp.percentile(_cast_view(), qa, axis=axis, method=method, keepdims=keepdims)
    if np.isscalar(q) or qa.ndim == 0:
        result = _wrap_reduced(x, res, axis, keepdims=keepdims)
    else:
        # array q prepends a q-axis: replicate rather than mis-shift split
        garr = x.comm.apply_sharding(res, None)
        result = DNDarray(
            garr, tuple(garr.shape), types.canonical_heat_type(garr.dtype),
            None, x.device, x.comm, True,
        )
    if out is not None:
        out.larray = result.larray
        return out
    return result


def _interp_sorted(svals, qa, method: str):
    """numpy-method percentile lookup on an array already sorted along
    axis 0 (NaNs sorted last); trailing dims are independent fibers, so
    the result has shape ``qa.shape + svals.shape[1:]``.  Propagates NaN
    like jnp.percentile: any NaN in a fiber — visible as a NaN tail after
    the sort — poisons that fiber's every quantile."""
    n = svals.shape[0]
    batch = svals.ndim - 1
    # the virtual position q/100*(n-1) is pure host data (q and n are
    # both host-known) — compute it in float64 regardless of the x64
    # policy: in float32, 30% of 1001 lands at 299.99997 and floors to
    # the WRONG element for the exact-index methods
    pos = np.asarray(qa, dtype=np.float64) / 100.0 * (n - 1)
    lo = np.clip(np.floor(pos).astype(np.int32), 0, n - 1)
    hi = np.clip(np.ceil(pos).astype(np.int32), 0, n - 1)
    vlo, vhi = svals[lo], svals[hi]  # qa.shape + batch dims
    if method == "lower":
        res = vlo
    elif method == "higher":
        res = vhi
    elif method == "nearest":
        # numpy rounds half to even — np.round matches; a plain 0.5
        # threshold picks a different element at exact half positions
        idx = np.clip(np.round(pos).astype(np.int32), 0, n - 1)
        res = svals[idx]
    elif method == "midpoint":
        res = (vlo + vhi) / 2.0
    else:  # linear
        frac = jnp.asarray((pos - lo).reshape(pos.shape + (1,) * batch), svals.dtype)
        res = vlo * (1 - frac) + vhi * frac
    if jnp.issubdtype(svals.dtype, jnp.floating):
        res = jnp.where(jnp.isnan(svals[-1]), jnp.nan, res)
    return res


def _form(a, axis) -> str:
    """How :func:`_var` makes the variance of ``a`` along ``axis``, by how many
    times it reads ``a``: ``one_pass`` where :func:`_colvar.conforms` holds,
    ``two_pass`` for every other operand.  The launch spans of ``var`` and
    ``std`` carry it as their ``form`` field."""
    return "one_pass" if _colvar.conforms(a, axis) else "two_pass"


def _var(a, axis, ddof, keepdims):
    """The program of :func:`var` and :func:`std`, in one of two forms chosen
    from the operand alone (:func:`_form`); exact types are worked on in
    float32, 16-bit floats summed in it.  In both the mean is taken off before
    the squares are summed (the raw form ``E[x**2] - mean**2`` cancels where a
    mean is large beside its deviation).

    ``two_pass``: ``jnp.var``'s own arithmetic with its mean made apart, so
    that each of its two reads of the operand carries a scope of its own.
    ``one_pass`` (a float32 matrix reduced over its rows that fills a good
    part of the one chip the process drives): the Pallas kernel of
    :mod:`._colvar` keeps a tile of columns, all rows of it, on the chip
    between the two sums, and reads the operand once."""
    if _form(a, axis) == "one_pass":
        with jax.named_scope("stat.var.onepass"):
            m2 = _colvar.centred_squares(a, interpret=_colvar._interpret())
        res = m2 / (a.shape[0] - ddof)  # 0 / 0 where no row is left, as jnp.var
        return res[None, :] if keepdims else res
    if not jnp.issubdtype(a.dtype, jnp.inexact):
        a = a.astype(jnp.float32)
    # jnp.var works on 16-bit floats in float32: its mean is made so too
    wide = jnp.float32 if a.dtype.itemsize < 4 else a.dtype
    with jax.named_scope("stat.var.mean"):
        mu = jnp.mean(a, axis=axis, dtype=wide, keepdims=True)
    with jax.named_scope("stat.var.centred"):
        return jnp.var(a, axis=axis, ddof=ddof, keepdims=keepdims, mean=mu)


def _moment2(x, axis, ddof, kwargs, name, finalize):
    """Shared var/std engine: ddof/bessel semantics + one fused executable
    (``finalize`` is identity for var, sqrt for std)."""
    sanitize_in(x)
    if "bessel" in kwargs:
        ddof = 1 if kwargs.pop("bessel") else 0
    if ddof not in (0, 1):
        raise ValueError(f"ddof must be 0 or 1, got {ddof}")
    axis = sanitize_axis(x.shape, axis)
    keepdims = merge_keepdims(kwargs.pop("keepdims", None), kwargs.pop("keepdim", None))
    if kwargs:
        raise TypeError(f"unexpected keyword arguments: {sorted(kwargs)}")
    res = _compressed_moment(
        x, axis, keepdims, kind=("std" if name == "stat.std" else "var"), ddof=ddof
    )
    if res is None:
        arr = x.larray
        form = _form(arr, axis)
        fn = jitted(
            ("stat.moment2", name, axis, ddof, form, keepdims),
            lambda: lambda a: finalize(_var(a, axis, ddof, keepdims)),
            fields={"reads": 1 if form == "one_pass" else 2, "form": form, "route": "exact", "axis": axis},
        )
        res = fn(arr)
    return _wrap_reduced(x, res, axis, keepdims=keepdims)


@_entry("stat:std")
def std(x, axis=None, ddof: int = 0, **kwargs):
    """Standard deviation (reference statistics.py:1466-1558) — one fused
    sqrt(var) executable rather than two dispatches, its operand read as
    :func:`var` reads it.  Accepts numpy's ``keepdims`` and tuple axes like
    :func:`var`."""
    return _moment2(x, axis, ddof, kwargs, "stat.std", jnp.sqrt)


@_entry("stat:var")
def var(x, axis=None, ddof: int = 0, **kwargs):
    """Variance with ddof semantics (reference statistics.py:1559-1705).

    The mean is taken off before the squares are summed, whatever the operand
    (:func:`_var`).  That is two reads of the operand (the reference's merged
    single-pass moments are not what XLA makes of ``jnp.var``), except for a
    float32 matrix reduced over axis 0 that fills a good part of the one TPU
    the process drives: there a kernel keeps a tile of columns on the chip
    between the two sums and the operand is read once.  The launch span's
    ``reads`` and ``form`` fields say which was compiled.

    Note: like the reference, ``ddof`` ∈ {0, 1} (bessel correction via
    ``bessel=True`` kwarg is also accepted)."""
    return _moment2(x, axis, ddof, kwargs, "stat.var", lambda r: r)


# split semantics for heat_tpu.analysis.splitflow (see core/_split_semantics.py)
from ._split_semantics import declare_split_semantics_table  # noqa: E402

declare_split_semantics_table(
    __name__,
    {
        "reduction": (
            "argmax", "argmin", "max", "mean", "median", "min", "std",
            "var", "kurtosis", "skew",
        ),
        "binary": ("maximum", "minimum"),
    },
)
