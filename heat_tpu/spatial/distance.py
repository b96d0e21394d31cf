"""Pairwise distance computations.

Reference: heat/spatial/distance.py:28-475 — ``cdist``/``rbf``/``manhattan``
route into ``_dist``, which hand-rolls a **ring communication** schedule:
with X split=0, each of (size+1)//2 rounds Sends the local block to rank+i,
Recvs from rank−i, computes a tile, and ships the result back to exploit
symmetry (:244-345).

TPU-first formulation: the distance matrix is one global computation and
GSPMD schedules the inter-shard movement; row-sharding of X propagates to
row-sharding of D.  The default (``quadratic_expansion=False``) is the exact
form ``sqrt(sum((x - y)²))`` on the vector units: one program, bound by the
vector units and not by the write of D (``job_ms`` 87.2, ``roofline_pct``
9.08 at 40 000 x 18; ledger, PR 29, ``cdist_40k_c1``).
``quadratic_expansion=True`` is the MXU form ``|x|² + |y|² − 2xy``
(reference :28-72 uses the same trick locally): one large matmul, paid for
in cancellation error: at that size its largest difference to the exact
form reads 0.535 where the cell allows 5e-5 (``dist_err_all``, ``PERF.md``
§2); its time on the chip is not measured.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..core import types
from ..core._compile import jitted
from ..core._tracing import in_trace
from ..core.dndarray import DNDarray
from ..core.linalg import basics as _linalg
from ..core.sanitation import sanitize_in
from ..telemetry import _core as _tel

__all__ = ["cdist", "manhattan", "rbf", "quadratic_d2"]


def quadratic_d2(xa, ya, precision=None):
    """Squared euclidean distances via the MXU-native quadratic expansion
    |x|² + |y|² − 2xy, clamped at 0 against rounding (the one shared
    implementation — reference _quadratic_expand, distance.py:40-72).
    ``precision`` is the product's (``None``: jax's default, one bf16 pass
    on a TPU)."""
    x2 = jnp.sum(xa * xa, axis=-1, keepdims=True)
    y2 = jnp.sum(ya * ya, axis=-1, keepdims=True).swapaxes(-1, -2)
    xy = jnp.matmul(xa, ya.swapaxes(-1, -2), precision=precision)
    return jnp.maximum(x2 + y2 - 2.0 * xy, 0.0)


def _prep(x: DNDarray, y: Optional[DNDarray]):
    sanitize_in(x)
    if x.ndim != 2:
        raise NotImplementedError(f"X should be a 2D DNDarray, but is {x.ndim}D")
    if y is not None:
        sanitize_in(y)
        if y.ndim != 2:
            raise NotImplementedError(f"Y should be a 2D DNDarray, but is {y.ndim}D")
        if x.shape[1] != y.shape[1]:
            raise ValueError(
                f"inputs must have the same number of features, got {x.shape[1]} and {y.shape[1]}"
            )
    promoted = types.promote_types(x.dtype, types.float32)
    xa = x.larray.astype(promoted.jax_type())
    ya = xa if y is None else y.larray.astype(promoted.jax_type())
    return xa, ya, promoted


def _wrap(x: DNDarray, garr, dtype) -> DNDarray:
    split = x.split if x.split == 0 else None
    garr = x.comm.apply_sharding(garr, split)
    return DNDarray(garr, tuple(garr.shape), dtype, split, x.device, x.comm, True)


def _euclidean(xa, ya, quadratic_expansion: bool):
    if quadratic_expansion:
        with jax.named_scope("cdist.quadratic"):
            return jnp.sqrt(quadratic_d2(xa, ya))
    with jax.named_scope("cdist.exact"):
        diff = xa[:, None, :] - ya[None, :, :]
        return jnp.sqrt(jnp.sum(diff * diff, axis=-1))


from ..core._split_semantics import split_semantics as _split_semantics


def _entry(site: str):
    """A public entry as a span of kind ``entry`` (one predicate a call when
    nothing records).  Inside an ``ht.fuse`` trace the call inlines into the
    surrounding program and is no entry of its own."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if in_trace() or not _tel.recording():
                return fn(*args, **kwargs)
            with _tel.span(site, "entry"):
                return fn(*args, **kwargs)

        return wrapper

    return deco


@_split_semantics("entry_split0")
@_entry("spatial:cdist")
def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Pairwise euclidean distances (reference distance.py:166-172).

    ``quadratic_expansion=True`` uses the |x|²+|y|²−2xy form — on TPU this
    is the fast path (a single MXU matmul); the exact broadcast form is the
    default, like the reference's torch.cdist.
    """
    xa, ya, dtype = _prep(X, Y)
    fn = jitted(
        ("dist.euclidean", quadratic_expansion),
        lambda: lambda a, b: _euclidean(a, b, quadratic_expansion),
    )
    return _wrap(X, fn(xa, ya), dtype)


@_entry("spatial:rbf")
def rbf(
    X: DNDarray,
    Y: Optional[DNDarray] = None,
    sigma: float = 1.0,
    quadratic_expansion: bool = False,
) -> DNDarray:
    """Gaussian (RBF) kernel matrix exp(−d²/2σ²)
    (reference distance.py:173-179).

    A similarity is read through ``exp``, so an absolute error in d² is a
    relative one in the result: the expansion's product takes the library's
    linalg precision (``ht.linalg.set_matmul_precision``, ``highest`` unless
    set otherwise), not the one bf16 pass ``cdist``'s expansion runs."""
    xa, ya, dtype = _prep(X, Y)
    precision = _linalg._precision() if quadratic_expansion else None

    def _make():
        def _rbf(a, b, sig):
            if quadratic_expansion:
                with jax.named_scope("rbf.quadratic"):
                    d2 = quadratic_d2(a, b, precision)
            else:
                with jax.named_scope("rbf.exact"):
                    diff = a[:, None, :] - b[None, :, :]
                    d2 = jnp.sum(diff * diff, axis=-1)
            return jnp.exp(-d2 / (2.0 * sig * sig))

        return _rbf

    fn = jitted(("dist.rbf", quadratic_expansion, precision), _make)
    return _wrap(X, fn(xa, ya, jnp.asarray(sigma, xa.dtype)), dtype)


@_entry("spatial:manhattan")
def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """Pairwise L1 distances (reference distance.py:180-186)."""
    xa, ya, dtype = _prep(X, Y)
    del expand  # accepted for API parity; one formulation here
    fn = jitted(
        ("dist.manhattan",),
        lambda: lambda a, b: jnp.sum(jnp.abs(a[:, None, :] - b[None, :, :]), axis=-1),
    )
    return _wrap(X, fn(xa, ya), dtype)
