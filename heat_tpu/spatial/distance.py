"""Pairwise distance computations.

Reference: heat/spatial/distance.py:28-475 — ``cdist``/``rbf``/``manhattan``
route into ``_dist``, which hand-rolls a **ring communication** schedule:
with X split=0, each of (size+1)//2 rounds Sends the local block to rank+i,
Recvs from rank−i, computes a tile, and ships the result back to exploit
symmetry (:244-345).

TPU-first formulation: the distance matrix is one global computation and
GSPMD schedules the inter-shard movement; row-sharding of X propagates to
row-sharding of D (the programs here lay their result out so themselves:
``_rows_of``).  The default (``quadratic_expansion=False``) is the exact form
``sqrt(sum((x - y)²))`` on the vector units: one program and, at up to
``_UNROLL_MAX_FEATURES`` features, one pass that writes D once
(``_pairwise_sum``, which has two more loop orders for wider operands:
``rows`` where one operand has few rows, as ``KMedians``' centres have, and
the broadcast-and-``reduce``).  It is bound by the vector units' three operations a
feature and pair, not by the write of D (see ledger, PR 31, ``cdist_40k_c1``;
``job_ms`` 22.1 against 87.6 before, ``roofline_pct`` 37.0 at 40 000 x 18: my
chip run, PR 31).
``quadratic_expansion=True`` is the MXU form ``|x|² + |y|² − 2xy``
(reference :28-72 uses the same trick locally): one large matmul, paid for
in cancellation error: at that size its largest difference to the exact
form reads 0.535 where the cell allows 5e-5 (``dist_err_all``, ``PERF.md``
§2); its time on the chip is not measured.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from ..core import types
from ..core._compile import entry as _entry, jitted
from ..core.dndarray import DNDarray
from ..core.linalg import basics as _linalg
from ..core.sanitation import sanitize_in

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


def _rows_of(x: DNDarray):
    """The layout :func:`_wrap` gives the result where X lies by rows over
    several devices (else ``None``), for the program to produce it so.  Left
    to itself GSPMD lays the (n, m) result of two row-sharded operands out
    through an all-to-all of n x m intermediates: 114 GB of temporaries a chip
    at 80 000 x 18 on four chips, either form (``tests/test_tpu_compile.py``)."""
    return x.comm.sharding(2, 0) if x.split == 0 and x.comm.size > 1 else None


def _laid(d, rows):
    return d if rows is None else jax.lax.with_sharding_constraint(d, rows)


#: Most features the exact forms unroll.  One program at 40 000 rows on a TPU
#: v5e, reduce / unrolled (my chip run, PR 31, ``PERF.md`` §6): 86.9 / 22.0 ms
#: at 18 features, 98.4 / 39.3 at 32, 225.4 / 84.9 at 64 (9.8 s to compile),
#: 362.3 / 158.3 at 96 (13.6 s); at 128 the unrolled form falls off a cliff,
#: 428.2 / 1 201 ms, 33 MB of temporaries, 26 s to compile.  The compiler's own
#: estimate (no chip) shows the same cliff: 128 M cycles at 64, 966 M at 128.
_UNROLL_MAX_FEATURES = 64


#: Fewest rows (of the operand that has fewer) up to which a wide operand takes
#: the ``rows`` order.  One L1 program at 300 x 6 291 456 against 8 rows on a
#: TPU v5e (my chip run, PR 36, ``PERF.md`` §6): ``reduce`` 60.8 ms, ``rows``
#: 14.3 ms, one multi-output fusion over X (10.5 ms a pass inside ``KMedians``'
#: fit, the read of X alone being 9.2).  Beyond 16 rows: not measured.
_ROWS_MAX = 16


def _form(features: int, fewest_rows: Optional[int] = None) -> str:
    """The loop order :func:`_pairwise_sum` takes at this feature count and at
    this many rows of the operand that has fewer (``None``: not known, many):
    the launch spans of the exact forms carry it as their ``form`` field."""
    if 0 < features <= _UNROLL_MAX_FEATURES:
        return "unrolled"
    if fewest_rows is not None and 0 < fewest_rows <= _ROWS_MAX:
        return "rows"
    return "reduce"


def _form_of(xa, ya) -> str:
    """:func:`_form` of a pair of operands."""
    return _form(xa.shape[1], min(xa.shape[0], ya.shape[0]))


def _pairwise_sum(xa, ya, term):
    """``sum_k term(xa[i, k] - ya[j, k])`` for every pair: (n, f), (m, f) ->
    (n, m), every feature, in the operands' own precision.

    One sum in one of three loop orders, chosen from the static shapes
    (:func:`_form`).  Few features (``unrolled``): feature by feature over the
    (n, m) result, so the compiler makes each output tile in registers from
    ``f`` subtract-``term``-adds on two broadcast vectors, fuses what the
    caller does next (``sqrt``, ``exp``) into the same pass and writes the
    result once.  Wide operands, one of them of few rows (``rows``: a
    clusterer's data against its centres): row by row of the smaller operand,
    each a reduce of ``term(x - y[j])`` along the features with nothing of the
    broadcast's size in between; the reduces share one pass over the larger
    operand.  Everything else (``reduce``): the (n, m, f) broadcast reduced
    over its minor axis, which the compiler pads to the tile width at few
    features and, on wide operands, tiles by the row count (a cliff at 300
    rows, ``PERF.md`` §6)."""
    form = _form_of(xa, ya)
    if form == "reduce":
        return jnp.sum(term(xa[:, None, :] - ya[None, :, :]), axis=-1)
    if form == "rows":
        if ya.shape[0] <= xa.shape[0]:
            return jnp.stack(
                [jnp.sum(term(xa - ya[j][None, :]), axis=1) for j in range(ya.shape[0])], axis=1
            )
        return jnp.stack(
            [jnp.sum(term(xa[i][None, :] - ya), axis=1) for i in range(xa.shape[0])], axis=0
        )
    xt, yt = xa.T, ya.T
    acc = term(xt[0][:, None] - yt[0][None, :])
    for k in range(1, xa.shape[1]):
        acc = acc + term(xt[k][:, None] - yt[k][None, :])
    return acc


def _euclidean(xa, ya, quadratic_expansion: bool):
    if quadratic_expansion:
        with jax.named_scope("cdist.quadratic"):
            return jnp.sqrt(quadratic_d2(xa, ya))
    with jax.named_scope("cdist.exact"):
        return jnp.sqrt(_pairwise_sum(xa, ya, jnp.square))


from ..core._split_semantics import split_semantics as _split_semantics


@_split_semantics("entry_split0")
@_entry("spatial:cdist")
def cdist(X: DNDarray, Y: Optional[DNDarray] = None, quadratic_expansion: bool = False) -> DNDarray:
    """Pairwise euclidean distances (reference distance.py:166-172).

    ``quadratic_expansion=True`` uses the |x|²+|y|²−2xy form — on TPU this
    is the fast path (a single MXU matmul); the exact broadcast form is the
    default, like the reference's torch.cdist.
    """
    xa, ya, dtype = _prep(X, Y)
    form = None if quadratic_expansion else _form_of(xa, ya)
    rows = _rows_of(X)
    fn = jitted(
        ("dist.euclidean", quadratic_expansion, form, rows),
        lambda: lambda a, b: _laid(_euclidean(a, b, quadratic_expansion), rows),
        fields=form and {"form": form},
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
    form = None if quadratic_expansion else _form_of(xa, ya)
    rows = _rows_of(X)

    def _make():
        def _rbf(a, b, sig):
            if quadratic_expansion:
                with jax.named_scope("rbf.quadratic"):
                    d2 = quadratic_d2(a, b, precision)
            else:
                with jax.named_scope("rbf.exact"):
                    d2 = _pairwise_sum(a, b, jnp.square)
            return _laid(jnp.exp(-d2 / (2.0 * sig * sig)), rows)

        return _rbf

    fn = jitted(
        ("dist.rbf", quadratic_expansion, precision, form, rows),
        _make,
        fields=form and {"form": form},
    )
    return _wrap(X, fn(xa, ya, jnp.asarray(sigma, xa.dtype)), dtype)


@_entry("spatial:manhattan")
def manhattan(X: DNDarray, Y: Optional[DNDarray] = None, expand: bool = False) -> DNDarray:
    """Pairwise L1 distances (reference distance.py:180-186)."""
    xa, ya, dtype = _prep(X, Y)
    del expand  # accepted for API parity; one formulation here
    form = _form_of(xa, ya)
    rows = _rows_of(X)
    fn = jitted(
        ("dist.manhattan", form, rows),
        lambda: lambda a, b: _laid(_pairwise_sum(a, b, jnp.abs), rows),
        fields={"form": form},
    )
    return _wrap(X, fn(xa, ya), dtype)
