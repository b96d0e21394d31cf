"""K-Medians clustering.

Reference: heat/cluster/kmedians.py:5-130 — the KMeans skeleton with the
assignment by **Manhattan** distance (``ht.spatial.distance.manhattan``,
:5-42) and the centroid update replaced by a per-cluster **median** (masked
rows → ``balance_`` → distributed median, :43-86): the coordinate-wise
median is what minimises the sum of L1 distances within a cluster.  The
reference re-draws the centre of an empty cluster (:67-80); here it keeps
its place.

TPU formulation: the ENTIRE fit is one jitted ``lax.while_loop`` (the KMeans
pattern, kmeans.py:61-102): one dispatch, zero per-epoch host syncs.  A
sweep is an assignment, ``argmin_c sum_j |x_ij - c_cj|`` in float32 through
``spatial/distance.py:_pairwise_sum`` (the one pairwise sum, in its order for
wide operands against few rows), and the exact medians of every cluster and
feature, by one of two routes the operand decides (:func:`_medians_route`):

* ``column_select``: where ``core/_colmedian.py:conforms`` (a float32 matrix
  of few rows that fills a chip, one TPU in the process), a Pallas kernel
  that brings a column tile of ALL rows into VMEM once and selects each
  cluster's two middle members there (a cluster of at most
  ``_colmedian._NETWORK_MAX`` members by a comparison network, a larger one by
  counting passes; ``selections_by_network_`` says how many took the first):
  one read of X a sweep, nothing of X's size beside it.
* ``rank_bisection``: every other operand (the CPU mesh, several chips, many
  rows).  The data matrix never changes across sweeps, so each feature
  column is value-sorted ONCE; every sweep then finds all k·f medians by
  rank-space bisection whose rank counts are matmuls over the cluster
  one-hot (:func:`_cluster_medians`).  It holds a sorted copy of X and
  (n, f) temporaries: an operand near a chip's memory does not fit it.
"""

from __future__ import annotations

from functools import partial
from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core._compile import launch
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ..spatial import distance
from ..telemetry import _core as _tel
from ._kcluster import _KCluster

__all__ = ["KMedians"]


def _manhattan(x: DNDarray, y: DNDarray) -> DNDarray:
    """The estimator's metric for ``predict``: pairwise L1 distances.
    Module-level, so its identity is call-stable and the fused assignment
    program caches across estimators (``_kcluster._fused_assign``)."""
    return distance.manhattan(x, y)


def _medians_route(arr, k: int) -> str:
    """Which route the medians of this operand take: ``column_select`` where
    the kernel's own predicate holds, else ``rank_bisection``.  Asked once a
    fit, ahead of the program: the route is part of its key and the
    ``medians`` field of its launch span.  The kernel's module is imported
    here, at the first fit: ``import heat_tpu`` does not bring it."""
    from ..core import _colmedian

    return "column_select" if _colmedian.conforms(arr, k) else "rank_bisection"


def _presort_values(arr):
    """One-time (per fit) value sort of every feature column plus the
    per-column finite clamp range: ``(svals, fmin, fmax)``.  The sort is
    a single-operand non-stable ``lax.sort`` (no index operand to carry,
    as the stable ``argsort`` had) and the ONLY sort in the whole KMedians
    fit.  The clamp range is computed HERE because it is loop-invariant:
    inside the Lloyd while_loop it is two full-matrix reduces an
    iteration (XLA does not hoist out of while bodies).  The route of
    every operand the kernel of ``core/_colmedian.py`` does not take; its
    time on the chip is not measured."""
    svals = jax.lax.sort(arr, dimension=0, is_stable=False)
    finite = jnp.isfinite(svals)
    fmax = jnp.max(jnp.where(finite, svals, -jnp.inf), axis=0)
    fmin = jnp.min(jnp.where(finite, svals, jnp.inf), axis=0)
    fmax = jnp.where(jnp.isfinite(fmax), fmax, 0.0)  # all-non-finite column
    fmin = jnp.where(jnp.isfinite(fmin), fmin, 0.0)
    return svals, fmin, fmax


#: warm-start half-window (positions): after the first Lloyd iteration the
#: median positions barely move, so the bisection restarts from
#: ``[prev - W, prev + W]`` instead of ``[0, n)`` — validated EXACTLY (two
#: edge count-probes re-establish the bisection invariant; any slot whose
#: answer escaped the window falls back to the full range), so warm
#: starting is a pure speed knob, never an approximation.
_WARM_WINDOW = 64


def _cluster_medians(arr, svals, fmin, fmax, onehot, counts, k, prev_pos=None):
    """Exact per-cluster per-feature medians, (k, f), by RANK-SPACE
    BISECTION with matmul rank counts — zero per-iteration sorts and zero
    O(n·f) gathers (this routine's only gathers are (k, f, 2) threshold
    probes).

    The t-th smallest member of cluster c in feature j is found by binary
    search over the pre-sorted column ``svals[:, j]``: the probe position
    p maps to a value threshold, and the count of members with
    ``x <= thr`` comes from two MXU matmuls —

    * ``thr_row = onehot @ thr_table``: each row picks its own cluster's
      threshold (exact: a one-hot dot selects a single f32 term), then
    * ``cnt = onehot.T @ (x <= thr_row)`` with int8 operands and int32
      accumulation (exact for any n < 2^31).

    The search over positions is exact under duplicate values: it
    converges to the smallest position p* with count(<= svals[p*]) >= t,
    whose value IS the t-th member value.  Both middle members (numpy's
    even-count average) run as a second stacked search.  NaN members sort
    last and are never counted by ``x <= thr``, so a cluster whose median
    position lands in its NaN tail returns the column maximum/NaN — the
    sort-last semantics of the reference's gathered-member median
    (reference kmedians.py:43-66).  Replaces a per-cluster
    ``nanmedian``, which is k full sorts per step."""
    n, f = arr.shape
    from ..core._colmedian import _middle_ranks

    # 1-indexed member ranks of the two middles (equal when count is odd)
    t = jnp.maximum(jnp.stack(_middle_ranks(counts), axis=-1), 1)  # (k, 2)
    onehot8 = onehot.astype(jnp.int8)
    # fmin/fmax: the per-column finite clamp for PROBE thresholds (from
    # _presort_values — loop-invariant).  A probe landing in a column's
    # NaN/±inf tail would otherwise put a non-finite value into the
    # one-hot matmul, where 0·NaN = NaN poisons EVERY row's threshold and
    # corrupts every cluster's bracket in that feature.  Clamping keeps
    # the matmul finite and the predicate correct for all finite-valued
    # clusters; clusters whose median genuinely sits in a non-finite tail
    # still converge there (the final value gather is unclamped).  ±inf
    # *data* can shift the boundary probe by one rank — rows with
    # non-finite features already have undefined assignments (their
    # distances are NaN), so only this bracket caveat remains.

    def count_at(pos):
        """Per-slot member count ``|{x in c : x[:, j] <= svals[pos, j]}|``
        for a (k, f, 2) position probe — the bisection's primitive, also
        used standalone to validate warm-start brackets.  Costs one
        bisection step (two threshold matmuls + one int8 count matmul)."""
        pos = jnp.clip(pos, 0, n - 1)
        # value thresholds at the probe positions: tiny (k*2, f) gather
        thr = jnp.take_along_axis(
            svals, jnp.transpose(pos, (2, 0, 1)).reshape(2 * k, f), axis=0
        ).reshape(2, k, f)
        thr = jnp.clip(jnp.where(jnp.isnan(thr), fmax, thr), fmin, fmax)
        # each row's own-cluster threshold, one per search: (n, f) each.
        # HIGHEST precision is load-bearing: the MXU's default bf16
        # truncation would round thresholds off the probed values and
        # silently corrupt the bisection (the CPU test mesh cannot see it)
        thr_a = jnp.matmul(onehot, thr[0], precision=jax.lax.Precision.HIGHEST)
        thr_b = jnp.matmul(onehot, thr[1], precision=jax.lax.Precision.HIGHEST)
        ind = jnp.concatenate(
            [(arr <= thr_a), (arr <= thr_b)], axis=1
        ).astype(jnp.int8)  # (n, 2f)
        cnt = jax.lax.dot_general(
            onehot8, ind, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        )  # (k, 2f): members of c with x[:, j] <= thr[s, c, j]
        return jnp.stack([cnt[:, :f], cnt[:, f:]], axis=-1)  # (k, f, 2)

    tkf = t[:, None, :]  # (k, 1, 2) target ranks, broadcast over features

    def step(st):
        lo, hi = st  # (k, f, 2) position brackets: answer in [lo, hi]
        pos = lo + (hi - lo) // 2
        found = count_at(pos) >= tkf
        return jnp.where(found, lo, pos + 1), jnp.where(found, pos, hi)

    if prev_pos is None:
        lo0 = jnp.zeros((k, f, 2), jnp.int32)
        hi0 = jnp.full((k, f, 2), n - 1, jnp.int32)
    else:
        # warm start around last iteration's answer, then RE-ESTABLISH the
        # bisection invariant exactly: the answer (smallest p with
        # count(p) >= t) lies in [lo0, hi0] iff count(hi0) >= t and
        # count(lo0 - 1) < t.  Slots where labels churned past the window
        # widen back to the full range — correctness never depends on the
        # window (VERDICT r3 #4: warm-started brackets, re-widened on
        # churn).
        lo0 = jnp.clip(prev_pos - _WARM_WINDOW, 0, n - 1)
        hi0 = jnp.clip(prev_pos + _WARM_WINDOW, 0, n - 1)
        ok_hi = count_at(hi0) >= tkf
        ok_lo = (lo0 == 0) | (count_at(lo0 - 1) < tkf)
        ok = ok_hi & ok_lo
        lo0 = jnp.where(ok, lo0, 0)
        hi0 = jnp.where(ok, hi0, n - 1)

    # adaptive depth: warm brackets converge in ~log2(2W) trips instead of
    # the full log2(n) (the while_loop stops as soon as every slot closes)
    lo, _ = jax.lax.while_loop(
        lambda st: jnp.any(st[0] < st[1]), step, (lo0, hi0)
    )
    val = jnp.take_along_axis(
        svals, jnp.transpose(lo, (2, 0, 1)).reshape(2 * k, f), axis=0
    ).reshape(2, k, f)
    return (val[0] + val[1]) * 0.5, lo


class KMedians(_KCluster):
    """K-Medians estimator (reference kmedians.py:5-42): Manhattan assignment,
    exact per-cluster medians (numpy's: the mean of the two middle members at
    an even count).  A cluster without members keeps its centre."""

    _init_plus_plus_alias = "kmedians++"

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        tol: float = 1e-4,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            metric=_manhattan,  # module-level: fused-assign cache hit
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=tol,
            random_state=random_state,
        )
        self._selections = None

    @property
    def selections_by_network_(self) -> int:
        """How many of the fit's median selections (one a sweep and cluster
        with members) the kernel of ``core/_colmedian.py`` made by its
        comparison network; 0 on the ``rank_bisection`` route.  A device
        scalar until asked for: the read is a host sync of its own (site
        ``sync:kmedians.selections``), which a fit alone does not make."""
        if self._selections is not None and not isinstance(self._selections, int):
            self._selections = _tel.host_read("sync:kmedians.selections", self._selections, int)
        return self._selections

    @staticmethod
    @partial(jax.jit, static_argnames=("route",))
    def _fit_loop(arr, centers, tol, max_iter, route="rank_bisection"):
        """The whole fit as one compiled ``lax.while_loop`` (the KMeans
        pattern, kmeans.py:61-102): Manhattan assignment and exact medians
        per sweep, convergence decided on device.  Replaces the per-epoch
        ``float(shift)`` host sync of the reference's loop
        (kmedians.py:87-130).  ``route`` is :func:`_medians_route`'s answer:
        ``column_select`` reads X once for the assignment and once for the
        medians of a sweep and holds nothing else of its size, and counts in
        the loop's state how many of its selections the kernel made by its
        comparison network (the fourth result; 0 on the other route);
        ``rank_bisection`` sorts the feature columns ONCE before the loop and
        warm-starts each sweep's bisection from the last
        (:func:`_cluster_medians`)."""
        k = centers.shape[0]

        def assign(c):
            return jnp.argmin(distance._pairwise_sum(arr, c, jnp.abs), axis=1)

        if route == "column_select":
            from ..core import _colmedian

            def medians(labels, state):
                med, counts = _colmedian.group_medians(
                    arr, labels, k, interpret=_colmedian._interpret()
                )
                return med, counts, state + _colmedian.by_network(counts)

            state0 = jnp.int32(0)
        else:
            svals, fmin, fmax = _presort_values(arr)

            def medians(labels, prev_pos):
                member = labels[:, None] == jnp.arange(k)
                counts = jnp.sum(member, axis=0, dtype=jnp.int32)
                med, pos = _cluster_medians(
                    arr, svals, fmin, fmax, member.astype(jnp.float32), counts, k, prev_pos
                )
                return med, counts, pos

            # sentinel start: an impossible previous position makes the warm
            # brackets collapse to [0, 0], whose exact validation widens every
            # slot back to the full range — iteration 1 is a full bisection
            # with no special-casing, later iterations warm-start (the answer
            # rarely moves more than a few ranks once labels stabilize)
            state0 = jnp.full((k, arr.shape[1], 2), -2 * _WARM_WINDOW, jnp.int32)

        def cond(carry):
            it, _, shift, _ = carry
            return jnp.logical_and(it < max_iter, shift > tol)

        def body(carry):
            it, c, _, state = carry
            with jax.named_scope("kmedians.sweep.assign"):
                labels = assign(c)
            with jax.named_scope("kmedians.sweep.medians"):
                med, counts, state = medians(labels, state)
                # keep the previous coordinate for empty clusters AND for NaN
                # medians (a NaN-feature member): a NaN center would poison
                # shift, silently end the loop, and NaN every distance
                nc = jnp.where((counts > 0)[:, None] & ~jnp.isnan(med), med, c)
            return it + 1, nc, jnp.sum((nc - c) ** 2), state

        init = (jnp.int32(0), centers, jnp.float32(jnp.inf), state0)
        n_iter, centers, _, state = jax.lax.while_loop(cond, body, init)
        with jax.named_scope("kmedians.finalize"):
            labels = assign(centers)
        return centers, labels, n_iter, state if route == "column_select" else jnp.int32(0)

    def fit(self, x: DNDarray) -> "KMedians":
        """(reference kmedians.py:87-130), as a single on-device loop, issued
        through ``launch`` at ``jit:kmedians.fit``.  The span's fields:
        ``sweeps`` (``max_iter``: the most the loop runs, and what it runs
        where ``tol`` is negative), ``assign`` (``manhattan``), ``medians``
        (the route) and, on ``column_select``, ``x_passes``: how many times
        that many sweeps read X (two a sweep, one for the last assignment),
        and ``network_max``: the most members of a cluster the kernel selects
        by its comparison network."""
        sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        self._initialize_cluster_centers(x)
        arr = x.larray.astype(jnp.float32)
        centers = self._cluster_centers.larray.astype(jnp.float32)
        route = _medians_route(arr, self.n_clusters)
        fields = {"sweeps": int(self.max_iter), "assign": "manhattan", "medians": route}
        if route == "column_select":
            from ..core import _colmedian

            fields["x_passes"] = 2 * int(self.max_iter) + 1
            fields["network_max"] = _colmedian._NETWORK_MAX

        centers, labels, n_iter, self._selections = launch(
            "jit:kmedians.fit",
            KMedians._fit_loop,
            (arr, centers, jnp.float32(self.tol), jnp.int32(self.max_iter)),
            {"route": route},
            **fields,
        )
        self._finalize_fit(x, centers, labels, n_iter)
        return self
