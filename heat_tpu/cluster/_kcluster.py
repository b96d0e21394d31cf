"""Shared engine for k-clustering estimators.

Reference: heat/cluster/_kcluster.py:4-249 — centroid initialization
(uniform sampling or k-means++/probability-based), cluster assignment via
the distance metric, and the fit/predict skeleton.  The reference's
per-sample owner-rank ``Bcast`` during init (:104-113) is
:func:`~heat_tpu.core.communication.fetch_row` where the rows lie evenly
over the mesh (the owner answers, one all-reduce of a single row) and
plain global indexing everywhere else.
"""

from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Union

import numpy as np
import jax.numpy as jnp

from ..core import factories, random, types
from ..core._compile import launch
from ..core._split_semantics import split_semantics as _split_semantics
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.communication import fetch_row
from ..core.dndarray import DNDarray
from ..core.fuse import fuse
from ..telemetry import _core as _tel

__all__ = ["_KCluster"]

import jax
from jax.sharding import NamedSharding, PartitionSpec


def _quadratic_cdist(x, y):
    """Default k-clustering metric: pairwise squared-expansion distances.

    Module-level (not a per-instance lambda) so its identity is
    call-stable and the fused assignment program below caches across
    estimators — see ``cache_stable`` in core/_compile.py.
    """
    from ..spatial import distance

    return distance.cdist(x, y, quadratic_expansion=True)


def _assign_program(x: DNDarray, centers: DNDarray, metric: Callable) -> DNDarray:
    return metric(x, centers).argmin(axis=1)


_fused_assign = fuse(_assign_program)


def _rows_evenly_sharded(x: DNDarray) -> bool:
    """Whether ``x``'s rows lie over the mesh in equal blocks: the layout
    the explicit ``shard_map`` forms (the row fetch of k-means++, KMeans'
    quantized ring) are written for.  Everything else (one device,
    ``split=None``, ``split=1``, a ragged ``n`` whose ``larray`` GSPMD
    keeps replicated) can read any row locally."""
    return x.split == 0 and x.comm.size > 1 and x.shape[0] % x.comm.size == 0


@partial(jax.jit, static_argnames=("rows_sh",))
def _kmeanspp(arr, first, us, rows_sh=None):
    """The ENTIRE k-means++ draw sequence as one compiled ``fori_loop``:
    each step folds the newest center into the running min-distance vector
    and samples the next row index from the d² CDF, then reads that one
    row — zero host syncs and ONE compilation for all k draws.  (A
    per-draw formulation with ``arr[int(idx)]`` on the host compiles the
    gather anew for every distinct index: k compilations and k host reads
    a fit.)  ``us`` is the (k,)
    uniform draw vector; its static length sets the number of centers.

    ``rows_sh`` (a NamedSharding, hashable → static; None on one device)
    is the sharding of an (n,) vector laid out like ``arr``'s rows.  It
    carries the mesh and decides two things:

    * the (n,) min-distance vector is pinned to every device: the distance
      pass still runs row-sharded, but the cumsum/searchsorted sampling
      runs on a local replica: GSPMD turns a prefix scan along a SHARDED
      axis into a sequential program across the shards, where the
      replicated vector is 4 bytes a row (its time on the chip: not
      measured apart; k-means++ whole is 26.1 of 354.4 ms a job on four
      chips, ``PERF.md`` §5, ``kmeans_448_c4``);
    * where it shards the rows (``comm.sharding(1, 0)``, see
      :func:`_rows_evenly_sharded`), the drawn row comes from its owner
      (:func:`fetch_row`: f elements on the wire).  ``arr[idx]`` there
      makes GSPMD all-gather ALL of ``arr`` onto every device per draw
      (795 of 1 147 ms a fit on four chips; ledger, PR 24 against PR 26,
      ``kmeans_448_c4``: ``job_ms`` 1 147.2 -> 354.5; and the compiler
      refuses the source's 1200 rows).  Where it does not
      (``comm.sharding(1, None)``), and on one device, the read is the
      plain dynamic slice."""
    n, k = arr.shape[0], us.shape[0]
    rep_sh = None if rows_sh is None else NamedSharding(rows_sh.mesh, PartitionSpec())
    by_owner = rows_sh is not None and len(rows_sh.spec) > 0

    def rep(v):
        return jax.lax.with_sharding_constraint(v, rep_sh) if rep_sh is not None else v

    def row(i):
        if not by_owner:
            return arr[i]
        with jax.named_scope("kmeanspp.fetch"):
            return fetch_row(arr, i, rows_sh)

    def body(i, state):
        dmin, centers = state
        with jax.named_scope("kmeanspp.distance"):
            d_new = rep(jnp.sum((arr - centers[i - 1]) ** 2, axis=1))
            dmin = jnp.minimum(dmin, d_new)
        with jax.named_scope("kmeanspp.sample"):
            cdf = jnp.cumsum(dmin)
            total = cdf[-1]
            draw = us[i] * jnp.where(total > 0, total, 1.0)
            idx = jnp.clip(jnp.searchsorted(cdf, draw), 0, n - 1)
            return dmin, centers.at[i].set(row(idx))

    centers0 = jnp.zeros((k, arr.shape[1]), arr.dtype).at[0].set(row(first))
    dmin0 = rep(jnp.full((n,), jnp.inf, dtype=arr.dtype))
    _, centers = jax.lax.fori_loop(1, k, body, (dmin0, centers0))
    return centers


class _KCluster(ClusteringMixin, BaseEstimator):
    """Base class for KMeans/KMedians/KMedoids (reference _kcluster.py:4-62).

    Parameters
    ----------
    metric : callable(DNDarray, DNDarray) -> DNDarray
        Pairwise distance function (from :mod:`heat_tpu.spatial.distance`).
    n_clusters, init, max_iter, tol, random_state : as in the reference.
    """

    #: estimator-specific "++" spelling of probability_based init
    #: (reference kmeans.py:46-47, kmedians.py:31-32, kmedoids.py:31-32)
    _init_plus_plus_alias: Optional[str] = None

    def __init__(
        self,
        metric: Callable,
        n_clusters: int,
        init: Union[str, DNDarray],
        max_iter: int,
        tol: float,
        random_state: Optional[int],
        checkpoint_every: int = 0,
        checkpoint_path: Optional[str] = None,
    ):
        # isinstance guard: DNDarray overloads == elementwise
        if isinstance(init, str) and init == self._init_plus_plus_alias:
            init = "probability_based"
        self.n_clusters = n_clusters
        self.init = init
        self.max_iter = max_iter
        self.tol = tol
        self.random_state = random_state
        self.checkpoint_every = checkpoint_every
        self.checkpoint_path = checkpoint_path
        self._metric = metric
        self._cluster_centers = None
        self._labels = None
        self._inertia = None
        self._n_iter = None

    def _checkpointer(self, algo: str, meta: dict, comm=None, splits=None):
        """The loop-snapshot driver for resumable fits (KMeans; the other
        k-clusterers accept the parameters but run unsegmented)."""
        from ..resilience.resume import LoopCheckpointer

        return LoopCheckpointer(
            self.checkpoint_path, self.checkpoint_every, algo, meta,
            comm=comm, splits=splits,
        )

    def _checkpoint_attrs(self):
        # fitted state lives in private storage behind the *_ properties
        return ["_cluster_centers", "_labels", "_inertia", "_n_iter"]

    @property
    def cluster_centers_(self) -> DNDarray:
        return self._cluster_centers

    @property
    def labels_(self) -> DNDarray:
        return self._labels

    @property
    def inertia_(self) -> float:
        # fit() leaves device scalars in place so it never blocks on the
        # host; the sync happens (once) here on first access
        if self._inertia is not None and not isinstance(self._inertia, float):
            self._inertia = _tel.host_read("sync:kcluster.inertia", self._inertia, float)
        return self._inertia

    @property
    def n_iter_(self) -> int:
        if self._n_iter is not None and not isinstance(self._n_iter, int):
            self._n_iter = _tel.host_read("sync:kcluster.n_iter", self._n_iter, int)
        return self._n_iter

    def _initialize_cluster_centers(self, x: DNDarray):
        """Pick initial centroids (reference _kcluster.py:70-190)."""
        if self.random_state is not None:
            random.seed(self.random_state)

        if isinstance(self.init, DNDarray):
            if self.init.shape != (self.n_clusters, x.shape[1]):
                raise ValueError("passed centroids do not match cluster count or data shape")
            self._cluster_centers = self.init.resplit(None)
            return
        if self.init == "random":
            # uniform sampling of k distinct rows (reference :82-117);
            # draws land on x's communicator so sub-mesh fits (elastic
            # recovery on a shrunk device set) don't mix device sets
            idx = random.randperm(
                x.shape[0], device=x.device, comm=x.comm
            )[: self.n_clusters]
            centers = x.larray[idx.larray]
            self._cluster_centers = DNDarray(
                x.comm.apply_sharding(centers, None),
                (self.n_clusters, x.shape[1]),
                x.dtype,
                None,
                x.device,
                x.comm,
                True,
            )
            return
        if self.init == "probability_based":
            # k-means++ (reference :129-180): iterative distance-weighted
            # draws.  The running min-distance vector is updated against
            # only the NEWEST center (one (n, f) pass per draw, no
            # (n, k, f) temporary), and the whole draw sequence runs as a
            # single compiled loop — no host round trips at all.
            arr = x.larray.astype(jnp.float32)
            n = arr.shape[0]

            first = random.randint(0, n, (1,), device=x.device, comm=x.comm).larray[0]
            us = random.rand(
                self.n_clusters, device=x.device, comm=x.comm
            ).larray.astype(jnp.float32)
            by_owner = _rows_evenly_sharded(x)
            rows_sh = (
                x.comm.sharding(1, 0 if by_owner else None) if x.comm.size > 1 else None
            )
            carr = launch(
                "jit:kmeans.kmeanspp", _kmeanspp, (arr, first, us), {"rows_sh": rows_sh},
                row_fetch="owner_psum" if by_owner else "local",
            ).astype(x.dtype.jax_type())
            if by_owner and _tel.enabled:
                from ..comm.compressed import _account_wire

                # the row fetches run INSIDE the compiled program (one
                # all-reduce of f float32 per centre), out of sight of the
                # host-level accounting: credited here, as KMeans.fit
                # credits its quantized ring
                _account_wire(
                    "allreduce", None, arr.shape[1], x.comm.size, reps=self.n_clusters
                )
            self._cluster_centers = DNDarray(
                x.comm.apply_sharding(carr, None),
                (self.n_clusters, x.shape[1]),
                x.dtype,
                None,
                x.device,
                x.comm,
                True,
            )
            return
        raise ValueError(
            f"init needs to be one of 'random', DNDarray or 'probability_based', got {self.init}"
        )

    def _assign_to_cluster(self, x: DNDarray) -> DNDarray:
        """Nearest-centroid labels (reference _kcluster.py:192-204) as one
        fused program: distance matmul + argmin + layout commit in a single
        device dispatch.  A custom per-instance metric (lambda/closure)
        still works but compiles transiently per call; module-level metrics
        (the default) cache."""
        if self._cluster_centers is None:
            raise RuntimeError(
                f"{type(self).__name__} has no cluster centers — call fit() first"
            )
        from ..core.sanitation import sanitize_predict_in

        x = sanitize_predict_in(
            x,
            n_features=self._cluster_centers.shape[1],
            op=f"{type(self).__name__}.predict",
        )
        return _fused_assign(x, self._cluster_centers, self._metric)

    @_split_semantics("entry_fit")
    def fit(self, x: DNDarray):
        raise NotImplementedError()

    def _finalize_fit(self, x: DNDarray, centers, labels, n_iter) -> None:
        """Store fused-loop results as DNDarrays (shared tail of every
        fit(): device scalars stay on device, labels keep the input's row
        sharding)."""
        # device scalar; n_iter_ property syncs lazily on access
        self._n_iter = n_iter
        self._cluster_centers = DNDarray(
            centers.astype(x.dtype.jax_type()),
            (self.n_clusters, x.shape[1]),
            x.dtype,
            None,
            x.device,
            x.comm,
            True,
        )
        labels_split = x.split if x.split == 0 else None
        lab = x.comm.apply_sharding(labels, labels_split)
        self._labels = DNDarray(
            lab, tuple(lab.shape), types.int64, labels_split, x.device, x.comm, True
        )

    @_split_semantics("entry_split0")
    def predict(self, x: DNDarray) -> DNDarray:
        """Nearest learned centroid for each sample
        (reference _kcluster.py:233-249); input sanitation lives in
        :meth:`_assign_to_cluster`, the one gate fit() shares."""
        return self._assign_to_cluster(x)
