"""K-Medoids clustering (centroids snapped to actual data points).

Reference: heat/cluster/kmedoids.py:5-130 — the shared skeleton with a
medoid update: compute the cluster mean, then snap to the nearest real
datapoint of that cluster (:43-103).

TPU formulation: the fit is one jitted ``lax.while_loop`` (the KMeans
pattern, kmeans.py:61-102) — snapping makes convergence exact, so the
loop's device-side stop test is ``shift > 0``; no per-epoch host sync.
"""

from __future__ import annotations

from typing import Optional, Union

import jax
import jax.numpy as jnp

from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in
from ..spatial import distance
from ._kcluster import _KCluster, _quadratic_cdist

__all__ = ["KMedoids"]


def _assign(arr, c):
    """Nearest-medoid labels; |x|² dropped (constant across candidates,
    see kmeans.py:70-76)."""
    c2 = jnp.sum(c * c, axis=1)[None, :]
    return jnp.argmin(c2 - 2.0 * jnp.matmul(arr, c.T), axis=1)


def _medoid_update(arr, labels, c):
    """Mean per cluster, snapped to the nearest member datapoint
    (reference kmedoids.py:43-103); empty clusters keep the old medoid."""
    from ..spatial.distance import quadratic_d2

    k = c.shape[0]
    member = labels[None, :] == jnp.arange(k)[:, None]  # (k, n)
    counts = jnp.sum(member, axis=1)[:, None]
    sums = jnp.matmul(member.astype(arr.dtype), arr)
    means = sums / jnp.maximum(counts, 1)
    # snap each mean to the closest member point, +inf on outsiders
    d2 = jnp.where(member, quadratic_d2(means, arr), jnp.inf)
    medoid_idx = jnp.argmin(d2, axis=1)
    return jnp.where(counts > 0, arr[medoid_idx], c)


class KMedoids(_KCluster):
    """K-Medoids estimator (reference kmedoids.py:5-42)."""

    _init_plus_plus_alias = "kmedoids++"

    def __init__(
        self,
        n_clusters: int = 8,
        init: Union[str, DNDarray] = "random",
        max_iter: int = 300,
        random_state: Optional[int] = None,
    ):
        super().__init__(
            # quadratic expansion: one MXU matmul, no (n, k, f) temporary
            metric=_quadratic_cdist,  # module-level: fused-assign cache hit
            n_clusters=n_clusters,
            init=init,
            max_iter=max_iter,
            tol=0.0,  # medoids converge exactly (reference kmedoids.py:37)
            random_state=random_state,
        )

    @staticmethod
    @jax.jit
    def _fit_loop(arr, centers, max_iter):
        """The whole fit as one compiled ``lax.while_loop`` (the KMeans
        pattern, kmeans.py:61-102).  Medoids are snapped to actual rows of
        ``arr``, so convergence is exact: the loop stops when the squared
        shift is exactly zero — no float tolerance, no per-epoch host sync
        (the reference checks ``equal(...)`` on host each epoch,
        kmedoids.py:104-130)."""

        def cond(state):
            it, _, shift = state
            return jnp.logical_and(it < max_iter, shift > 0.0)

        def body(state):
            it, c, _ = state
            nc = _medoid_update(arr, _assign(arr, c), c)
            return it + 1, nc, jnp.sum((nc - c) ** 2)

        init = (jnp.int32(0), centers, jnp.float32(jnp.inf))
        n_iter, centers, _ = jax.lax.while_loop(cond, body, init)
        return centers, _assign(arr, centers), n_iter

    def fit(self, x: DNDarray) -> "KMedoids":
        """Iterate until the medoids stop moving (reference
        kmedoids.py:104-130), as a single on-device loop."""
        sanitize_in(x)
        if x.ndim != 2:
            raise ValueError(f"input needs to be 2D, but was {x.ndim}D")
        self._initialize_cluster_centers(x)
        arr = x.larray.astype(jnp.float32)
        centers = self._cluster_centers.larray.astype(jnp.float32)

        centers, labels, n_iter = KMedoids._fit_loop(
            arr, centers, jnp.int32(self.max_iter)
        )
        self._finalize_fit(x, centers, labels, n_iter)
        return self
