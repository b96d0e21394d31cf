"""Spectral clustering via graph Laplacian + Lanczos embedding.

Reference: heat/cluster/spectral.py:6-197 — similarity (rbf/euclidean) →
``graph.Laplacian`` → ``lanczos(L, m)`` → local eig of the tridiagonal T →
spectral embedding → KMeans on the first k eigenvectors, with a
spectral-gap heuristic choosing k when unspecified (:98-165).
"""

from __future__ import annotations

import functools
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from ..core import types
from ..core._compile import jitted
from ..core.base import BaseEstimator, ClusteringMixin
from ..core.dndarray import DNDarray
from ..core.linalg import basics, solver
from ..core.sanitation import sanitize_in
from ..graph import Laplacian
from ..spatial import distance
from ..telemetry import _core as _tel
from .kmeans import KMeans

__all__ = ["Spectral"]


class Spectral(ClusteringMixin, BaseEstimator):
    """Spectral clustering estimator (reference spectral.py:6-97).

    Parameters follow the reference: gamma is the rbf kernel coefficient
    (sigma = sqrt(1/(2·gamma)) ties it to the rbf form), n_lanczos the
    Krylov dimension, metric ∈ {'rbf', 'euclidean'}.
    """

    def __init__(
        self,
        n_clusters: Optional[int] = None,
        gamma: float = 1.0,
        metric: str = "rbf",
        laplacian: str = "fully_connected",
        threshold: float = 1.0,
        boundary: str = "upper",
        n_lanczos: int = 300,
        assign_labels: str = "kmeans",
        **params,
    ):
        self.n_clusters = n_clusters
        self.gamma = gamma
        self.metric = metric
        self.laplacian = laplacian
        self.threshold = threshold
        self.boundary = boundary
        self.n_lanczos = n_lanczos
        self.assign_labels = assign_labels

        if metric == "rbf":
            sigma = float(np.sqrt(1.0 / (2.0 * gamma)))
            sim = lambda x: distance.rbf(x, sigma=sigma, quadratic_expansion=True)
        elif metric == "euclidean":
            sim = lambda x: distance.cdist(x, quadratic_expansion=True)
        else:
            raise NotImplementedError(f"Metric {metric} not implemented")

        self._laplacian = Laplacian(
            sim,
            definition="norm_sym",
            mode=laplacian,
            threshold_key=boundary,
            threshold_value=threshold,
        )
        self._labels = None
        self._cluster_centers = None
        self._eigenvalues = None
        self._embedding = None

    def _checkpoint_attrs(self):
        # the fitted KMeans nests recursively; _laplacian is rebuilt by
        # __init__ from the constructor params
        return [
            "_labels", "_cluster_centers", "_kmeans", "_embedding_dim",
            "_eigenvalues", "_embedding",
        ]

    @property
    def labels_(self):
        return self._labels

    @property
    def eigenvalues_(self):
        """The k smallest Ritz values of the Laplacian (host, float64)."""
        return self._eigenvalues

    @property
    def embedding_(self):
        """The (n, k) spectral embedding ``fit`` clustered: the Ritz vectors
        of the k smallest Ritz values, laid out like the data."""
        return self._embedding

    def _spectral_embedding(self, x: DNDarray, k: Optional[int]):
        """The k lowest eigenpairs of the Laplacian
        (reference spectral.py:98-137): Lanczos tridiagonalization, an
        on-host eig of the small (m, m) tridiagonal T while the chip has
        nothing to do, then the Ritz vectors ``V @ evecs[:, :k]`` at the
        library's linalg precision and k of their rows as seeds for KMeans
        (:func:`_embed`).  ``k=None`` picks k by the spectral-gap
        heuristic (reference spectral.py:151-157)."""
        L = self._laplacian.construct(x)
        m = min(self.n_lanczos, x.shape[0])
        # deterministic start vector: fit() and predict() on the same data
        # must produce the identical Krylov basis (a random v0 would flip
        # eigenvector signs between the two embeddings)
        n = x.shape[0]
        v0 = DNDarray(
            jnp.full((n,), 1.0 / np.sqrt(n), dtype=jnp.float32),
            (n,), types.float32, None, x.device, x.comm, True,
        )
        V, T = solver.lanczos(L, m, v0=v0)
        del L
        # the one wait for similarity, Laplacian and Lanczos together
        t = _tel.host_read("sync:spectral.tridiag", T.larray, np.asarray)
        evals, evecs = _tel.spanned("spectral:eigh", "other", np.linalg.eigh, t.astype(np.float64))
        if k is None:
            diffs = np.diff(evals[: min(len(evals), 15)])
            k = max(int(np.argmax(diffs) + 1) if len(diffs) else 1, 1)
        precision = basics._precision()
        embed = jitted(("spectral.embed", precision), lambda: functools.partial(_embed, precision=precision))
        emb, seeds = embed(V.larray, jnp.asarray(evecs[:, :k], dtype=V.larray.dtype))
        comp = DNDarray(
            x.comm.apply_sharding(emb, x.split), tuple(emb.shape), types.float32,
            x.split, x.device, x.comm, True,
        )
        return evals[:k], comp, seeds

    def fit(self, x: DNDarray) -> "Spectral":
        """(reference spectral.py:138-180)"""
        sanitize_in(x)
        if x.split is not None and x.split != 0:
            raise NotImplementedError("Not implemented for other splitting-axes")
        evals, comp, seeds = self._spectral_embedding(x, self.n_clusters)
        k = comp.shape[1]
        seeds = DNDarray(
            x.comm.apply_sharding(seeds, None), (k, k), types.float32, None, x.device, x.comm, True
        )
        kmeans = KMeans(n_clusters=k, init=seeds)
        kmeans.fit(comp)
        self._labels = kmeans.labels_
        self._cluster_centers = kmeans.cluster_centers_
        self._kmeans = kmeans
        self._embedding_dim = k
        self._eigenvalues = evals
        self._embedding = comp
        return self

    def predict(self, x: DNDarray) -> DNDarray:
        """Embed ``x`` and classify with the fitted k-means
        (reference spectral.py:167-197)."""
        sanitize_in(x)
        if self._labels is None:
            raise RuntimeError("Spectral has not been fitted — call fit() first")
        _, comp, _ = self._spectral_embedding(x, self._embedding_dim)
        return self._kmeans.predict(comp)


def _embed(V, evecs, precision):
    """Ritz vectors of the Laplacian from the Krylov basis, and k of their
    rows to seed KMeans with: row 0, then k - 1 times the row farthest from
    the rows chosen so far (Ng, Jordan & Weiss 2001 seed their k-means on a
    spectral embedding the same way).  The rows of one group lie close
    together beside the distance between groups, so this puts one seed in
    each group; one k-means++ draw, which the reference's ``KMeans`` would
    make, puts two in one group for about one data set in ten and ends in a
    partition with two groups merged and one split."""
    with jax.named_scope("spectral.embed"):
        emb = jnp.matmul(V, evecs, precision=precision)
    with jax.named_scope("spectral.seed"):
        k = evecs.shape[1]

        def pick(i, state):
            dmin, seeds = state
            dmin = jnp.minimum(dmin, jnp.sum((emb - seeds[i - 1]) ** 2, axis=1))
            return dmin, seeds.at[i].set(emb[jnp.argmax(dmin)])

        seeds = jnp.zeros((k, k), emb.dtype).at[0].set(emb[0])
        _, seeds = jax.lax.fori_loop(1, k, pick, (jnp.full(emb.shape[:1], jnp.inf, emb.dtype), seeds))
    return emb, seeds
