"""Graph Laplacians from similarity matrices.

Reference: heat/graph/laplacian.py:5-108 — adjacency from a pairwise
similarity (fully-connected or ε-neighborhood thresholding, :87-108),
then the simple ``L = D − A`` (:82) or the symmetrically normalized
``I − D^{-1/2} A D^{-1/2}`` (:68) Laplacian.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp

from ..core import types
from ..core._compile import jitted
from ..core.dndarray import DNDarray
from ..core.sanitation import sanitize_in

__all__ = ["Laplacian"]


class Laplacian:
    """Laplacian operator builder (reference laplacian.py:5-66).

    Parameters
    ----------
    similarity : callable(DNDarray) -> DNDarray
        Maps (n, f) data to an (n, n) similarity/affinity matrix.
    definition : 'simple' | 'norm_sym'
    mode : 'fully_connected' | 'eNeighbour'
    threshold_key : 'upper' | 'lower' — keep edges below/above the threshold
    threshold_value : float
    """

    def __init__(
        self,
        similarity: Callable,
        weighted: bool = True,
        definition: str = "norm_sym",
        mode: str = "fully_connected",
        threshold_key: str = "upper",
        threshold_value: float = 1.0,
        neighbours: int = 10,
    ):
        self.similarity_metric = similarity
        self.weighted = weighted
        if definition not in ("simple", "norm_sym"):
            raise NotImplementedError(
                "Only simple and normalized symmetric graphs supported, got " + definition
            )
        if mode not in ("fully_connected", "eNeighbour"):
            raise NotImplementedError(
                "Only eNeighbour or fully-connected graphs supported, got " + mode
            )
        self.definition = definition
        self.mode = mode
        self.epsilon = (threshold_key, threshold_value)
        self.neighbours = neighbours

    def construct(self, X: DNDarray) -> DNDarray:
        """Build L from data (reference laplacian.py:87-108): the
        similarity, then ONE compiled program that thresholds it, zeroes its
        diagonal and turns it into L in the similarity's own buffer.  The
        similarity's result is consumed (donated to that program), so beside
        the program's temporaries one (n, n) float32 array is live, not the
        four an eager chain holds; a similarity that hands back ``X`` itself
        (a precomputed matrix) is left alone and costs one copy."""
        sanitize_in(X)
        S = self.similarity_metric(X)
        A = S.larray
        # only a float32 buffer can become L's, and never the caller's own
        donate = A.dtype == jnp.float32 and A is not X.larray
        fn = _program(self.definition, self.mode, *self.epsilon, bool(self.weighted), donate)
        del S
        L = fn(A)
        split = X.split if X.split == 0 else None
        L = X.comm.apply_sharding(L, split)
        return DNDarray(L, tuple(L.shape), types.float32, split, X.device, X.comm, True)


def _program(definition: str, mode: str, key: str, val, weighted: bool, donate: bool):
    """The cached compiled program of one Laplacian (site
    ``jitted:laplacian.norm_sym`` / ``jitted:laplacian.simple``); with
    ``donate`` its input's buffer becomes the result's."""
    val = float(val)
    kwargs = {"donate_argnums": (0,)} if donate else None

    def make():
        return functools.partial(
            _laplacian, definition=definition, mode=mode, key=key, val=val, weighted=weighted
        )

    if definition == "norm_sym":
        return jitted(("laplacian.norm_sym", mode, key, val, weighted, donate), make, jit_kwargs=kwargs)
    return jitted(("laplacian.simple", mode, key, val, weighted, donate), make, jit_kwargs=kwargs)


def _on_diagonal(A):
    return jax.lax.broadcasted_iota(jnp.int32, A.shape, 0) == jax.lax.broadcasted_iota(jnp.int32, A.shape, 1)


def _adjacency(S, mode: str, key: str, val: float, weighted: bool):
    """The similarity as an adjacency: ε-neighbourhood thresholding
    (reference laplacian.py:87-108), no self-loops."""
    A = S.astype(jnp.float32)
    if mode == "eNeighbour":
        keep = A < val if key == "upper" else A > val
        A = jnp.where(keep, A if weighted else 1.0, 0.0)
    return jnp.where(_on_diagonal(A), 0.0, A)


def _norm_sym(A):
    """I − D^{-1/2} A D^{-1/2} (reference laplacian.py:68-81)."""
    with jax.named_scope("laplacian.degree"):
        degree = jnp.sum(A, axis=1)
        d_inv_sqrt = jnp.where(degree > 0, 1.0 / jnp.sqrt(degree), 0.0)
    with jax.named_scope("laplacian.normalize"):
        L = -A * d_inv_sqrt[:, None] * d_inv_sqrt[None, :]
        return jnp.where(_on_diagonal(A), 1.0, L)


def _simple(A):
    """D − A (reference laplacian.py:82-86)."""
    with jax.named_scope("laplacian.degree"):
        degree = jnp.sum(A, axis=1)
    with jax.named_scope("laplacian.normalize"):
        return jnp.where(_on_diagonal(A), degree[:, None], 0.0) - A


def _laplacian(S, definition: str, mode: str, key: str, val: float, weighted: bool):
    A = _adjacency(S, mode, key, val, weighted)
    return _norm_sym(A) if definition == "norm_sym" else _simple(A)
