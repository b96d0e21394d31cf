"""Job entry ``spectral_fit``: one job is one call of the program's public
``ht.cluster.Spectral(...).fit(X)`` on the resident data: the rbf similarity,
the normalised symmetric Laplacian, ``n_lanczos`` Lanczos steps, the host's
``eigh`` of the tridiagonal, KMeans on the embedding (HeAT v0.5.1
``heat/cluster/spectral.py``, its documented defaults).  Every job does the
same work: the start vector is the uniform one and KMeans is seeded alike.

The configuration's ``job`` block: ``clusters``, ``gamma``, ``n_lanczos``,
``metric``, ``laplacian``.
"""

from __future__ import annotations

import importlib


def _reference(config):
    return importlib.import_module(f"references.{config['reference']}")


def _job(config) -> tuple:
    job = config["job"]
    return int(job["clusters"]), float(job["gamma"]), int(job["n_lanczos"])


def prepare(ht, config, x):
    """Hand the benchmark's array to the program: a ``split=0`` DNDarray over
    the same buffers."""
    return ht.array(x, split=0, copy=False)


def run(ht, config, state, job_index: int, seed: int) -> dict:
    job = config["job"]
    k, gamma, m = _job(config)
    sp = ht.cluster.Spectral(
        n_clusters=k, gamma=gamma, metric=job["metric"], laplacian=job["laplacian"], n_lanczos=m
    )
    sp.fit(state)
    # eigenvalues_ is the host's (the fit read T back); the arrays are not
    # yet waited for: the harness fences them
    return {
        "labels": sp.labels_.larray,
        "embedding": sp.embedding_.larray,
        "eigenvalues": sp.eigenvalues_,
    }


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, *_job(config))


def control(config, x, seed: int) -> dict:
    """The reference in the program's place, one precision below the
    configuration's float32: every array and operation in bfloat16."""
    import jax.numpy as jnp

    return _reference(config).fit(x, *_job(config), jnp.bfloat16)


def work(config) -> dict:
    """Bytes and FLOPs one job needs, from its shapes, whoever implements it.

    The similarity and the Laplacian are symmetric, so the least any float32
    implementation must move is the HALF: ``n(n+1)/2`` entries written once and
    read once by each of the ``m`` matrix-vector products (the start and the
    ``m - 1`` steps).  Counting the half is deliberate: a dense matvec that
    streams all n*n entries then reads at most about half of this roofline,
    which is the room a kernel that reads the symmetric half has; counted
    densely, such a kernel would read over 100 % through no fault of its own.
    Beside it: the basis read by the two re-orthogonalisation products of each
    step (``2 * i * n`` entries at step i, ``n * m**2`` over the steps), X read
    once, the embedding and the int64 labels written.  FLOPs: ``2 n^2 f`` for
    the similarity's product, ``2 n^2 m`` for the matvecs, ``4 n m^2`` for the
    re-orthogonalisation; held against float32 products (``highest``)."""
    d = config["data"]
    n, f = int(d["rows"]), int(d["features"])
    k, _, m = _job(config)
    m = min(m, n)
    half = n * (n + 1) // 2
    return {
        "bytes": (1 + m) * half * 4 + n * m * m * 4 + n * f * 4 + n * k * 4 + n * 8,
        "flops": 2 * n * n * f + 2 * n * n * m + 4 * n * m * m,
        "flops_peak": "f32_highest_tflops",
    }
