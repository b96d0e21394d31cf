"""Job entry ``kmeans_fit``: one job is one call of the program's public
``ht.cluster.KMeans(...).fit(X)`` on the resident data, as the reference
harness times it (``benchmarks/kmeans/heat-cpu.py``: 8 clusters, 30
iterations, k-means++ seeding).  ``tol=-1.0`` makes the iteration count
exact.  Each job seeds the estimator anew (``random_state`` from the run's
seed and the job's index), so the window's jobs draw different centres and
do the same work.

The configuration's ``job`` block: ``clusters``, ``iterations``, ``init``.
"""

from __future__ import annotations

import importlib


def _reference(config):
    return importlib.import_module(f"references.{config['reference']}")


def prepare(ht, config, x):
    """Hand the benchmark's array to the program: a ``split=0`` DNDarray over
    the same buffers (no second copy of the data on the chip)."""
    return ht.array(x, split=0, copy=False)


def run(ht, config, state, job_index: int, seed: int) -> dict:
    job = config["job"]
    km = ht.cluster.KMeans(
        n_clusters=int(job["clusters"]),
        init=job["init"],
        max_iter=int(job["iterations"]),
        tol=-1.0,
        random_state=(int(seed) + int(job_index)) % (2**31 - 1),
    )
    km.fit(state)
    # inertia_ and n_iter_ read their device scalars (a fence each); the
    # arrays are not yet waited for: the harness fences them
    return {
        "centres": km.cluster_centers_.larray,
        "labels": km.labels_.larray,
        "inertia": km.inertia_,
        "n_iter": km.n_iter_,
    }


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, int(config["job"]["iterations"]))


def control(config, x, seed: int) -> dict:
    """The reference in the program's place, one precision below the
    configuration's float32: every array and operation in bfloat16."""
    import jax.numpy as jnp

    import datagen

    job = config["job"]
    return _reference(config).fit(
        x, int(job["clusters"]), int(job["iterations"]),
        datagen.seed_key(int(seed) + 1), jnp.bfloat16,
    )


def work(config) -> dict:
    """Bytes and FLOPs one job needs, from its shapes, whoever implements
    it: one read of X for each sweep, for each k-means++ distance pass
    (k - 1: the pass against the last centre drawn is never needed) and for
    the final assignment; the centres read and written in each sweep and
    read by the final assignment; the labels (int64) and inertia written;
    2*n*f*k FLOPs for the distance and for the masked-sum product of each
    sweep and for the final assignment's distances.  ``flops_peak`` names the
    row of ``peaks.json`` the FLOPs are held against."""
    d, job = config["data"], config["job"]
    n, f, k, it = int(d["rows"]), int(d["features"]), int(job["clusters"]), int(job["iterations"])
    reads = it + (k - 1) + 1
    return {
        "bytes": reads * n * f * 4 + (2 * it + 1) * k * f * 4 + n * 8 + k * f * 4 + 4,
        "flops": 2 * n * f * k * (2 * it + 1),
        "flops_peak": "bf16_tflops",  # the products are the MXU's, at jax's default precision
    }
