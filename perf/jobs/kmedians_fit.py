"""Job entry ``kmedians_fit``: one job is one call of the program's public
``ht.cluster.KMedians(...).fit(X)`` on the resident data, at the estimator's
documented settings (HeAT v0.5.1 ``heat/cluster/kmedians.py:5-42``: 8
clusters, ``init="random"``, the Manhattan metric) for the suite's k-means
iteration count.  ``tol=-1.0`` makes the iteration count exact.  Each job
seeds the estimator anew (``random_state`` from the run's seed and the job's
index), so the window's jobs draw different starting rows and do the same
work.  Every sweep does its assignment and its medians: nothing of one sweep
or one job may be kept for the next because the labels did not change.

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
    km = ht.cluster.KMedians(
        n_clusters=int(job["clusters"]),
        init=job["init"],
        max_iter=int(job["iterations"]),
        tol=-1.0,
        random_state=(int(seed) + int(job_index)) % (2**31 - 1),
    )
    km.fit(state)
    # n_iter_ reads its device scalar (a fence); the arrays are not yet
    # waited for: the harness fences them
    return {
        "centres": km.cluster_centers_.larray,
        "labels": km.labels_.larray,
        "n_iter": km.n_iter_,
    }


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, seed, int(config["job"]["iterations"]))


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
    """Bytes and FLOPs one job needs, from its shapes, whoever implements it.

    X is read ONCE for each sweep and once more for the last assignment,
    iterations + 1 reads: a sweep's medians need the labels, which need every
    feature of the sweep's distances, so one sweep cannot do with one read of
    its own; but the medians of sweep t over a tile of columns and the partial
    L1 distances of sweep t + 1 over the same tile (against the medians just
    made) can come from ONE read of that tile, and the first assignment needs
    a read to itself.  No sound float32 program needs fewer.  A program that
    reads X for the assignment and again for the medians of every sweep
    (2 x iterations + 1 reads, as the estimator does today) then reads about
    half of this roofline at best, which is the room a fused sweep has;
    counted as 61 reads such a sweep would read over 100 % through no fault of
    its own.  Beside the reads: the centres read and written in each sweep
    and read by the last assignment, the labels (int64) and centres written.

    FLOPs: three operations (subtract, abs, add) an element and centre for
    each of the iterations + 1 assignments.  The selection's comparisons are
    not counted: there is no agreed least for a median of m values.
    ``flops_peak`` names the row of ``peaks.json`` the FLOPs are held against;
    ``x_bytes`` is one read of X, for ``layer_metrics/pass_roofline_pct.py``."""
    d, job = config["data"], config["job"]
    n, f, k, it = int(d["rows"]), int(d["features"]), int(job["clusters"]), int(job["iterations"])
    return {
        "bytes": (it + 1) * n * f * 4 + (2 * it + 1) * k * f * 4 + n * 8 + k * f * 4,
        "flops": 3 * n * f * k * (it + 1),
        "x_bytes": n * f * 4,
        # the table of peaks has no row for the vector units; held against the
        # MXU's the job's least time is the reads', as it would be on any unit
        "flops_peak": "bf16_tflops",
    }


def kernel_work(config) -> dict:
    """For ``PERF.md`` section 5: what ONE assignment pass and ONE median pass
    need, each against one read of X.  Bytes: X and the centres read (the
    assignment) or written (the medians).  Vector operations: three an
    element and centre for the L1 sums; for the medians none is stated (no
    agreed least), so the pass is held to its read alone."""
    d, job = config["data"], config["job"]
    n, f, k = int(d["rows"]), int(d["features"]), int(job["clusters"])
    return {
        "assign": {"bytes": n * f * 4 + k * f * 4, "vector_ops": 3 * n * f * k},
        "medians": {"bytes": n * f * 4 + k * f * 4, "vector_ops": None},
    }
