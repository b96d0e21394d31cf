"""Plain reference for the KMeans configurations: Lloyd's algorithm with
k-means++ seeding, in straightforward ``jax.numpy``, exact-form distances
(``sum((x - c)**2)``, no matmul, so no MXU precision enters), float32.
Imports nothing of the program and is handed nothing the program made but
the outputs under judgement.

Two entries:

- :func:`judge` holds one fit's outputs (centres, labels, inertia, iteration
  count) to the data: the reference's own distances, assignment, Lloyd step
  and inertia, computed from the data and the served centres.
- :func:`fit` is the whole algorithm in a given dtype.  In float32 it is the
  reference a test holds the program to at a small size; in bfloat16 it is
  the control, put in the program's place (``perf/README.md``).

A fit's random draws are the program's own, so two sound fits agree only on
what Lloyd's algorithm guarantees of any run: every row labelled with its
nearest served centre, the inertia of that labelling, the iteration count,
and centres that one more Lloyd step does not move once the partition has
settled.  The numbers:

``label_gap``    widest gap, over all rows, by which the squared distance to
                 the served label's centre exceeds the reference's nearest,
                 in units of the mean squared distance (inertia / rows)
``inertia_rel``  |served inertia - reference inertia of the served labels|
                 over the reference's
``centre_step``  per centre, the norm of (mean of the rows the reference
                 assigns to it) - (served centre) over the norm of the served
                 centre; reported is the WORST of the centres: after the
                 sweeps asked for, one more reference Lloyd step moves no
                 centre by more than rounding.  Every fault of the sweep
                 (rows left out, a missing exchange, a step not taken) moves
                 a centre.
``iters_off``    |iterations run - iterations asked|
"""

from __future__ import annotations

import functools

import numpy as np


@functools.lru_cache(maxsize=None)
def _judge_program(k: int):
    import jax
    import jax.numpy as jnp

    def run(x, centres, labels):
        x = x.astype(jnp.float32)
        centres = centres.astype(jnp.float32)
        # (n, k) squared distances, one centre at a time: x - c is fused into
        # the reduction, nothing of size (n, k, f) exists
        d2 = jnp.stack(
            [jnp.sum((x - centres[j]) ** 2, axis=1) for j in range(k)], axis=1
        )
        best = jnp.min(d2, axis=1)
        near = jnp.argmin(d2, axis=1)
        served = jnp.sum(
            jnp.where(labels[:, None] == jnp.arange(k)[None, :], d2, 0.0), axis=1
        )
        gap = jnp.max(served - best)
        # 4096-row partial sums in float32; the host adds them in float64
        n = x.shape[0]
        pad = (-n) % 4096
        partials = jnp.sum(jnp.pad(served, (0, pad)).reshape(-1, 4096), axis=1)
        steps, counts = [], []
        for j in range(k):
            mine = near == j
            cnt = jnp.sum(mine)
            # mean of (x - c_j) over the rows nearest c_j: the residuals are
            # small, so the float32 sum loses nothing to cancellation
            resid = jnp.sum(jnp.where(mine[:, None], x - centres[j], 0.0), axis=0)
            step = resid / jnp.maximum(cnt, 1)
            steps.append(jnp.sqrt(jnp.sum(step * step) / jnp.sum(centres[j] * centres[j])))
            counts.append(cnt)
        return gap, partials, jnp.stack(steps), jnp.stack(counts)

    return jax.jit(run)


def judge(x, outputs: dict, asked_iters: int) -> dict:
    """The numbers of the module docstring for one fit's ``outputs``
    (``centres`` (k, f), ``labels`` (n,), ``inertia`` scalar, ``n_iter``)."""
    centres, labels = outputs["centres"], outputs["labels"]
    k = int(centres.shape[0])
    gap, partials, steps, counts = _judge_program(k)(x, centres, labels)
    inertia_ref = float(np.sum(np.asarray(partials, dtype=np.float64)))
    n = int(x.shape[0])
    inertia = float(np.asarray(outputs["inertia"], dtype=np.float64))
    return {
        "label_gap": float(gap) / (inertia_ref / n),
        "inertia_rel": abs(inertia - inertia_ref) / inertia_ref,
        "centre_step": float(np.max(np.asarray(steps, dtype=np.float64))),
        "iters_off": float(abs(int(np.asarray(outputs["n_iter"])) - int(asked_iters))),
    }


def fit(x, k: int, iters: int, key, dtype):
    """Lloyd's algorithm, k-means++ seeding, ``iters`` sweeps, every array
    and every operation in ``dtype``.  Returns the outputs dict of
    :func:`judge`."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def run(x, key):
        x = x.astype(dtype)
        n = x.shape[0]

        def d2_to(c):  # (n, k) in dtype
            return jnp.stack(
                [jnp.sum((x - c[j]) ** 2, axis=1) for j in range(c.shape[0])], axis=1
            )

        k0, k1 = jax.random.split(key)
        first = jax.random.randint(k0, (), 0, n)
        us = jax.random.uniform(k1, (k,), jnp.float32)
        centres = jnp.zeros((k, x.shape[1]), dtype).at[0].set(x[first])
        dmin = jnp.full((n,), jnp.inf, jnp.float32)
        for i in range(1, k):
            dmin = jnp.minimum(
                dmin, jnp.sum((x - centres[i - 1]) ** 2, axis=1).astype(jnp.float32)
            )
            cdf = jnp.cumsum(dmin)
            idx = jnp.clip(jnp.searchsorted(cdf, us[i] * cdf[-1]), 0, n - 1)
            centres = centres.at[i].set(x[idx])

        def sweep(_, c):
            near = jnp.argmin(d2_to(c), axis=1)
            rows = []
            for j in range(k):
                mine = (near == j)[:, None]
                cnt = jnp.sum(mine.astype(dtype))
                tot = jnp.sum(jnp.where(mine, x, jnp.zeros((), dtype)), axis=0)
                rows.append(jnp.where(cnt > 0, tot / jnp.maximum(cnt, 1), c[j]))
            return jnp.stack(rows).astype(dtype)

        centres = jax.lax.fori_loop(0, iters, sweep, centres)
        d2 = d2_to(centres)
        labels = jnp.argmin(d2, axis=1)
        inertia = jnp.sum(jnp.min(d2, axis=1))
        return centres, labels, inertia

    centres, labels, inertia = run(x, key)
    return {
        "centres": centres.astype(jnp.float32),
        "labels": labels,
        "inertia": inertia.astype(jnp.float32),
        "n_iter": np.int32(iters),
    }
