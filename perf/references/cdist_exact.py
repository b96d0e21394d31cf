"""Plain reference for the cdist configurations: pairwise euclidean
distances in their exact form, ``sqrt(sum((x_i - y_j)**2))``.  Imports
nothing of the program and is handed nothing the program made but the
matrix under judgement.

:func:`judge` compares the whole served matrix, block of rows by block of
rows so that it fits beside it, with the same form in ``jax.numpy`` float32
on the device (``dist_err_all``: largest absolute difference over all n*n
entries, so a row left out or one entry altered shows), and a sample of rows
drawn from the seed with numpy float64 on the host (``dist_err_f64``: what
anchors the precision).  :func:`pairwise` in a given dtype is the reference
a test uses (float32) and the control put in the program's place (bfloat16).
"""

from __future__ import annotations

import numpy as np


def pairwise(x, dtype, block_rows: int = 2000):
    """The whole (n, n) matrix of ``x`` against itself, computed in ``dtype``
    a block of rows at a time, returned as float32."""
    import jax
    import jax.numpy as jnp

    n = int(x.shape[0])
    if n % block_rows:
        block_rows = n

    @jax.jit
    def whole(x):
        xd = x.astype(dtype)

        def rows(block):
            diff = block[:, None, :] - xd[None, :, :]
            return jnp.sqrt(jnp.sum(diff * diff, axis=-1)).astype(jnp.float32)

        # one block of rows at a time, written straight into the one result
        return jax.lax.map(rows, xd.reshape(n // block_rows, block_rows, -1)).reshape(n, n)

    return whole(x)


def judge(x, outputs: dict, seed: int, sample_rows: int = 256, block_rows: int = 2000) -> dict:
    import jax
    import jax.numpy as jnp

    served = outputs["distances"]
    n = int(x.shape[0])
    xf = x.astype(jnp.float32)

    @jax.jit
    def block_err(block, full, got):
        diff = block[:, None, :] - full[None, :, :]
        ref = jnp.sqrt(jnp.sum(diff * diff, axis=-1))
        return jnp.max(jnp.abs(got.astype(jnp.float32) - ref))

    worst = 0.0
    for lo in range(0, n, block_rows):
        hi = min(lo + block_rows, n)
        worst = max(worst, float(block_err(xf[lo:hi], xf, served[lo:hi])))

    rng = np.random.default_rng(int(seed))
    pick = np.sort(rng.choice(n, size=min(sample_rows, n), replace=False))
    host = np.asarray(xf, dtype=np.float64)
    got = np.asarray(served[jnp.asarray(pick)], dtype=np.float64)
    err64 = 0.0
    for a in range(0, len(pick), 32):
        rows64 = host[pick[a:a + 32]]
        ref = np.sqrt(((rows64[:, None, :] - host[None, :, :]) ** 2).sum(-1))
        err64 = max(err64, float(np.max(np.abs(got[a:a + 32] - ref))))
    return {"dist_err_all": worst, "dist_err_f64": err64}
