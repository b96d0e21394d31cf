"""Plain reference for the KMedians configurations: Lloyd's sweep under the L1
norm, in straightforward ``jax.numpy``, float32: the assignment by Manhattan
distance ``sum_j |x_ij - c_cj|`` (ties to the lowest c), the centre update by
the exact coordinate-wise median of each cluster's rows (numpy's: the mean of
the two middle members at an even count), a cluster without members keeping
its centre.  A block of columns at a time, so that it fits beside the data
made again from the seed: the L1 distances are summed over the blocks, the
medians come from a plain sort of each cluster's members in the block (one
sort by (label, value): a cluster's members then lie in the same rows of
every column).  Imports nothing of the program and is handed nothing the
program made but the outputs under judgement.

- :func:`judge` holds one fit's outputs (centres, labels, iteration count) to
  the data.  The start (k distinct rows drawn uniformly) is the program's own
  draw, so two sound fits agree only on what the algorithm guarantees of any
  run: every row labelled with its nearest served centre, the iteration count,
  and centres that one more reference sweep does not move.
- :func:`fit` is the whole algorithm in a given dtype: in float32 the
  reference a test holds the program to at a small size, in bfloat16 the
  control put in the program's place.

``label_gap``    widest gap, over all rows, by which the L1 distance to the
                 served label's centre exceeds the L1 distance to the nearest
                 served centre, in units of the mean L1 distance to the
                 nearest
``median_step``  per centre, the norm of (median of the rows the reference
                 assigns to it) - (served centre) over the centre's norm; the
                 WORST of the centres.  The medians are exact, so a sound fit
                 whose partition has settled reads 0.
``median_f64``   the same on ``sample_columns`` columns drawn from the seed,
                 against numpy float64 on the host (anchors the precision)
``iters_off``    |iterations run - iterations asked|
"""

from __future__ import annotations

import functools

import numpy as np

#: columns a block: 300 rows of it are 157 MB in float32
BLOCK_COLUMNS = 1 << 17

NUMBERS = ("label_gap", "median_step", "median_f64", "iters_off")


def _blocks(features: int, block: int):
    """``(first column, width)`` of each block; the last may be narrower."""
    return [(lo, min(block, features - lo)) for lo in range(0, features, block)]


@functools.lru_cache(maxsize=None)
def _block_distances(dtype, width: int, k: int):
    """The jitted (n, k) L1 distances over ``width`` columns from column
    ``lo``, data, differences and sums in ``dtype``."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x, centres, lo):
        with jax.default_matmul_precision("highest"):
            xb = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=1).astype(dtype)
            cb = jax.lax.dynamic_slice_in_dim(centres, lo, width, axis=1).astype(dtype)
            return jnp.stack(
                [jnp.sum(jnp.abs(xb - cb[j][None, :]), axis=1, dtype=dtype) for j in range(k)], axis=1
            )

    return block


def distances(x, centres, dtype, block: int = BLOCK_COLUMNS):
    """(n, k) Manhattan distances, summed over the blocks in ``dtype``."""
    k = int(centres.shape[0])
    total = None
    for lo, width in _blocks(int(x.shape[1]), block):
        part = _block_distances(dtype, width, k)(x, centres, lo)
        total = part if total is None else (total + part).astype(dtype)
    return total


@functools.lru_cache(maxsize=None)
def _block_medians(dtype, width: int, k: int):
    """The jitted (k, width) medians of the rows with each label, over
    ``width`` columns from column ``lo``: the block sorted by (label, value),
    the two middle rows of each label's run averaged in ``dtype``.  A label
    without rows gets a row of zeros (the caller keeps its centre)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x, labels, lo):
        with jax.default_matmul_precision("highest"):
            xb = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=1).astype(dtype)
            n = xb.shape[0]
            lab = jnp.broadcast_to(labels.astype(jnp.int32)[:, None], xb.shape)
            _, ordered = jax.lax.sort((lab, xb), dimension=0, num_keys=2)
            counts = jnp.sum(labels[:, None] == jnp.arange(k)[None, :], axis=0)
            starts = jnp.cumsum(counts) - counts
            lower = jnp.clip(starts + (counts - 1) // 2, 0, n - 1)
            upper = jnp.clip(starts + counts // 2, 0, n - 1)
            a = jnp.take(ordered, lower, axis=0)
            b = jnp.take(ordered, upper, axis=0)
            med = jnp.where((lower == upper)[:, None], a, (a + b) / jnp.asarray(2, dtype))
            return jnp.where((counts > 0)[:, None], med, jnp.zeros((), dtype))

    return block


def medians(x, labels, k: int, dtype, block: int = BLOCK_COLUMNS):
    """(k, f) per-cluster medians in ``dtype``, a block of columns at a time."""
    import jax.numpy as jnp

    parts = [_block_medians(dtype, width, k)(x, labels, lo) for lo, width in _blocks(int(x.shape[1]), block)]
    return jnp.concatenate(parts, axis=1)


def _counts(labels, k: int):
    import jax.numpy as jnp

    return jnp.sum(labels[:, None] == jnp.arange(k)[None, :], axis=0)


def fit(x, k: int, iters: int, key, dtype, block: int = BLOCK_COLUMNS) -> dict:
    """``iters`` sweeps from k distinct rows drawn uniformly, every array and
    every operation in ``dtype``.  Returns the outputs dict of :func:`judge`."""
    import jax
    import jax.numpy as jnp

    n = int(x.shape[0])
    first = jax.random.permutation(key, n)[:k]
    centres = jnp.concatenate([jax.lax.dynamic_slice_in_dim(x, int(i), 1, axis=0) for i in np.asarray(first)])
    centres = centres.astype(dtype)
    for _ in range(iters):
        labels = jnp.argmin(distances(x, centres, dtype, block), axis=1)
        med = medians(x, labels, k, dtype, block)
        centres = jnp.where((_counts(labels, k) > 0)[:, None], med, centres)
    labels = jnp.argmin(distances(x, centres, dtype, block), axis=1)
    return {"centres": centres.astype(jnp.float32), "labels": labels, "n_iter": np.int32(iters)}


@functools.lru_cache(maxsize=None)
def _column():
    """One column of ``x``, jitted (a gather along the minor axis makes the
    chip's compiler lay the whole of ``x`` out anew: read a column at a time)."""
    import jax

    return jax.jit(lambda x, c: jax.lax.dynamic_slice_in_dim(x, c, 1, axis=1))


@functools.lru_cache(maxsize=None)
def _block_step(width: int, k: int):
    """Per centre, the squared norms of (reference median - served centre) and
    of the served centre over ``width`` columns from column ``lo``.  The
    medians are made by their own program and handed over whole: fused into
    the subtraction a compiler may contract ``(a + b) / 2 - c`` and move the
    last bit of what is an exact comparison."""
    import jax
    import jax.numpy as jnp

    ref = _block_medians(jnp.float32, width, k)

    @jax.jit
    def norms(med, near, centres, lo):
        cb = jax.lax.dynamic_slice_in_dim(centres, lo, width, axis=1).astype(jnp.float32)
        moved = jnp.where((_counts(near, k) > 0)[:, None], med - cb, 0.0)
        return jnp.sum(moved * moved, axis=1), jnp.sum(cb * cb, axis=1)

    return lambda x, near, centres, lo: norms(ref(x, near, lo), near, centres, lo)


def judge(x, outputs: dict, seed: int, asked_iters: int, sample_columns: int = 256,
          block: int = BLOCK_COLUMNS) -> dict:
    """The numbers of the module docstring for one fit's ``outputs``
    (``centres`` (k, f), ``labels`` (n,), ``n_iter``)."""
    import jax.numpy as jnp

    centres, labels = outputs["centres"], outputs["labels"]
    n, features = int(x.shape[0]), int(x.shape[1])
    k = int(centres.shape[0])
    if tuple(centres.shape) != (k, features) or tuple(labels.shape) != (n,):
        return dict.fromkeys(NUMBERS, float("inf"))
    centres = centres.astype(jnp.float32)
    d = distances(x, centres, jnp.float32, block)
    best = jnp.min(d, axis=1)
    near = jnp.argmin(d, axis=1)
    served = jnp.take_along_axis(d, jnp.clip(labels.astype(jnp.int32), 0, k - 1)[:, None], axis=1)[:, 0]
    gap = _number(jnp.max(served - best) / jnp.mean(best))

    moved, norm = 0.0, 0.0
    for lo, width in _blocks(features, block):
        m, c = _block_step(width, k)(x, near, centres, lo)
        moved, norm = moved + m, norm + c
    step = _number(jnp.max(jnp.sqrt(moved / norm)))

    rng = np.random.default_rng(int(seed))
    pick = np.sort(rng.choice(features, size=min(sample_columns, features), replace=False))
    host = np.asarray(jnp.concatenate([_column()(x, int(c)) for c in pick], axis=1), dtype=np.float64)
    got = np.asarray(centres[:, jnp.asarray(pick)], dtype=np.float64)
    near_host = np.asarray(near)
    worst = 0.0
    for c in range(k):
        mine = host[near_host == c]
        if len(mine):
            with np.errstate(divide="ignore", invalid="ignore"):  # a centre at the origin reads inf
                off = np.linalg.norm(np.median(mine, axis=0) - got[c]) / np.linalg.norm(got[c])
            worst = max(worst, _number(off))
    return {
        "label_gap": gap,
        "median_step": step,
        "median_f64": worst,
        "iters_off": float(abs(int(np.asarray(outputs["n_iter"])) - int(asked_iters))),
    }


def _number(value) -> float:
    """A float; a NaN (a result that holds one) is over every limit."""
    value = float(value)
    return float("inf") if value != value else value
