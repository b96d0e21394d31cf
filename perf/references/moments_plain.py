"""Plain reference for the moments configurations: the mean and the standard
deviation of every column of a (rows, features) array,

    mean = sum(x, 0) / n        std = sqrt(sum((x - mean)**2, 0) / (n - ddof))

in ``jax.numpy`` float32, a block of columns at a time so that it fits beside
the data made again from the seed.  Imports nothing of the program and is
handed nothing the program made but the two results under judgement.

:func:`judge` holds both served results, all of their columns, to the same
form on the device (``mean_err_all``: largest ``abs(mean - mean_ref)`` in
units of the column's own deviation, so a row left out shows as the share of
a deviation by which it moves the mean; ``std_rel_all``: largest
``abs(std - std_ref) / std_ref``, so another ``ddof`` or a variance served
for a deviation shows), and a sample of columns drawn from the seed to numpy
float64 on the host (``mean_err_f64``, ``std_rel_f64``: what anchors the
precision).  :func:`moments` in a given dtype is the reference a test uses
(float32) and the control put in the program's place (bfloat16).
"""

from __future__ import annotations

import functools

import numpy as np

#: columns a block: 300 rows of it are 157 MB in float32
BLOCK_COLUMNS = 1 << 17


def _blocks(features: int, block: int):
    """``(first column, width)`` of each block; the last may be narrower."""
    return [(lo, min(block, features - lo)) for lo in range(0, features, block)]


@functools.lru_cache(maxsize=None)
def _block_moments(dtype, width: int, ddof: int):
    """The jitted moments of ``width`` columns of ``x`` from column ``lo``, in
    ``dtype``: data rounded to it, sums and results held in it."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def block(x, lo):
        with jax.default_matmul_precision("highest"):
            xd = jax.lax.dynamic_slice_in_dim(x, lo, width, axis=1).astype(dtype)
            n = xd.shape[0]
            mean = jnp.sum(xd, axis=0, dtype=dtype) / jnp.asarray(n, dtype)
            centred = xd - mean[None, :]
            var = jnp.sum(centred * centred, axis=0, dtype=dtype) / jnp.asarray(n - ddof, dtype)
            return mean, jnp.sqrt(var)

    return block


def moments(x, dtype, ddof: int = 0, block: int = BLOCK_COLUMNS) -> dict:
    """``{"mean", "std"}`` of every column of ``x``, computed in ``dtype`` a
    block of columns at a time, returned as float32."""
    import jax.numpy as jnp

    means, stds = [], []
    for lo, width in _blocks(int(x.shape[1]), block):
        mean, std = _block_moments(dtype, width, ddof)(x, lo)
        means.append(mean.astype(jnp.float32))
        stds.append(std.astype(jnp.float32))
    return {"mean": jnp.concatenate(means), "std": jnp.concatenate(stds)}


@functools.lru_cache(maxsize=None)
def _block_errors(width: int, ddof: int):
    """The jitted worst ``(mean_err, std_rel)`` of ``width`` served columns
    from column ``lo`` against the float32 reference of the same block."""
    import jax
    import jax.numpy as jnp

    ref = _block_moments(jnp.float32, width, ddof)

    @jax.jit
    def block_err(x, lo, mean, std):
        mean_ref, std_ref = ref(x, lo)
        got_mean = jax.lax.dynamic_slice_in_dim(mean, lo, width).astype(jnp.float32)
        got_std = jax.lax.dynamic_slice_in_dim(std, lo, width).astype(jnp.float32)
        return (jnp.max(jnp.abs(got_mean - mean_ref) / std_ref),
                jnp.max(jnp.abs(got_std - std_ref) / std_ref))

    return block_err


@functools.lru_cache(maxsize=None)
def _column():
    """One column of ``x``, jitted.  The sample is read a column at a time:
    a gather along the minor axis makes the chip's compiler lay the whole of
    ``x`` out anew, a second 7.5 GB."""
    import jax

    return jax.jit(lambda x, c: jax.lax.dynamic_slice_in_dim(x, c, 1, axis=1))


NUMBERS = ("mean_err_all", "std_rel_all", "mean_err_f64", "std_rel_f64")


def judge(x, outputs: dict, seed: int, ddof: int = 0, sample_columns: int = 256,
          block: int = BLOCK_COLUMNS) -> dict:
    import jax.numpy as jnp

    mean, std = outputs["mean"], outputs["std"]
    n, features = int(x.shape[0]), int(x.shape[1])
    if tuple(mean.shape) != (features,) or tuple(std.shape) != (features,):
        return dict.fromkeys(NUMBERS, float("inf"))

    # the blocks' worst on the device, read once (jnp.max hands a NaN on)
    worst = [_block_errors(width, ddof)(x, lo, mean, std) for lo, width in _blocks(features, block)]
    mean_err, std_rel = (_number(jnp.max(jnp.stack(part))) for part in zip(*worst))

    rng = np.random.default_rng(int(seed))
    pick = np.sort(rng.choice(features, size=min(sample_columns, features), replace=False))
    host = np.asarray(jnp.concatenate([_column()(x, int(c)) for c in pick], axis=1), dtype=np.float64)
    pick = jnp.asarray(pick)
    mean64 = host.sum(0) / n
    std64 = np.sqrt(((host - mean64) ** 2).sum(0) / (n - ddof))
    got_mean = np.asarray(mean[pick], dtype=np.float64)
    got_std = np.asarray(std[pick], dtype=np.float64)
    return {
        "mean_err_all": mean_err,
        "std_rel_all": std_rel,
        "mean_err_f64": _number(np.max(np.abs(got_mean - mean64) / std64)),
        "std_rel_f64": _number(np.max(np.abs(got_std - std64) / std64)),
    }


def _number(value) -> float:
    """A float; a NaN (a result that holds one) is over every limit."""
    value = float(value)
    return float("inf") if value != value else value
