"""Shared test utilities: numpy-oracle comparison helpers.

Mirrors the reference's test_suites/basic_test.py:12-170 —
``assert_array_equal`` validates both the global value and the shard
geometry; ``assert_func_equal`` sweeps a function over every dtype × split
combination against a numpy oracle.
"""

from __future__ import annotations

import numpy as np

import heat_tpu as ht

SPLITS = (None, 0)
FLOAT_TYPES = (ht.float32, ht.float64)
INT_TYPES = (ht.int32, ht.int64)
ALL_TYPES = FLOAT_TYPES + INT_TYPES
#: the reference's full sweep list (basic_test.py:141-170 iterates every
#: heat dtype); small ints included here, bool swept separately where the
#: op's domain admits it
WIDE_TYPES = ALL_TYPES + (ht.int16, ht.int8, ht.uint8)


def assert_array_equal(heat_array: ht.DNDarray, expected, rtol=1e-5, atol=1e-8):
    """Verify global value + metadata consistency
    (reference basic_test.py:68-140)."""
    expected = np.asarray(expected)
    assert isinstance(heat_array, ht.DNDarray), f"not a DNDarray: {type(heat_array)}"
    assert tuple(heat_array.shape) == tuple(expected.shape), (
        f"global shape {heat_array.shape} != expected {expected.shape}"
    )
    got = heat_array.numpy()
    if expected.dtype.kind in "fc":
        np.testing.assert_allclose(got.astype(np.float64), expected.astype(np.float64), rtol=rtol, atol=atol)
    else:
        np.testing.assert_array_equal(got, expected)
    # shard geometry: lshape_map must tile the global shape along split
    if heat_array.split is not None:
        lmap = heat_array.lshape_map
        assert lmap[:, heat_array.split].sum() == heat_array.shape[heat_array.split]


def all_splits(shape) -> tuple:
    """Every valid split for ``shape``: None plus each axis — the sweep the
    reference runs (basic_test.py:141-170 iterates range(ndim) + None)."""
    try:
        ndim = len(shape)
    except TypeError:
        ndim = 1
    return (None,) + tuple(range(ndim))


def assert_func_equal(
    shape,
    heat_func,
    numpy_func,
    heat_args=None,
    numpy_args=None,
    dtypes=FLOAT_TYPES,
    splits=None,
    low=-100,
    high=100,
    rtol=1e-5,
    atol=1e-6,
):
    """Sweep dtype × split against a numpy oracle
    (reference basic_test.py:141-170).

    ``splits=None`` (default) sweeps None plus *every* axis of ``shape`` —
    including the column-sharded split=1 path for matrices.  Pass an
    explicit tuple to restrict.
    """
    heat_args = heat_args or {}
    numpy_args = numpy_args or {}
    if splits is None:
        splits = all_splits(shape)
    rng = np.random.default_rng(42)
    for dtype in dtypes:
        npdt = np.dtype(dtype._np_type)
        if npdt.kind == "f":
            data = rng.uniform(low, high, size=shape).astype(npdt)
        else:
            data = rng.integers(low, high, size=shape).astype(npdt)
        expected = numpy_func(data, **numpy_args)
        for split in splits:
            x = ht.array(data, split=split)
            result = heat_func(x, **heat_args)
            if isinstance(result, ht.DNDarray):
                assert_array_equal(result, expected, rtol=rtol, atol=atol)
            else:
                np.testing.assert_allclose(result, expected, rtol=rtol, atol=atol)


def run_in_fresh_python(script: str, env_overrides=None, drop_env=(), timeout=240):
    """Run ``script`` in a fresh interpreter from the repo root and return
    the CompletedProcess.  For tests that must control what happens before
    jax backend initialization (multihost bootstrap, import hygiene)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    for k in drop_env:
        env.pop(k, None)
    env.update(env_overrides or {})
    return subprocess.run(
        [sys.executable, "-c", script],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )


def assert_within_bound(got, want, bound):
    """``|got - want| <= bound`` entry by entry, ``got`` finite: for a
    comparison stated as a rounding bound, which is entrywise where
    ``assert_allclose``'s ``atol`` is one number.  Names the worst entry."""
    got, want = np.asarray(got), np.asarray(want)
    assert got.shape == want.shape, (got.shape, want.shape)
    assert np.isfinite(got).all()
    err = np.abs(got.astype(np.float64) - want.astype(np.float64))
    worst = np.unravel_index(np.argmax(err - bound), err.shape)
    assert (err <= bound).all(), (worst, err[worst], np.broadcast_to(bound, err.shape)[worst])
