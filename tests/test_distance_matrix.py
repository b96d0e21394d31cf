"""Spatial-distance split matrix — the reference's test_distances.py
case grid (X.split x Y.split x metric, with result-split assertions,
reference heat/spatial/tests/test_distances.py:14-263) driven against
scipy's oracle on ragged sizes.  The reference supports split 0/None and
hand-rolls a ring for the both-split case (distance.py:244-470); here
every combination — including the column split it rejects — lowers
through one GSPMD plan."""

from __future__ import annotations

import jax
import numpy as np
import pytest
from scipy.spatial.distance import cdist as scipy_cdist

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.spatial import distance

RNG = np.random.default_rng(31)
A = RNG.normal(size=(11, 3)).astype(np.float32)  # 11, 7: ragged on 2/4/7/8
B = RNG.normal(size=(7, 3)).astype(np.float32)


@pytest.mark.parametrize("sx", [None, 0])
@pytest.mark.parametrize("sy", [None, 0])
@pytest.mark.parametrize("quad", [False, True])
def test_cdist_split_matrix(sx, sy, quad):
    d = ht.spatial.cdist(
        ht.array(A, split=sx), ht.array(B, split=sy), quadratic_expansion=quad
    )
    np.testing.assert_allclose(d.numpy(), scipy_cdist(A, B), atol=2e-3)
    # result rows follow X's sharding (reference case table,
    # test_distances.py:25-110)
    assert d.split == sx
    assert d.gshape == (11, 7)


@pytest.mark.parametrize("sx", [None, 0])
@pytest.mark.parametrize("sy", [None, 0])
def test_manhattan_split_matrix(sx, sy):
    d = ht.spatial.manhattan(ht.array(A, split=sx), ht.array(B, split=sy))
    np.testing.assert_allclose(
        d.numpy(), scipy_cdist(A, B, metric="cityblock"), rtol=1e-4, atol=1e-4
    )
    assert d.split == sx


@pytest.mark.parametrize("sx", [None, 0])
@pytest.mark.parametrize("sigma", [0.5, 1.0, 2.0])
def test_rbf_split_sigma_matrix(sx, sigma):
    d = ht.spatial.rbf(ht.array(A, split=sx), sigma=sigma)
    want = np.exp(-scipy_cdist(A, A) ** 2 / (2.0 * sigma**2))
    np.testing.assert_allclose(d.numpy(), want, atol=1e-5)
    # self-distance: symmetric with unit diagonal
    got = d.numpy()
    np.testing.assert_allclose(got, got.T, atol=1e-5)
    np.testing.assert_allclose(np.diag(got), np.ones(11), atol=1e-5)


def test_cdist_self_symmetric_zero_diag():
    d = ht.spatial.cdist(ht.array(A, split=0))
    got = d.numpy()
    np.testing.assert_allclose(got, got.T, atol=1e-4)
    np.testing.assert_allclose(np.diag(got), np.zeros(11), atol=1e-3)


def test_cdist_column_split_superset():
    # the reference's _dist REJECTS feature-split operands
    # (distance.py:187-243); the GSPMD formulation handles them — pinned
    # here as a deliberate superset
    d = ht.spatial.cdist(ht.array(A, split=1), ht.array(B))
    np.testing.assert_allclose(d.numpy(), scipy_cdist(A, B), atol=2e-3)


def test_cdist_error_contracts():
    with pytest.raises(NotImplementedError):
        ht.spatial.cdist(ht.ones(3))  # 1-D operand
    with pytest.raises(ValueError):
        ht.spatial.cdist(ht.ones((3, 2)), ht.ones((3, 4)))  # feature mismatch


def test_big_ragged_cdist_matches():
    # a larger ragged case across the mesh: 83 x 59 rows, 5 features
    x = RNG.normal(size=(83, 5)).astype(np.float32)
    y = RNG.normal(size=(59, 5)).astype(np.float32)
    d = ht.spatial.cdist(ht.array(x, split=0), ht.array(y, split=0))
    np.testing.assert_allclose(d.numpy(), scipy_cdist(x, y), atol=5e-3)
    assert d.split == 0


@pytest.fixture
def tel():
    """``telemetry.enable()`` around one test, the record empty at both ends."""
    was = telemetry.is_enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("with_y", [False, True])
@pytest.mark.parametrize("split", [None, 0, 1])
@pytest.mark.parametrize("f", [1, 3, 18, 64, 65])
def test_exact_form_in_either_loop_order(f, split, with_y, dtype, tel, monkeypatch):
    """The exact form's two loop orders (``distance._pairwise_sum``) are one
    sum: unrolled over the features up to ``_UNROLL_MAX_FEATURES`` of them,
    reduced over the broadcast beyond; both agree with each other and with
    scipy to rounding, every pair, and the launch span says which ran."""
    if dtype is np.float64 and not jax.config.jax_enable_x64:
        pytest.skip("x64 is off")
    x = RNG.normal(size=(37, f)).astype(dtype)  # 37, 23: n != m, ragged on 2/4/8
    y = RNG.normal(size=(23, f)).astype(dtype) if with_y else None
    X = ht.array(x, split=split)
    Y = ht.array(y, split=split) if with_y else None
    want = scipy_cdist(x.astype(np.float64), (y if with_y else x).astype(np.float64))
    eps = np.finfo(dtype).eps
    tol = 4 * eps * np.sqrt(f) * want.max()  # sqrt of a sum of f rounded squares

    def launched():
        d = ht.spatial.cdist(X, Y)
        (span,) = [e for e in tel.events() if e.get("site") == "jitted:dist.euclidean"]
        tel.reset()
        assert d.split == (0 if split == 0 else None) and d.gshape == want.shape
        assert d.dtype == ht.types.canonical_heat_type(dtype)
        return d.numpy(), span["form"]

    taken, form = launched()
    assert form == ("reduce" if f == 65 else "unrolled") == distance._form(f)
    monkeypatch.setattr(distance, "_UNROLL_MAX_FEATURES", 0)  # the reduce, whatever the width
    reduced, form = launched()
    assert form == "reduce"
    np.testing.assert_allclose(taken, reduced, rtol=0, atol=tol)
    np.testing.assert_allclose(taken, want, rtol=0, atol=tol)
    if not with_y:  # x - x is exactly 0 feature by feature, in either order
        assert not np.diag(taken).any() and not np.diag(reduced).any()
    monkeypatch.undo()

    inlined = ht.fuse(ht.spatial.cdist)(X, Y).numpy()
    # inside the fused program the call is no launch and no entry of its own
    assert not {"jitted:dist.euclidean", "spatial:cdist"} & {e.get("site") for e in tel.events()}
    np.testing.assert_allclose(inlined, taken, rtol=0, atol=tol)
