"""KMeans' Lloyd segment picks the layout of its sweep operand
(``cluster/kmeans.py:_feature_layout``): rows split over the mesh keep the
row layout unless the segment's sweeps would put more bytes on the ICI than
moving X once to feature columns, which wide, short data does.  Both
layouts run the same loop; the feature layout only sums its distance
partials and its shift over the mesh, so it holds the row layout's and a
plain Lloyd loop's centres to float32 rounding, and its checkpointed fits
resume bit for bit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.cluster import kmeans
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.resilience import faults
from heat_tpu.resilience.faults import Preempted

K, SWEEPS = 4, 12


def _comm(p):
    devs = jax.devices()
    if len(devs) < p:
        pytest.skip(f"needs {p} devices")
    return XlaCommunication(devs[:p])


def _blobs(n, f, seed=0):
    rng = np.random.default_rng(seed)
    centres = 4.0 * rng.standard_normal((K, f))
    return (centres[np.arange(n) % K] + rng.standard_normal((n, f))).astype(np.float32)


def _plain_lloyd(X, c, sweeps):
    """Lloyd's algorithm in plain ``jax.numpy``, on one device: the
    reference both layouts are held to."""
    X, c = jnp.asarray(X), jnp.asarray(c)
    for _ in range(sweeps):
        labels = jnp.argmin(jnp.sum(c * c, axis=1)[None, :] - 2.0 * X @ c.T, axis=1)
        sel = jax.nn.one_hot(labels, K, dtype=X.dtype)
        counts = jnp.sum(sel, axis=0)[:, None]
        c = jnp.where(counts > 0, sel.T @ X / jnp.maximum(counts, 1), c)
    return np.asarray(c), np.asarray(labels)


def _fit(X, comm, **kw):
    """A fit from the first K rows, and the ``layout`` fields of its
    ``jit:kmeans.fit_segment`` spans."""
    telemetry.enable()
    first = len(telemetry.events())
    x = ht.array(X, split=0, comm=comm)
    km = ht.cluster.KMeans(K, init=ht.array(X[:K], comm=comm), max_iter=SWEEPS, tol=-1.0, **kw).fit(x)
    layouts = [
        e["layout"] for e in telemetry.events()[first:]
        if e["type"] == "span" and e["site"] == "jit:kmeans.fit_segment"
    ]
    return km, layouts


@pytest.fixture
def recording():
    was = telemetry.is_enabled()
    telemetry.reset()
    yield
    telemetry.reset()
    if not was:
        telemetry.disable()


@pytest.mark.parametrize("p", [4, 8])
@pytest.mark.parametrize("f", [8192, 8190])  # 8190: the columns padded to the mesh
def test_feature_layout_matches_the_rows_and_a_plain_loop(p, f):
    comm = _comm(p)
    X = _blobs(64, f)
    arr = ht.array(X, split=0, comm=comm).larray
    cols = kmeans._feature_layout(arr, K, SWEEPS)
    assert cols is not None and cols.spec == jax.sharding.PartitionSpec(None, comm.axis_name)
    carry = (jnp.int32(0), jnp.asarray(X[:K]), jnp.float32(jnp.inf))
    tol, stop = jnp.float32(-1.0), jnp.int32(SWEEPS)
    by_cols = kmeans._fit_segment(arr, tol, stop, carry, cols=cols)
    by_rows = kmeans._fit_segment(arr, tol, stop, carry)
    want, labels = _plain_lloyd(X, X[:K], SWEEPS)
    assert int(by_cols[0]) == int(by_rows[0]) == SWEEPS
    assert by_cols[1].shape == (K, f) and by_cols[1].sharding.is_fully_replicated
    scale = np.abs(want).max()
    np.testing.assert_allclose(np.asarray(by_cols[1]), np.asarray(by_rows[1]), rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(np.asarray(by_cols[1]), want, rtol=0, atol=1e-5 * scale)
    np.testing.assert_allclose(float(by_cols[2]), float(by_rows[2]), rtol=1e-4, atol=1e-6)
    final = ht.cluster.KMeans._finalize(arr, by_cols[1])[0]
    np.testing.assert_array_equal(np.asarray(final), labels)


@pytest.mark.parametrize("p", [4, 8])
def test_a_wide_fit_takes_the_feature_layout_and_says_so(recording, p):
    comm = _comm(p)
    X = _blobs(64, 8192)
    km, layouts = _fit(X, comm)
    assert layouts == ["features"]
    want, labels = _plain_lloyd(X, X[:K], SWEEPS)
    np.testing.assert_allclose(km.cluster_centers_.numpy(), want, rtol=0, atol=1e-5 * np.abs(want).max())
    np.testing.assert_array_equal(km.labels_.numpy(), labels)
    assert km.n_iter_ == SWEEPS


@pytest.mark.parametrize(
    "why,n,f,p",
    [
        ("tall and narrow", 4096, 16, 4),
        ("one device", 64, 8192, 1),
        ("ragged rows", 66, 8192, 4),
        ("fewer features than devices", 64, 3, 4),
    ],
)
def test_every_other_operand_keeps_the_row_layout(recording, why, n, f, p):
    comm = _comm(p)
    X = _blobs(n, f)
    assert kmeans._feature_layout(ht.array(X, split=0, comm=comm).larray, K, SWEEPS) is None, why
    km, layouts = _fit(X, comm)
    assert layouts == ["rows"], why
    want, _ = _plain_lloyd(X, X[:K], SWEEPS)
    np.testing.assert_allclose(km.cluster_centers_.numpy(), want, rtol=0, atol=1e-5 * np.abs(want).max())


def test_the_choice_weighs_the_segments_own_sweeps():
    """One sweep of the centre sums' all-reduce is less than the exchange of
    X: a segment that short keeps the rows, a long one takes the columns."""
    arr = ht.array(_blobs(64, 8192), split=0, comm=_comm(4)).larray
    assert kmeans._feature_layout(arr, K, 1) is None
    assert kmeans._feature_layout(arr, K, SWEEPS) is not None


def _bits(a):
    return np.asarray(a).view(np.uint32)


def test_a_checkpointed_feature_fit_resumes_bit_for_bit(recording, tmp_path):
    comm = _comm(4)
    X = _blobs(64, 8192, seed=3)
    p = str(tmp_path / "km.h5")
    whole, layouts = _fit(X, comm)
    uninterrupted, seg_layouts = _fit(X, comm, checkpoint_every=6, checkpoint_path=str(tmp_path / "u.h5"))
    assert layouts == ["features"] and seg_layouts == ["features", "features"]
    with pytest.raises(Preempted):
        with faults.inject("preempt", site="iteration", nth=1):
            _fit(X, comm, checkpoint_every=6, checkpoint_path=p)
    x = ht.array(X, split=0, comm=comm)
    resumed = ht.cluster.KMeans(K, max_iter=SWEEPS, tol=-1.0, checkpoint_every=6, checkpoint_path=p)
    resumed.fit(x, resume=True)
    for other in (uninterrupted, whole):
        np.testing.assert_array_equal(_bits(resumed.cluster_centers_.numpy()), _bits(other.cluster_centers_.numpy()))
        np.testing.assert_array_equal(resumed.labels_.numpy(), other.labels_.numpy())
    assert resumed.n_iter_ == SWEEPS
