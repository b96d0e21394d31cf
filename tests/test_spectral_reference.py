"""``ht.cluster.Spectral`` against its plain reference, and the programs it is
made of.

The reference is the benchmark's (``perf/references/spectral_plain.py``:
straightforward ``jax.numpy``, exact-form similarity, a Python Lanczos loop,
nothing imported from the program); ``spectral_40k_c1`` holds the chip's fits
to it at 40 000 rows, this file holds a small fit to it on the CPU mesh by
the same five numbers.  Beside it: ``Laplacian.construct`` is one compiled
program that gives what the eager chain of ``jax.numpy`` calls it replaced
gave and consumes the similarity's buffer; a fit records the spans the
benchmark's ``solvers`` metrics read; the two fitted attributes the
comparison needs survive the estimator checkpoint.
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import _compile, _tracing
from heat_tpu.telemetry import _core

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")

N, F, K, M, GAMMA = 768, 18, 8, 64, 1.0


def _load(name: str, *parts: str):
    """A module of ``perf/`` by its file (``perf/`` is no package of the
    program's and is not put on ``sys.path`` for the whole suite)."""
    spec = importlib.util.spec_from_file_location(name, os.path.join(PERF, *parts))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def plain():
    return _load("spectral_plain", "references", "spectral_plain.py")


def _blobs(seed: int, n: int = N) -> np.ndarray:
    """The cell's stand-in data at test size: row i is centre i % K plus
    noise, as ``perf/datagen.py`` makes it (noise 0.15, centres 0.35)."""
    rng = np.random.default_rng(seed)
    centres = 0.35 * rng.standard_normal((K, F))
    return (centres[np.arange(n) % K] + 0.15 * rng.standard_normal((n, F))).astype(np.float32)


# --------------------------------------------------------------------- #
# the fit against the plain reference                                   #
# --------------------------------------------------------------------- #
#: what a float32 fit reads on the CPU mesh at this size, with room: the
#: residual and the orthogonality are float32 rounding through 63 steps,
#: the partition is the reference's own
LIMITS = {
    "eig_residual": 2e-5,
    "embedding_orth": 2e-5,
    "eigval_err": 2e-5,
    "ncut_excess": 1e-4,
    "label_mismatch": 0.0,
}


@pytest.fixture(scope="module")
def judged(plain):
    x = _blobs(28)
    sp = ht.cluster.Spectral(n_clusters=K, gamma=GAMMA, n_lanczos=M).fit(ht.array(x, split=0))
    out = {
        "labels": sp.labels_.larray,
        "embedding": sp.embedding_.larray,
        "eigenvalues": sp.eigenvalues_,
    }
    numbers = plain.judge(jnp.asarray(x), out, K, GAMMA, M)
    plain.forget()
    return sp, numbers


@pytest.mark.parametrize("number", sorted(LIMITS))
def test_fit_agrees_with_the_plain_reference(judged, number):
    _, numbers = judged
    assert numbers[number] <= LIMITS[number], numbers


def test_fitted_attributes(judged):
    sp, _ = judged
    assert sp.eigenvalues_.shape == (K,) and sp.eigenvalues_.dtype == np.float64
    assert np.all(np.diff(sp.eigenvalues_) >= 0)  # the k smallest, ascending
    assert sp.embedding_.shape == (N, K) and sp.embedding_.split == 0
    assert sp.labels_.shape == (N,)
    # eight groups below a gap: row i belongs to group i % K
    lab = sp.labels_.numpy()
    assert all(len(np.unique(lab[g::K])) == 1 for g in range(K))
    assert len(np.unique(lab)) == K


def test_the_bfloat16_control_is_not_correct(plain):
    """The reference one precision lower, in the program's place, is over
    the limits a float32 fit keeps (the benchmark's control)."""
    x = jnp.asarray(_blobs(29))
    numbers = plain.judge(x, plain.fit(x, K, GAMMA, M, jnp.bfloat16), K, GAMMA, M)
    plain.forget()
    assert numbers["eig_residual"] > 100 * LIMITS["eig_residual"], numbers
    assert numbers["embedding_orth"] > 100 * LIMITS["embedding_orth"], numbers


def test_predict_embeds_like_fit(judged):
    sp, _ = judged
    x = ht.array(_blobs(28), split=0)
    np.testing.assert_array_equal(sp.predict(x).numpy(), sp.labels_.numpy())


# --------------------------------------------------------------------- #
# Laplacian.construct: one program, the eager chain's result            #
# --------------------------------------------------------------------- #
def _eager_chain(S, definition, mode, key, val, weighted=True):
    """``Laplacian.construct`` as it was before it became one program: a
    chain of eager ``jax.numpy`` calls, each with an (n, n) result."""
    A = S.astype(jnp.float32)
    if mode == "eNeighbour":
        if key == "upper":
            A = jnp.where(A < val, A if weighted else 1.0, 0.0)
        else:
            A = jnp.where(A > val, A if weighted else 1.0, 0.0)
    n = A.shape[0]
    A = A.at[jnp.arange(n), jnp.arange(n)].set(0.0)
    if definition == "simple":
        return jnp.diag(jnp.sum(A, axis=1)) - A
    degree = jnp.sum(A, axis=1)
    d = jnp.where(degree > 0, 1.0 / jnp.sqrt(degree), 0.0)
    L = -A * d[:, None] * d[None, :]
    return L.at[jnp.arange(n), jnp.arange(n)].set(1.0)


@pytest.mark.parametrize(
    "definition,mode,key,val",
    [
        ("norm_sym", "fully_connected", "upper", 1.0),
        ("simple", "fully_connected", "upper", 1.0),
        ("norm_sym", "eNeighbour", "upper", 0.5),
        ("norm_sym", "eNeighbour", "lower", 0.1),
        ("simple", "eNeighbour", "lower", 0.1),
    ],
)
def test_laplacian_is_the_eager_chains(definition, mode, key, val):
    x = ht.array(_blobs(3, n=256), split=0)
    sim = lambda a: ht.spatial.rbf(a, sigma=float(np.sqrt(0.5)))
    want = np.asarray(_eager_chain(sim(x).larray, definition, mode, key, val))
    lap = ht.graph.Laplacian(
        sim, definition=definition, mode=mode, threshold_key=key, threshold_value=val
    )
    got = lap.construct(x)
    assert got.shape == (256, 256) and got.split == 0 and got.dtype == ht.float32
    # the same operations in the same order on the same values, so the same
    # bits, but for the degrees: compiled as one program the row sums are
    # fused with the thresholding and may add in another order, one rounding
    # of a sum of 255 terms (2e-7 relative) on every entry it scales
    np.testing.assert_allclose(got.numpy(), want, rtol=1e-6, atol=0.0)
    if mode == "eNeighbour":  # the same edges were kept
        np.testing.assert_array_equal(got.numpy() == 0, want == 0)


def test_unweighted_neighbourhood_graph():
    x = ht.array(_blobs(5, n=128), split=0)
    sim = lambda a: ht.spatial.rbf(a, sigma=float(np.sqrt(0.5)))
    want = np.asarray(_eager_chain(sim(x).larray, "simple", "eNeighbour", "lower", 0.1, weighted=False))
    lap = ht.graph.Laplacian(
        sim, weighted=False, definition="simple", mode="eNeighbour", threshold_key="lower", threshold_value=0.1
    )
    np.testing.assert_array_equal(lap.construct(x).numpy(), want)


def test_compiled_laplacian_takes_the_similaritys_buffer():
    """The similarity's (n, n) result is donated: the compiled program writes
    L into it (``input_output_alias``), and the buffer is gone afterwards."""
    x = ht.array(_blobs(7, n=256), split=0)
    made = []

    def sim(a):
        made.append(ht.spatial.rbf(a, sigma=1.0))
        return made[-1]

    ht.graph.Laplacian(sim, definition="norm_sym").construct(x)
    assert made[0].larray.is_deleted()
    entry = next(fn for key, fn in _compile._CACHE.items() if key[0] == "laplacian.norm_sym" and key[5] is True)
    shape = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    text = entry.lower(shape).compile().as_text()
    assert "input_output_alias={ {}: (0, {}" in text.splitlines()[0], text.splitlines()[0]


def test_a_precomputed_similarity_is_left_alone():
    """``similarity=lambda x: x`` on a precomputed matrix: the caller's own
    buffer is not the Laplacian's to take."""
    rng = np.random.default_rng(9)
    s = rng.uniform(0.1, 1.0, size=(64, 64)).astype(np.float32)
    S = ht.array((s + s.T) / 2, split=0)
    L = ht.graph.Laplacian(lambda a: a, definition="norm_sym").construct(S)
    assert not S.larray.is_deleted()
    np.testing.assert_allclose(
        L.numpy(), np.asarray(_eager_chain(S.larray, "norm_sym", "fully_connected", "upper", 1.0)), rtol=1e-6
    )


# --------------------------------------------------------------------- #
# what a fit records                                                    #
# --------------------------------------------------------------------- #
@pytest.fixture
def tel():
    was = _core.is_enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


@pytest.fixture
def recorded(tel):
    x = ht.array(_blobs(11, n=256), split=0)
    ht.cluster.Spectral(n_clusters=K, n_lanczos=32).fit(x)  # warm: no span carries a compile
    telemetry.reset()
    with _tracing.counting_dispatches() as window:
        ht.cluster.Spectral(n_clusters=K, n_lanczos=32).fit(x)
    spans = [e for e in telemetry.events() if e["type"] == "span"]
    return spans, window.count


def _at(spans, site):
    return [e for e in spans if e["site"] == site]


@pytest.mark.parametrize(
    "site,kind",
    [
        ("jitted:dist.rbf", "launch"),
        ("jitted:laplacian.norm_sym", "launch"),
        ("jit:lanczos.start", "launch"),
        ("jit:lanczos.segment", "launch"),
        ("sync:spectral.tridiag", "sync"),
        ("spectral:eigh", "other"),
        ("jitted:spectral.embed", "launch"),
        ("fit:Spectral", "entry"),
    ],
)
def test_a_fit_records_one_span_at_each_site(recorded, site, kind):
    spans, _ = recorded
    (span,) = _at(spans, site)
    assert span["kind"] == kind


def test_the_segment_span_counts_its_steps(recorded):
    spans, _ = recorded
    (seg,) = _at(spans, "jit:lanczos.segment")
    assert (seg["steps"], seg["n"], seg["m"]) == (31, 256, 32)


def test_the_counters_see_the_laplacian_and_the_segment(recorded):
    spans, dispatches = recorded
    launches = [e for e in spans if e["kind"] in ("launch", "comm")]
    assert dispatches == len(launches), sorted(e["site"] for e in launches)  # one span a counted dispatch
    (entry,) = _at(spans, "fit:Spectral")
    assert entry["launches"] == dispatches
    # the tridiagonal's read, then KMeans' own reads
    assert entry["syncs"] >= 1 and len(_at(spans, "sync:spectral.tridiag")) == 1
    # the host's eigh starts when the read of T has returned
    (read,), (eigh,) = _at(spans, "sync:spectral.tridiag"), _at(spans, "spectral:eigh")
    assert eigh["ts"] >= read["ts"] + read["dur"]


def test_lanczos_products_take_the_linalg_precision(monkeypatch):
    """``ht.linalg.set_matmul_precision`` reaches the solver's products: the
    lowered segment asks for ``HIGHEST`` by default and for nothing under
    ``default``."""
    from heat_tpu.core.linalg import solver

    seen = []
    real = solver._lanczos_segment

    def spy(*args, **kwargs):
        seen.append(kwargs["precision"])
        return real(*args, **kwargs)

    monkeypatch.setattr(solver, "_lanczos_segment", spy)
    rng = np.random.default_rng(2)
    b = rng.normal(size=(48, 48)).astype(np.float32)
    A = ht.array((b + b.T) / 2, split=0)
    ht.linalg.lanczos(A, 8)
    was = ht.linalg.get_matmul_precision()
    ht.linalg.set_matmul_precision("default")
    try:
        ht.linalg.lanczos(A, 8)
    finally:
        ht.linalg.set_matmul_precision(was)
    assert seen == ["highest", None]
    shapes = (
        jax.ShapeDtypeStruct((48, 48), jnp.float32), jax.ShapeDtypeStruct((48, 8), jnp.float32),
        jax.ShapeDtypeStruct((), jnp.int32), jax.ShapeDtypeStruct((), jnp.int32),
        (jax.ShapeDtypeStruct((48, 8), jnp.float32), jax.ShapeDtypeStruct((8, 8), jnp.float32),
         jax.ShapeDtypeStruct((48,), jnp.float32), jax.ShapeDtypeStruct((48,), jnp.float32)),
    )
    assert "HIGHEST" in real.lower(*shapes, precision="highest").as_text()
    assert "HIGHEST" not in real.lower(*shapes, precision=None).as_text()


# --------------------------------------------------------------------- #
# the checkpoint keeps the fit's fine-grained result                    #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("attr", ["eigenvalues_", "embedding_", "labels_"])
def test_fitted_attributes_survive_the_checkpoint(judged, tmp_path, attr):
    sp, _ = judged
    path = str(tmp_path / "spectral.h5")
    sp.save(path)
    back = ht.load_estimator(path)
    want, got = getattr(sp, attr), getattr(back, attr)
    if isinstance(want, ht.DNDarray):
        assert got.split == want.split
        want, got = want.numpy(), got.numpy()
    np.testing.assert_array_equal(got, want)
