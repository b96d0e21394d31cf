"""The symmetric-half matvec of Lanczos (``heat_tpu/core/linalg/_symv.py``).

The kernel reads only the tiles of the operator on and above the block
diagonal and uses each for both halves of the product.  Held here in the
Pallas interpreter on the CPU: it applies ``triu(A) + triu(A, 1)^T`` to
float32 rounding at every tile size, ragged or not; nothing below the diagonal
tiles and nothing of a ragged tile's padding (the interpreter fills it with
NaN) reaches the result; the route predicate keeps every operand the kernel
was not written for on the dense product; and the launch spans of ``lanczos``
say which route a fit compiled.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.core.linalg import _symv, solver
from heat_tpu.telemetry import _core

#: (n, tile edge): whole tiles, ragged last tiles, an odd and an even count of
#: block rows, one tile alone, the cell's own tile edge
SIZES = [(256, 128), (384, 128), (1000, 128), (1088, 256), (200, 256), (1100, 1024)]


def _operands(n: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal((n, n)).astype(np.float32)
    w = rng.standard_normal(n).astype(np.float32)
    return a, w


def _upper_half_operator(a: np.ndarray) -> np.ndarray:
    a = a.astype(np.float64)
    return np.triu(a) + np.triu(a, 1).T


@pytest.mark.parametrize("n,block", SIZES)
def test_symv_applies_the_upper_halfs_operator_to_float32_rounding(n, block):
    a, w = _operands(n)
    op = _upper_half_operator(a)
    got = np.asarray(_symv.symv(jnp.asarray(a), jnp.asarray(w), interpret=True, block=block))
    assert got.shape == (n,) and got.dtype == np.float32
    # a float32 sum of n terms, each entry its own: rounding grows like
    # sqrt(n) times the norm of the row's terms
    room = 2 * np.finfo(np.float32).eps * np.sqrt(n) * np.linalg.norm(op * w, axis=1)
    assert np.all(np.abs(got - op @ w.astype(np.float64)) <= room)


@pytest.mark.parametrize("n,block", SIZES)
def test_symv_reads_nothing_below_the_diagonal_tiles_and_no_padding(n, block):
    """The strict lower block-triangle filled with NaN: the result is bit for
    bit the clean operand's, and finite (the interpreter pads a ragged tile
    with NaN, so a padding entry that reached a sum would show too)."""
    a, w = _operands(n, seed=1)
    tile = np.arange(n) // block
    poisoned = np.where(tile[:, None] > tile[None, :], np.nan, a).astype(np.float32)
    clean = np.asarray(_symv.symv(jnp.asarray(a), jnp.asarray(w), interpret=True, block=block))
    got = np.asarray(_symv.symv(jnp.asarray(poisoned), jnp.asarray(w), interpret=True, block=block))
    assert np.isfinite(got).all()
    assert np.array_equal(got, clean)


def test_symv_equals_the_dense_product_on_a_symmetric_operand():
    a, w = _operands(640, seed=2)
    sym = (a + a.T) / 2
    got = np.asarray(_symv.symv(jnp.asarray(sym), jnp.asarray(w), interpret=True, block=256))
    np.testing.assert_allclose(got, sym.astype(np.float64) @ w, rtol=0, atol=2e-4)


def test_the_tile_walk_is_the_block_triangle_row_major():
    ii, jj = _symv._tiles(4)
    assert list(zip(ii.tolist(), jj.tolist())) == [
        (0, 0), (0, 1), (0, 2), (0, 3), (1, 1), (1, 2), (1, 3), (2, 2), (2, 3), (3, 3)
    ]
    # the cell's operand: 820 of 1600 tiles, 53.7 % of the dense bytes
    nb = -(-40_000 // _symv.BLOCK)
    assert len(_symv._tiles(nb)[0]) == 820
    assert 820 * _symv.BLOCK**2 * 4 / (40_000**2 * 4) == pytest.approx(0.537, abs=1e-3)


# --------------------------------------------------------------------- #
# the route                                                              #
# --------------------------------------------------------------------- #
# ``one_tpu`` (a TPU backend with one device, as the cell's machine) is conftest.py's


def _shape(n, dtype=jnp.float32, m=None):
    return jax.ShapeDtypeStruct((n, n if m is None else m), dtype)


def test_the_cells_operand_takes_the_symmetric_half(one_tpu):
    assert solver._matvec_route(_shape(40_000)) == "symmetric_half"
    assert solver._matvec_route(_shape(_symv.MIN_N)) == "symmetric_half"


@pytest.mark.parametrize(
    "operand",
    [
        _shape(40_000, jnp.float64),
        _shape(40_000, jnp.bfloat16),
        _shape(_symv.MIN_N - 64),
        _shape(40_000, m=300),
    ],
    ids=["float64", "bfloat16", "under_the_threshold", "not_square"],
)
def test_every_other_operand_takes_the_dense_product(one_tpu, operand):
    assert solver._matvec_route(operand) == "dense"


def test_a_process_with_several_devices_takes_the_dense_product(monkeypatch):
    """Where an operand can be sharded (this mesh; four chips) the kernel is
    never compiled: GSPMD would gather the operand around it."""
    if jax.device_count() == 1:
        pytest.skip("one device here")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert solver._matvec_route(_shape(40_000)) == "dense"
    x = ht.random.rand(64, 64, split=0)
    assert len(x.larray.sharding.device_set) > 1
    assert solver._matvec_route(x.larray) == "dense"


def test_the_cpu_backend_takes_the_dense_product(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert jax.default_backend() == "cpu"
    assert solver._matvec_route(_shape(40_000)) == "dense"
    assert not _symv._interpret()


# --------------------------------------------------------------------- #
# through lanczos                                                        #
# --------------------------------------------------------------------- #
N, M = 384, 12


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
def interpreted_route(monkeypatch, one_tpu):
    """The kernel's route opened to a small operand on the CPU: the
    interpreter for the chip, 128-wide tiles, a threshold under ``N``.  The
    programs traced meanwhile are dropped on both sides."""
    monkeypatch.setattr(_symv, "_interpret", lambda: True)
    monkeypatch.setattr(_symv, "MIN_N", 256)
    monkeypatch.setattr(_symv, "BLOCK", 128)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _spd(n: int) -> np.ndarray:
    rng = np.random.default_rng(5)
    q = rng.standard_normal((n, n)).astype(np.float32) / np.sqrt(n)
    return (q @ q.T + np.eye(n, dtype=np.float32)).astype(np.float32)


def _lanczos_spans():
    return [
        e for e in telemetry.events()
        if e["type"] == "span" and e["site"] in ("jit:lanczos.start", "jit:lanczos.segment")
    ]


def _one_chip(a: np.ndarray):
    return ht.array(a, comm=XlaCommunication(jax.devices()[:1]))


def test_lanczos_on_the_cpu_mesh_says_dense(tel):
    ht.linalg.lanczos(ht.array(_spd(N), split=0), M)
    spans = _lanczos_spans()
    assert {s["site"] for s in spans} == {"jit:lanczos.start", "jit:lanczos.segment"}
    assert all(s["matvec"] == "dense" for s in spans)


def test_lanczos_says_symmetric_half_and_tridiagonalises_by_the_kernel(tel, interpreted_route):
    a = _spd(N)
    V, T = ht.linalg.lanczos(_one_chip(a), M)
    spans = _lanczos_spans()
    assert {s["site"] for s in spans} == {"jit:lanczos.start", "jit:lanczos.segment"}
    assert all(s["matvec"] == "symmetric_half" for s in spans)
    assert sum(s["steps"] for s in spans if s["site"] == "jit:lanczos.segment") == M - 1
    v, t = np.asarray(V.larray, np.float64), np.asarray(T.larray, np.float64)
    np.testing.assert_allclose(v.T @ v, np.eye(M), atol=1e-5)
    np.testing.assert_allclose(v.T @ a.astype(np.float64) @ v, t, atol=1e-4)


@pytest.mark.parametrize("dtype", [np.float64, np.float32], ids=["float64", "under_the_threshold"])
def test_lanczos_keeps_other_operands_dense_where_the_kernel_is_open(tel, interpreted_route, dtype):
    n = N if dtype == np.float64 else 192
    ht.linalg.lanczos(_one_chip(_spd(n).astype(dtype)), 6)
    assert {s["matvec"] for s in _lanczos_spans()} == {"dense"}


def test_a_checkpointed_lanczos_by_the_kernel_resumes_bit_for_bit(tmp_path, interpreted_route):
    a = _one_chip(_spd(N))
    v0 = ht.array(np.linspace(1.0, 2.0, N, dtype=np.float32), comm=a.comm)
    V1, T1 = ht.linalg.lanczos(a, M, v0=v0)
    path = str(tmp_path / "lanczos.ckpt")
    V2, T2 = ht.linalg.lanczos(a, M, v0=v0, checkpoint_every=4, checkpoint_path=path)
    assert np.array_equal(np.asarray(T1.larray), np.asarray(T2.larray))
    assert np.array_equal(np.asarray(V1.larray), np.asarray(V2.larray))
