"""The one-read variance of ``ht.var`` / ``ht.std`` (``heat_tpu/core/_colvar.py``).

The kernel keeps a tile of columns, all rows of it, on the chip between the
column means and the sums of centred squares, so the operand is read once.
Held here in the Pallas interpreter on the CPU, against numpy float64: it is
the two-pass arithmetic (a far mean and an outlier in the first row read as
rounding, where the raw form and a shift by a data row cancel); every row
counts once whatever the row count leaves of a group of 8, and nothing of a
ragged tile's padding (the interpreter fills it with NaN) reaches a sum; a NaN
or an inf stays in its own column.  The route predicate keeps every operand the
kernel was not written for on ``jnp.var``'s two passes, bit for bit and
instruction for instruction, and the launch spans of ``var`` / ``std`` say which
form a call compiled.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu.core import _colvar, statistics
from heat_tpu.core.communication import XlaCommunication
from tests.test_moments_reference import LIMIT, _blobs, _far_mean, tel  # noqa: F401  (tel: the fixture)

#: (rows, columns, tile): no row group whole, one short of a group, one group,
#: one over, the cell's 37 groups and 4 rows; columns that fill their tiles,
#: leave a ragged last one, fit one tile alone; tiles of four and of five chunks
SIZES = [
    (1, 384, 128), (7, 512, 128), (8, 512, 256), (9, 700, 512), (300, 1000, 256),
    (300, 512, 512), (37, 2500, 2048), (64, 640, 128), (16, 1300, 640),
]


def _m2(x: np.ndarray, tile=None) -> np.ndarray:
    return np.asarray(_colvar.centred_squares(jnp.asarray(x), interpret=True, tile=tile))


def _m2_f64(x: np.ndarray) -> np.ndarray:
    x = x.astype(np.float64)
    return np.square(x - x.mean(0)).sum(0)


@pytest.mark.parametrize("rows,cols,tile", SIZES)
def test_the_kernel_sums_the_centred_squares_of_every_row_to_float32_rounding(rows, cols, tile):
    x = _blobs(11, rows, cols)
    got = _m2(x, tile)
    assert got.shape == (cols,) and got.dtype == np.float32
    # finite: a padding row or column that reached a sum would be NaN
    assert np.isfinite(got).all()
    assert np.all(np.abs(got - _m2_f64(x)) <= LIMIT * _m2_f64(x))


def test_the_tile_is_the_budgets_and_whole_chunks():
    assert _colvar._tile(300, 6_291_456) == 8192  # the cell: 10 MB a tile, two under the VMEM limit
    assert 304 * 8192 * 4 * 2 < _colvar._VMEM_LIMIT
    assert _colvar._tile(300, 5000) == 4608 and _colvar._tile(300, 1000) == 512  # no wider than the operand
    assert _colvar._tile(8, 1 << 25) == 327_680  # few rows: as many more columns
    assert _colvar._tile(2048, 1 << 20) == 1024 and _colvar._tile(5120, 1 << 20) == 512 == _colvar.MIN_TILE
    assert _colvar._tile(5121, 1 << 20) == 0  # all rows of one chunk do not fit: not taken
    for rows in (1, 300, 2500, 5120):
        tile = _colvar._tile(rows, 1 << 24)
        assert tile % _colvar._CHUNK == 0 and 4 * -(-rows // 8) * 8 * tile <= _colvar._TILE_BYTES


def test_a_far_mean_reads_as_rounding():
    """Every column's mean a thousand deviations: the raw form has cancelled
    every digit there (``test_the_judge_fails_what_the_cell_must_fail``)."""
    x = _far_mean(12, 300, 640)
    std = np.sqrt(_m2(x, 256) / 300)
    want = x.astype(np.float64).std(0)
    assert np.all(np.abs(std - want) <= LIMIT * want)
    raw = np.sqrt(np.maximum((x * x).mean(0) - x.mean(0) ** 2, 0.0))
    assert np.abs(raw - want).max() > 1000 * LIMIT * want.max()


def test_an_outlier_in_the_first_row_reads_as_rounding():
    """The first row a million deviations off: a variance shifted by a data
    row (``x - x[0]``) is the raw form again on such a column."""
    x = np.random.default_rng(13).standard_normal((300, 640)).astype(np.float32)
    x[0] += 1e6
    std = np.sqrt(_m2(x, 256) / 300)
    want = x.astype(np.float64).std(0)
    assert np.all(np.abs(std - want) <= LIMIT * want)
    shifted = (x - x[0]).astype(np.float32)
    raw = np.sqrt(np.maximum((shifted * shifted).mean(0) - shifted.mean(0) ** 2, 0.0))
    assert np.abs(raw - want).max() > LIMIT * want.max()


def test_a_nan_and_an_inf_each_stay_in_their_own_column():
    x = _blobs(14, 300, 1000)
    clean = _m2(x, 256)
    bad = x.copy()
    bad[17, 3], bad[299, 700], bad[0, 999] = np.nan, np.inf, -np.inf
    got = _m2(bad, 256)
    hit = np.zeros(1000, bool)
    hit[[3, 700, 999]] = True
    assert np.isnan(got[hit]).all()  # inf - inf: as numpy's and jnp.var's
    assert np.array_equal(got[~hit], clean[~hit])


# --------------------------------------------------------------------- #
# the route                                                              #
# --------------------------------------------------------------------- #
CELL = (300, 6_291_456)


def _shape(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def test_the_cells_operand_takes_one_pass(one_tpu):
    assert statistics._form(_shape(CELL), 0) == "one_pass"
    assert statistics._form(_shape(CELL), (0,)) == "one_pass"
    assert statistics._form(_shape((5120, 1 << 20)), 0) == "one_pass"
    assert statistics._form(_shape((40_000, 512)), 0) == "two_pass"  # 512 columns of 40 000 rows: no tile
    assert statistics._form(_shape((4096, 4096)), 0) == "one_pass"
    assert statistics._form(_shape((300, -(-_colvar.MIN_BYTES // 1200))), 0) == "one_pass"


#: name -> (operand, axis): what the kernel was not written for
TURNED_DOWN = {
    "float64": (_shape(CELL, jnp.float64), 0),
    "bfloat16": (_shape(CELL, jnp.bfloat16), 0),
    "int32": (_shape(CELL, jnp.int32), 0),
    "axis_1": (_shape(CELL), 1),
    "axis_none": (_shape(CELL), None),
    "both_axes": (_shape(CELL), (0, 1)),
    "three_dimensions": (_shape((300, 2048, 3072)), 0),
    "one_dimension": (_shape((1 << 26,)), 0),
    "tall": (_shape((8_000_000, 32)), 0),  # chip_smoke's
    "rows_that_fit_no_tile": (_shape((5121, 1 << 20)), 0),
    "narrower_than_a_tile": (_shape((40_000, 511)), 0),
    "under_the_threshold": (_shape((300, -(-_colvar.MIN_BYTES // 1200) - 1)), 0),
}


@pytest.mark.parametrize("case", sorted(TURNED_DOWN))
def test_every_other_operand_takes_two_passes(one_tpu, case):
    assert statistics._form(*TURNED_DOWN[case]) == "two_pass"


def test_a_process_with_several_devices_takes_two_passes(monkeypatch):
    """Where an operand can be sharded (this mesh; four chips) the kernel is
    never compiled: the chips' partial moments would need merging."""
    if jax.device_count() == 1:
        pytest.skip("one device here")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert statistics._form(_shape(CELL), 0) == "two_pass"
    monkeypatch.setattr(jax, "device_count", lambda: 4)
    assert statistics._form(_shape(CELL), 0) == "two_pass"


def test_the_cpu_backend_takes_two_passes(monkeypatch):
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    assert jax.default_backend() == "cpu"
    assert statistics._form(_shape(CELL), 0) == "two_pass"
    assert not _colvar._interpret()


def _parents_var(a, axis, ddof, keepdims):
    """``statistics._var`` as it stood before the kernel, with the cast that
    ``_moment2`` made ahead of it."""
    if not jnp.issubdtype(a.dtype, jnp.inexact):
        a = a.astype(jnp.float32)
    wide = jnp.float32 if a.dtype.itemsize < 4 else a.dtype
    with jax.named_scope("stat.var.mean"):
        mu = jnp.mean(a, axis=axis, dtype=wide, keepdims=True)
    with jax.named_scope("stat.var.centred"):
        return jnp.var(a, axis=axis, ddof=ddof, keepdims=keepdims, mean=mu)


@pytest.mark.parametrize("open_route", [False, True], ids=["cpu_mesh", "kernel_open"])
@pytest.mark.parametrize("case", sorted(TURNED_DOWN))
def test_a_turned_down_operand_lowers_to_the_parents_program(case, open_route, request):
    """Instruction for instruction, on this mesh and where the process is the
    cell's (one TPU device: the kernel's route open to what conforms)."""
    if open_route:
        request.getfixturevalue("one_tpu")
    operand, axis = TURNED_DOWN[case]
    small = _shape(tuple(min(s, 24) for s in operand.shape), operand.dtype)
    for shape in (small, operand) if open_route else (small,):
        got = jax.jit(lambda a: statistics._var(a, axis, 1, False)).lower(shape).as_text()
        want = jax.jit(lambda a: _parents_var(a, axis, 1, False)).lower(shape).as_text()
        assert got == want and "stablehlo.reduce" in got and "custom_call" not in got


# --------------------------------------------------------------------- #
# through ht.var / ht.std                                                #
# --------------------------------------------------------------------- #
@pytest.fixture
def interpreted_route(monkeypatch, one_tpu):
    """The kernel's route opened to a small operand on the CPU: the
    interpreter for the chip, chunks of 128 columns and tiles of 256 at 300
    rows (wider at fewer), no least size.  The programs traced meanwhile are
    dropped on both sides."""
    monkeypatch.setattr(_colvar, "_interpret", lambda: True)
    monkeypatch.setattr(_colvar, "MIN_BYTES", 0)
    monkeypatch.setattr(_colvar, "_CHUNK", 128)
    monkeypatch.setattr(_colvar, "MIN_TILE", 128)
    monkeypatch.setattr(_colvar, "_TILE_BYTES", 304 * 256 * 4)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _one_chip(a, split=0):
    return ht.array(a, split=split, comm=XlaCommunication(jax.devices()[:1]))


def _launches(tel):
    return [e for e in tel.events() if e.get("type") == "span" and e["site"] == "jitted:stat.moment2"]


@pytest.mark.parametrize("rows", [1, 7, 8, 9, 300])
@pytest.mark.parametrize("ddof", [0, 1])
def test_var_and_std_by_the_kernel_agree_with_float64(tel, interpreted_route, rows, ddof):
    host = _blobs(15, rows, 1000)  # four tiles, the last ragged
    X = _one_chip(host)
    v, s = ht.var(X, axis=0, ddof=ddof), X.std(axis=0, ddof=ddof, keepdims=True)
    assert [(e["form"], e["reads"], e["route"], e["axis"]) for e in _launches(tel)] == [("one_pass", 1, "exact", 0)] * 2
    assert v.gshape == (1000,) and s.gshape == (1, 1000) and v.split is s.split is None and v.dtype is ht.float32
    if rows - ddof == 0:  # no row left: NaN, as jnp.var's
        assert np.isnan(v.numpy()).all() and np.isnan(s.numpy()).all()
        return
    want = host.astype(np.float64).var(0, ddof=ddof)
    np.testing.assert_allclose(v.numpy(), want, rtol=2 * LIMIT, atol=0)
    np.testing.assert_allclose(s.numpy()[0], np.sqrt(want), rtol=LIMIT, atol=0)


def test_the_kernels_variance_holds_the_cells_limits_on_a_far_mean(tel, interpreted_route):
    host = _far_mean(16, 300, 640)
    got = ht.std(_one_chip(host), axis=0).numpy()
    assert {e["form"] for e in _launches(tel)} == {"one_pass"}
    want = host.astype(np.float64).std(0)
    assert np.abs(got / want - 1).max() <= LIMIT


@pytest.mark.parametrize("case", ["float64", "int32", "bfloat16", "axis_1", "axis_none", "both_axes", "three_dimensions"])
def test_other_operands_stay_jnp_vars_own_where_the_kernel_is_open(tel, interpreted_route, case):
    operand, axis = TURNED_DOWN[case]
    shape = (37, 24, 5) if operand.ndim == 3 else (37, 640)
    host = np.random.default_rng(17).standard_normal(shape) * 3.0 + 50.0
    a = jnp.asarray(host).astype(operand.dtype)
    wide = a if jnp.issubdtype(a.dtype, jnp.inexact) else a.astype(jnp.float32)
    want = jax.jit(lambda v: jnp.var(v, axis=axis, ddof=1))(wide)
    got = ht.var(_one_chip(a), axis=axis, ddof=1).larray
    assert [(e["form"], e["reads"]) for e in _launches(tel)] == [("two_pass", 2)]
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_the_kernel_inlines_into_a_fuse_trace(tel, interpreted_route):
    host = _blobs(18, 300, 640)
    X = _one_chip(host)
    fused = ht.fuse(lambda x: ht.std(x, axis=0) + ht.var(x, axis=0, ddof=1))(X)
    assert not _launches(tel)
    want = host.astype(np.float64)
    np.testing.assert_allclose(fused.numpy(), want.std(0) + want.var(0, ddof=1), rtol=2 * LIMIT)


def test_the_kernels_scope_reaches_the_lowered_program(interpreted_route):
    text = jax.jit(lambda a: statistics._var(a, 0, 0, False)).lower(_shape((300, 640))).as_text(debug_info=True)
    assert "stat.var.onepass" in text and "stat.var.mean" not in text and "stat.var.centred" not in text
