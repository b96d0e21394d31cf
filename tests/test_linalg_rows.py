"""``ht.linalg.svd`` and ``ht.linalg.qr`` of a tall float32 operand split by
rows over a 1-D mesh of several devices, on the row-sharded CholeskyQR2
(``core/linalg/qr.py:rows_route``, ``cholqr2_rows``).

The route a process on a TPU takes where every shard holds at least n rows
and ``MIN_BYTES`` is steered onto the CPU mesh here (``_chips_route``, as
``tests/test_linalg_tall.py`` steers the one-device route) and held to numpy
float64 over several block sizes, a ragged row count and κ(A) up to 1e3,
with ``calc_q`` both ways, and to the benchmark's shard-wise plain reference
(``perf/references/svd_rows_plain.py``).  Operands on which CholeskyQR2 is
not sound (κ(A) past ``KAPPA_MAX``, rank-deficient) take the shards' blocked
TSQR inside the same program (``rows_tsqr``) and are held to rounding.
Shards with fewer rows than columns keep the TSQR chain.  The launch span
states the route, its reads of A, its fallback, the shards and the bytes of
its collectives.  (``tests/test_tpu_compile.py`` compiles the program at the
cell's size for a described v5e:2x2.)
"""

import importlib
import importlib.util
import os

import numpy as np
import pytest

import jax

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core.communication import XlaCommunication, grid_comm

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
SHARDS = 4


@pytest.fixture(scope="module")
def comm():
    if len(jax.devices()) < SHARDS:
        pytest.skip(f"needs {SHARDS} devices")
    return XlaCommunication(jax.devices()[:SHARDS])


def _chips_route(monkeypatch, block=None):
    """What a process on a TPU answers, and the case's block of rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qr_mod, "MIN_BYTES", 0)
    if block is not None:
        monkeypatch.setattr(qr_mod, "BLOCK_ROWS", block)


def _operand(m, n, kappa, seed=0):
    """float32 ``m x n`` with singular values from 10 down to ``10 / kappa``,
    and its float64 singular values."""
    rng = np.random.default_rng(seed + m + n)
    left = np.linalg.qr(rng.standard_normal((m, n)))[0]
    right = np.linalg.qr(rng.standard_normal((n, n)))[0]
    s = 10.0 * np.logspace(0.0, -np.log10(kappa), n)
    a = ((left * s) @ right.T).astype(np.float32)
    return a, np.linalg.svd(a.astype(np.float64), compute_uv=False)


def _tolerance(kappa):
    """U = A·W (Q = A·R⁻¹) is orthonormal to a few units of 2^-24 times κ,
    never under 1e-5 (``tests/test_linalg_tall.py``)."""
    return max(1e-5, 4e-6 * kappa)


def _spans(call):
    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        out = call()
        return out, [e for e in telemetry.events() if e.get("site", "").startswith("jitted:linalg.")]
    finally:
        if not was:
            telemetry.disable()


def _f64(t):
    return np.asarray(t.numpy(), np.float64)


#: (rows, columns, rows a block, κ(A)): a shard of several blocks with a tail,
#: one block a shard, a ragged row count (zero rows padded onto the last shard)
CASES = [(2048, 40, 128, 10.0), (1601, 48, 1 << 16, 1e2), (1200, 64, 100, 1e3)]
IDS = [f"{m}x{n}-block{b}-kappa{k:g}" for m, n, b, k in CASES]


@pytest.mark.parametrize("m,n,block,kappa", CASES, ids=IDS)
def test_rows_svd_against_float64(monkeypatch, comm, m, n, block, kappa):
    _chips_route(monkeypatch, block)
    a, s64 = _operand(m, n, kappa)
    x = ht.array(a, split=0, comm=comm)
    assert qr_mod.rows_route(x.shape, x.larray.dtype, x.split, comm)
    (u, s, v), spans = _spans(lambda: ht.linalg.svd(x))
    assert [e["route"] for e in spans] == ["cholqr2_rows"]
    assert u.split == 0 and u.shape == (m, n) and len(u.larray.sharding.device_set) == SHARDS
    u, s, v = _f64(u), _f64(s), _f64(v)
    tol = _tolerance(kappa)
    assert np.max(np.abs(s - s64) / s64) < tol
    assert np.max(np.abs(u.T @ u - np.eye(n))) < tol
    assert np.max(np.abs(v.T @ v - np.eye(n))) < 1e-5
    assert np.linalg.norm(a @ v - u * s) / np.linalg.norm(a) < 1e-5
    np.testing.assert_allclose(_f64(ht.linalg.svd(x, compute_uv=False)), s64, rtol=tol)


@pytest.mark.parametrize("calc_q", [True, False])
def test_rows_qr_against_float64(monkeypatch, comm, calc_q):
    m, n, block, kappa = CASES[0]
    _chips_route(monkeypatch, block)
    a, s64 = _operand(m, n, kappa, seed=1)
    (q, r), spans = _spans(lambda: ht.linalg.qr(ht.array(a, split=0, comm=comm), calc_q=calc_q))
    assert [e["route"] for e in spans] == ["cholqr2_rows"]
    assert r.split is None
    r = _f64(r)
    assert np.array_equal(np.tril(r, -1), np.zeros_like(r)) and np.all(np.diag(r) > 0)
    np.testing.assert_allclose(np.linalg.svd(r, compute_uv=False), s64, rtol=_tolerance(kappa))
    if not calc_q:
        assert q is None
        return
    assert q.split == 0
    q = _f64(q)
    assert np.max(np.abs(q.T @ q - np.eye(n))) < _tolerance(kappa)
    assert np.linalg.norm(q @ r - a) / np.linalg.norm(a) < 1e-5


def _not_for_cholqr(kind, m=1601, n=40):
    """Operands CholeskyQR2 cannot factor soundly: a factor past
    ``KAPPA_MAX``, or a Gram whose Cholesky breaks down; the ragged row count
    pads the last shard with zero rows."""
    a, _ = _operand(m, n, 1e5 if kind == "kappa1e5" else 10.0, seed=2)
    if kind == "column_repeated":  # a duplicate image
        a[:, -1] = a[:, 0]
    return a


@pytest.mark.parametrize("kind", ["kappa1e5", "column_repeated"])
def test_an_operand_cholqr_cannot_factor_takes_the_shards_tsqr(monkeypatch, comm, kind):
    """Held to rounding, as Householder's QR is: at κ 1e5 the direct branch
    would leave U and Q orthonormal only to about 6e-3, and a repeated column
    leaves R not finite; the shards' blocked TSQR reads 1e-5."""
    _chips_route(monkeypatch, 256)
    a = _not_for_cholqr(kind)
    m, n = a.shape
    a64 = a.astype(np.float64)
    s64 = np.linalg.svd(a64, compute_uv=False)
    scale = float(s64[0])
    x = ht.array(a, split=0, comm=comm)
    with jax.enable_x64(False):
        sound = qr_mod._sound(*qr_mod._cholqr2(x.larray, "highest"), False)[0]
    assert not bool(sound)  # the whole operand's factor: the branch every shard takes
    (u, s, v), spans = _spans(lambda: ht.linalg.svd(x))
    assert [(e["route"], e["fallback"]) for e in spans] == [("cholqr2_rows", "rows_tsqr")]
    u, s, v = _f64(u), _f64(s), _f64(v)
    assert np.max(np.abs(u.T @ u - np.eye(n))) < 1e-5
    assert np.max(np.abs(v.T @ v - np.eye(n))) < 1e-5
    assert np.max(np.abs(s - s64)) / scale < 1e-5
    assert np.linalg.norm(a64 - (u * s) @ v.T) / scale < 1e-5
    q, r = ht.linalg.qr(x)
    q, r = _f64(q), _f64(r)
    assert np.max(np.abs(q.T @ q - np.eye(n))) < 1e-5
    assert np.linalg.norm(q @ r - a64) / scale < 1e-5
    assert np.array_equal(np.tril(r, -1), np.zeros_like(r)) and np.all(np.diag(r) >= 0)


def _by_file(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_the_cells_numbers_against_the_shard_wise_reference(monkeypatch, comm):
    """The cell's data (blobs of 8 centres at scale 10, unit noise) split by
    rows over the mesh: the route's U, S and V sound by the reference's judge,
    which works each shard on its own device."""
    import jax.numpy as jnp

    rows = _by_file("svd_rows_plain", os.path.join(PERF, "references", "svd_rows_plain.py"))
    datagen = _by_file("datagen", os.path.join(PERF, "datagen.py"))
    _chips_route(monkeypatch, 512)
    data = {"kind": "blobs", "rows": 4096, "features": 64, "centres": 8, "centre_scale": 10.0, "noise": 1.0}
    x = datagen.make(data, 2**31 + 9, comm.devices)
    u, s, v = ht.linalg.svd(ht.array(x, split=0, copy=False, comm=comm))
    numbers = rows.judge(x, {"U": u.larray, "S": s.larray, "V": v.larray}, seed=1, block=300)
    reference = rows.judge(x, rows.svd(x, jnp.float32, block=300), seed=1, block=300)
    assert max(numbers.values()) < 1e-5 and max(reference.values()) < 1e-5, (numbers, reference)


def test_shards_narrower_than_the_columns_keep_the_tsqr(monkeypatch, comm):
    """200 rows over four devices leave shards of 50 rows for 64 columns: the
    row-sharded route is not taken, and no program states it."""
    _chips_route(monkeypatch)
    a, s64 = _operand(200, 64, 10.0)
    x = ht.array(a, split=0, comm=comm)
    assert not qr_mod.rows_route(x.shape, x.larray.dtype, 0, comm)
    (u, s, v), spans = _spans(lambda: ht.linalg.svd(x))
    assert all(e.get("route") != "cholqr2_rows" for e in spans)
    np.testing.assert_allclose(_f64(s), s64, rtol=1e-5)


def test_the_row_sharded_route_is_the_chips_alone(comm):
    """Off a TPU; and on one, for another dtype, layout, mesh or size: no."""
    big = (1 << 20, 64)
    one = XlaCommunication(jax.devices()[:1])
    grid = grid_comm((2, 2), jax.devices()[:4])
    assert not qr_mod.rows_route(big, np.float32, 0, comm)  # the CPU
    with pytest.MonkeyPatch.context() as chip:
        chip.setattr(jax, "default_backend", lambda: "tpu")
        assert qr_mod.rows_route(big, np.float32, 0, comm)
        assert not qr_mod.rows_route(big, np.float16, 0, comm)
        assert not qr_mod.rows_route(big, np.float32, 1, comm)
        assert not qr_mod.rows_route(big, np.float32, None, comm)
        assert not qr_mod.rows_route(big, np.float32, 0, one)  # one device: tall_route's
        assert not qr_mod.rows_route(big, np.float32, 0, grid)
        assert not qr_mod.rows_route((64, 1 << 20), np.float32, 0, comm)
        assert not qr_mod.rows_route((4 * 4096, 64), np.float32, 0, comm)  # 1 MB a shard
        assert qr_mod.rows_route((4 * 16384, 64), np.float32, 0, comm)  # 4 MB a shard, the smallest timed
        chip.setattr(qr_mod, "MIN_BYTES", 0)
        assert qr_mod.rows_route((4 * 63 + 1, 64), np.float32, 0, comm)  # ragged: shards padded to 64 rows
        assert not qr_mod.rows_route((4 * 63, 64), np.float32, 0, comm)  # 63 rows a shard for 64 columns


#: call -> (site, a_passes, u)
CALLS = {
    "svd": (lambda x: ht.linalg.svd(x), "jitted:linalg.svd", 3, "direct"),
    "svd_values": (lambda x: ht.linalg.svd(x, compute_uv=False), "jitted:linalg.svd", 2, None),
    "qr": (lambda x: ht.linalg.qr(x), "jitted:linalg.qr", 3, None),
    "qr_r_only": (lambda x: ht.linalg.qr(x, calc_q=False), "jitted:linalg.qr", 2, None),
}


@pytest.mark.parametrize("call", sorted(CALLS))
def test_the_launch_span_states_route_shards_and_collective_bytes(monkeypatch, comm, call):
    fn, site, passes, u = CALLS[call]
    m, n, block, kappa = CASES[0]
    _chips_route(monkeypatch, block)
    a, _ = _operand(m, n, kappa, seed=1)
    _, spans = _spans(lambda: fn(ht.array(a, split=0, comm=comm)))
    (span,) = spans
    want = {"site": site, "kind": "launch", "route": "cholqr2_rows", "a_passes": passes, "precision": qr_mod.TALL_PRECISION,
            "fallback": "rows_tsqr", "col_blocks": 1, "shards": SHARDS, "collective_bytes": 2 * n * n * 4}
    assert {k: span[k] for k in want} == want
    assert span.get("u") == u and (u is None) == ("u" not in span)
