"""``ht.linalg.svd`` and ``ht.linalg.qr`` of a tall operand held whole on a
device, on both of its routes (``core/linalg/qr.py:tall_route``).

The route a float32 operand of ``MIN_BYTES`` (4 MB) or more takes in a process
on a TPU, CholeskyQR2 over blocks of rows with U (or Q) formed in one more pass,
is steered onto here (``_chips_route``) and held to numpy float64 over ragged
row counts, several block counts and κ(A) up to 1e3, with ``calc_q`` both
ways, and to the benchmark's plain reference (``perf/references/svd_plain.py``)
by the cell's own numbers and limits.  Operands on which CholeskyQR2 is not
sound (κ(A) past ``KAPPA_MAX``, rank-deficient, zero) take the blocked TSQR
inside the same program and are held to rounding.  Every other operand keeps
XLA's Householder QR of the whole operand.  Each program states its route,
its reads of A, the precision of its tall products and how U is formed in its
launch span.  (``tests/test_tpu_compile.py`` holds ``a_passes`` to the program
compiled for the chip at the cell's size.)
"""

import importlib
import importlib.util
import json
import os

import numpy as np
import pytest

import jax

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core.communication import XlaCommunication

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")

#: (rows, columns, rows a block, κ(A)): blocks that divide the rows and blocks
#: that leave a tail, one block, many blocks, and a square operand
CASES = [
    (3000, 40, 256, 1.0),
    (4097, 64, 1000, 1e2),
    (2048, 32, 1 << 16, 1e3),
    (1500, 300, 512, 10.0),
    (777, 77, 100, 1e3),
    (96, 96, 32, 1e2),
]
IDS = [f"{m}x{n}-block{b}-kappa{k:g}" for m, n, b, k in CASES]


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
    """float32 rounding carried through the factor: U = A·W (or Q = A·R⁻¹),
    W of norm κ / |A|, is orthonormal to a few units of 2^-24 times κ, never
    under 1e-5."""
    return max(1e-5, 4e-6 * kappa)


def _chips_route(monkeypatch, block=None):
    """What a one-TPU process answers, and the case's block of rows."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(qr_mod, "MIN_BYTES", 0)
    if block is not None:
        monkeypatch.setattr(qr_mod, "BLOCK_ROWS", block)


def _one_device(a):
    return ht.array(a, split=0, comm=XlaCommunication(jax.devices()[:1]))


@pytest.mark.parametrize("m,n,block,kappa", CASES, ids=IDS)
def test_one_chip_svd_against_float64(monkeypatch, m, n, block, kappa):
    _chips_route(monkeypatch, block)
    a, s64 = _operand(m, n, kappa)
    assert qr_mod.tall_route((m, n), np.float32) == "cholqr2"
    u, s, v = (np.asarray(t.larray, np.float64) for t in ht.linalg.svd(_one_device(a)))
    tol = _tolerance(kappa)
    assert np.max(np.abs(s - s64) / s64) < tol
    assert np.max(np.abs(u.T @ u - np.eye(n))) < tol
    assert np.max(np.abs(v.T @ v - np.eye(n))) < 1e-5
    assert np.linalg.norm(a @ v - u * s) / np.linalg.norm(a) < 1e-5
    only = np.asarray(ht.linalg.svd(_one_device(a), compute_uv=False).larray, np.float64)
    np.testing.assert_allclose(only, s64, rtol=tol)


@pytest.mark.parametrize("calc_q", [True, False])
@pytest.mark.parametrize("m,n,block,kappa", CASES, ids=IDS)
def test_one_chip_qr_against_float64(monkeypatch, m, n, block, kappa, calc_q):
    _chips_route(monkeypatch, block)
    a, s64 = _operand(m, n, kappa, seed=1)
    q, r = ht.linalg.qr(_one_device(a), calc_q=calc_q)
    r = np.asarray(r.larray, np.float64)
    assert np.array_equal(np.tril(r, -1), np.zeros_like(r))  # upper triangular, exactly
    assert np.all(np.diag(r) > 0)
    np.testing.assert_allclose(np.linalg.svd(r, compute_uv=False), s64, rtol=_tolerance(kappa))
    if not calc_q:
        assert q is None
        return
    q = np.asarray(q.larray, np.float64)
    assert np.max(np.abs(q.T @ q - np.eye(n))) < _tolerance(kappa)
    assert np.linalg.norm(q @ r - a) / np.linalg.norm(a) < 1e-5


def _dots(f, *args):
    """``(lhs shape, rhs shape, out shape)`` of every ``dot_general`` ``f``
    traces to, nested jaxprs included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append(tuple(tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


@pytest.mark.parametrize("n", [64, 128, 129, 200, 256, 300, 384])
def test_the_structured_products_skip_the_zero_and_mirrored_tiles(monkeypatch, n):
    """The Grams on and below their diagonal tiles, ``A·R⁻¹`` (of a block of
    rows, and Q of the whole operand) without the zero tiles under R⁻¹'s
    first diagonal block, planned on ``ceil(n / 128)`` tiles of columns, two
    products each: each equals its dense product to float32 rounding, the
    Gram exactly symmetric, and CholeskyQR2's R and R⁻¹ those of the dense
    products; up to 128 columns the dense products themselves."""
    import jax.numpy as jnp

    monkeypatch.setattr(qr_mod, "BLOCK_ROWS", 256)  # several blocks of rows and a tail
    a, _ = _operand(n + 700, n, 10.0)
    a64 = a.astype(np.float64)
    blocks = -(-n // 128)
    assert qr_mod.route_fields("cholqr2", True, n)["col_blocks"] == blocks
    with jax.enable_x64(False):
        x = jnp.asarray(a)
        g = np.asarray(qr_mod._gram(x, None, "highest"), np.float64)
        r, rinv = (np.asarray(t, np.float64) for t in qr_mod._cholqr2(x, "highest"))
        rinv32 = jnp.asarray(rinv, jnp.float32)
        q = np.asarray(jnp.concatenate([p for _, p in qr_mod._upper_parts(x, rinv32, "highest")], axis=1), np.float64)
        q_whole = np.asarray(qr_mod._upper_product(x, rinv32, "highest"), np.float64)
        gram_dots = _dots(lambda t: qr_mod._lower_gram(t, "highest"), x)
        q_dots = _dots(lambda t, u: qr_mod._upper_parts(t, u, "highest"), x, rinv32)
        monkeypatch.setattr(qr_mod, "MXU_COLS", 1 << 30)  # one tile: the dense products
        r_dense, rinv_dense = (np.asarray(t, np.float64) for t in qr_mod._cholqr2(x, "highest"))
    assert np.array_equal(g, g.T)
    assert np.max(np.abs(g - a64.T @ a64)) < 1e-5 * np.max(np.abs(g))
    for product in (q, q_whole):  # a block of rows; the whole operand, a block of rows at a time
        assert np.max(np.abs(product - a64 @ np.triu(rinv))) < 1e-5 * np.max(np.abs(product))
    assert np.max(np.abs(r - r_dense)) < 1e-5 * np.max(np.abs(r))
    assert np.max(np.abs(rinv - rinv_dense)) < 1e-5 * np.max(np.abs(rinv))
    assert len(gram_dots) == len(q_dots) == min(blocks, 2)
    if n <= 128:
        assert gram_dots == [(a.shape[::-1], a.shape, (n, n))] and q_dots == [(a.shape, (n, n), a.shape)]
    else:  # no product has an n x n operand or result
        assert (n, n) not in {shape for dot in gram_dots + q_dots for shape in dot}


def _not_for_cholqr(kind, m=2000, n=48):
    """Operands CholeskyQR2 cannot factor soundly: a Gram whose Cholesky breaks
    down, or a factor past ``KAPPA_MAX``."""
    a, _ = _operand(m, n, 10.0, seed=2)
    if kind == "kappa1e5":
        a, _ = _operand(m, n, 1e5, seed=2)
    elif kind == "column_repeated":  # a duplicate image
        a[:, -1] = a[:, 0]
    elif kind == "constant_columns":  # two constant images
        a[:, 3] = a[:, 4] = 2.5
    elif kind == "zero_column":
        a[:, n // 2] = 0.0
    elif kind == "zero":
        a[:] = 0.0
    return a


UNSOUND = ["kappa1e5", "column_repeated", "constant_columns", "zero_column", "zero"]


@pytest.mark.parametrize("kind", UNSOUND + ["kappa1e2"])
def test_the_factor_says_whether_it_may_form_u(kind):
    import jax.numpy as jnp

    a = _operand(2000, 48, 1e2)[0] if kind == "kappa1e2" else _not_for_cholqr(kind)
    with jax.enable_x64(False):
        sound = qr_mod._sound(*qr_mod._cholqr2(jnp.asarray(a), "highest"), False)[0]
    assert bool(sound) == (kind == "kappa1e2")


@pytest.mark.parametrize("block", [512, 1 << 16], ids=["blocks", "one_block"])
@pytest.mark.parametrize("kind", UNSOUND)
def test_an_operand_cholqr_cannot_factor_takes_the_blocked_tsqr(monkeypatch, kind, block):
    """Held to rounding on every output, as Householder's QR is: U and Q
    orthonormal, the factorizations reconstruct A, S against float64 in units
    of its largest value, R upper triangular with a non-negative diagonal."""
    _chips_route(monkeypatch, block)
    a = _not_for_cholqr(kind)
    m, n = a.shape
    a64 = a.astype(np.float64)
    s64 = np.linalg.svd(a64, compute_uv=False)
    scale = max(float(s64[0]), 1.0)
    u, s, v = (np.asarray(t.larray, np.float64) for t in ht.linalg.svd(_one_device(a)))
    assert np.max(np.abs(u.T @ u - np.eye(n))) < 1e-5
    assert np.max(np.abs(v.T @ v - np.eye(n))) < 1e-5
    assert np.max(np.abs(s - s64)) / scale < 1e-5
    assert np.linalg.norm(a64 - (u * s) @ v.T) / scale < 1e-5
    only = np.asarray(ht.linalg.svd(_one_device(a), compute_uv=False).larray, np.float64)
    assert np.max(np.abs(only - s64)) / scale < 1e-5
    q, r = (np.asarray(t.larray, np.float64) for t in ht.linalg.qr(_one_device(a)))
    assert np.max(np.abs(q.T @ q - np.eye(n))) < 1e-5
    assert np.linalg.norm(q @ r - a64) / scale < 1e-5
    assert np.array_equal(np.tril(r, -1), np.zeros_like(r)) and np.all(np.diag(r) >= 0)
    r_only = np.asarray(ht.linalg.qr(_one_device(a), calc_q=False).R.larray, np.float64)
    np.testing.assert_allclose(r_only, r, atol=1e-5 * scale)


def _by_file(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def plain():
    """``perf/`` is no package of the program's: the module by its file."""
    return _by_file("svd_plain", os.path.join(PERF, "references", "svd_plain.py"))


@pytest.fixture(scope="module")
def limits():
    with open(os.path.join(PERF, "workloads", "svd_300_c1.json")) as fh:
        return json.load(fh)["limits"]


@pytest.mark.parametrize("m,block", [(8192, 1000), (6000, 4096), (4096, 1 << 16)])
@pytest.mark.parametrize("route", ["cholqr2", "householder"])
def test_the_cells_numbers_against_the_plain_reference(monkeypatch, plain, limits, m, block, route):
    """The cell's data (blobs of 8 centres at scale 10, unit noise: κ about 76)
    at 300 columns: both routes within the cell's limits of the plain
    reference's blocked Householder TSQR, by the cell's own judge."""
    import jax.numpy as jnp

    datagen = _by_file("datagen", os.path.join(PERF, "datagen.py"))
    x = datagen.make({"kind": "blobs", "rows": m, "features": 300, "centres": 8, "centre_scale": 10.0, "noise": 1.0},
                     2**31 + m, jax.devices()[:1])
    if route == "cholqr2":
        _chips_route(monkeypatch, block)
    assert qr_mod.tall_route((m, 300), jnp.float32) == route
    u, s, v = ht.linalg.svd(_one_device(np.asarray(x)))
    numbers = plain.judge(x, {"U": u.larray, "S": s.larray, "V": v.larray}, seed=m, block=block)
    assert all(numbers[k] <= limits[k] for k in limits), numbers


def _spans(call):
    was = telemetry.is_enabled()
    telemetry.enable()
    try:
        telemetry.reset()
        call()
        return [e for e in telemetry.events() if e.get("site", "").startswith("jitted:linalg.")]
    finally:
        if not was:
            telemetry.disable()


#: call -> (site, a_passes on cholqr2, u on cholqr2)
CALLS = {
    "svd": (lambda x: ht.linalg.svd(x), "jitted:linalg.svd", 3, "direct"),
    "svd_values": (lambda x: ht.linalg.svd(x, compute_uv=False), "jitted:linalg.svd", 2, None),
    "qr": (lambda x: ht.linalg.qr(x), "jitted:linalg.qr", 3, None),
    "qr_r_only": (lambda x: ht.linalg.qr(x, calc_q=False), "jitted:linalg.qr", 2, None),
}


@pytest.mark.parametrize("steered", [True, False], ids=["chips_route", "elsewhere"])
@pytest.mark.parametrize("call", sorted(CALLS))
def test_the_launch_span_states_route_passes_precision_and_u(monkeypatch, call, steered):
    fn, site, passes, u = CALLS[call]
    if steered:
        _chips_route(monkeypatch)
    a, _ = _operand(512, 16, 10.0)
    spans = _spans(lambda: fn(_one_device(a)))
    if not steered and site == "jitted:linalg.svd":
        assert spans == []  # the fused chain, its QR inlined: ``fuse:*`` launches it
        return
    (span,) = spans
    assert span["site"] == site and span["kind"] == "launch"
    if steered:
        want = {"route": "cholqr2", "a_passes": passes, "precision": qr_mod.TALL_PRECISION, "fallback": "blocked_tsqr",
                "col_blocks": 1}
    else:  # the whole operand copied once into XLA's QR; the products at the linalg default
        want = {"route": "householder", "a_passes": 1, "precision": ht.linalg.get_matmul_precision()}
    assert {k: span[k] for k in want} == want and ("fallback" in span) == ("col_blocks" in span) == steered
    assert span.get("u") == u and (u is None) == ("u" not in span)


def test_the_blocked_route_is_the_chips_alone():
    """Off a TPU, under the size floor, another dtype or a wide operand: the
    Householder form."""
    big = (1 << 20, 64)
    assert qr_mod.tall_route(big, np.float32) == "householder"  # the CPU
    with pytest.MonkeyPatch.context() as chip:
        chip.setattr(jax, "default_backend", lambda: "tpu")
        assert qr_mod.tall_route(big, np.float32) == "cholqr2"
        assert qr_mod.tall_route(big, np.float16) == "householder"
        assert qr_mod.tall_route((4096, 64), np.float32) == "householder"  # 1 MB
        assert qr_mod.tall_route((16384, 64), np.float32) == "cholqr2"  # 4 MB, the smallest timed
        assert qr_mod.tall_route((64, 1 << 20), np.float32) == "householder"


def test_a_replicated_operand_on_the_mesh_takes_the_one_device_program(monkeypatch):
    _chips_route(monkeypatch)
    a, s64 = _operand(200, 12, 10.0)
    x = ht.array(a)  # split=None over every device of the mesh
    spans = _spans(lambda: ht.linalg.svd(x))
    assert [e["site"] for e in spans] == ["jitted:linalg.svd"]
    u, s, v = ht.linalg.svd(x)
    assert u.split is None
    np.testing.assert_allclose(s.numpy(), s64, rtol=1e-5)
    np.testing.assert_allclose(u.numpy() @ np.diag(s.numpy()) @ v.numpy().T, a, atol=1e-4)
