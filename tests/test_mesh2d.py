"""2-D mesh layouts: splits-tuple metadata, the grid SUMMA matmul, and
planned 2-D redistribution.

The ISSUE acceptance contracts pinned here:

- grid SUMMA on 2x2 and 2x4 meshes equals the replicated panel-ordered
  twin to within the float32 rounding of its contraction
  (:func:`_assert_within_roundoff`: the CPU compiler blocks the kernel's
  per-device dots and the twin's whole-matrix dots differently, so the
  last bit is the machine's), its overlap arm equals its serial arm
  BITWISE (one program shape, two schedules), and it launches exactly
  ONE compiled dispatch;
- its telemetry wire bytes equal :func:`heat_tpu.comm._costs.summa_grid_model`
  byte-for-byte (accounting delegates to the model, so a drift in either
  breaks this test);
- ``plan()`` over a grid factors a (src-splits -> dst-splits) change into
  per-mesh-axis 1-D stages, prices it, honors ``max_live_bytes`` at plan
  time, and the executed schedule is value-exact vs the monolithic
  reshard as one dispatch;
- ``split`` stays the exact compat view of ``splits`` — every 1-D layout
  round-trips losslessly.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.comm import _costs
from heat_tpu.comm import redistribute as rd
from heat_tpu.comm.overlap import overlap
from heat_tpu.core import _tracing
from heat_tpu.core.communication import grid_comm
from suite import assert_within_bound

RNG = np.random.default_rng(29)

MESHES = [(2, 2), (2, 4)]


def _grid(mesh_shape):
    if len(jax.devices()) < mesh_shape[0] * mesh_shape[1]:
        pytest.skip(f"needs {mesh_shape[0] * mesh_shape[1]} devices")
    return grid_comm(mesh_shape)


def _pair(comm, m, k, n):
    a = RNG.normal(size=(m, k)).astype(np.float32)
    b = RNG.normal(size=(k, n)).astype(np.float32)
    A = ht.array(a, splits=(0, 1), comm=comm)
    B = ht.array(b, splits=(0, 1), comm=comm)
    return a, b, A, B


def _replicated_twin(a, b, mesh_shape):
    """The replicated twin of the grid SUMMA: the SAME panel schedule
    (k padded to L*w, L partial products accumulated in panel order) on
    unsharded operands."""
    r, c = mesh_shape
    L = r * c
    k = a.shape[1]
    w = -(-k // L)
    aj = jnp.pad(jnp.asarray(a), ((0, 0), (0, L * w - k)))
    bj = jnp.pad(jnp.asarray(b), ((0, L * w - k), (0, 0)))
    acc = jnp.zeros((a.shape[0], b.shape[1]), aj.dtype)
    for t in range(L):
        acc = acc + jnp.matmul(aj[:, t * w:(t + 1) * w],
                               bj[t * w:(t + 1) * w, :])
    return np.asarray(acc)


def _assert_within_roundoff(got, a, b, mesh_shape):
    """``got`` against the panel-ordered twin of ``a @ b``, to what float32
    can guarantee of two evaluations of one sum.

    The twin adds the same L panel products in the same order, but it
    forms each from whole (m, w) x (w, n) operands where the kernel's
    devices form (m/r, w) x (w, n/c) blocks, and XLA's CPU backend
    chooses a dot's blocking (and whether it contracts to FMA) from its
    shape and fusion context: the twins read a rounding or two apart in
    half of the entries on some machines and bit-equal on others.  One
    reduction order by construction cannot be had from outside the
    compiler, so the comparison is the standard forward bound instead:
    each side is within ``gamma_K * sum(|a||b|)`` of the exact sum,
    ``gamma_K = K u / (1 - K u)``, ``u = 2**-24``, K the padded
    contraction length, whatever its order.  The bound is entrywise (an
    entry whose terms cancel gets no more room than its terms give) and
    finite only where ``got`` is."""
    r, c = mesh_shape
    L = r * c
    K = L * -(-a.shape[1] // L)
    u = 2.0 ** -24
    bound = 2 * (K * u / (1 - K * u)) * (
        np.abs(a).astype(np.float64) @ np.abs(b).astype(np.float64))
    assert_within_bound(got, _replicated_twin(a, b, mesh_shape), bound)


# --------------------------------------------------------------------- #
# splits metadata and the split compat view                              #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("split", [None, 0, 1])
def test_split_compat_view_roundtrips_on_1d_mesh(split):
    x = ht.ones((8, 8), split=split)
    assert x.split == split
    if split is None:
        assert x.splits == (None, None)
    else:
        expect = [None, None]
        expect[split] = 0
        assert x.splits == tuple(expect)
    # the one-hot splits spelling commits the IDENTICAL layout
    y = ht.ones((8, 8), splits=x.splits)
    assert y.split == split
    assert y.larray.sharding == x.larray.sharding


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_splits_metadata(mesh_shape):
    comm = _grid(mesh_shape)
    A = ht.ones((8, 16), splits=(0, 1), comm=comm)
    assert A.splits == (0, 1)
    # compat view: the array dim mesh axis 0 shards
    assert A.split == 0
    assert ht.ones((8, 16), splits=(None, 0), comm=comm).split == 1
    assert ht.ones((8, 16), splits=(None, None), comm=comm).split is None


def test_split_and_splits_are_mutually_exclusive():
    with pytest.raises(ValueError):
        ht.ones((8, 8), split=0, splits=(0, None))


def test_splits_validates_against_mesh_rank():
    # entry 1 names a second mesh axis the default 1-D comm doesn't have
    with pytest.raises(ValueError):
        ht.ones((8, 8), splits=(0, 1))
    with pytest.raises(ValueError):
        ht.ones((8, 8), splits=(0,))  # arity mismatch
    comm = _grid((2, 2))
    with pytest.raises(ValueError):
        ht.ones((8, 8), splits=(0, 0), comm=comm)  # duplicate mesh axis


# --------------------------------------------------------------------- #
# grid SUMMA: twin parity, one dispatch, telemetry == model              #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (7, 13, 9), (8, 12, 10)])
def test_grid_summa_bitwise_vs_replicated_twin(mesh_shape, m, k, n):
    """A stated tolerance, not one reduction order by construction (why:
    :func:`_assert_within_roundoff`).  The bitwise claims that do hold by
    construction are the overlap arm's (below) and a repeated call's."""
    comm = _grid(mesh_shape)
    a, b, A, B = _pair(comm, m, k, n)
    got = A @ B
    assert got.splits == (0, 1)
    assert got.shape == (m, n)
    _assert_within_roundoff(got.numpy(), a, b, mesh_shape)
    np.testing.assert_array_equal((A @ B).numpy(), got.numpy())
    np.testing.assert_allclose(got.numpy(), a @ b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_is_one_dispatch(mesh_shape):
    comm = _grid(mesh_shape)
    L = mesh_shape[0] * mesh_shape[1]
    # k divisible by r*c and m/n divisible by r/c: no pads anywhere, so
    # the count is the SUMMA program alone
    a, b, A, B = _pair(comm, 4 * mesh_shape[0], 2 * L, 4 * mesh_shape[1])
    jax.block_until_ready((A @ B).larray)  # warm the compile cache
    with _tracing.counting_dispatches() as d:
        jax.block_until_ready((A @ B).larray)
    assert d.count == 1, f"grid SUMMA must be ONE dispatch, saw {d.count}"


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_overlap_arm_bitwise_equal(mesh_shape):
    comm = _grid(mesh_shape)
    a, b, A, B = _pair(comm, 7, 13, 9)
    serial = (A @ B).numpy()
    with overlap("on"):
        overlapped = (A @ B).numpy()
    np.testing.assert_array_equal(overlapped, serial)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_telemetry_matches_wire_model(mesh_shape):
    comm = _grid(mesh_shape)
    m, k, n = 8, 12, 10
    a, b, A, B = _pair(comm, m, k, n)
    model = _costs.summa_grid_model(m, k, n, mesh_shape)
    telemetry.enable()
    telemetry.reset()
    try:
        jax.block_until_ready((A @ B).larray)
        snap = telemetry.snapshot()
        assert snap["counters"]["comm.collectives.summa2d"] == 1
        assert snap["counters"]["comm.wire_bytes"] == model["wire_bytes"]
        assert snap["counters"]["comm.exact_bytes"] == model["exact_wire_bytes"]
        assert "comm:summa2d" in snap["spans"]
    finally:
        telemetry.reset()
        telemetry.disable()


def test_grid_summa_model_shape():
    model = _costs.summa_grid_model(64, 64, 64, (2, 4))
    assert model["panels"] == 8
    assert model["panel_width"] == 8
    assert model["exact_wire_bytes"] > 0
    assert model["wire_bytes"] == model["exact_wire_bytes"]  # f32 wire
    assert model["peak_live_bytes"] > 0
    assert set(model["critical_path_ms"]) == {"serial", "overlap"}
    # with per-step compute to hide behind, overlap wins the modeled path
    busy = _costs.summa_grid_model(64, 64, 64, (2, 4),
                                   compute_ms_per_step=1.0)
    assert busy["critical_path_ms"]["overlap"] < \
        busy["critical_path_ms"]["serial"]


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_pad_poisoning(mesh_shape):
    """Ragged k over the panel grid: BOTH operands carry k-axis pads, and
    ht.log leaves -inf there.  The SUMMA must mask them (0 * inf = NaN
    would poison every output element through the k-sum).  Exact part:
    every valid entry is finite.  Stated-tolerance part: each equals the
    twin on the unpadded logs to the contraction's rounding (why not
    bitwise: :func:`_assert_within_roundoff`), so a pad that leaked even
    one finite term of the logs' size would show."""
    comm = _grid(mesh_shape)
    m, k, n = 7, 13, 9
    a = (np.abs(RNG.normal(size=(m, k))) + 0.5).astype(np.float32)
    b = (np.abs(RNG.normal(size=(k, n))) + 0.5).astype(np.float32)
    A = ht.log(ht.array(a, splits=(0, 1), comm=comm))
    B = ht.log(ht.array(b, splits=(0, 1), comm=comm))
    got = (A @ B).numpy()
    assert np.isfinite(got).all()
    # twin inputs through the SAME XLA log (numpy's differs in the ulp)
    la = np.asarray(jnp.log(jnp.asarray(a)))
    lb = np.asarray(jnp.log(jnp.asarray(b)))
    _assert_within_roundoff(got, la, lb, mesh_shape)


def test_matmul_precision_and_out_forwarding_on_grid():
    comm = _grid((2, 2))
    a, b, A, B = _pair(comm, 8, 8, 8)
    want = (A @ B).numpy()
    hi = ht.matmul(A, B, precision="highest")
    np.testing.assert_allclose(hi.numpy(), want, rtol=1e-5, atol=1e-5)
    out = ht.zeros((8, 8), splits=(0, 1), comm=comm)
    res = ht.matmul(A, B, out=out)
    assert res is out
    np.testing.assert_array_equal(out.numpy(), want)


# --------------------------------------------------------------------- #
# rank-local SUMMA schedules: (0,None)x(None,1) and (None,1)x(0,None)    #
# --------------------------------------------------------------------- #
RANK_LOCAL_LAYOUTS = [
    ("rowcol", (0, None), (None, 1)),
    ("colrow", (None, 1), (0, None)),
]


@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("layout,sa,sb", RANK_LOCAL_LAYOUTS)
@pytest.mark.parametrize("m,k,n", [(8, 16, 8), (7, 13, 9)])
def test_grid_summa_rank_local_bitwise_vs_replicated_twin(
    mesh_shape, layout, sa, sb, m, k, n
):
    """The rank-local schedules run the IDENTICAL L-step panel-ordered
    accumulation as the (0,1)x(0,1) grid schedule, so all three layouts
    share one replicated twin — no redistribution to (0,1) ever
    happens (the result commits straight to (0,1)).  A stated tolerance
    (:func:`_assert_within_roundoff` says why not one reduction order by
    construction)."""
    comm = _grid(mesh_shape)
    a = RNG.normal(size=(m, k)).astype(np.float32)
    b = RNG.normal(size=(k, n)).astype(np.float32)
    A = ht.array(a, splits=sa, comm=comm)
    B = ht.array(b, splits=sb, comm=comm)
    got = A @ B
    assert got.splits == (0, 1)
    assert got.shape == (m, n)
    _assert_within_roundoff(got.numpy(), a, b, mesh_shape)


@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("layout,sa,sb", RANK_LOCAL_LAYOUTS)
def test_grid_summa_rank_local_one_dispatch(mesh_shape, layout, sa, sb):
    comm = _grid(mesh_shape)
    L = mesh_shape[0] * mesh_shape[1]
    a = RNG.normal(size=(4 * mesh_shape[0], 2 * L)).astype(np.float32)
    b = RNG.normal(size=(2 * L, 4 * mesh_shape[1])).astype(np.float32)
    A = ht.array(a, splits=sa, comm=comm)
    B = ht.array(b, splits=sb, comm=comm)
    jax.block_until_ready((A @ B).larray)  # warm the compile cache
    with _tracing.counting_dispatches() as d:
        jax.block_until_ready((A @ B).larray)
    assert d.count == 1, f"rank-local SUMMA must be ONE dispatch, saw {d.count}"


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_rowcol_wire_strictly_below_redistribute(mesh_shape):
    """The rank-local (0,None)x(None,1) schedule ships ZERO bytes; the
    alternative — redistribute both operands to (0,1), then grid SUMMA —
    pays two planned layout changes plus the full panel-broadcast wire.
    The modeled gap is the whole point of the layout-freedom work."""
    m, k, n = 64, 64, 64
    size = mesh_shape[0] * mesh_shape[1]
    model = _costs.summa_grid_model(m, k, n, mesh_shape, layout="rowcol")
    assert model["wire_bytes"] == 0
    assert model["exact_wire_bytes"] == 0
    grid = _costs.summa_grid_model(m, k, n, mesh_shape)
    alt = (
        grid["wire_bytes"]
        + rd.plan((m, k), "float32", (0, None), (0, 1), size,
                  mesh_shape=mesh_shape).wire_bytes
        + rd.plan((k, n), "float32", (None, 1), (0, 1), size,
                  mesh_shape=mesh_shape).wire_bytes
    )
    assert model["wire_bytes"] < alt
    assert grid["wire_bytes"] > 0  # the gap is real, not two zeros


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_summa_colrow_wire_parity_with_grid_schedule(mesh_shape):
    """(None,1)x(0,None) ships exactly the grid schedule's bytes (owners
    slice their own blocks before the masked psums); the win over
    redistribute-then-SUMMA is eliding the two planned redistributions."""
    m, k, n = 64, 64, 64
    size = mesh_shape[0] * mesh_shape[1]
    model = _costs.summa_grid_model(m, k, n, mesh_shape, layout="colrow")
    grid = _costs.summa_grid_model(m, k, n, mesh_shape)
    assert model["wire_bytes"] == grid["wire_bytes"]
    assert model["exact_wire_bytes"] == grid["exact_wire_bytes"]
    # the alternative's redistributions to (0,1) are themselves zero-wire
    # (sharding a replicated dim is a local slice), so there is no byte
    # gap — only the two elided dispatches and their committed copies
    for shape, src in (((m, k), (None, 1)), ((k, n), (0, None))):
        p = rd.plan(shape, "float32", src, (0, 1), size, mesh_shape=mesh_shape)
        assert p.wire_bytes == 0
        assert len(p.steps) >= 1


@pytest.mark.parametrize("layout,sa,sb", RANK_LOCAL_LAYOUTS)
def test_grid_summa_rank_local_telemetry_matches_model(layout, sa, sb):
    mesh_shape = (2, 2)
    comm = _grid(mesh_shape)
    m, k, n = 8, 12, 10
    a = RNG.normal(size=(m, k)).astype(np.float32)
    b = RNG.normal(size=(k, n)).astype(np.float32)
    A = ht.array(a, splits=sa, comm=comm)
    B = ht.array(b, splits=sb, comm=comm)
    model = _costs.summa_grid_model(m, k, n, mesh_shape, layout=layout)
    telemetry.enable()
    telemetry.reset()
    try:
        jax.block_until_ready((A @ B).larray)
        snap = telemetry.snapshot()
        assert snap["counters"]["comm.collectives.summa2d"] == 1
        assert snap["counters"].get("comm.wire_bytes", 0) == model["wire_bytes"]
        assert snap["counters"].get("comm.exact_bytes", 0) == model["exact_wire_bytes"]
    finally:
        telemetry.reset()
        telemetry.disable()


# --------------------------------------------------------------------- #
# planned 2-D redistribution                                             #
# --------------------------------------------------------------------- #
GRID_TRANSITIONS = [
    ((0, 1), (1, 0)),        # full transpose of the mesh assignment
    ((0, 1), (None, None)),  # gather everything
    ((None, None), (0, 1)),  # scatter everything
    ((0, None), (0, 1)),     # add a second sharded dim
    ((0, 1), (0, None)),     # drop one
    ((0, None), (None, 0)),  # 1-D move along one mesh axis
]


def _grid_committed(comm, data, splits):
    with rd.redistribution("monolithic"):
        return comm.commit_split(jnp.asarray(data), splits)


@pytest.mark.parametrize("mesh_shape", MESHES)
@pytest.mark.parametrize("src,dst", GRID_TRANSITIONS)
def test_grid_plan_parity_vs_monolithic(mesh_shape, src, dst):
    comm = _grid(mesh_shape)
    data = RNG.normal(size=(16, 16)).astype(np.float32)
    x = _grid_committed(comm, data, src)
    with rd.redistribution("monolithic"):
        ref = comm.resplit(x, dst)
    with rd.redistribution("planned"):
        got = comm.resplit(x, dst)
    assert got.sharding == ref.sharding
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_plan_executes_as_one_dispatch(mesh_shape):
    comm = _grid(mesh_shape)
    data = RNG.normal(size=(16, 16)).astype(np.float32)
    x = _grid_committed(comm, data, (0, 1))
    with rd.redistribution("planned"):
        jax.block_until_ready(comm.resplit(x, (1, 0)))  # warm the cache
        with _tracing.counting_dispatches() as d:
            jax.block_until_ready(comm.resplit(x, (1, 0)))
    assert d.count == 1, (
        f"the factored multi-stage schedule must still be ONE compiled "
        f"dispatch, saw {d.count}"
    )


def test_grid_plan_factors_cyclic_transpose():
    # (0,1)->(1,0) is a cyclic mesh-axis swap: no direct per-axis move is
    # possible, so the planner routes one axis through replicated
    p_obj = rd.plan((64, 64), "float32", (0, 1), (1, 0), 8, mesh_shape=(2, 4))
    assert p_obj.mesh_shape == (2, 4)
    assert len(p_obj.steps) >= 3
    assert p_obj.wire_bytes > 0
    assert p_obj.peak_live_bytes > 0


def test_grid_plan_max_live_bytes_raises_at_plan_time():
    with pytest.raises(ValueError, match="max_live_bytes"):
        rd.plan((64, 64), "float32", (0, 1), (1, 0), 8,
                mesh_shape=(2, 4), max_live_bytes=10)


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_plan_peak_model_holds_end_to_end(mesh_shape):
    """The modeled peak is a usable bound: planning WITH it succeeds and
    the executed schedule stays value-exact; one byte less refuses at
    plan time."""
    comm = _grid(mesh_shape)
    size = comm.size
    p_obj = rd.plan((16, 16), "float32", (0, 1), (1, 0), size,
                    mesh_shape=mesh_shape)
    bounded = rd.plan((16, 16), "float32", (0, 1), (1, 0), size,
                      mesh_shape=mesh_shape,
                      max_live_bytes=p_obj.peak_live_bytes)
    assert bounded.peak_live_bytes <= p_obj.peak_live_bytes
    with pytest.raises(ValueError):
        rd.plan((16, 16), "float32", (0, 1), (1, 0), size,
                mesh_shape=mesh_shape,
                max_live_bytes=p_obj.peak_live_bytes - 1)
    data = RNG.normal(size=(16, 16)).astype(np.float32)
    x = _grid_committed(comm, data, (0, 1))
    got = rd.redistribute(x, (1, 0), comm,
                          max_live_bytes=p_obj.peak_live_bytes)
    with rd.redistribution("monolithic"):
        ref = comm.resplit(x, (1, 0))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_grid_plan_rejects_ragged_source():
    # the per-axis kernels assume canonical equal chunks on the SOURCE
    # (same contract as the 1-D planner); ragged sources stay monolithic
    with pytest.raises(ValueError, match="ragged"):
        rd.plan((7, 16), "float32", (0, 1), (None, None), 8,
                mesh_shape=(2, 4))


@pytest.mark.parametrize("mesh_shape", MESHES)
def test_grid_resplit_ragged_source_falls_back_monolithic(mesh_shape):
    # end-to-end: comm.resplit under "planned" must still be correct for
    # ragged sources — via the monolithic fallback, not a broken plan
    comm = _grid(mesh_shape)
    data = RNG.normal(size=(7, 9)).astype(np.float32)
    x = _grid_committed(comm, data, (0, 1))
    with rd.redistribution("planned"):
        got = comm.resplit(x, (None, None))
    with rd.redistribution("monolithic"):
        ref = comm.resplit(x, (None, None))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))


def test_dndarray_resplit_tuple_roundtrip():
    comm = _grid((2, 2))
    data = RNG.normal(size=(8, 8)).astype(np.float32)
    x = ht.array(data, splits=(0, 1), comm=comm)
    y = x.resplit((1, 0))
    assert y.splits == (1, 0)
    np.testing.assert_array_equal(y.numpy(), data)
    z = y.resplit((None, None))
    assert z.splits == (None, None)
    np.testing.assert_array_equal(z.numpy(), data)


def test_grid_plan_cache_is_keyed_by_mesh_shape():
    p22 = rd.plan((16, 16), "float32", (0, 1), (None, None), 4,
                  mesh_shape=(2, 2))
    p14 = rd.plan((16, 16), "float32", (0, 1), (None, None), 4,
                  mesh_shape=(4, 1))
    assert p22.mesh_shape == (2, 2)
    assert p14.mesh_shape == (4, 1)
    assert p22 is not p14
