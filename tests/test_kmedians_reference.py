"""``ht.cluster.KMedians`` against plain references, and the record it leaves.

The estimator assigns by Manhattan distance and serves exact medians
(numpy's), on both routes of its medians: the rank bisection every operand of
the CPU mesh takes, and the Pallas kernel of ``core/_colmedian.py`` that a
wide float32 operand takes on one TPU (held here in the Pallas interpreter).
From a given start a fit is deterministic, so it is held to plain numpy
sweeps centre for centre, bit for bit, over both splits and four mesh sizes,
on data with odd and even member counts, duplicate values, a cluster that
empties and a NaN feature; and to the benchmark's plain reference
(``perf/references/kmedians_plain.py``) by the cell's own numbers.  The cell's
blobs lie too far apart to show a Euclidean assignment or L1 sums in
bfloat16 (``PERF.md`` section 2): two data sets here do, and the comparison
fails when either is planted.  ``ht.spatial.manhattan`` takes the same wide
sum and says so in its span.  (``tests/test_tpu_compile.py`` holds the fit's
``x_passes`` to the program compiled for the chip.)
"""

import importlib.util
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.cluster import kmedians
from heat_tpu.core import _colmedian
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.spatial import distance

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")

#: the cell's limits (``perf/workloads/kmedians_300_c1.json``)
LIMITS = {"label_gap": 1e-3, "median_step": 1e-5, "median_f64": 1e-5, "iters_off": 0.0}


def _by_file(name: str, path: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def plain():
    """``perf/`` is no package of the program's: the module by its file."""
    return _by_file("kmedians_plain", os.path.join(PERF, "references", "kmedians_plain.py"))


@pytest.fixture(scope="module")
def probe():
    """The benchmark's planted faults (``perf/tools/limits_probe_kmedians.py``)."""
    sys.path.insert(0, PERF)
    try:
        return _by_file("limits_probe_kmedians", os.path.join(PERF, "tools", "limits_probe_kmedians.py"))
    finally:
        sys.path.remove(PERF)


@pytest.fixture
def tel():
    was = telemetry.is_enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


# --------------------------------------------------------------------- #
# plain numpy: the sweeps from a given start                             #
# --------------------------------------------------------------------- #
def _medians_nan_last(members: np.ndarray) -> np.ndarray:
    """numpy's median of each column with NaN members sorted last (a column
    whose middle lies among them gives NaN)."""
    ordered = np.sort(members, axis=0)
    m = len(members)
    a, b = ordered[(m - 1) // 2], ordered[m // 2]
    with np.errstate(invalid="ignore"):  # the mean of -inf and inf is NaN, as numpy's median has it
        return np.where(a == b, a, (a + b) / np.float32(2))


def _numpy_fit(x: np.ndarray, start: np.ndarray, sweeps: int):
    """Manhattan assignment (float64 sums: the data leave no near ties),
    exact medians; an empty cluster and a NaN median keep the coordinate."""
    centres = start.astype(np.float32).copy()
    k = len(centres)

    def assign(c):
        with np.errstate(invalid="ignore"):
            d = np.abs(x[:, None, :].astype(np.float64) - c[None, :, :]).sum(-1)
        # a row with a NaN feature: every distance NaN, the first index
        return np.where(np.isnan(d).any(1), 0, np.argmin(np.nan_to_num(d, nan=np.inf), axis=1))

    for _ in range(sweeps):
        labels = assign(centres)
        for c in range(k):
            if (labels == c).any():
                med = _medians_nan_last(x[labels == c])
                centres[c] = np.where(np.isnan(med), centres[c], med)
    return centres, assign(centres)


def _blobs(seed, sizes, features, spread=10.0):
    rng = np.random.default_rng(seed)
    centres = spread * rng.standard_normal((len(sizes), features))
    rows = [centres[c] + rng.standard_normal((m, features)) for c, m in enumerate(sizes)]
    x = np.concatenate(rows).astype(np.float32)
    return x[rng.permutation(len(x))], centres.astype(np.float32)


def _odd_and_even():
    x, centres = _blobs(1, (7, 8, 9, 12), 24)
    return x, centres + np.float32(0.5)


def _duplicates():
    x, centres = _blobs(2, (10, 11, 6), 16, spread=4.0)
    return np.round(x), np.round(centres)  # whole numbers: every column full of ties


def _a_cluster_empties():
    x, centres = _blobs(3, (9, 10), 12)
    return x, np.concatenate([centres, np.full((1, 12), 1e3, np.float32)])  # no row is near the third


def _a_nan_feature():
    x, centres = _blobs(4, (8, 9, 10), 10)
    x[5, 3] = np.nan
    return x, centres + np.float32(0.25)


DATA = {
    "odd_and_even_counts": _odd_and_even,
    "duplicates": _duplicates,
    "a_cluster_empties": _a_cluster_empties,
    "a_nan_feature": _a_nan_feature,
}
SWEEPS = 4


@pytest.mark.parametrize("devices", [1, 2, 4, 8])
@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("name", sorted(DATA))
def test_a_fit_from_a_given_start_is_the_plain_sweeps_bit_for_bit(name, split, devices):
    if devices > len(jax.devices()):
        pytest.skip(f"the mesh has {len(jax.devices())} devices")
    x, start = DATA[name]()
    comm = XlaCommunication(jax.devices()[:devices])
    km = ht.cluster.KMedians(
        n_clusters=len(start), init=ht.array(start, comm=comm), max_iter=SWEEPS, tol=-1.0
    ).fit(ht.array(x, split=split, comm=comm))
    want_centres, want_labels = _numpy_fit(x, start, SWEEPS)
    assert km.n_iter_ == SWEEPS
    got = km.cluster_centers_.numpy()
    assert got.dtype == np.float32 and np.array_equal(got, want_centres), np.abs(got - want_centres).max()
    assert np.array_equal(km.labels_.numpy(), want_labels)
    assert km.labels_.split == split
    if name == "a_cluster_empties":
        assert np.array_equal(got[-1], start[-1]) and not (want_labels == len(start) - 1).any()
    if name == "a_nan_feature":
        assert np.isfinite(got).all()


@pytest.mark.parametrize("name", ["odd_and_even_counts", "duplicates"])
def test_a_fit_reads_sound_by_the_cells_numbers_and_predicts_by_manhattan(plain, name):
    x, start = DATA[name]()
    km = ht.cluster.KMedians(n_clusters=len(start), init=ht.array(start), max_iter=SWEEPS, tol=-1.0)
    km.fit(ht.array(x, split=0))
    out = {"centres": km.cluster_centers_.larray, "labels": km.labels_.larray, "n_iter": km.n_iter_}
    numbers = plain.judge(jnp.asarray(x), out, 5, SWEEPS, block=7)
    assert set(numbers) == set(LIMITS) and all(numbers[n] <= LIMITS[n] for n in LIMITS), numbers
    assert numbers["median_step"] == 0.0  # exact medians: not one bit moves
    centres = km.cluster_centers_.numpy().astype(np.float64)
    want = np.argmin(np.abs(x[:, None, :] - centres[None]).sum(-1), axis=1)
    assert np.array_equal(km.predict(ht.array(x, split=0)).numpy(), want)
    # the reference's own fit, in float32, by its own judge
    own = plain.fit(jnp.asarray(x), len(start), SWEEPS, jax.random.key(3), jnp.float32, block=7)
    numbers = plain.judge(jnp.asarray(x), own, 5, SWEEPS, block=7)
    assert all(numbers[n] <= LIMITS[n] for n in LIMITS), numbers


# --------------------------------------------------------------------- #
# what the cell's data cannot show                                       #
# --------------------------------------------------------------------- #
def _where_euclid_differs():
    """Rows at (3, 3, 0...) between a centre at the origin and one at
    (7.5, 3, 0...): 6 against 4.5 by the L1 norm, 4.24 against 4.5 by the
    Euclidean.  A little noise, so that the medians have something to do."""
    rng = np.random.default_rng(6)
    f = 8
    a = np.zeros(f, np.float32)
    b = np.zeros(f, np.float32)
    b[:2] = (7.5, 3.0)
    between = np.zeros((12, f), np.float32)
    between[:, :2] = 3.0
    x = np.concatenate([np.tile(a, (10, 1)), np.tile(b, (10, 1)), between])
    x = (x + 0.01 * rng.standard_normal(x.shape)).astype(np.float32)
    return x, np.stack([a, b])


def _where_bfloat16_sums_differ():
    """Two centres a part in 500 apart in L1 distance from every row, over 512
    features: float32 sums tell them apart, sums held in bfloat16 (8 bits) do
    not."""
    rng = np.random.default_rng(7)
    f = 512
    x = rng.standard_normal((24, f)).astype(np.float32)
    a = np.full(f, 4.0, np.float32)
    b = np.full(f, 4.0, np.float32) + np.float32(0.008) * np.where(np.arange(f) % 2 == 0, 1.0, 1.0).astype(np.float32)
    return x, np.stack([b, a])  # the second centre is the nearer of every row


@pytest.mark.parametrize("fault,data", [
    ("euclidean_assignment", _where_euclid_differs),
    ("l1_sums_in_bfloat16", _where_bfloat16_sums_differ),
])
def test_a_fault_the_cell_cannot_see_fails_the_comparison_here(plain, probe, fault, data):
    """No sweep, so that the labels are the assignment to the given centres
    (a sweep would move a centre onto the rows it was wrongly given): the
    served label's centre is then not the nearest by the reference's L1 sums."""
    x, start = data()

    def label_gap():
        km = ht.cluster.KMedians(n_clusters=2, init=ht.array(start), max_iter=0, tol=-1.0)
        km.fit(ht.array(x, split=0))
        out = {"centres": km.cluster_centers_.larray, "labels": km.labels_.larray, "n_iter": km.n_iter_}
        numbers = plain.judge(jnp.asarray(x), out, 5, 0, block=256)
        assert numbers["iters_off"] == 0.0
        return numbers["label_gap"]

    assert label_gap() == 0.0
    with probe.UNSEEN[fault]():
        assert label_gap() > LIMITS["label_gap"]
    assert label_gap() == 0.0  # mended on the way out


@pytest.mark.parametrize("fault", ["mean_for_median", "lower_middle_alone", "one_row_left_out"])
def test_a_fault_of_the_medians_fails_the_comparison(plain, probe, fault):
    x, start = _odd_and_even()
    with probe.FAULTS[fault][0]():
        km = ht.cluster.KMedians(n_clusters=len(start), init=ht.array(start), max_iter=SWEEPS, tol=-1.0)
        km.fit(ht.array(x, split=0))
        out = {"centres": km.cluster_centers_.larray, "labels": km.labels_.larray, "n_iter": km.n_iter_}
    got = plain.judge(jnp.asarray(x), out, 5, SWEEPS)
    assert got[probe.FAULTS[fault][1]] > LIMITS[probe.FAULTS[fault][1]], got


# --------------------------------------------------------------------- #
# the kernel, in the Pallas interpreter                                  #
# --------------------------------------------------------------------- #
#: (rows, columns, clusters, slab): no row group whole, one short of a group,
#: one group, one over, the cell's 37 groups and 4 rows; columns that fill
#: their tiles, leave a ragged last one, fit one tile alone
SIZES = [
    (1, 1024, 1, 128), (7, 1024, 3, 128), (8, 2048, 2, 256), (9, 1300, 4, 128),
    (300, 1024, 8, 128), (37, 2500, 5, 128), (64, 3000, 8, 256),
]


#: ``_NETWORK_MAX`` as the interpreter runs it: every cluster of two or more on
#: the counting passes, clusters on both sides of a small threshold in one
#: kernel, and the module's own (the cell's blobs, 37 and 38 rows, by the
#: network)
ARMS = {"counting": 1, "both": 5, "as_it_stands": None}


def _kernel_medians(x, labels, k, slab, network_max=None):
    """The kernel in the interpreter; ``network_max`` stands for the module's
    ``_NETWORK_MAX`` in a program of its own (the constant is read when the
    kernel is traced, so the module's cached program is passed by)."""
    with pytest.MonkeyPatch.context() as patch:
        if network_max is not None:
            patch.setattr(_colmedian, "_NETWORK_MAX", network_max)
        run = jax.jit(lambda a, lab: _colmedian.group_medians.__wrapped__(a, lab, k, interpret=True, slab=slab))
        med, counts = run(jnp.asarray(x), jnp.asarray(labels))
    return np.asarray(med), np.asarray(counts)


def _assert_numpys_medians(x, labels, k, med, counts):
    assert med.shape == (k, x.shape[1]) and med.dtype == np.float32
    assert np.array_equal(counts, np.bincount(labels, minlength=k))
    for c in range(k):
        if counts[c]:
            want = _medians_nan_last(x[labels == c])
            assert np.array_equal(med[c], want, equal_nan=True), (c, counts[c], np.abs(med[c] - want).max())
            if not np.isnan(x[labels == c]).any():
                with np.errstate(invalid="ignore"):
                    assert np.array_equal(want, np.median(x[labels == c], axis=0), equal_nan=True)


@pytest.mark.parametrize("arm", sorted(ARMS))
@pytest.mark.parametrize("rows,cols,k,slab", SIZES)
def test_the_kernel_is_numpys_median_of_every_cluster_bit_for_bit(rows, cols, k, slab, arm):
    rng = np.random.default_rng(rows * cols)
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    x[:, : cols // 4] = np.round(x[:, : cols // 4])  # columns full of ties
    x[:, cols // 4] = np.float32(1e30)  # a constant column, far out
    labels = rng.integers(0, k, size=rows).astype(np.int32)
    med, counts = _kernel_medians(x, labels, k, slab, ARMS[arm])
    _assert_numpys_medians(x, labels, k, med, counts)


#: members of each cluster, in label order: none, one, two, three, the cell's
#: 37 and 38; every row in one cluster; rows short of and over a row group
MEMBERS = {
    "0_1_2_3_37_38": (0, 1, 2, 3, 37, 38),
    "all_rows_in_the_last": (0, 0, 45),
    "all_rows_in_the_first": (300, 0),
    "38_37_0_2": (38, 37, 0, 2),
}


@pytest.mark.parametrize("network_max", [1, 2, 37, None])
@pytest.mark.parametrize("name", sorted(MEMBERS))
def test_the_kernel_on_either_side_of_the_networks_threshold(name, network_max):
    """Clusters of the sizes the cell sees, each by the method its member
    count takes at this threshold (at 37: 37 members by the network, 38 by
    counting, in one kernel; ``None``: the module's own), on columns with
    duplicates across the middle, NaN and infinite members."""
    sizes = MEMBERS[name]
    rng = np.random.default_rng(sum(sizes))
    labels = rng.permutation(np.repeat(np.arange(len(sizes)), sizes)).astype(np.int32)
    rows, cols = len(labels), 1024
    x = rng.standard_normal((rows, cols)).astype(np.float32)
    x[:, :256] = np.round(x[:, :256])  # ties across the middle
    x[:, 256:320] = np.float32(7.0)  # every member the same
    nan = rng.random((rows, 192)) < 0.3
    x[:, 320:512][nan] = np.nan  # some middles among NaN members, some not
    x[:, 512:640][rng.random((rows, 128)) < 0.4] = np.inf
    x[:, 512:640][rng.random((rows, 128)) < 0.4] = -np.inf
    med, counts = _kernel_medians(x, labels, len(sizes), 128, network_max)
    assert counts.tolist() == list(sizes)
    assert int(_colmedian.by_network(jnp.asarray(counts))) == sum(0 < m <= _colmedian._NETWORK_MAX for m in sizes)
    _assert_numpys_medians(x, labels, len(sizes), med, counts)


@pytest.mark.parametrize("members", range(1, 11))
def test_the_network_passes_the_zero_one_principle_through_the_kernel(members):
    """A comparison network sorts every input if it sorts every input of
    zeros and ones: all ``2 ** members`` of them as the columns of one
    operand, one cluster, its two middles by the network made for that many
    members, and by the module's own with the members it lacks filled in."""
    cols = 1024
    bits = (np.arange(cols)[None, :] >> np.arange(members)[:, None]) & 1  # column j: the bits of j
    x = bits.astype(np.float32)
    for network_max in (members, None):
        med, counts = _kernel_medians(x, np.zeros(members, np.int32), 1, 128, network_max)
        assert counts.tolist() == [members]
        assert np.array_equal(med[0], np.median(x, axis=0))


def _through(exchanges, x):
    """The rows of ``x`` after the compare-exchanges ``(i, j)``, ``i < j``."""
    y = x.copy()
    for i, j in exchanges:
        assert 0 <= i < j < len(x)
        y[i], y[j] = np.minimum(y[i], y[j]), np.maximum(y[i], y[j])
    return y


@pytest.mark.parametrize("n", [1, 2, 3, 5, 8, 12, 16])
def test_the_selection_keeps_the_exchanges_the_lower_half_hears_of(n):
    """The list the kernel unrolls, by the zero-one principle in numpy: merge
    exchange sorts all ``2 ** n`` inputs, and the pruned list leaves the
    lower half's outputs (and the one above) as the whole list does."""
    x = ((np.arange(1 << n)[None, :] >> np.arange(n)[:, None]) & 1).astype(np.int8)
    whole, outputs = _colmedian._merge_exchange(n), range(n // 2 + 1)
    kept = _colmedian._selection(n, outputs)
    assert np.array_equal(_through(whole, x), np.sort(x, axis=0))
    assert len(kept) <= len(whole)
    assert np.array_equal(_through(kept, x)[: n // 2 + 1], np.sort(x, axis=0)[: n // 2 + 1])


def test_the_modules_own_selection_on_random_members_and_its_size():
    """``_NETWORK_MAX`` items are too many for every input of zeros and ones:
    random thresholds of random orders instead, every member count up to it
    (the missing ones filled with ones, as the kernel fills them with the
    largest key), and the count of exchanges the comment of the constant
    gives."""
    n = _colmedian._NETWORK_MAX
    kept = _colmedian._selection(n, range(n // 2 + 1))
    assert (n, len(_colmedian._merge_exchange(n)), len(kept)) == (40, 283, 258)
    rng = np.random.default_rng(40)
    for m in range(1, n + 1):
        order = rng.permuted(np.tile(np.arange(m)[:, None], (1, 64)), axis=0)
        x = np.ones((n, 64 * m), np.int8)
        x[:m] = np.concatenate([order >= t for t in range(m)], axis=1)  # each order cut at every rank
        y, want = _through(kept, x), np.sort(x[:m], axis=0)
        assert np.array_equal(y[(m - 1) // 2], want[(m - 1) // 2]) and np.array_equal(y[m // 2], want[m // 2]), m


@pytest.mark.parametrize("rank", [1, 2, 10, 19, 20])
def test_the_network_leaves_every_rank_of_the_lower_half_in_its_place(rank, monkeypatch):
    """Not only the middle: the ``rank``-th of a cluster of 37 asked for in
    the middles' place, among 45 rows (a probe plants its faults so)."""
    monkeypatch.setattr(_colmedian, "_middle_ranks", lambda m: (jnp.minimum(rank, m), jnp.minimum(rank, m)))
    rng = np.random.default_rng(rank)
    x = np.round(3 * rng.standard_normal((45, 1024))).astype(np.float32)
    labels = rng.permutation(np.repeat([0, 1], [8, 37])).astype(np.int32)
    med, _ = _kernel_medians(x, labels, 2, 128)
    assert np.array_equal(med[1], np.sort(x[labels == 1], axis=0)[rank - 1])
    assert np.array_equal(med[0], np.sort(x[labels == 0], axis=0)[min(rank, 8) - 1])


def test_the_kernel_sorts_nan_last_keeps_inf_in_order_and_leaves_an_empty_cluster_alone():
    rng = np.random.default_rng(8)
    x = rng.standard_normal((21, 1024)).astype(np.float32)
    labels = (np.arange(21) % 2).astype(np.int32)  # 11 and 10 members; cluster 2 empty
    x[0, 0] = np.nan  # one NaN among 11: the middle is a number
    x[0:12:2, 1] = np.nan  # six NaN among 11: the middle is among them
    x[1, 2], x[3, 2] = np.inf, -np.inf
    x[1:21:2, 3] = -np.nan  # every member of cluster 1 a NaN of the other sign
    med, counts = _kernel_medians(x, labels, 3, 128)
    assert counts.tolist() == [11, 10, 0]
    for c in range(2):
        want = _medians_nan_last(x[labels == c])
        assert np.array_equal(med[c], want, equal_nan=True)
    assert np.isfinite(med[0, 0]) and np.isnan(med[0, 1]) and np.isnan(med[1, 3])
    assert np.isfinite(med[1, 2])


def test_the_slab_fits_the_stated_vmem_and_the_operand():
    assert _colmedian._slab(300, 6_291_456) == 1024  # the cell: 10 MB a tile, three under the limit
    assert 3 * 304 * 8 * 1024 * 4 < _colmedian._VMEM_LIMIT
    assert _colmedian._slab(300, 5000) == 512 and _colmedian._slab(300, 1024) == 128  # no wider than the operand
    assert _colmedian._slab(300, 1000) == 0  # narrower than the narrowest tile: not taken
    assert _colmedian._slab(600, 1 << 20) == 512 and _colmedian._slab(_colmedian.MAX_ROWS, 1 << 20) == 128
    for rows in (1, 300, 1000, _colmedian.MAX_ROWS):
        slab = _colmedian._slab(rows, 1 << 24)
        assert slab % 128 == 0 and 3 * -(-rows // 8) * 8 * 8 * slab * 4 <= _colmedian._VMEM_LIMIT


def test_the_route_predicate_at_its_edges(one_tpu, monkeypatch):
    wide = jax.ShapeDtypeStruct((300, 1 << 20), jnp.float32)
    assert _colmedian.conforms(wide, 8) and kmedians._medians_route(wide, 8) == "column_select"
    assert _colmedian.conforms(jax.ShapeDtypeStruct((300, -(-_colmedian.MIN_BYTES // 1200)), jnp.float32), 8)
    for other in (
        jax.ShapeDtypeStruct((300, 1 << 20), jnp.float64),
        jax.ShapeDtypeStruct((300, 1 << 20), jnp.bfloat16),
        jax.ShapeDtypeStruct((300, _colmedian.MIN_BYTES // 1200), jnp.float32),  # a column short
        jax.ShapeDtypeStruct((_colmedian.MAX_ROWS + 1, 1 << 16), jnp.float32),  # tall, not wide
        jax.ShapeDtypeStruct((4, 300, 1 << 16), jnp.float32),
    ):
        assert not _colmedian.conforms(other, 8), other
        assert kmedians._medians_route(other, 8) == "rank_bisection"
    monkeypatch.setattr(jax, "device_count", lambda: 4)  # a sharded operand must never meet the kernel
    assert not _colmedian.conforms(wide, 8)
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert not _colmedian.conforms(wide, 8)


def test_import_heat_tpu_does_not_bring_the_kernels_module():
    """The kernel's module comes with the first fit (``_medians_route``), so
    that ``import heat_tpu``, the largest stage of every cell's ``setup_s``,
    imports what it imported before."""
    import subprocess

    code = "import sys, heat_tpu; sys.exit('heat_tpu.core._colmedian' in sys.modules)"
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    assert subprocess.run([sys.executable, "-c", code], env=env, timeout=300).returncode == 0


@pytest.fixture
def interpreted_route(one_tpu, monkeypatch):
    """A process that drives one TPU, the kernel answered by the interpreter
    and asked no least size: ``KMedians`` takes ``column_select`` here."""
    monkeypatch.setattr(_colmedian, "_interpret", lambda: True)
    monkeypatch.setattr(_colmedian, "MIN_BYTES", 0)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_the_kernel_route_through_the_estimator_is_the_bisections_fit(interpreted_route, tel):
    x, centres = _blobs(9, (12, 13, 8, 11), 1100)  # one tile of 1024 columns and a ragged one
    start = centres + np.float32(0.5)
    comm = XlaCommunication(jax.devices()[:1])

    def fit():
        km = ht.cluster.KMedians(n_clusters=4, init=ht.array(start, comm=comm), max_iter=3, tol=-1.0)
        km.fit(ht.array(x, split=0, comm=comm))
        return km.cluster_centers_.numpy(), km.labels_.numpy(), km.n_iter_, km.selections_by_network_

    got = fit()
    (span,) = [e for e in tel.events() if e.get("site") == "jit:kmedians.fit"]
    assert span["kind"] == "launch"
    assert (span["medians"], span["assign"], span["sweeps"], span["x_passes"]) == ("column_select", "manhattan", 3, 7)
    assert span["network_max"] == _colmedian._NETWORK_MAX >= 13
    want_centres, want_labels = _numpy_fit(x, start, 3)
    assert np.array_equal(got[0], want_centres) and np.array_equal(got[1], want_labels) and got[2] == 3
    assert got[3] == 3 * 4  # every sweep's four clusters have members, few enough for the network


# --------------------------------------------------------------------- #
# the record, and ht.spatial.manhattan's wide sum                        #
# --------------------------------------------------------------------- #
def test_the_fit_is_one_launch_with_its_fields_and_one_sync(tel):
    x, start = _odd_and_even()
    km = ht.cluster.KMedians(n_clusters=len(start), init=ht.array(start), max_iter=SWEEPS, tol=-1.0)
    data = ht.array(x, split=0)
    with telemetry.counting_dispatches() as count:
        km.fit(data)
    assert km.n_iter_ == SWEEPS
    events = tel.events()
    (span,) = [e for e in events if e.get("site") == "jit:kmedians.fit"]
    assert span["kind"] == "launch"
    assert (span["medians"], span["assign"], span["sweeps"]) == ("rank_bisection", "manhattan", SWEEPS)
    assert "x_passes" not in span  # the bisection's reads depend on the data: no count is stated
    assert "network_max" not in span  # the kernel's field: the interpreted route's test holds it
    assert sum(1 for e in events if e.get("site") == "sync:kcluster.n_iter") == 1
    # the selections by the network are read only when asked: a sync of their own, once
    assert not [e for e in events if e.get("site") == "sync:kmedians.selections"]
    syncs = telemetry.host_sync_count()
    assert km.selections_by_network_ == 0 == km.selections_by_network_  # the bisection makes none
    assert telemetry.host_sync_count() == syncs + 1
    assert sum(1 for e in tel.events() if e.get("site") == "sync:kmedians.selections") == 1
    (entry,) = [e for e in events if e.get("site") == "fit:KMedians"]
    assert entry["kind"] == "entry"
    assert count.count >= 1  # the fit's one program, and what lays the given start out


@pytest.mark.parametrize("rows_y,form", [(5, "rows"), (16, "rows"), (17, "reduce"), (None, "reduce")])
@pytest.mark.parametrize("split", [None, 0])
def test_manhattan_on_a_wide_operand_takes_the_row_order_and_says_so(tel, split, rows_y, form):
    rng = np.random.default_rng(10)
    x = rng.standard_normal((20, 300)).astype(np.float32)
    y = None if rows_y is None else rng.standard_normal((rows_y, 300)).astype(np.float32)
    d = ht.spatial.manhattan(ht.array(x, split=split), None if y is None else ht.array(y))
    other = x if y is None else y
    want = np.abs(x[:, None, :].astype(np.float64) - other[None]).sum(-1)
    np.testing.assert_allclose(d.numpy(), want, rtol=2e-6)
    assert d.split == split
    span = [e for e in tel.events() if e.get("site") == "jitted:dist.manhattan"][-1]
    assert span["form"] == form == distance._form(300, min(20, len(other)))


def test_the_three_loop_orders_agree_whichever_operand_is_the_smaller():
    rng = np.random.default_rng(11)
    few = jnp.asarray(rng.standard_normal((3, 200)).astype(np.float32))
    many = jnp.asarray(rng.standard_normal((40, 200)).astype(np.float32))
    for a, b in ((few, many), (many, few)):
        assert distance._form(200, 3) == "rows"
        rows = distance._pairwise_sum(a, b, jnp.abs)
        want = jnp.sum(jnp.abs(a[:, None, :] - b[None, :, :]), axis=-1)
        assert rows.shape == want.shape
        np.testing.assert_allclose(np.asarray(rows), np.asarray(want), rtol=2e-6)
    assert distance._form(64, 3) == "unrolled" and distance._form(65, 17) == "reduce" == distance._form(65)
    assert distance._form(65, 0) == "reduce"
