"""``ht.mean`` / ``ht.std`` / ``ht.var`` along axis 0 against the benchmark's
plain reference and numpy float64, and the record they leave.

The reference is the benchmark's (``perf/references/moments_plain.py``:
``jax.numpy`` by blocks of columns, nothing imported from the program);
``moments_300_c1`` holds the chip's results to it at 300 x 6 291 456, this
file holds small ones to it on the CPU mesh by the same four numbers.  Beside
it: the variance is ``jnp.var``'s own arithmetic, bit for bit, with a scope
on each of its two reads (everywhere but on a wide float32 matrix on one TPU,
whose one-read kernel ``tests/test_colvar.py`` holds to the same numbers);
the three entries and their launches are spans
while something records, with the fields the benchmark's
``operand_reads_per_job`` reads, and nothing inside an ``ht.fuse`` trace.
(``tests/test_tpu_compile.py`` holds the ``reads`` field to the program
compiled for the chip.)
"""

import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.comm import compressed as cq
from heat_tpu.core import statistics

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")

#: float32 sums of a few hundred terms in another order, with room (the
#: cell's own limits are set from readings on the chip: PERF.md section 2)
LIMIT = 2e-5


@pytest.fixture(scope="module")
def plain():
    """``perf/`` is no package of the program's and is not put on
    ``sys.path`` for the whole suite: the module by its file."""
    path = os.path.join(PERF, "references", "moments_plain.py")
    spec = importlib.util.spec_from_file_location("moments_plain", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _blobs(seed: int, rows: int, features: int) -> np.ndarray:
    """The cell's stand-in data at test size: row i is centre i % 8 (scale
    10) plus unit noise, as ``perf/datagen.py`` makes it."""
    rng = np.random.default_rng(seed)
    centres = 10.0 * rng.standard_normal((8, features))
    return (centres[np.arange(rows) % 8] + rng.standard_normal((rows, features))).astype(np.float32)


def _far_mean(seed: int, rows: int, features: int) -> np.ndarray:
    """Every column's mean a thousand times its deviation: where the raw form
    ``E[x**2] - mean**2`` has cancelled every digit of a float32."""
    rng = np.random.default_rng(seed)
    return (1000.0 + rng.standard_normal((rows, features))).astype(np.float32)


#: name -> (maker, rows, features, room of the mean's numbers); 301 and 37
#: rows divide no mesh of 2, 4 or 8.  A float32 mean of 1000 is rounded to
#: 6e-5, and the numbers count in deviations (1 there): a few roundings of
#: the sum of 300 such terms are 2e-4 of one
SHAPES = {
    "cell_rows": (_blobs, 300, 512, 1),
    "ragged_rows": (_blobs, 301, 96, 1),
    "few_rows": (_blobs, 37, 1000, 1),
    "far_mean": (_far_mean, 300, 64, 50),
}


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_mean_and_std_agree_with_the_plain_reference_and_float64(plain, shape, split):
    make, rows, features, room = SHAPES[shape]
    limits = {n: LIMIT * (room if n.startswith("mean") else 1) for n in plain.NUMBERS}
    host = make(31, rows, features)
    X = ht.array(host, split=split)
    m, s, v = ht.mean(X, axis=0), ht.std(X, axis=0), ht.var(X, axis=0)
    assert m.gshape == s.gshape == v.gshape == (features,)
    assert m.split is s.split is v.split is None and m.dtype is s.dtype is ht.float32
    numbers = plain.judge(jnp.asarray(host), {"mean": m.larray, "std": s.larray}, seed=3, block=200)
    assert set(numbers) == set(plain.NUMBERS)
    assert all(numbers[n] <= limits[n] for n in limits), numbers
    h64 = host.astype(np.float64)
    np.testing.assert_allclose(v.numpy(), h64.var(0), rtol=4e-5)
    np.testing.assert_allclose(ht.std(X, axis=0, ddof=1).numpy(), h64.std(0, ddof=1), rtol=2e-5)
    # the reference itself, in float32, is as close to float64
    own = plain.moments(jnp.asarray(host), jnp.float32, block=200)
    numbers = plain.judge(jnp.asarray(host), own, seed=3)
    assert all(numbers[n] <= limits[n] for n in limits), numbers


def test_the_judge_fails_what_the_cell_must_fail(plain):
    """On the cell's data at test size: the bfloat16 control, another
    ``ddof``, a row left out, the variance for the deviation, and, on a far
    mean, the raw form."""
    host = _blobs(32, 300, 512)
    x = jnp.asarray(host)
    X = ht.array(host, split=0)
    sound = {"mean": ht.mean(X, axis=0).larray, "std": ht.std(X, axis=0).larray}
    control = plain.judge(x, plain.moments(x, jnp.bfloat16), seed=4)
    assert min(control.values()) > 10 * LIMIT, control
    ddof = plain.judge(x, dict(sound, std=ht.std(X, axis=0, ddof=1).larray), seed=4)
    assert ddof["std_rel_all"] == pytest.approx(np.sqrt(300 / 299) - 1, rel=1e-2) and ddof["mean_err_all"] <= LIMIT
    short = ht.array(host[:-1], split=0)
    left = plain.judge(x, {"mean": ht.mean(short, axis=0).larray, "std": ht.std(short, axis=0).larray}, seed=4)
    assert left["mean_err_all"] > 100 * LIMIT, left
    as_var = plain.judge(x, dict(sound, std=ht.var(X, axis=0).larray), seed=4)
    assert as_var["std_rel_all"] > 1.0, as_var
    far = jnp.asarray(_far_mean(33, 300, 64))
    raw = jnp.sqrt(jnp.maximum(jnp.mean(far * far, axis=0) - jnp.mean(far, axis=0) ** 2, 0.0))
    cancelled = plain.judge(far, {"mean": jnp.mean(far, axis=0), "std": raw}, seed=4)
    assert cancelled["std_rel_all"] > 100 * LIMIT and cancelled["mean_err_all"] <= 50 * LIMIT, cancelled


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int32])
@pytest.mark.parametrize("axis", [None, 0, 1, (0, 1)])
@pytest.mark.parametrize("ddof", [0, 1])
def test_the_variance_is_jnp_vars_own_bit_for_bit(dtype, axis, ddof):
    """``statistics._var`` in its two-pass form makes ``jnp.var``'s mean apart
    (for the scope on each read) and hands it back: the same operations in the
    same order."""
    host = np.random.default_rng(5).standard_normal((37, 24)) * 3.0 + 50.0
    a = jnp.asarray(host).astype(dtype)
    want = jax.jit(lambda v: jnp.var(v.astype(jnp.float32) if dtype is jnp.int32 else v, axis=axis, ddof=ddof))(a)
    for split in (None, 0):
        got = ht.var(ht.array(a, split=split), axis=axis, ddof=ddof).larray
        assert got.dtype == want.dtype
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))
    one = ht.var(ht.array(a[:1], split=None), axis=0, ddof=1).numpy()  # n - ddof = 0
    assert np.isnan(np.asarray(one, np.float32)).all()


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


def _spans(tel):
    return [e for e in tel.events() if e.get("type") == "span"]


#: public entry -> (its entry span, its launch span, the reads its program
#: makes on this mesh, the form the launch names: ``two_pass`` wherever the
#: one-read kernel does not conform, ``tests/test_colvar.py`` has the other)
ENTRIES = {
    "mean": ("stat:mean", "jitted:stat.mean", 1, None),
    "var": ("stat:var", "jitted:stat.moment2", 2, "two_pass"),
    "std": ("stat:std", "jitted:stat.moment2", 2, "two_pass"),
}


@pytest.mark.parametrize("split", [None, 0])
@pytest.mark.parametrize("name", sorted(ENTRIES))
def test_an_entry_and_its_launch_are_recorded_with_their_fields(tel, name, split):
    entry_site, launch_site, reads, form = ENTRIES[name]
    X = ht.array(_blobs(6, 48, 16), split=split)
    for axis in (0, (0, 1)):
        tel.reset()
        getattr(ht, name)(X, axis=axis)
        spans = _spans(tel)
        (entry,) = [e for e in spans if e["kind"] == "entry"]
        (launch,) = [e for e in spans if e["kind"] == "launch"]
        assert entry["site"] == entry_site and entry["launches"] == 1 and entry["syncs"] == 0
        assert launch["site"] == launch_site and launch["parent"] == entry["id"]
        assert (launch["reads"], launch["route"], launch["axis"]) == (reads, "exact", axis)
        assert launch.get("form") == form


def test_nothing_is_recorded_inside_a_fuse_trace_or_when_nothing_records(tel):
    X = ht.array(_blobs(7, 48, 16), split=0)
    fused = ht.fuse(lambda x: ht.std(x, axis=0) + ht.mean(x, axis=0) + ht.var(x, axis=0))(X)
    sites = {e["site"] for e in _spans(tel)}
    assert not sites & {"stat:mean", "stat:std", "stat:var", "jitted:stat.mean", "jitted:stat.moment2"}, sites
    want = ht.std(X, axis=0) + ht.mean(X, axis=0) + ht.var(X, axis=0)
    np.testing.assert_allclose(fused.numpy(), want.numpy(), rtol=1e-6)
    tel.disable()
    tel.reset()
    ht.mean(X, axis=0), ht.std(X, axis=0)
    assert not _spans(tel)
    tel.enable()


def test_the_compressed_route_says_so_on_its_own_launch(tel):
    """Where the collective-precision policy takes the quantized ring, the
    program launched is the ring's (``jitted:commq.*``): it carries the route
    and the axes, and there is no ``jitted:stat.*`` launch to count reads on."""
    X = ht.array(_far_mean(8, 64, 7), split=0)
    if X.comm.size < 2:
        pytest.skip("one device: no ring")
    with cq.collective_precision("int8_block"):
        ht.mean(X, axis=0), ht.std(X, axis=0)
    spans = _spans(tel)
    launches = {e["site"]: e for e in spans if e["kind"] == "launch"}
    assert set(launches) == {"jitted:commq.reduce", "jitted:commq.moments"}, set(launches)
    for launch in launches.values():
        assert (launch["route"], launch["axis"]) == ("compressed", (0,)) and "reads" not in launch
    assert [e["site"] for e in spans if e["kind"] == "entry"] == ["stat:mean", "stat:std"]


def test_the_scopes_reach_the_lowered_programs():
    x = jnp.zeros((16, 8), jnp.float32)
    mean = jax.jit(lambda a: statistics._mean(a, 0, False)).lower(x).as_text(debug_info=True)
    var = jax.jit(lambda a: statistics._var(a, 0, 0, False)).lower(x).as_text(debug_info=True)
    assert "stat.mean" in mean
    assert "stat.var.mean" in var and "stat.var.centred" in var
