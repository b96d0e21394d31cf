"""heat_tpu.fuse: whole-program compilation over DNDarrays.

Covers the PR-3 acceptance criteria directly:

- a ≥5-op pipeline under ``ht.fuse`` issues EXACTLY one device dispatch
  and is bitwise-identical to eager execution on the 8-device mesh for
  split in {None, 0, 1}, including ragged split axes;
- eager-vs-fused parity sweeps across op families (arithmetics,
  relational, statistics, manipulations);
- cache behavior: one compile per (fn, treedef, avals, splits, comm)
  signature, a recompile on shape/split change, transient compiles for
  identity-unstable functions (lambdas);
- the tracing-mode error contract: value-forcing operations raise
  ``FuseTraceError`` with an actionable message instead of silently
  freezing trace-time constants.

Parity notes (docs/design.md "Fused vs eager numerics"): eager ops pass
scalars into their jitted programs as ARGUMENTS, while under ``fuse``
they are trace-time constants — XLA may strength-reduce a constant
divide (``x / 3.0`` → reciprocal multiply), so chains with
non-power-of-two constant mul/div are compared with a 1-ULP-tight
allclose, and the bitwise assertions stick to exact-safe ops
(add/sub/abs/sqrt/min/max/relational and power-of-two scalars).
"""

import numpy as np
import pytest

import heat_tpu as ht
from heat_tpu.core import _tracing
from heat_tpu.core.fuse import fuse

from suite import assert_array_equal


SPLITS = [None, 0, 1]
SHAPES = [(4, 6), (7, 5)]  # even and ragged on the 8-device mesh


def _pair(shape, split, seed=0):
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape).astype(np.float32)
    b = (rng.standard_normal(shape) ** 2 + 0.5).astype(np.float32)
    return ht.array(a, split=split), ht.array(b, split=split)


def _dispatches(fn, *args):
    """Dispatch count of one ``fn(*args)`` call, after a warmup call
    (compilation itself is not a steady-state dispatch)."""
    fn(*args)
    _tracing.reset_dispatch_count()
    out = fn(*args)
    return _tracing.dispatch_count(), out


# --------------------------------------------------------------------- #
# the acceptance pipeline: >= 5 ops, one dispatch, bitwise parity        #
# --------------------------------------------------------------------- #
def _pipeline(a, b):
    c = a + b
    d = c - a
    e = ht.abs(d)
    f = ht.sqrt(e)
    return ht.minimum(f + c, b * 2.0)  # power-of-two scalar: exact


_fused_pipeline = fuse(_pipeline)


@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("split", SPLITS)
def test_acceptance_pipeline_bitwise_and_single_dispatch(shape, split):
    a, b = _pair(shape, split)
    eager = _pipeline(a, b)
    n, fused = _dispatches(_fused_pipeline, a, b)
    assert n == 1, f"fused 5-op pipeline issued {n} dispatches, wanted exactly 1"
    assert fused.split == eager.split == split
    assert fused.gshape == eager.gshape
    assert fused.dtype == eager.dtype
    ev, fv = eager.numpy(), fused.numpy()
    assert ev.dtype == fv.dtype
    assert np.array_equal(ev, fv), "fused result is not bitwise-identical to eager"


def test_eager_pipeline_issues_many_dispatches():
    a, b = _pair((4, 6), 0)
    _pipeline(a, b)  # warm the per-op jit caches
    _tracing.reset_dispatch_count()
    _pipeline(a, b)
    assert _tracing.dispatch_count() >= 5


# --------------------------------------------------------------------- #
# parity sweeps across op families                                      #
# --------------------------------------------------------------------- #
def _arith(a, b):
    return (a * b + a) / b - ht.exp(-ht.abs(a))


def _relational(a, b):
    gt = a > b
    eq = (a - a) == 0.0
    return ht.where(gt, a, b), gt & eq


def _stats(a, b):
    m = ht.mean(a, axis=0)
    s = ht.std(b, axis=1)
    return ht.sum(a * a, axis=1) + ht.max(b), m, s


def _manip(a, b):
    t = ht.transpose(a)
    c = ht.concatenate([a, b], axis=0)
    return t @ c[: a.shape[0]], ht.reshape(c, (-1,))


@pytest.mark.parametrize("family", [_arith, _relational, _stats, _manip])
@pytest.mark.parametrize("shape", SHAPES)
@pytest.mark.parametrize("split", SPLITS)
def test_fused_matches_eager_across_families(family, shape, split):
    a, b = _pair(shape, split, seed=3)
    eager = family(a, b)
    fused = fuse(family)(a, b)
    eager = eager if isinstance(eager, tuple) else (eager,)
    fused = fused if isinstance(fused, tuple) else (fused,)
    for e, f in zip(eager, fused):
        assert f.gshape == e.gshape
        assert f.split == e.split
        assert f.dtype == e.dtype
        # constant-folding caveat: const mul/div chains may differ by ~1 ULP
        np.testing.assert_allclose(f.numpy(), e.numpy(), rtol=3e-7, atol=1e-7)


def test_fused_scalar_and_static_outputs():
    @fuse
    def prog(a, k):
        return a * k, k, "tag"

    a, _ = _pair((4, 6), 0)
    out, k, tag = prog(a, 3)
    assert k == 3 and tag == "tag"
    np.testing.assert_allclose(out.numpy(), (a * 3).numpy(), rtol=3e-7)


# --------------------------------------------------------------------- #
# cache behavior                                                        #
# --------------------------------------------------------------------- #
def _cached_prog(a, b):
    return ht.sqrt(ht.abs(a - b)) + a


def test_cache_one_entry_per_signature():
    fuse.clear_cache()
    fused = fuse(_cached_prog)
    a, b = _pair((4, 6), 0)
    fused(a, b)
    assert fuse.cache_size() == 1
    fused(a, b)
    fused(a, b)
    assert fuse.cache_size() == 1, "repeat calls with the same signature must hit"

    # changed split: new program
    a1, b1 = _pair((4, 6), 1)
    fused(a1, b1)
    assert fuse.cache_size() == 2

    # changed global shape: new program
    a2, b2 = _pair((7, 5), 0)
    fused(a2, b2)
    assert fuse.cache_size() == 3
    fused(a2, b2)
    assert fuse.cache_size() == 3


def test_unstable_fn_compiles_transiently():
    fuse.clear_cache()
    a, b = _pair((4, 6), 0)
    out = fuse(lambda x, y: x + y)(a, b)  # fresh identity: must still work...
    assert_array_equal(out, a.numpy() + b.numpy())
    assert fuse.cache_size() == 0, "identity-unstable functions must not grow the cache"


def test_unstable_static_argument_compiles_transiently():
    fuse.clear_cache()

    def prog(x, f):
        return f(x)

    a, _ = _pair((4, 6), 0)
    out = fuse(prog)(a, lambda x: x * 2.0)
    np.testing.assert_allclose(out.numpy(), (a * 2.0).numpy())
    assert fuse.cache_size() == 0


# --------------------------------------------------------------------- #
# tracing-mode error contract                                           #
# --------------------------------------------------------------------- #
def test_value_forcing_raises_fuse_trace_error():
    a, _ = _pair((4, 6), 0)

    @fuse
    def syncs_scalar(x):
        return x * float(x.sum())

    @fuse
    def syncs_item(x):
        return x * x.sum().item()

    @fuse
    def syncs_print(x):
        print(x)
        return x

    for bad, what in [(syncs_scalar, "float()"), (syncs_item, ".item()"),
                      (syncs_print, "print()")]:
        with pytest.raises(ht.FuseTraceError) as err:
            bad(a)
        msg = str(err.value)
        assert what in msg
        assert "on-device" in msg, "the error must point at the fix"


def test_trace_context_manager_enforces_same_contract():
    a, _ = _pair((4, 6), 0)
    with fuse.trace():
        b = a + 1.0  # ops still work under the context manager
        with pytest.raises(ht.FuseTraceError):
            float(b.sum())
        with pytest.raises(ht.FuseTraceError):
            np.asarray(b)
    # and the restriction lifts on exit
    assert float((a + 1.0).sum()) == pytest.approx(float(b.sum()))


def test_error_names_public_entry_point():
    assert ht.FuseTraceError is _tracing.FuseTraceError
    assert ht.fuse is fuse


# --------------------------------------------------------------------- #
# library pipelines: one dispatch each                                  #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("split", [None, 0])
def test_library_svd_single_dispatch(split):
    rng = np.random.default_rng(7)
    a = ht.array(rng.standard_normal((24, 4)).astype(np.float32), split=split)
    n, res = _dispatches(ht.linalg.svd, a)
    assert n == 1, f"fused qr→svd pipeline issued {n} dispatches, wanted exactly 1"
    rec = res.U.numpy() @ np.diag(res.S.numpy()) @ res.V.numpy().T
    np.testing.assert_allclose(rec, a.numpy(), atol=1e-4)


@pytest.mark.parametrize("split", [None, 0, 1])
def test_library_statistics_single_dispatch(split):
    a, _ = _pair((6, 8), split, seed=11)
    for stat in (ht.kurtosis, ht.skew):
        n, _ = _dispatches(stat, a)
        assert n == 1, f"fused {stat.__name__} issued {n} dispatches"


def test_library_statistics_match_eager_values():
    """The fused and the eager chain agree to rounding.  Skew and kurtosis
    are sums of standardized deviations' powers, terms of order one that
    cancel (a skew of 0.0083 here), and the two chains add them in
    different orders: the difference is a few float32 roundings of the
    TERMS' scale, not of the result's, so an ``atol`` of 8 eps stands
    beside the ``rtol`` (a relative bound alone asks a cancelled sum for
    more digits than its terms hold)."""
    from heat_tpu.core.statistics import _kurtosis_program, _skew_program

    a, _ = _pair((6, 8), 0, seed=13)
    atol = 8 * float(np.finfo(np.float32).eps)
    np.testing.assert_allclose(
        ht.kurtosis(a, axis=0).numpy(),
        _kurtosis_program(a, 0, True, True).numpy(),
        rtol=3e-6, atol=atol,
    )
    np.testing.assert_allclose(
        ht.skew(a, axis=1).numpy(), _skew_program(a, 1, True).numpy(),
        rtol=3e-6, atol=atol,
    )


# --------------------------------------------------------------------- #
# nesting + donation                                                    #
# --------------------------------------------------------------------- #
def test_fused_functions_compose():
    inner = fuse(_cached_prog)

    @fuse
    def outer(a, b):
        return inner(a, b) * 0.5  # inlines: still one program

    a, b = _pair((4, 6), 0)
    n, out = _dispatches(outer, a, b)
    assert n == 1
    np.testing.assert_allclose(out.numpy(), (_cached_prog(a, b) * 0.5).numpy(), rtol=3e-7)


def test_donate_smoke():
    @fuse(donate=True)
    def prog(a, b):
        return a + b

    a, b = _pair((4, 6), 0)
    want = a.numpy() + b.numpy()
    # CPU ignores donation (the XLA note goes to absl logging, not Python
    # warnings) — the smoke test is that the donating program is correct
    out = prog(a, b)
    assert_array_equal(out, want)
