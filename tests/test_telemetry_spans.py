"""The one span record and its one timeline.

What ``tests/test_telemetry.py`` does not hold: a span's ``id`` / ``parent``
/ ``root`` / ``kind`` and self time; the switch (recording is on under
``telemetry.enable()`` **or while a jax profiler trace is being taken**, and
an untraced run records nothing); the sink (every recorded span is a
``TraceAnnotation`` in the profiler's own trace, on its clock, read back here
with the benchmark's ``perf/trace_reduce.load``); the launch helper (one span
a counted dispatch, the estimators' bare ``jax.jit`` programs included) and
the host-read helper (``host_syncs``).

The traced tiny fit is recorded once for the module (a profiler session is a
few tenths of a second) and read by several tests.
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.core import _compile, _tracing
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.telemetry import _core

PERF = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "perf")
RNG = np.random.default_rng(25)


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
def dark():
    """``enable()`` not called (the CI lane's HEAT_TELEMETRY=1 parked), the
    record empty; the prior state comes back afterwards."""
    was = _core.is_enabled()
    telemetry.disable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if was:
        telemetry.enable()


def _one_chip():
    return XlaCommunication(jax.devices()[:1])


def _data(comm=None, rows=64, features=16):
    return ht.array(
        RNG.normal(size=(rows, features)).astype(np.float32), split=0, comm=comm
    )


def _job(x):
    """The benchmark's KMeans job at a tiny size: one fit and the two
    scalar properties."""
    km = ht.cluster.KMeans(
        n_clusters=4, init="probability_based", max_iter=5, tol=-1.0, random_state=3
    )
    km.fit(x)
    return km.inertia_, km.n_iter_


def _spans(events=None):
    return [e for e in (telemetry.events() if events is None else events) if e["type"] == "span"]


def _descendants(spans, root):
    out, ids = [], {root["id"]}
    for e in sorted(spans, key=lambda e: e["id"]):  # a child's id is larger than its parent's
        if e["parent"] in ids:
            ids.add(e["id"])
            out.append(e)
    return out


# --------------------------------------------------------------------- #
# the record                                                             #
# --------------------------------------------------------------------- #
def test_span_ids_parent_and_root_follow_the_nesting(tel):
    with telemetry.span("a", "entry"):
        with telemetry.span("b"):
            with telemetry.span("c", "sync"):
                pass
        with telemetry.span("d", "launch"):
            pass
    with telemetry.span("e"):
        pass
    by = {e["site"]: e for e in _spans()}
    assert by["a"]["parent"] is None and by["a"]["root"] == by["a"]["id"]
    assert by["b"]["parent"] == by["a"]["id"] and by["d"]["parent"] == by["a"]["id"]
    assert by["c"]["parent"] == by["b"]["id"]
    assert {by[s]["root"] for s in "abcd"} == {by["a"]["id"]}
    assert by["e"]["parent"] is None and by["e"]["root"] == by["e"]["id"] != by["a"]["id"]
    assert [by[s]["kind"] for s in "abcde"] == ["entry", "other", "sync", "launch", "other"]
    assert len({e["id"] for e in by.values()}) == 5
    # counts are taken at the entry's own boundary
    assert by["a"]["launches"] == 0 and by["a"]["syncs"] == 0 and "launches" not in by["b"]


def test_spans_of_another_thread_are_roots_of_their_own(tel):
    import threading

    def worker():
        with telemetry.span("in-thread"):
            pass

    with telemetry.span("main"):
        t = threading.Thread(target=worker)
        t.start()
        t.join()
    by = {e["site"]: e for e in _spans()}
    assert by["in-thread"]["parent"] is None and by["in-thread"]["tid"] != by["main"]["tid"]


def test_self_time_on_a_hand_made_tree_with_overlapping_children():
    def ev(i, parent, ts, dur):
        return {"type": "span", "id": i, "parent": parent, "ts": ts, "dur": dur}

    spans = [
        ev(0, None, 0.0, 10.0),
        ev(1, 0, 1.0, 3.0),     # [1, 4)
        ev(2, 0, 3.0, 3.0),     # [3, 6): overlaps 1 by one second
        ev(3, 0, 8.0, 4.0),     # [8, 12): runs one... two seconds past its parent
        ev(4, 1, 1.5, 1.0),     # a grandchild: taken off 1, not off 0
        ev(5, 7, 0.0, 5.0),     # its parent is not in the list
    ]
    st = telemetry.self_times(spans)
    assert st[0] == pytest.approx(10.0 - (5.0 + 2.0))  # union [1,6) and [8,10)
    assert st[1] == pytest.approx(2.0)
    assert st[2] == pytest.approx(3.0) and st[3] == pytest.approx(4.0)
    assert st[4] == pytest.approx(1.0) and st[5] == pytest.approx(5.0)
    assert telemetry.self_times([]) == {}


def test_self_time_of_recorded_spans_adds_up(tel):
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
    outer = next(e for e in _spans() if e["site"] == "outer")
    inner = next(e for e in _spans() if e["site"] == "inner")
    st = telemetry.self_times(_spans())
    assert st[outer["id"]] == pytest.approx(outer["dur"] - inner["dur"])
    assert st[inner["id"]] == inner["dur"]


# --------------------------------------------------------------------- #
# the switch                                                             #
# --------------------------------------------------------------------- #
def test_untraced_fit_and_cdist_record_nothing(dark):
    x = _data()
    _job(x)
    ht.spatial.cdist(x, x)
    float(ht.sum(x))
    assert not telemetry.recording()
    assert telemetry.events() == () and telemetry.snapshot() == {}
    assert telemetry.profiled_spans() == ()


def test_recording_follows_the_profiler_with_enable_never_called(dark, tmp_path):
    x = _data()
    assert not telemetry.recording()
    jax.profiler.start_trace(str(tmp_path))
    try:
        assert telemetry.recording() and not telemetry.is_enabled()
        ht.spatial.cdist(x, x)
    finally:
        jax.profiler.stop_trace()
    assert not telemetry.recording()
    ht.spatial.cdist(x, x)  # after the trace: not recorded
    sites = [e["site"] for e in telemetry.events()]
    assert sites == ["jitted:dist.euclidean", "spatial:cdist"]
    assert telemetry.snapshot() == {}  # counters and gauges stay enable()'s


def test_profiled_spans_are_the_trace_window_alone_under_enable(tel, tmp_path):
    x = _data()
    ht.spatial.cdist(x, x)  # recorded (enable), before the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        ht.spatial.manhattan(x, x)
    finally:
        jax.profiler.stop_trace()
    ht.spatial.rbf(x, x)  # recorded, after the trace
    entries = [e["site"] for e in _spans() if e["kind"] == "entry"]
    assert entries == ["spatial:cdist", "spatial:manhattan", "spatial:rbf"]
    window = telemetry.profiled_spans()
    assert [e["site"] for e in window if e["kind"] == "entry"] == ["spatial:manhattan"]
    assert {e["root"] for e in window} == {window[-1]["id"]}
    telemetry.reset()
    assert telemetry.profiled_spans() == ()


# --------------------------------------------------------------------- #
# the sink: one timeline, the profiler's                                 #
# --------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """One tiny KMeans job and one cdist under a profiler trace, on one
    device, ``enable()`` not called: the in-memory spans, the trace read
    back with the benchmark's own loader, and the counters' differences."""
    if PERF not in sys.path:
        sys.path.insert(0, PERF)
    import trace_reduce

    was = _core.is_enabled()
    telemetry.disable()
    telemetry.reset()
    x = _data(_one_chip())
    _job(x)
    ht.spatial.cdist(x, x)  # warm: the traced calls compile nothing
    logdir = str(tmp_path_factory.mktemp("trace"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    syncs0 = telemetry.host_sync_count()
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        with telemetry.counting_dispatches() as fit_window:
            _job(x)
        fit_dispatches, fit_syncs = fit_window.count, telemetry.host_sync_count() - syncs0
        with telemetry.counting_dispatches() as cdist_window:
            jax.block_until_ready(ht.spatial.cdist(x, x).larray)
        cdist_dispatches = cdist_window.count
        cdist_syncs = telemetry.host_sync_count() - syncs0 - fit_syncs
    finally:
        jax.profiler.stop_trace()
    spans = telemetry.profiled_spans()
    loaded = trace_reduce.load(trace_reduce.find_trace(logdir))
    telemetry.reset()
    if was:
        telemetry.enable()
    return {
        "spans": spans, "loaded": loaded,
        "fit": (fit_dispatches, fit_syncs), "cdist": (cdist_dispatches, cdist_syncs),
    }


def test_traced_fit_lies_in_the_profilers_trace_on_its_clock(traced_fit):
    """The program's spans are host events of the profiler's own trace
    (plane ``/host:CPU``, where ``trace_reduce`` looks for what the host did
    in an idle gap), and agree with the in-memory record to 0.2 ms."""
    host = [ev for evs in traced_fit["loaded"]["host"].values() for ev in evs]
    mem = {e["site"]: e for e in traced_fit["spans"]}
    in_trace = {}
    for name, start, end in host:
        if name in mem:
            assert name not in in_trace, f"{name} twice in the trace"
            in_trace[name] = (start, end)
    for site in ("fit:KMeans", "kmeans:init", "jit:kmeans.kmeanspp", "jit:kmeans.fit_segment",
                 "sync:kmeans.it", "jit:kmeans.finalize", "kmeans:wrap",
                 "sync:kcluster.inertia", "spatial:cdist", "jitted:dist.euclidean"):
        assert site in in_trace, site
    fit_mem, fit_tr = mem["fit:KMeans"], in_trace["fit:KMeans"]
    for site, (start, end) in in_trace.items():
        assert (end - start) == pytest.approx(mem[site]["dur"], abs=2e-4), site
        # start-to-start offsets from the fit's start: one clock
        assert (start - fit_tr[0]) == pytest.approx(mem[site]["ts"] - fit_mem["ts"], abs=2e-4), site


def test_traced_fit_is_one_tree_of_kinds(traced_fit):
    spans = traced_fit["spans"]
    fit = next(e for e in spans if e["site"] == "fit:KMeans")
    under = _descendants(spans, fit)
    assert [e["site"] for e in sorted(under, key=lambda e: e["id"])] == [
        "kmeans:init", "jit:kmeans.kmeanspp", "sync:kmeans.it0", "jit:kmeans.fit_segment",
        "sync:kmeans.it", "jit:kmeans.finalize", "kmeans:wrap",
    ]
    assert {e["root"] for e in under} == {fit["id"]}
    kinds = {e["site"]: e["kind"] for e in spans}
    assert set(kinds.values()) <= set(_core.KINDS)
    assert kinds["fit:KMeans"] == kinds["spatial:cdist"] == "entry"
    assert kinds["kmeans:init"] == kinds["kmeans:wrap"] == "other"
    assert {kinds[s] for s in kinds if s.startswith(("jit:", "jitted:"))} == {"launch"}
    assert {kinds[s] for s in kinds if s.startswith("sync:")} == {"sync"}
    # the two properties are read outside fit: a root span each
    for site in ("sync:kcluster.inertia", "sync:kcluster.n_iter"):
        e = next(e for e in spans if e["site"] == site)
        assert e["parent"] is None and e["root"] == e["id"]
    st = telemetry.self_times(spans)
    assert 0.0 <= st[fit["id"]] <= fit["dur"]
    assert sum(st.values()) == pytest.approx(sum(e["dur"] for e in spans if e["parent"] is None))


def test_an_entrys_launches_are_its_launch_descendants(traced_fit):
    """One span a counted dispatch: every launch goes through the one
    helper, so the entry's counter difference is the ``launch`` (and
    ``comm``: a reshard is a counted dispatch but no compiled program) spans
    under it.  On one device nothing reshards."""
    spans = traced_fit["spans"]
    for site in ("fit:KMeans", "spatial:cdist"):
        entry = next(e for e in spans if e["site"] == site)
        under = _descendants(spans, entry)
        assert entry["launches"] == sum(e["kind"] in ("launch", "comm") for e in under)
        assert entry["launches"] == sum(e["kind"] == "launch" for e in under)
        assert entry["syncs"] == sum(e["kind"] == "sync" for e in under)
    assert next(e for e in spans if e["site"] == "fit:KMeans")["launches"] == 3


def test_host_syncs_four_a_kmeans_job_none_a_cdist(traced_fit):
    """Two reads in ``fit`` (``sync:kmeans.it0``, ``sync:kmeans.it``) and the
    two properties; the counter is always on, the spans agree with it."""
    assert traced_fit["fit"] == (3, 4)
    assert traced_fit["cdist"] == (1, 0)
    assert sum(e["kind"] == "sync" for e in traced_fit["spans"]) == 4


def test_counting_dispatches_counts_the_bare_programs_untraced(dark):
    """``_kmeanspp``, ``_fit_segment`` and ``_finalize`` are bare ``jax.jit``
    programs; they used to pass the program's counter by."""
    x = _data(_one_chip())
    syncs0 = telemetry.host_sync_count()
    with _tracing.counting_dispatches() as d:
        _job(x)
    assert d.count == 3
    assert telemetry.host_sync_count() - syncs0 == 4
    assert telemetry.events() == ()


def test_reshard_is_a_comm_span_through_the_same_helper(tel):
    if len(jax.devices()) < 2:
        pytest.skip("needs a multi-device mesh")
    x = ht.array(np.arange(32, dtype=np.float32).reshape(16, 2), split=0)
    telemetry.reset()
    with _tracing.counting_dispatches() as d:
        x.resplit_(None)
    under = [e for e in _spans() if e["kind"] in ("launch", "comm")]
    assert d.count == len(under) >= 1
    assert any(e["site"] == "comm:reshard" and e["kind"] == "comm" for e in under)


def test_launch_inside_a_fuse_trace_is_neither_counted_nor_spanned(tel):
    seen = []
    with _tracing.trace_mode():
        with _tracing.counting_dispatches() as d:
            seen.append(_compile.launch("jit:probe", lambda a: a + 1, (1,)))
    assert seen == [2] and d.count == 0 and _spans() == []
    with _tracing.counting_dispatches() as d:
        assert _compile.launch("jit:probe", lambda a: a + 1, (1,), tag="x") == 2
    (ev,) = _spans()
    assert d.count == 1 and ev["site"] == "jit:probe" and ev["kind"] == "launch" and ev["tag"] == "x"


# --------------------------------------------------------------------- #
# host reads                                                             #
# --------------------------------------------------------------------- #
def test_host_read_always_counts_and_spans_when_recording(dark):
    n0 = telemetry.host_sync_count()
    assert telemetry.host_read("sync:probe", jnp.float32(2.5), float) == 2.5
    assert telemetry.host_sync_count() == n0 + 1 and telemetry.events() == ()
    telemetry.enable()
    try:
        assert telemetry.host_read("sync:probe", jnp.int32(7), int) == 7
        (ev,) = _spans()
        assert ev["site"] == "sync:probe" and ev["kind"] == "sync"
        assert telemetry.host_sync_count() == n0 + 2
        assert telemetry.snapshot()["counters"]["host_syncs"] == 1
    finally:
        telemetry.disable()


def test_dndarray_value_reads_are_sync_spans(tel):
    x = ht.array(np.arange(6, dtype=np.float32), split=0)
    n0 = telemetry.host_sync_count()
    assert x.numpy().shape == (6,)
    assert np.asarray(x).shape == (6,)
    assert x.tolist() == [0.0, 1.0, 2.0, 3.0, 4.0, 5.0]
    assert float(ht.sum(x)) == 15.0 and int(x[1]) == 1 and bool(x[0]) is False
    assert telemetry.host_sync_count() - n0 == 6
    syncs = [e["site"] for e in _spans() if e["kind"] == "sync"]
    assert syncs == ["sync:dndarray.numpy", "sync:dndarray.asarray", "sync:dndarray.tolist"] + [
        "sync:dndarray.item"
    ] * 3


# --------------------------------------------------------------------- #
# names                                                                  #
# --------------------------------------------------------------------- #
def test_jitted_names_the_compiled_function_after_its_site():
    x = _data(_one_chip(), rows=8, features=4)
    ht.spatial.cdist(x, x)
    ht.spatial.cdist(x, x, quadratic_expansion=True)
    (x + 1.0), ht.sqrt(x)
    by_site = {}
    for key, fn in _compile._CACHE.items():
        by_site.setdefault(key[0], []).append(fn)
    exact = next(f for k, f in _compile._CACHE.items() if k[:2] == ("dist.euclidean", False))
    text = exact.lower(x.larray, x.larray).as_text()
    assert "module @jit_dist.euclidean" in text and "_lambda_" not in text
    assert exact.lower(x.larray, x.larray).compile().as_text().count("cdist.exact") >= 1
    quad = next(f for k, f in _compile._CACHE.items() if k[:2] == ("dist.euclidean", True))
    assert "cdist.quadratic" in quad.lower(x.larray, x.larray).compile().as_text()
    names = {f.jitted.__name__ for fns in by_site.values() for f in fns}
    assert {"dist.euclidean", "binary.add", "local.sqrt"} <= names
    assert "<lambda>" not in names


def test_jitted_wraps_a_shared_function_and_leaves_its_name(tel):
    def make():
        return jnp.negative  # an import-time singleton: never renamed in place

    fn = _compile.jitted(("telemetry-test-shared",), make)
    assert float(fn(jnp.float32(2.0))) == -2.0
    assert fn.jitted.__name__ == "telemetry-test-shared"
    assert jnp.negative.__name__ == "negative"


def test_profiler_annotate_is_a_span_of_kind_other(tel):
    from heat_tpu.utils import profiler

    with profiler.annotate("my-region"):
        pass
    (ev,) = _spans()
    assert ev["site"] == "my-region" and ev["kind"] == "other" and ev["parent"] is None
