"""heat_tpu.telemetry: spans, counters, wire-byte accounting, exporters.

The suite pins the two halves of the observability contract:

* enabled, the registry reproduces ground truth — span aggregates match
  the nesting structure, the wire-byte ledger matches the hand-derived
  ring arithmetic of docs/design.md at every mesh size, and deterministic
  mode makes two identical runs bitwise-equal (the one timeline, spans in
  the profiler's trace, is tests/test_telemetry_spans.py's);
* disabled, telemetry is invisible — ``snapshot()`` is empty, zero
  events record, no compile-cache keys change, and the tier-1
  dispatch-count gates keep their exact values (asserted indirectly by
  the unchanged gates in test_fuse.py / test_compressed_collectives.py,
  directly by the cache-stability test here).

Fixtures restore the PRIOR enabled state rather than blanket-disabling,
so the CI telemetry lane (HEAT_TELEMETRY=1) keeps its process-wide
collection alive across this file.
"""

import json
import os
import threading

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import heat_tpu as ht
from heat_tpu import telemetry
from heat_tpu.comm import collective_precision, compressed as cq
from heat_tpu.core import _tracing
from heat_tpu.core.communication import XlaCommunication
from heat_tpu.telemetry import _core

RNG = np.random.default_rng(11)


def _sub_comm(k):
    devs = jax.devices()
    if len(devs) < k:
        pytest.skip(f"needs {k} devices")
    return XlaCommunication(devs[:k])


@pytest.fixture
def tel():
    """Enabled telemetry with a clean registry; restores the prior
    enabled state (NOT a blanket disable) on exit."""
    was = _core.is_enabled()
    telemetry.enable()
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if not was:
        telemetry.disable()


@pytest.fixture
def det_tel():
    """Deterministic-mode telemetry; same restore discipline."""
    was = _core.is_enabled()
    telemetry.enable(deterministic=True)
    telemetry.reset()
    yield telemetry
    telemetry.reset()
    if was:
        telemetry.enable()
    else:
        telemetry.disable()


# --------------------------------------------------------------------- #
# spans                                                                  #
# --------------------------------------------------------------------- #
def test_span_nesting_aggregates_per_site(tel):
    with telemetry.span("outer"):
        with telemetry.span("inner"):
            pass
        with telemetry.span("inner"):
            pass
    snap = telemetry.snapshot()
    assert snap["spans"]["outer"]["count"] == 1
    assert snap["spans"]["inner"]["count"] == 2
    # inner spans close before outer: event order is inner, inner, outer
    sites = [e["site"] for e in telemetry.events() if e["type"] == "span"]
    assert sites == ["inner", "inner", "outer"]


def test_span_exception_safety(tel):
    with pytest.raises(ValueError):
        with telemetry.span("boom"):
            raise ValueError("x")
    (ev,) = [e for e in telemetry.events() if e["site"] == "boom"]
    assert ev["error"] == "ValueError"
    assert telemetry.snapshot()["spans"]["boom"]["count"] == 1


def test_span_decorator_rechecks_flag_per_call(tel):
    @telemetry.span("decorated")
    def f(x):
        return x + 1

    assert f.__telemetry_site__ == "decorated"
    assert f(1) == 2
    telemetry.disable()
    try:
        assert f(2) == 3  # no record while disabled
    finally:
        telemetry.enable()
    assert f(3) == 4
    assert telemetry.snapshot()["spans"]["decorated"]["count"] == 2


def test_span_extra_fields_land_on_event(tel):
    with telemetry.span("tagged", mode="int8_block", mesh=4):
        pass
    (ev,) = [e for e in telemetry.events() if e["site"] == "tagged"]
    assert ev["mode"] == "int8_block" and ev["mesh"] == 4


# --------------------------------------------------------------------- #
# disabled mode is a no-op                                               #
# --------------------------------------------------------------------- #
def test_disabled_records_nothing():
    was = _core.is_enabled()
    telemetry.disable()
    try:
        before = len(_core._events)
        with telemetry.span("ghost"):
            pass
        telemetry.inc("ghost.counter")
        telemetry.gauge("ghost.gauge", 1.0)
        telemetry.record_event("ghost")
        assert telemetry.snapshot() == {}
        assert len(_core._events) == before
    finally:
        if was:
            telemetry.enable()


def test_toggling_telemetry_never_changes_cache_keys(tmp_path, monkeypatch):
    """Enabling telemetry must not register a key context or retrace:
    the same op replayed across toggles adds zero cache entries.  And the
    always-on start-up record is written when jax compiles, never on a
    replay: a warm program makes no call into it, recording or not."""
    from heat_tpu.core import _compile

    was = _core.is_enabled()
    x = ht.arange(8, split=0)
    (x + 1).larray.block_until_ready()  # populate the cache
    n0 = _compile.cache_size()

    def no_call(*args, **kwargs):
        raise AssertionError(f"a warm replay wrote to the start-up record: {args}")

    monkeypatch.setattr(_core, "record_compile_stage", no_call)
    monkeypatch.setattr(_core, "_startup_append", no_call)
    traces0 = {k: f.jitted._cache_size() for k, f in _compile._CACHE.items()}
    try:
        telemetry.enable()
        (x + 1).larray.block_until_ready()
        telemetry.disable()
        (x + 1).larray.block_until_ready()
        # the other switch: a profiler trace turns recording on and off
        jax.profiler.start_trace(str(tmp_path))
        try:
            assert telemetry.recording()
            (x + 1).larray.block_until_ready()
        finally:
            jax.profiler.stop_trace()
        assert not telemetry.recording()
        (x + 1).larray.block_until_ready()
        assert _compile.cache_size() == n0
        # and no entry was traced again (jit's own cache of traces)
        assert {k: f.jitted._cache_size() for k, f in _compile._CACHE.items()} == traces0
    finally:
        if was:
            telemetry.enable()
        else:
            telemetry.disable()


# --------------------------------------------------------------------- #
# counters, dispatch windows, thread safety                              #
# --------------------------------------------------------------------- #
def test_counters_and_gauges(tel):
    telemetry.inc("a")
    telemetry.inc("a", 4)
    telemetry.gauge("g", 0.5)
    snap = telemetry.snapshot()
    assert snap["counters"]["a"] == 5
    assert snap["gauges"]["g"] == 0.5


def test_counting_dispatches_window_is_a_baseline_diff(tel):
    with _tracing.counting_dispatches() as outer:
        _tracing.record_dispatch()
        with _tracing.counting_dispatches() as inner:
            _tracing.record_dispatch()
        assert inner.count == 1
    assert outer.count == 2


def test_dispatch_counter_thread_safe():
    base = _tracing.dispatch_count()
    n, k = 8, 200

    def worker():
        for _ in range(k):
            _tracing.record_dispatch()

    ts = [threading.Thread(target=worker) for _ in range(n)]
    with _tracing.counting_dispatches() as d:
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert d.count == n * k
    assert _tracing.dispatch_count() == base + n * k


def test_counter_increments_thread_safe(tel):
    n, k = 8, 200

    def worker():
        for _ in range(k):
            telemetry.inc("threads.hits")

    ts = [threading.Thread(target=worker) for _ in range(n)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    assert telemetry.snapshot()["counters"]["threads.hits"] == n * k


# --------------------------------------------------------------------- #
# wire-byte ledger vs hand math                                          #
# --------------------------------------------------------------------- #
def _hand_wire(n_elems, p, mode, op):
    """Independent re-derivation of the design.md ring-byte arithmetic."""
    block = cq.BLOCK
    if op == "allreduce":
        chunk = (n_elems + p - 1) // p
        hops = 2 * (p - 1)
    else:
        chunk = n_elems
        hops = p - 1
    chunk_p = ((chunk + block - 1) // block) * block
    exact = hops * chunk_p * 4
    if mode == "int8_block":
        wire = hops * (chunk_p + (chunk_p // block) * 4)
    elif mode == "bf16":
        wire = hops * chunk_p * 2
    else:
        wire = exact
    return exact, wire


@pytest.mark.parametrize("mesh_size", [1, 2, 4, 8])
@pytest.mark.parametrize("mode", ["bf16", "int8_block"])
def test_allreduce_q_byte_accounting(tel, mesh_size, mode):
    comm = _sub_comm(mesh_size)
    telemetry.reset()
    x = jnp.asarray(RNG.normal(size=(mesh_size, 37, 5)).astype(np.float32))
    cq.allreduce_q(x, comm=comm, precision=mode)
    snap = telemetry.snapshot()
    c = snap["counters"]
    if mesh_size == 1:
        # a single-position mesh runs no ring: nothing moves, nothing
        # is credited to the ledger
        assert "comm.collectives.allreduce" not in c
        return
    exact, wire = _hand_wire(37 * 5, mesh_size, mode, "allreduce")
    assert c["comm.collectives.allreduce"] == 1
    assert c[f"comm.exact_bytes.{mode}"] == exact
    assert c[f"comm.wire_bytes.{mode}"] == wire
    if exact:
        assert snap["gauges"][f"comm.wire_ratio.{mode}"] == wire / exact
    assert snap["spans"]["commq:allreduce"]["count"] == 1


@pytest.mark.parametrize("mesh_size", [1, 2, 4, 8])
@pytest.mark.parametrize("mode", ["bf16", "int8_block"])
def test_allgather_q_byte_accounting(tel, mesh_size, mode):
    comm = _sub_comm(mesh_size)
    telemetry.reset()
    data = RNG.normal(size=(mesh_size * 6, 9)).astype(np.float32)
    x = comm.apply_sharding(jnp.asarray(data), 0)
    cq.allgather_q(x, axis=0, comm=comm, precision=mode)
    snap = telemetry.snapshot()
    c = snap["counters"]
    if mesh_size == 1:
        assert "comm.collectives.allgather" not in c
        return
    exact, wire = _hand_wire(6 * 9, mesh_size, mode, "allgather")
    assert c["comm.collectives.allgather"] == 1
    assert c[f"comm.exact_bytes.{mode}"] == exact
    assert c[f"comm.wire_bytes.{mode}"] == wire
    assert snap["spans"]["commq:allgather"]["count"] == 1


def test_int8_block_steady_state_ratio_is_0258(tel):
    # a block-aligned payload: ratio is exactly (BLOCK+4)/(4*BLOCK)
    comm = _sub_comm(4)
    telemetry.reset()
    x = jnp.asarray(RNG.normal(size=(4, 4 * cq.BLOCK)).astype(np.float32))
    cq.allreduce_q(x, comm=comm, precision="int8_block")
    ratio = telemetry.snapshot()["gauges"]["comm.wire_ratio.int8_block"]
    assert ratio == (cq.BLOCK + 4) / (4 * cq.BLOCK) == 0.2578125


def test_wire_model_matches_ledger_source():
    wm = cq.wire_model(512, 4, "int8_block", op="allreduce")
    exact, wire = _hand_wire(512, 4, "int8_block", "allreduce")
    assert wm["exact_wire_bytes"] == exact and wm["wire_bytes"] == wire
    assert wm["ring_hops_per_device"] == 6
    with pytest.raises(ValueError, match="ring op"):
        cq.wire_model(8, 2, None, op="scatter")


def test_exact_allreduce_accounts_f32_bytes(tel):
    comm = _sub_comm(2)
    telemetry.reset()
    x = jnp.asarray(RNG.normal(size=(2, 16)).astype(np.float32))
    comm.allreduce(x, "sum")
    c = telemetry.snapshot()["counters"]
    assert c["comm.collectives.allreduce"] == 1
    assert c["comm.exact_bytes.f32"] == c["comm.wire_bytes.f32"] > 0


# --------------------------------------------------------------------- #
# compile-cache observability                                            #
# --------------------------------------------------------------------- #
def test_first_call_is_the_same_launch_span_marked_miss(tel):
    """A first call is no other code path: the same ``launch`` span with
    ``miss=True`` (its duration holds trace, lower and compile), and the
    jit function is traced exactly once however often recording toggles."""
    from heat_tpu.core._compile import jitted

    def make():
        return lambda a: a * 3

    fn = jitted(("telemetry-test-miss", 0), make)
    fn(jnp.ones((4,), jnp.float32)).block_until_ready()
    fn(jnp.ones((4,), jnp.float32)).block_until_ready()
    first, second = [e for e in telemetry.events() if e["site"] == "jitted:telemetry-test-miss"]
    assert first["kind"] == second["kind"] == "launch"
    assert first["miss"] is True and "miss" not in second
    assert first["dur"] >= second["dur"] >= 0.0
    assert not [e for e in telemetry.events() if e["type"] == "compile"]
    assert fn.jitted._cache_size() == 1  # compiled once, by jit itself
    c = telemetry.snapshot()["counters"]
    assert c["compile.cache.misses"] >= 1
    # a second jitted() lookup of the same key is a hit, not a miss
    jitted(("telemetry-test-miss", 0), make)
    c2 = telemetry.snapshot()["counters"]
    assert c2["compile.cache.hits"] >= 1
    assert c2["compile.cache.misses"] == c["compile.cache.misses"]


# --------------------------------------------------------------------- #
# exporters                                                              #
# --------------------------------------------------------------------- #
def test_jsonl_sink_streams_events(tmp_path, tel):
    path = str(tmp_path / "events.jsonl")
    telemetry.set_jsonl(path)
    try:
        assert telemetry.jsonl_path() == path
        with telemetry.span("logged"):
            pass
        telemetry.record_event("checkpoint", site="loop", op="save")
    finally:
        telemetry.set_jsonl(None)
    lines = [json.loads(ln) for ln in open(path)]
    assert [ln["type"] for ln in lines] == ["span", "checkpoint"]
    assert lines[0]["site"] == "logged" and lines[1]["op"] == "save"


# --------------------------------------------------------------------- #
# determinism                                                            #
# --------------------------------------------------------------------- #
def _det_run():
    telemetry.reset()
    with telemetry.span("a"):
        with telemetry.span("b"):
            pass
    telemetry.record_event("incident", site="guard", kind="nonfinite")
    return telemetry.events()


def test_deterministic_mode_is_bitwise_replayable(det_tel):
    first = _det_run()
    second = _det_run()
    assert json.dumps(first, sort_keys=True) == json.dumps(second, sort_keys=True)
    # timestamps are the monotone integer sequence, not wall time:
    # a opens at 0, b spans [1, 2), a closes at 3, the incident is 4
    # (span events append at EXIT, so b's event precedes a's)
    assert [e["ts"] for e in first] == [1.0, 0.0, 4.0]
    assert [e["site"] for e in first] == ["b", "a", "guard"]
    # ids are a counter that reset() rewinds: the same in both runs
    assert [(e["id"], e["parent"], e["root"]) for e in first[:2]] == [(1, 0, 0), (0, None, 0)]
    assert [e.get("id") for e in second] == [e.get("id") for e in first]


def test_incident_log_uses_injectable_telemetry_clock(tel):
    from heat_tpu.resilience import incidents

    telemetry.set_clock(lambda: 1234.5)
    try:
        incidents.clear_incident_log()
        incidents.record("nonfinite", "test.site", "warn", "warned")
        (inc,) = incidents.incident_log()
        assert inc.timestamp == 1234.5
    finally:
        telemetry.set_clock(None)
        incidents.clear_incident_log()
    evs = [e for e in telemetry.events() if e["type"] == "incident"]
    assert evs and evs[-1]["site"] == "test.site" and evs[-1]["kind"] == "nonfinite"
    c = telemetry.snapshot()["counters"]
    assert c["resilience.incidents"] == 1
    assert c["resilience.incidents.warned"] == 1


# --------------------------------------------------------------------- #
# end-to-end acceptance: a fused KMeans fit, fully observed              #
# --------------------------------------------------------------------- #
@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a multi-device mesh")
def test_kmeans_fit_snapshot_acceptance(tel):
    """The ISSUE acceptance scenario: with telemetry enabled, a KMeans
    fit under the int8_block policy yields a snapshot carrying compile
    cache hit/miss counts, per-site span totals, and a live
    exact-vs-wire ratio within 2% of 0.258x."""
    telemetry.reset()
    p = len(jax.devices())
    x = ht.array(RNG.normal(size=(8 * p, 16)).astype(np.float32), split=0)
    with collective_precision("int8_block"):
        ht.cluster.KMeans(n_clusters=4, max_iter=5, random_state=0).fit(x)
    snap = telemetry.snapshot()
    c, g = snap["counters"], snap["gauges"]
    assert c["compile.cache.misses"] >= 1
    assert "compile.cache.hits" in c or c["compile.cache.misses"] >= 1
    assert snap["spans"]["fit:KMeans"]["count"] == 1
    assert snap["spans"]["fit:KMeans"]["total_s"] >= 0.0
    assert any(s.startswith("jitted:") for s in snap["spans"])
    ratio = g["comm.wire_ratio.int8_block"]
    assert abs(ratio - 0.258) / 0.258 < 0.02
    assert c["comm.wire_bytes.int8_block"] < c["comm.exact_bytes.int8_block"]


def test_estimator_spans_report_subclass_name(tel):
    x = ht.array(RNG.normal(size=(16, 4)).astype(np.float32), split=0)
    km = ht.cluster.KMeans(n_clusters=2, max_iter=2, random_state=0)
    km.fit(x)
    km.predict(x)
    snap = telemetry.snapshot()
    assert snap["spans"]["fit:KMeans"]["count"] == 1
    assert snap["spans"]["predict:KMeans"]["count"] == 1


def test_checkpoint_events_record(tmp_path, tel):
    if not ht.supports_hdf5():
        pytest.skip("h5py unavailable")
    from heat_tpu.resilience.resume import load_loop_state, save_loop_state

    path = str(tmp_path / "loop.h5")
    save_loop_state(path, {"it": np.int32(3)}, {"algo": "t"})
    load_loop_state(path)
    c = telemetry.snapshot()["counters"]
    assert c["checkpoint.saves"] == 1
    assert c["checkpoint.loads"] == 1
    ops = [e.get("op") for e in telemetry.events() if e["type"] == "checkpoint"]
    assert ops == ["save", "load"]
    assert telemetry.snapshot()["spans"]["ckpt:save"]["count"] == 1
