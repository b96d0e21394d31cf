"""The five per-layer readers that read the program's own record
(``heat_tpu.telemetry.profiled_spans``: the spans recorded while the
profiler ran): each on a synthetic ``view`` beside a recorded tiny window of
KMeans jobs, ``None`` on an empty record, and all five found by name from
``BENCHMARK.json`` in every cell."""

import importlib
import json
import os

import numpy as np
import pytest

from conftest import REPO

NAMES = (
    "dispatches_per_job", "dispatch_ms_per_job", "host_syncs_per_job",
    "entry_self_ms_per_job", "sync_wait_ms_per_job",
)
JOBS = 2


def _reader(name):
    return importlib.import_module("layer_metrics." + name).read


def _view(jobs=JOBS):
    return {"trace": {"jobs": jobs, "window_s": 1.0, "busy_s": 0.5}}


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_on_an_empty_record(name):
    """What the parent commit gives: no span recorded during a trace, or no
    ``profiled_spans`` at all.  The reader returns None and does not raise,
    and the line leaves the metric out.  (Before the module's window is
    recorded: the fixture below is made at its first use.)"""
    from heat_tpu import telemetry

    telemetry.reset()
    assert telemetry.profiled_spans() == ()
    assert _reader(name)(_view()) is None
    gone = telemetry.profiled_spans
    del telemetry.profiled_spans
    try:
        assert _reader(name)(_view()) is None
    finally:
        telemetry.profiled_spans = gone


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """Two tiny KMeans jobs (fit, ``inertia_``, ``n_iter_``) on one device
    under a profiler trace, as ``run.py --trace 1`` takes its window: the
    program is warm, ``telemetry.enable()`` is never called."""
    import jax

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core.communication import XlaCommunication

    telemetry.disable()
    telemetry.reset()
    x = ht.array(
        np.random.default_rng(5).normal(size=(64, 16)).astype(np.float32),
        split=0, comm=XlaCommunication(jax.devices()[:1]),
    )

    def job(i):
        km = ht.cluster.KMeans(n_clusters=4, init="probability_based", max_iter=5, tol=-1.0, random_state=i)
        km.fit(x)
        return km.inertia_, km.n_iter_

    job(0)
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
    try:
        for i in range(JOBS):
            with jax.profiler.TraceAnnotation("perf_job"):
                job(1 + i)
    finally:
        jax.profiler.stop_trace()
    yield telemetry
    telemetry.reset()


def test_counts_repeat_exactly(window):
    assert _reader("dispatches_per_job")(_view()) == 3.0  # k-means++, the segment, the finalize
    assert _reader("host_syncs_per_job")(_view()) == 4.0  # two reads in fit, two properties


def test_times_are_parts_of_the_jobs(window):
    spans = window.profiled_spans()
    roots_ms = sum(e["dur"] for e in spans if e["parent"] is None) / JOBS * 1e3
    parts = [_reader(n)(_view()) for n in ("dispatch_ms_per_job", "entry_self_ms_per_job", "sync_wait_ms_per_job")]
    assert all(p > 0 for p in parts)
    # launch, sync and the entries' own time are disjoint and make up the roots
    assert sum(parts) == pytest.approx(roots_ms, rel=1e-6)
    assert _reader("dispatch_ms_per_job")(_view(2 * JOBS)) == pytest.approx(parts[0] / 2)


def test_found_by_name_in_every_cell(window):
    import run

    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    added = [m for m in bench["per_layer"] if m["name"] in NAMES]  # by name: later PRs append theirs
    assert sorted(m["name"] for m in added) == sorted(NAMES)
    assert all("workloads" not in m and m["moves"] == "job_ms" and m["better"] == "lower" for m in added)
    for cell in ("kmeans_300_c1", "cdist_40k_c1", "kmeans_448_c4"):
        loaded = run.load_cell(cell)
        loaded["bench"] = dict(bench, per_layer=added)
        got = run.layer_metrics(loaded, _view())
        assert set(got) == set(NAMES)
        assert got["host_syncs_per_job"] == {"value": 4.0, "unit": "count"}
