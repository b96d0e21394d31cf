"""The spectral cell on the CPU at a size a test can hold: a run end to end
is ``correct``; the control and every planted fault come out not correct by
the number named; the work model by hand; the three ``solvers`` readers on a
recorded window.  The file adds its own tiny configuration and cell to a copy
of ``perf/`` as new files (``conftest.py`` is as it was), the way
``test_harness.py::test_new_files_are_found_by_name`` adds its own."""

import importlib
import json
import os
import shutil

import numpy as np
import pytest

from conftest import PERF, REPO, result_lines, run_child

from tools.limits_probe_spectral import FAULTS

CELL, CONFIG, LIKE_CELL, LIKE_CONFIG = "tiny_spectral_c1", "tiny-spectral", "spectral_40k_c1", "spectral-susy-1chip"
ROWS = 1024


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


@pytest.fixture(scope="module")
def spectral_copy(copy, tmp_path_factory):
    """The session's copy with the committed spectral configuration at 1024
    rows under new names; the cell's limits are the committed cell's."""
    dst = str(tmp_path_factory.mktemp("spectral_copy") / "copy")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    conf = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    conf["name"] = CONFIG
    conf["data"]["rows"] = ROWS
    _dump(os.path.join(dst, "perf", "configs", CONFIG + ".json"), conf)
    own = _read(os.path.join(PERF, "workloads", LIKE_CELL + ".json"))
    own.update(name=CELL, config=CONFIG)
    _dump(os.path.join(dst, "perf", "workloads", CELL + ".json"), own)
    bench = _read(os.path.join(dst, "BENCHMARK.json"))
    bench["configs"].append({"name": CONFIG, "source": "test", "file": f"perf/configs/{CONFIG}.json",
                             "reduced": ["rows"], "why": "a size a test can hold"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "closed_loop", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


RUN = "run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0'])"


def test_a_run_end_to_end_is_correct(spectral_copy):
    proc = run_child(spectral_copy, "sys.exit(" + RUN.format(cell=CELL, seed=3_000_000_019) + ")")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == {"eig_residual", "embedding_orth", "eigval_err", "ncut_excess", "label_mismatch"}
    assert set(line["metrics"]) == {"job_ms", "setup_s"}  # no tail: a window holds seven jobs
    assert 1 <= line["jobs_compared"] <= 2 and line["failed"] == 0


CONTROL = """
import importlib, datagen
loaded = run.load_cell({cell!r})
config, limits = loaded["config"], loaded["own"]["limits"]
entry = importlib.import_module("jobs." + config["entry"])
for seed in (11, 2**31 + 5):
    x = datagen.make(config["data"], seed, jax.devices())
    numbers = entry.judge(config, x, entry.control(config, x, seed), seed)
    print(json.dumps({{"seed": seed, "over": sorted(n for n in limits if not numbers[n] <= limits[n])}}))
"""


def test_the_control_is_not_correct(spectral_copy):
    lines = result_lines(run_child(spectral_copy, CONTROL.format(cell=CELL)))
    assert len(lines) == 2
    for line in lines:
        assert {"eig_residual", "embedding_orth"} <= set(line["over"]), line


#: child body: the cell once sound, then once under each fault planted in the program
FAULT_RUNS = """
from tools.limits_probe_spectral import FAULTS
def go(tag):
    print(json.dumps({{"tag": tag}}), flush=True)
    {run}
go("sound")
for name, (fault, _) in FAULTS.items():
    with fault():
        go(name)
"""


@pytest.fixture(scope="module")
def verdicts(spectral_copy):
    proc = run_child(spectral_copy, FAULT_RUNS.format(run=RUN.format(cell=CELL, seed=424243)), timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out, tag = {}, None
    for line in result_lines(proc):
        if "tag" in line:
            tag = line["tag"]
        else:
            out[tag] = line
    return out


def test_the_sound_run_beside_the_faults_is_correct(verdicts):
    assert verdicts["sound"]["correct"] is True, verdicts["sound"]["compared"]


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_comes_out_not_correct(verdicts, fault):
    line = verdicts[fault]
    over = {n for n, c in line["compared"].items() if not c["value"] <= c["limit"]}
    assert line["correct"] is False, line["compared"]
    assert FAULTS[fault][1] in over, line["compared"]


def test_work_by_hand():
    work = importlib.import_module("jobs.spectral_fit").work
    got = work({"data": {"rows": 1000, "features": 18},
                "job": {"clusters": 8, "gamma": 1.0, "n_lanczos": 30}})
    half = 1000 * 1001 // 2  # 500 500 entries: the symmetric half with its diagonal
    # the half written once and read by 30 matvecs; the basis 1000 * 30**2 entries
    # over the steps; X, the embedding, int64 labels
    assert got["bytes"] == 31 * half * 4 + 900_000 * 4 + 18_000 * 4 + 8_000 * 4 + 8_000
    assert got["flops"] == 2 * 10**6 * 18 + 2 * 10**6 * 30 + 4 * 1000 * 900
    assert got["flops_peak"] == "f32_highest_tflops"
    # more steps than rows: the basis cannot pass n columns
    few = work({"data": {"rows": 20, "features": 18}, "job": {"clusters": 8, "gamma": 1.0, "n_lanczos": 30}})
    assert few["flops"] == 2 * 400 * 18 + 2 * 400 * 20 + 4 * 20 * 400


def test_least_time_of_the_cell():
    least = importlib.import_module("layer_metrics.roofline_pct").least_seconds
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    # memory-bound: 301 passes over the 3.2 GB half and 14.4 GB of basis reads, 1.19 s;
    # the 1.03 TFLOP take 0.03 s at float32 products
    assert least({"work": work, "peaks": peaks, "chips": 1}) == pytest.approx(977.7e9 / 819e9, rel=1e-3)
    assert work["flops"] / 32.83e12 < 0.04


# --------------------------------------------------------------------- #
# the three readers of the layer ``solvers``                            #
# --------------------------------------------------------------------- #
NAMES = ("solver_steps_per_job", "solver_wait_ms_per_job", "host_eig_ms_per_job")
JOBS = 2


def _reader(name):
    return importlib.import_module("layer_metrics." + name).read


def _view(jobs=JOBS):
    return {"trace": {"jobs": jobs, "window_s": 1.0, "busy_s": 0.5}}


@pytest.mark.parametrize("name", NAMES)
def test_nothing_to_read_where_the_program_records_no_such_span(name):
    """The parent commit: no span at these sites, or no ``profiled_spans`` at
    all.  The reader returns None and does not raise."""
    from heat_tpu import telemetry

    telemetry.reset()
    assert _reader(name)(_view()) is None
    gone = telemetry.profiled_spans
    del telemetry.profiled_spans
    try:
        assert _reader(name)(_view()) is None
    finally:
        telemetry.profiled_spans = gone


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """Two tiny Spectral fits on one device under a profiler trace, as
    ``run.py --trace 1`` takes its window; ``telemetry.enable()`` is never
    called.  A KMeans fit beside them leaves spans the readers must pass by."""
    import jax

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core.communication import XlaCommunication

    telemetry.disable()
    telemetry.reset()
    rng = np.random.default_rng(5)
    centres = 0.45 * rng.standard_normal((4, 6))
    x = ht.array(
        (centres[np.arange(96) % 4] + 0.05 * rng.standard_normal((96, 6))).astype(np.float32),
        split=0, comm=XlaCommunication(jax.devices()[:1]),
    )

    def job():
        ht.cluster.Spectral(n_clusters=4, n_lanczos=20).fit(x)

    job()
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
    try:
        for _ in range(JOBS):
            with jax.profiler.TraceAnnotation("perf_job"):
                job()
        ht.cluster.KMeans(n_clusters=4, max_iter=3).fit(x)
    finally:
        jax.profiler.stop_trace()
    yield telemetry
    telemetry.reset()


def test_the_readers_on_a_recorded_window(window):
    assert _reader("solver_steps_per_job")(_view()) == 19.0  # n_lanczos - 1, in one segment
    wait, eig = _reader("solver_wait_ms_per_job")(_view()), _reader("host_eig_ms_per_job")(_view())
    assert wait > 0 and eig > 0
    spans = window.profiled_spans()
    assert wait == pytest.approx(sum(e["dur"] for e in spans if e["site"] == "sync:spectral.tridiag") / JOBS * 1e3)
    assert _reader("host_eig_ms_per_job")(_view(2 * JOBS)) == pytest.approx(eig / 2)


def test_found_by_name_in_their_cell_alone(window):
    import run

    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    added = [m for m in bench["per_layer"] if m["name"] in NAMES]  # by name: later PRs append theirs
    assert sorted(m["name"] for m in added) == sorted(NAMES)
    assert all(m["workloads"] == [LIKE_CELL] and m["moves"] == "job_ms" and m["layer"] == "solvers" for m in added)
    loaded = run.load_cell(LIKE_CELL)
    loaded["bench"] = dict(bench, per_layer=added)
    got = run.layer_metrics(loaded, _view())
    assert set(got) == set(NAMES) and got["solver_steps_per_job"] == {"value": 19.0, "unit": "count"}
    loaded = run.load_cell("kmeans_300_c1")
    loaded["bench"] = dict(bench, per_layer=added)
    assert run.layer_metrics(loaded, _view()) == {}
