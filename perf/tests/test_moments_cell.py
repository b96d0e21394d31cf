"""The moments cell on the CPU at a size a test can hold: a run end to end is
``correct``; the control and every planted fault come out not correct by the
number named; the reference by blocks against numpy float64; the work model
by hand; the ``operand_reads_per_job`` reader on a recorded window.  The file
adds its own tiny configuration and cell to a copy of ``perf/`` as new files
(``conftest.py`` is as it was), the way
``test_harness.py::test_new_files_are_found_by_name`` adds its own."""

import importlib
import json
import os
import shutil

import numpy as np
import pytest

from conftest import PERF, REPO, result_lines, run_child

from tools.limits_probe_moments import FAULTS

CELL, CONFIG, LIKE_CELL, LIKE_CONFIG = "tiny_moments_c1", "tiny-moments", "moments_300_c1", "moments-cityscapes-1chip"
FEATURES = 8192
NUMBERS = {"mean_err_all", "std_rel_all", "mean_err_f64", "std_rel_f64"}


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


@pytest.fixture(scope="module")
def moments_copy(copy, tmp_path_factory):
    """The session's copy with the committed moments configuration at 8192
    columns under new names; the cell's limits are the committed cell's."""
    dst = str(tmp_path_factory.mktemp("moments_copy") / "copy")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    conf = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    conf["name"] = CONFIG
    conf["data"]["features"] = FEATURES
    _dump(os.path.join(dst, "perf", "configs", CONFIG + ".json"), conf)
    own = _read(os.path.join(PERF, "workloads", LIKE_CELL + ".json"))
    own.update(name=CELL, config=CONFIG)
    _dump(os.path.join(dst, "perf", "workloads", CELL + ".json"), own)
    bench = _read(os.path.join(dst, "BENCHMARK.json"))
    bench["configs"].append({"name": CONFIG, "source": "test", "file": f"perf/configs/{CONFIG}.json",
                             "reduced": ["features"], "why": "a size a test can hold"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "closed_loop", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


RUN = "run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0'])"


def test_a_run_end_to_end_is_correct(moments_copy):
    proc = run_child(moments_copy, "sys.exit(" + RUN.format(cell=CELL, seed=3_000_000_019) + ")")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == NUMBERS
    assert set(line["metrics"]) == {"job_ms", "setup_s"}  # job_p95_ms keeps its list
    assert line["jobs_compared"] == 2 and line["failed"] == 0


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


def test_the_control_is_not_correct(moments_copy):
    lines = result_lines(run_child(moments_copy, CONTROL.format(cell=CELL)))
    assert len(lines) == 2
    for line in lines:
        assert set(line["over"]) == NUMBERS, line


#: child body: the cell once sound, then once under each fault planted in the program
FAULT_RUNS = """
from tools.limits_probe_moments import FAULTS
def go(tag):
    print(json.dumps({{"tag": tag}}), flush=True)
    {run}
go("sound")
for name, (fault, _) in FAULTS.items():
    with fault():
        go(name)
"""


@pytest.fixture(scope="module")
def verdicts(moments_copy):
    proc = run_child(moments_copy, FAULT_RUNS.format(run=RUN.format(cell=CELL, seed=424243)))
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


def test_the_reference_by_blocks_against_float64():
    """Blocks of columns that do not divide the width; a column whose mean is
    a thousand times its deviation; the judge on the reference's own results
    and on results with one column altered."""
    import jax.numpy as jnp

    reference = importlib.import_module("references.moments_plain")
    rng = np.random.default_rng(7)
    host = (rng.standard_normal((300, 1000)) * rng.uniform(0.5, 20.0, 1000) + rng.normal(0, 10, 1000)).astype(np.float32)
    host[:, 17] = (1000.0 + rng.standard_normal(300)).astype(np.float32)
    x = jnp.asarray(host)
    got = reference.moments(x, jnp.float32, block=384)
    h64 = host.astype(np.float64)
    assert got["mean"].shape == got["std"].shape == (1000,)
    np.testing.assert_allclose(np.asarray(got["mean"]), h64.mean(0), rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(got["std"]), h64.std(0), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(reference.moments(x, jnp.float32, ddof=1, block=384)["std"]),
                               h64.std(0, ddof=1), rtol=2e-5)
    numbers = reference.judge(x, got, seed=5, block=384)
    assert set(numbers) == NUMBERS and max(numbers.values()) < 5e-5, numbers
    altered = dict(got, std=got["std"].at[999].multiply(1.001))
    numbers = reference.judge(x, altered, seed=5, block=384)
    assert numbers["std_rel_all"] == pytest.approx(1e-3, rel=1e-2) and numbers["mean_err_all"] < 5e-5
    short = dict(got, mean=got["mean"][:-1])
    assert set(reference.judge(x, short, seed=5).values()) == {float("inf")}


def test_work_by_hand():
    work = importlib.import_module("jobs.moments").work
    got = work({"data": {"rows": 300, "features": 1000}, "job": {"functions": ["mean", "std"], "axis": 0, "ddof": 0}})
    # X read once a call, two results written; 1 + 3 operations an element
    assert got == {"bytes": 2 * 300_000 * 4 + 2 * 1000 * 4, "flops": 4 * 300_000, "flops_peak": "bf16_tflops"}


def test_least_time_of_the_cell():
    least = importlib.import_module("layer_metrics.roofline_pct").least_seconds
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    # memory-bound: two reads of 7.55 GB at 819 GB/s, 18.4 ms
    assert least({"work": work, "peaks": peaks, "chips": 1}) == pytest.approx(15.15e9 / 819e9, rel=1e-3)


def test_the_job_entry_refuses_another_job():
    entry = importlib.import_module("jobs.moments")
    with pytest.raises(ValueError):
        entry._job({"job": {"functions": ["std", "mean"], "axis": 0, "ddof": 0}})


# --------------------------------------------------------------------- #
# the reader of the layer ``array ops``                                 #
# --------------------------------------------------------------------- #
NAME = "operand_reads_per_job"
JOBS = 3


def _reader():
    return importlib.import_module("layer_metrics." + NAME).read


def _view(jobs=JOBS):
    return {"trace": {"jobs": jobs, "window_s": 1.0, "busy_s": 0.5}}


def test_nothing_to_read_where_the_program_records_no_such_field():
    """The parent commit: launch spans at these sites without the field, or
    no ``profiled_spans`` at all.  The reader returns None and does not raise."""
    from heat_tpu import telemetry

    telemetry.reset()
    assert _reader()(_view()) is None
    gone = telemetry.profiled_spans
    del telemetry.profiled_spans
    try:
        assert _reader()(_view()) is None
    finally:
        telemetry.profiled_spans = gone
    telemetry.profiled_spans = lambda: ({"site": "jitted:stat.mean", "kind": "launch", "id": 1},)
    try:
        assert _reader()(_view()) is None
    finally:
        telemetry.profiled_spans = gone


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """Three tiny jobs on one device under a profiler trace, as ``run.py
    --trace 1`` takes its window; ``telemetry.enable()`` is never called.  A
    ``ht.sum`` and a cdist beside them leave spans the reader must pass by."""
    import jax

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core.communication import XlaCommunication

    telemetry.disable()
    telemetry.reset()
    entry = importlib.import_module("jobs.moments")
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    x = ht.array(np.random.default_rng(5).standard_normal((96, 64)).astype(np.float32),
                 split=0, comm=XlaCommunication(jax.devices()[:1]))
    entry.run(ht, config, x, -1, 0)
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
    try:
        for i in range(JOBS):
            with jax.profiler.TraceAnnotation("perf_job"):
                jax.block_until_ready(entry.run(ht, config, x, i, 0))
        ht.sum(x, axis=0)
        ht.spatial.cdist(x)
    finally:
        jax.profiler.stop_trace()
    yield telemetry
    telemetry.reset()


def test_the_reader_on_a_recorded_window(window):
    assert _reader()(_view()) == 3.0  # one read for the mean, two for the deviation
    assert _reader()(_view(2 * JOBS)) == 1.5
    spans = window.profiled_spans()
    launches = [e for e in spans if e["kind"] == "launch" and e["site"].startswith("jitted:stat.")]
    assert len(launches) == 2 * JOBS and {e["route"] for e in launches} == {"exact"}
    assert sum(1 for e in spans if e["kind"] == "entry" and e["site"] in ("stat:mean", "stat:std")) == 2 * JOBS


def test_found_by_name_in_its_cell_alone(window):
    import run

    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    (added,) = [m for m in bench["per_layer"] if m["name"] == NAME]
    assert added["workloads"] == [LIKE_CELL] and added["moves"] == "job_ms" and added["layer"] == "array ops"
    loaded = run.load_cell(LIKE_CELL)
    loaded["bench"] = dict(bench, per_layer=[added])
    assert run.layer_metrics(loaded, _view()) == {NAME: {"value": 3.0, "unit": "count"}}
    loaded = run.load_cell("kmeans_300_c1")
    loaded["bench"] = dict(bench, per_layer=[added])
    assert run.layer_metrics(loaded, _view()) == {}
