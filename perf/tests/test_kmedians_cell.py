"""The KMedians cell on the CPU at a size a test can hold: a run end to end is
``correct``; the control and every planted fault of the medians come out not
correct by the number named, a label altered by ``label_gap``; the two faults
of the assignment that blobs this far apart cannot show read sound here too
(tier-1 holds them on data where they differ:
``tests/test_kmedians_reference.py``); the reference by blocks against numpy;
the work model by hand; the two readers on a recorded window.  The file adds
its own tiny configuration and cell to a copy of ``perf/`` as new files."""

import importlib
import json
import os
import shutil

import numpy as np
import pytest

from conftest import PERF, REPO, result_lines, run_child

from tools.limits_probe_kmedians import FAULTS, UNSEEN

CELL, CONFIG, LIKE_CELL, LIKE_CONFIG = "tiny_kmedians_c1", "tiny-kmedians", "kmedians_300_c1", "kmedians-cityscapes-1chip"
ROWS, FEATURES = 296, 8192
NUMBERS = {"label_gap", "median_step", "median_f64", "iters_off"}


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


@pytest.fixture(scope="module")
def kmedians_copy(copy, tmp_path_factory):
    """The session's copy with the committed configuration at 296 x 8192
    under new names; the cell's limits are the committed cell's."""
    dst = str(tmp_path_factory.mktemp("kmedians_copy") / "copy")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    conf = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    conf["name"] = CONFIG
    conf["data"].update(rows=ROWS, features=FEATURES)
    _dump(os.path.join(dst, "perf", "configs", CONFIG + ".json"), conf)
    own = _read(os.path.join(PERF, "workloads", LIKE_CELL + ".json"))
    own.update(name=CELL, config=CONFIG)
    _dump(os.path.join(dst, "perf", "workloads", CELL + ".json"), own)
    bench = _read(os.path.join(dst, "BENCHMARK.json"))
    bench["configs"].append({"name": CONFIG, "source": "test", "file": f"perf/configs/{CONFIG}.json",
                             "reduced": ["rows", "features"], "why": "a size a test can hold"})
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "closed_loop", "chips": 1, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


RUN = "run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0'])"


def test_a_run_end_to_end_is_correct(kmedians_copy):
    proc = run_child(kmedians_copy, "sys.exit(" + RUN.format(cell=CELL, seed=3_000_000_019) + ")")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == NUMBERS
    assert line["compared"]["median_step"]["value"] == 0.0  # exact medians
    assert set(line["metrics"]) == {"job_ms", "setup_s"}  # job_p95_ms keeps its list
    assert line["jobs_compared"] in (1, 2) and line["failed"] == 0  # a fit on the CPU may fill the window alone


CONTROL = """
import importlib, datagen
from tools.limits_probe_kmedians import one_label_altered
loaded = run.load_cell({cell!r})
config, limits = loaded["config"], loaded["own"]["limits"]
entry = importlib.import_module("jobs." + config["entry"])
for seed in (11, 2**31 + 5):
    x = datagen.make(config["data"], seed, jax.devices())
    out = entry.control(config, x, seed)
    for who, outputs in (("control", out), ("one_label_altered", one_label_altered(out))):
        numbers = entry.judge(config, x, outputs, seed)
        print(json.dumps({{"seed": seed, "who": who, "over": sorted(n for n in limits if not numbers[n] <= limits[n])}}))
"""


def test_the_control_and_an_altered_label_are_not_correct(kmedians_copy):
    lines = result_lines(run_child(kmedians_copy, CONTROL.format(cell=CELL)))
    assert len(lines) == 4
    for line in lines:
        if line["who"] == "control":  # bfloat16 medians are off; its own labels are its nearest
            assert {"median_step", "median_f64"} <= set(line["over"]), line
        else:
            assert "label_gap" in line["over"], line


#: child body: the cell once sound, then once under each fault planted in the program
FAULT_RUNS = """
from tools.limits_probe_kmedians import FAULTS, UNSEEN
def go(tag):
    print(json.dumps({{"tag": tag}}), flush=True)
    {run}
go("sound")
for name, (fault, _) in FAULTS.items():
    with fault():
        go(name)
for name, fault in UNSEEN.items():
    with fault():
        go(name)
"""


@pytest.fixture(scope="module")
def verdicts(kmedians_copy):
    proc = run_child(kmedians_copy, FAULT_RUNS.format(run=RUN.format(cell=CELL, seed=424243)), timeout=1200)
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


@pytest.mark.parametrize("fault", sorted(UNSEEN))
def test_a_fault_of_the_assignment_reads_sound_on_blobs_this_far_apart(verdicts, fault):
    """Said, not hidden (``PERF.md`` section 2): the cell cannot see these."""
    assert verdicts[fault]["correct"] is True, verdicts[fault]["compared"]


def test_the_reference_by_blocks_against_numpy():
    """Blocks of columns that do not divide the width; odd and even member
    counts; an empty cluster; the judge on the reference's own fit and on
    centres with one coordinate altered."""
    import jax
    import jax.numpy as jnp

    reference = importlib.import_module("references.kmedians_plain")
    rng = np.random.default_rng(7)
    centres = 10.0 * rng.standard_normal((5, 1000))
    host = (centres[np.arange(63) % 5] + rng.standard_normal((63, 1000))).astype(np.float32)
    labels = (np.arange(63) % 5).astype(np.int32)
    labels[labels == 4] = 3  # cluster 4 without rows; 13, 13, 13, 24 members
    x = jnp.asarray(host)
    med = np.asarray(reference.medians(x, jnp.asarray(labels), 5, jnp.float32, block=384))
    for c in range(4):
        assert np.array_equal(med[c], np.median(host[labels == c], axis=0))
    assert not med[4].any()
    d = np.asarray(reference.distances(x, jnp.asarray(centres, jnp.float32), jnp.float32, block=384))
    want = np.abs(host[:, None, :].astype(np.float64) - centres[None]).sum(-1)
    np.testing.assert_allclose(d, want, rtol=2e-6)
    fit = reference.fit(x, 5, 6, jax.random.key(1), jnp.float32, block=384)
    numbers = reference.judge(x, fit, seed=5, asked_iters=6, block=384)
    assert set(numbers) == NUMBERS and numbers["median_step"] == 0.0 and numbers["label_gap"] == 0.0
    assert numbers["median_f64"] < 1e-7 and numbers["iters_off"] == 0.0
    altered = dict(fit, centres=fit["centres"].at[0, 999].add(0.5))
    assert reference.judge(x, altered, seed=5, asked_iters=6, block=384)["median_step"] > 1e-3
    short = dict(fit, centres=fit["centres"][:, :-1])
    assert set(reference.judge(x, short, seed=5, asked_iters=6).values()) == {float("inf")}
    assert reference.judge(x, fit, seed=5, asked_iters=7, block=384)["iters_off"] == 1.0


def test_work_by_hand():
    entry = importlib.import_module("jobs.kmedians_fit")
    config = {"data": {"rows": 300, "features": 1000}, "job": {"clusters": 8, "iterations": 30}}
    x, centres = 300 * 1000 * 4, 8 * 1000 * 4
    # 31 reads of X; the centres read and written in each sweep and read once more; labels and centres written
    assert entry.work(config) == {"bytes": 31 * x + 61 * centres + 300 * 8 + centres, "flops": 3 * 300 * 1000 * 8 * 31,
                                  "x_bytes": x, "flops_peak": "bf16_tflops"}
    kernels = entry.kernel_work(config)
    assert kernels["assign"] == {"bytes": x + centres, "vector_ops": 3 * 300 * 1000 * 8}
    assert kernels["medians"] == {"bytes": x + centres, "vector_ops": None}


def test_least_time_of_the_cell():
    least = importlib.import_module("layer_metrics.roofline_pct").least_seconds
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    # memory-bound: 31 reads of 7.55 GB and 62 of the 201 MB of centres at 819 GB/s, 301 ms
    assert least({"work": work, "peaks": peaks, "chips": 1}) == pytest.approx(246.52e9 / 819e9, rel=1e-3)


# --------------------------------------------------------------------- #
# the two readers                                                        #
# --------------------------------------------------------------------- #
JOBS, SWEEPS = 2, 3


def _readers():
    return [importlib.import_module("layer_metrics." + n).read for n in ("x_passes_per_job", "pass_roofline_pct")]


def _view(jobs=JOBS, busy=0.5, x_bytes=4.0e9):
    return {"trace": {"jobs": jobs, "window_s": 1.0, "busy_s": busy}, "work": {"x_bytes": x_bytes},
            "peaks": {"hbm_gb_per_sec": 800.0}, "chips": 1}


def test_nothing_to_read_where_the_program_records_no_such_field():
    """The parent commit: no span at the site, or spans without the field, or
    no ``profiled_spans`` at all.  The readers return None and do not raise."""
    from heat_tpu import telemetry

    telemetry.reset()
    gone = telemetry.profiled_spans
    try:
        for spans in ((), ({"site": "jit:kmedians.fit", "kind": "launch", "id": 1},)):
            telemetry.profiled_spans = lambda spans=spans: spans
            assert [read(_view()) for read in _readers()] == [None, None]
        del telemetry.profiled_spans
        assert [read(_view()) for read in _readers()] == [None, None]
    finally:
        telemetry.profiled_spans = gone
    # a work model without ``x_bytes`` (another configuration's): the share has nothing to read
    telemetry.profiled_spans = lambda: ({"site": "jit:kmedians.fit", "kind": "launch", "id": 1, "x_passes": 7},)
    try:
        view = _view()
        view["work"] = {}
        assert _readers()[1](view) is None and _readers()[0](view) == 3.5
    finally:
        telemetry.profiled_spans = gone


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """Two tiny fits on one device under a profiler trace, as ``run.py
    --trace 1`` takes its window, the kernel answered by the interpreter so
    that the fit takes the route whose span states ``x_passes``."""
    import jax

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core import _colmedian
    from heat_tpu.core.communication import XlaCommunication

    telemetry.disable()
    telemetry.reset()
    x = ht.array(np.random.default_rng(5).standard_normal((24, 1024)).astype(np.float32),
                 split=0, comm=XlaCommunication(jax.devices()[:1]))
    with pytest.MonkeyPatch.context() as one_tpu:
        one_tpu.setattr(jax, "default_backend", lambda: "tpu")
        one_tpu.setattr(jax, "device_count", lambda: 1)
        one_tpu.setattr(_colmedian, "_interpret", lambda: True)
        one_tpu.setattr(_colmedian, "MIN_BYTES", 0)
        jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
        try:
            for i in range(JOBS):
                with jax.profiler.TraceAnnotation("perf_job"):
                    km = ht.cluster.KMedians(3, max_iter=SWEEPS, tol=-1.0, random_state=i).fit(x)
                    jax.block_until_ready(km.cluster_centers_.larray)
            ht.spatial.manhattan(x)
        finally:
            jax.profiler.stop_trace()
    yield telemetry
    telemetry.reset()
    jax.clear_caches()


def test_the_readers_on_a_recorded_window(window):
    passes, share = _readers()
    assert passes(_view()) == 2 * SWEEPS + 1  # two a sweep, one for the last assignment
    # 14 passes of 4 GB at 800 GB/s are 0.07 s of the 0.5 s the device was busy
    assert share(_view()) == pytest.approx(100.0 * 14 * 4.0e9 / 800e9 / 0.5)
    spans = [e for e in window.profiled_spans() if e["site"] == "jit:kmedians.fit"]
    assert len(spans) == JOBS and {e["medians"] for e in spans} == {"column_select"}


def test_found_by_name_in_their_cell_alone(window):
    import run

    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    added = [m for m in bench["per_layer"] if m["name"] in ("x_passes_per_job", "pass_roofline_pct")]
    assert [m["workloads"] for m in added] == [[LIKE_CELL], [LIKE_CELL]]
    assert [(m["layer"], m["moves"], m["source"]) for m in added] == [
        ("estimators", "job_ms", "program_counter"), ("kernels", "job_ms", "device_trace")]
    loaded = run.load_cell(LIKE_CELL)
    loaded["bench"] = dict(bench, per_layer=added)
    got = run.layer_metrics(loaded, _view())
    assert got["x_passes_per_job"] == {"value": 7.0, "unit": "count"} and got["pass_roofline_pct"]["unit"] == "%"
    loaded = run.load_cell("kmeans_300_c1")
    loaded["bench"] = dict(bench, per_layer=added)
    assert run.layer_metrics(loaded, _view()) == {}
