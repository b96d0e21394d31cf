"""The tall SVD cell on the CPU at a size a test can hold: a run end to end,
through the program's one-chip route, is ``correct``; the control and every
planted fault come out not correct by the number named; the reference judged
against itself is sound and agrees with numpy float64; the work model by
hand; the two readers on a recorded window, and nothing to read on a program
without the spans.  The file adds its own tiny configuration and cell to a
copy of ``perf/`` as new files.

The route is the chip's (``qr.tall_route``: float32, at least ``MIN_BYTES``,
a process on a TPU): each child steers ``jax.default_backend`` and the size
floor, and takes blocks of 4 096 rows, so that 20 000 rows make four blocks
and a ragged tail."""

import importlib
import json
import os
import shutil

import numpy as np
import pytest

from conftest import PERF, REPO, result_lines, run_child

from tools.limits_probe_svd import FAULTS

CELL, CONFIG, LIKE_CELL, LIKE_CONFIG = "tiny_svd_c1", "tiny-svd", "svd_300_c1", "svd-cityscapes-1chip"
ROWS, COLUMNS, BLOCK = 20_000, 300, 4096
NUMBERS = {"sv_rel", "u_orth", "v_orth", "recon_rel", "lead_angle"}

#: the child's steering: the chip's route, small blocks
ON_THE_CHIPS_ROUTE = f"""
import importlib
qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
jax.default_backend = lambda: "tpu"
qr_mod.MIN_BYTES = 0
qr_mod.BLOCK_ROWS = {BLOCK}
"""


def _read(path):
    with open(path) as fh:
        return json.load(fh)


def _dump(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


@pytest.fixture(scope="module")
def svd_copy(copy, tmp_path_factory):
    """The session's copy with the committed configuration at 20 000 x 300
    under new names; the cell's limits are the committed cell's."""
    dst = str(tmp_path_factory.mktemp("svd_copy") / "copy")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    conf = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    conf["name"] = CONFIG
    conf["data"].update(rows=ROWS)
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


def test_a_run_end_to_end_is_correct(svd_copy):
    proc = run_child(svd_copy, ON_THE_CHIPS_ROUTE + "sys.exit(" + RUN.format(cell=CELL, seed=3_000_000_019) + ")")
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == NUMBERS
    assert set(line["metrics"]) == {"job_ms", "setup_s"}  # job_p95_ms keeps its list
    assert line["jobs_compared"] == 1 and line["failed"] == 0


CONTROL = """
import importlib, datagen
loaded = run.load_cell({cell!r})
config, limits = loaded["config"], loaded["own"]["limits"]
entry = importlib.import_module("jobs." + config["entry"])
reference = importlib.import_module("references." + config["reference"])
for seed in (11, 2**31 + 5):
    x = datagen.make(config["data"], seed, jax.devices())
    for who, outputs in (("control", entry.control(config, x, seed)), ("reference", reference.svd(x, jax.numpy.float32))):
        numbers = entry.judge(config, x, outputs, seed)
        print(json.dumps({{"seed": seed, "who": who, "over": sorted(n for n in limits if not numbers[n] <= limits[n])}}))
"""


def test_the_control_is_not_correct_and_the_reference_is(svd_copy):
    lines = result_lines(run_child(svd_copy, CONTROL.format(cell=CELL)))
    assert len(lines) == 4
    for line in lines:
        if line["who"] == "control":  # bfloat16 data: U's columns and the factorization are off
            assert {"u_orth", "recon_rel"} <= set(line["over"]), line
        else:
            assert line["over"] == [], line


#: child body: the cell once sound, then once under each fault planted in the program
FAULT_RUNS = ON_THE_CHIPS_ROUTE + """
from tools.limits_probe_svd import FAULTS
def go(tag):
    print(json.dumps({{"tag": tag}}), flush=True)
    {run}
go("sound")
for name, (fault, _) in FAULTS.items():
    with fault():
        go(name)
"""


@pytest.fixture(scope="module")
def verdicts(svd_copy):
    proc = run_child(svd_copy, FAULT_RUNS.format(run=RUN.format(cell=CELL, seed=424243)), timeout=1200)
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


def test_the_reference_by_blocks_against_numpy():
    """Blocks that do not divide the rows; the spectrum and the leading vectors
    against numpy float64; the judge on outputs of the wrong shape."""
    import jax.numpy as jnp

    reference = importlib.import_module("references.svd_plain")
    rng = np.random.default_rng(7)
    host = (rng.standard_normal((3000, 40)) * np.linspace(1.0, 50.0, 40)).astype(np.float32)
    x = jnp.asarray(host)
    s, v = reference.spectrum(x, jnp.float32, block=700)
    u64, s64, vt64 = np.linalg.svd(host.astype(np.float64), full_matrices=False)
    np.testing.assert_allclose(s, s64, rtol=2e-6)
    out = reference.svd(x, jnp.float32, block=700)
    lead = np.abs(np.sum(np.asarray(out["U"], np.float64)[:, :8] * u64[:, :8], axis=0))
    np.testing.assert_allclose(lead, 1.0, atol=1e-5)
    numbers = reference.judge(x, out, seed=5, block=700)
    assert set(numbers) == NUMBERS and max(numbers.values()) < 1e-5, numbers
    short = dict(out, U=out["U"][:, :-1])
    assert set(reference.judge(x, short, seed=5).values()) == {float("inf")}


def test_work_by_hand():
    entry = importlib.import_module("jobs.svd_tall")
    config = {"data": {"rows": 6291456, "features": 300}}
    a = 6291456 * 300 * 4
    # two reads of A and one write of U, S and V written; two products of 2 m n^2
    assert entry.work(config) == {"bytes": 3 * a + 300 * 4 + 300 * 300 * 4, "flops": 4 * 6291456 * 300 * 300,
                                  "flops_peak": "bf16_tflops", "a_bytes": a, "pass_flops": 2 * 6291456 * 300 * 300}


def test_least_time_of_the_cell():
    least = importlib.import_module("layer_metrics.roofline_pct").least_seconds
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    # memory-bound at one bf16 pass: 22.65 GB at 819 GB/s, 27.7 ms (the products 11.5 ms)
    assert least({"work": work, "peaks": peaks, "chips": 1}) == pytest.approx(22.65e9 / 819e9, rel=1e-3)


# --------------------------------------------------------------------- #
# the two readers                                                        #
# --------------------------------------------------------------------- #
JOBS, M, N = 2, 8192, 64


def _readers():
    return [importlib.import_module("layer_metrics." + n).read for n in ("a_passes_per_job", "factor_roofline_pct")]


def _view(jobs=JOBS, busy=0.5):
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    work = importlib.import_module("jobs.svd_tall").work({"data": {"rows": 6291456, "features": 300}})
    return {"trace": {"jobs": jobs, "window_s": 1.0, "busy_s": busy}, "work": work, "peaks": peaks, "chips": 1}


def test_nothing_to_read_where_the_program_records_no_such_field():
    """The parent commit: no span at the site, or spans without the field, or
    no ``profiled_spans`` at all.  The readers return None and do not raise."""
    from heat_tpu import telemetry

    telemetry.reset()
    gone = telemetry.profiled_spans
    try:
        for spans in ((), ({"site": "fuse:replay", "kind": "launch", "id": 1},)):
            telemetry.profiled_spans = lambda spans=spans: spans
            assert [read(_view()) for read in _readers()] == [None, None]
        del telemetry.profiled_spans
        assert [read(_view()) for read in _readers()] == [None, None]
    finally:
        telemetry.profiled_spans = gone
    # a work model without ``a_bytes`` (another configuration's): the share has nothing to read
    telemetry.profiled_spans = lambda: (
        {"site": "jitted:linalg.svd", "kind": "launch", "id": 1, "a_passes": 3, "precision": "highest"},)
    try:
        view = _view()
        view["work"] = {}
        assert _readers()[1](view) is None and _readers()[0](view) == 1.5
    finally:
        telemetry.profiled_spans = gone


@pytest.fixture(scope="module")
def window(tmp_path_factory):
    """Two tiny SVDs on one device under a profiler trace, as ``run.py
    --trace 1`` takes its window, on the chip's route."""
    import jax

    import heat_tpu as ht
    from heat_tpu import telemetry
    from heat_tpu.core.communication import XlaCommunication

    qr_mod = importlib.import_module("heat_tpu.core.linalg.qr")
    telemetry.disable()
    telemetry.reset()
    x = ht.array(np.random.default_rng(5).standard_normal((M, N)).astype(np.float32),
                 split=0, comm=XlaCommunication(jax.devices()[:1]))
    with pytest.MonkeyPatch.context() as one_tpu:
        one_tpu.setattr(jax, "default_backend", lambda: "tpu")
        one_tpu.setattr(qr_mod, "MIN_BYTES", 0)
        jax.profiler.start_trace(str(tmp_path_factory.mktemp("trace")))
        try:
            for _ in range(JOBS):
                with jax.profiler.TraceAnnotation("perf_job"):
                    jax.block_until_ready(ht.linalg.svd(x).U.larray)
            ht.linalg.qr(x)  # another site: not read
        finally:
            jax.profiler.stop_trace()
    yield telemetry
    telemetry.reset()
    jax.clear_caches()


def test_the_readers_on_a_recorded_window(window):
    passes, share = _readers()
    assert passes(_view()) == 3  # two Gram passes and U's
    # each pass at least 2 m n^2 at `highest`'s peak (the cell's shape: 34.4 ms), over 0.5 s busy
    least = 2 * 6291456 * 300 * 300 / 32.833333333333336e12
    assert share(_view()) == pytest.approx(100.0 * JOBS * 3 * least / 0.5)
    spans = [e for e in window.profiled_spans() if e["site"] == "jitted:linalg.svd"]
    assert len(spans) == JOBS
    assert {(e["route"], e["a_passes"], e["precision"], e["u"]) for e in spans} == {("cholqr2", 3, "highest", "direct")}


def test_found_by_name_in_their_cell_alone(window):
    import run

    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    added = [m for m in bench["per_layer"] if m["name"] in ("a_passes_per_job", "factor_roofline_pct")]
    assert [m["workloads"] for m in added] == [[LIKE_CELL], [LIKE_CELL]]
    assert [(m["layer"], m["moves"], m["source"]) for m in added] == [
        ("linalg", "job_ms", "program_counter"), ("kernels", "job_ms", "device_trace")]
    loaded = run.load_cell(LIKE_CELL)
    loaded["bench"] = dict(bench, per_layer=added)
    got = run.layer_metrics(loaded, _view())
    assert got["a_passes_per_job"] == {"value": 3.0, "unit": "count"} and got["factor_roofline_pct"]["unit"] == "%"
    loaded = run.load_cell("kmeans_300_c1")
    loaded["bench"] = dict(bench, per_layer=added)
    assert run.layer_metrics(loaded, _view()) == {}
