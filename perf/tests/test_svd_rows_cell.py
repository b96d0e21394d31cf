"""The row-sharded SVD cell (``svd_1200_c4``) on the CPU at a size a test can
hold, over four virtual devices: a run end to end through the program's
row-sharded route is ``correct`` and its traced run reads the two new
metrics; the control and every planted fault of ``limits_probe_svd`` come
out not correct; the shard-wise reference agrees with ``svd_plain`` on one
device and over the mesh; the two readers on synthetic spans, with nothing
to read on a program without the route; the committed configuration and
cell load through ``run.load_cell``.  The file adds its own tiny
configuration and cell to a copy of ``perf/`` as new files.

The route is the chip's (``qr.rows_route``: float32, shards of at least n
rows and ``MIN_BYTES``, a process on a TPU): each child steers
``jax.default_backend`` and the size floor, and takes blocks of 1 024 rows,
so that a shard of 4 096 rows makes four blocks."""

import importlib
import json
import os
import shutil

import pytest

from conftest import PERF, REPO, result_lines, run_child

from tools.limits_probe_svd import FAULTS

CELL, CONFIG, LIKE_CELL, LIKE_CONFIG = "tiny_svd_rows_c4", "tiny-svd-rows", "svd_1200_c4", "svd-cityscapes-4chip"
ROWS, COLUMNS, BLOCK, DEVICES = 16_384, 1200, 1024, 4
NUMBERS = {"sv_rel", "u_orth", "v_orth", "recon_rel", "lead_angle"}
NEW = ("shard_factor_roofline_pct", "factor_collective_mb_per_job")

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
def rows_copy(copy, tmp_path_factory):
    """The session's copy with the committed configuration at 16 384 rows
    under new names; the cell's limits are the committed cell's, and every
    metric that lists the committed cell lists this one."""
    dst = str(tmp_path_factory.mktemp("svd_rows_copy") / "copy")
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
    bench["workloads"].append({"name": CELL, "config": CONFIG, "traffic": "closed_loop", "chips": DEVICES, "why": "test"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if LIKE_CELL in m.get("workloads", []):
            m["workloads"].append(CELL)
    _dump(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


RUN = "run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0'])"


def test_a_run_end_to_end_is_correct(rows_copy):
    body = ON_THE_CHIPS_ROUTE + "sys.exit(" + RUN.format(cell=CELL, seed=3_000_000_019) + ")"
    proc = run_child(rows_copy, body, devices=DEVICES, timeout=1200)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == NUMBERS
    assert line["device"]["count"] == DEVICES and line["jobs_compared"] == 1 and line["failed"] == 0
    assert set(line["metrics"]) == {"job_ms", "setup_s"}  # job_p95_ms keeps its list


#: child body: two SVDs of the route under a profiler trace, as ``run.py
#: --trace 1`` takes its window, and the new readers (and the one-chip cell's)
#: on that window's spans; the CPU's trace has no device plane, so the view's
#: summary is given
WINDOW = ON_THE_CHIPS_ROUTE + """
import importlib, tempfile
import heat_tpu as ht
import numpy as np
x = ht.array(np.random.default_rng(5).standard_normal(({rows}, {columns})).astype(np.float32), split=0)
jax.profiler.start_trace(tempfile.mkdtemp())
for _ in range({jobs}):
    with jax.profiler.TraceAnnotation("perf_job"):
        jax.block_until_ready(ht.linalg.svd(x).U.larray)
ht.linalg.qr(x)  # another site: not read
jax.profiler.stop_trace()
loaded = run.load_cell({cell!r})
view = {{"trace": {{"jobs": {jobs}, "window_s": 1.0, "busy_s": 0.5}}, "work": importlib.import_module("jobs.svd_tall").work(loaded["config"]),
        "peaks": loaded["peaks"]["TPU v5 lite"], "chips": {devices}}}
spans = [e for e in ht.telemetry.profiled_spans() if e["site"] == "jitted:linalg.svd"]
metrics = {{name: importlib.import_module("layer_metrics." + name).read(view) for name in {names!r}}}
print(json.dumps({{"metrics": metrics, "spans": spans}}))
"""


def test_the_readers_on_a_recorded_window(rows_copy):
    body = WINDOW.format(rows=4096, columns=64, jobs=JOBS, cell=CELL, devices=DEVICES, names=NEW + ("a_passes_per_job",))
    proc = run_child(rows_copy, body, devices=DEVICES, timeout=600)
    (line,) = result_lines(proc) or [proc.stderr[-3000:]]
    spans, metrics = line["spans"], line["metrics"]
    assert len(spans) == JOBS
    assert {(e["route"], e["a_passes"], e["shards"], e["collective_bytes"]) for e in spans} == {
        ("cholqr2_rows", 3, DEVICES, 2 * 64 * 64 * 4)}
    # the readers take the fields the spans state, the work the cell's configuration states
    least = 2 * ROWS * COLUMNS * COLUMNS / DEVICES / 32.833333333333336e12
    assert metrics["shard_factor_roofline_pct"] == pytest.approx(100.0 * JOBS * 3 * least / 0.5)
    assert metrics["factor_collective_mb_per_job"] == 2 * 64 * 64 * 4 / 1e6
    assert metrics["a_passes_per_job"] == 3  # reads any SVD span; the cell does not list it


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


def test_the_control_is_not_correct_and_the_reference_is(rows_copy):
    lines = result_lines(run_child(rows_copy, CONTROL.format(cell=CELL), devices=DEVICES, timeout=1200))
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
def verdicts(rows_copy):
    body = FAULT_RUNS.format(run=RUN.format(cell=CELL, seed=424243))
    proc = run_child(rows_copy, body, devices=DEVICES, timeout=2400)
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


AGREE = """
import importlib
import numpy as np
import datagen
rows = importlib.import_module("references.svd_rows_plain")
plain = importlib.import_module("references.svd_plain")
data = {{"kind": "blobs", "rows": 6000, "features": 96, "centres": 8, "centre_scale": 10.0, "noise": 1.0}}
f32 = jax.numpy.float32
one = datagen.make(data, 2**31 + 77, jax.devices()[:1])
ref = plain.judge(one, plain.svd(one, f32, block=700), 5, block=700)
s1 = plain.spectrum(one, f32, block=700)[0]
for devices in (1, {devices}):
    x = datagen.make(data, 2**31 + 77, jax.devices()[:devices])
    s = rows.spectrum(x, f32, block=700)[0]
    out = rows.svd(x, f32, block=700)
    got = rows.judge(x, out, 5, block=700)
    print(json.dumps({{"devices": devices, "spectrum": float(np.max(np.abs(s - s1) / s1)), "got": got, "plain": ref,
                      "u_split": len(out["U"].sharding.device_set), "plain_on_rows": plain.judge(one, out, 5, block=700) if devices == 1 else None}}))
"""


def test_the_shard_wise_reference_agrees_with_svd_plain(copy):
    """The same seeded blobs on one device and over the mesh (6 000 rows: 1 500
    a shard, blocks of 700 that do not divide them): the spectrum within
    float32 rounding of ``svd_plain``'s, U made on the shards' own devices,
    and the judge's numbers of the reference's own SVD sound as
    ``svd_plain``'s are."""
    proc = run_child(copy, AGREE.format(devices=DEVICES), devices=DEVICES, timeout=1200)
    lines = result_lines(proc)
    assert len(lines) == 2, proc.stderr[-3000:]
    for line in lines:
        assert line["spectrum"] < 1e-6, line
        assert line["u_split"] == line["devices"]
        assert set(line["got"]) == NUMBERS and max(line["got"].values()) < 1e-5, line
        assert max(line["plain"].values()) < 1e-5
    one = lines[0]
    assert all(abs(one["plain_on_rows"][k] - one["got"][k]) <= 1e-6 for k in NUMBERS), one


def test_the_judge_refuses_outputs_of_the_wrong_shape():
    import jax.numpy as jnp
    import numpy as np

    reference = importlib.import_module("references.svd_rows_plain")
    x = jnp.asarray(np.random.default_rng(3).standard_normal((500, 20)).astype(np.float32))
    out = reference.svd(x, jnp.float32, block=128)
    assert max(reference.judge(x, out, 1, block=128).values()) < 1e-5
    assert set(reference.judge(x, dict(out, U=out["U"][:, :-1]), 1).values()) == {float("inf")}


# --------------------------------------------------------------------- #
# the two readers                                                        #
# --------------------------------------------------------------------- #
JOBS = 2


def _view(busy=0.5):
    peaks = _read(os.path.join(PERF, "peaks.json"))["TPU v5 lite"]
    config = _read(os.path.join(PERF, "configs", LIKE_CONFIG + ".json"))
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    return {"trace": {"jobs": JOBS, "window_s": 1.0, "busy_s": busy}, "work": work, "peaks": peaks, "chips": 4}


def _readers():
    return [importlib.import_module("layer_metrics." + n).read for n in NEW]


def _span(**fields):
    span = {"site": "jitted:linalg.svd", "kind": "launch", "id": 1, "route": "cholqr2_rows", "a_passes": 3,
            "precision": "highest", "collective_bytes": 2 * COLUMNS * COLUMNS * 4}
    span.update(fields)
    return span


@pytest.fixture
def spans():
    from heat_tpu import telemetry

    gone = telemetry.profiled_spans
    recorded = []
    telemetry.profiled_spans = lambda: tuple(recorded)
    yield recorded
    telemetry.profiled_spans = gone


def test_the_readers_on_synthetic_spans(spans):
    share, collective = _readers()
    spans += [_span(), _span(id=2), {"site": "jitted:linalg.qr", "kind": "launch", "id": 3, "route": "cholqr2_rows",
                                     "a_passes": 3, "precision": "highest", "collective_bytes": 1 << 30}]
    # the cell's shape: a pass of 2 m n^2 / 4 at `highest`'s 32.83 TFLOP/s (137.9 ms) against a shard's read of A (9.2 ms)
    least = 2 * 6291456 * COLUMNS * COLUMNS / 4 / 32.833333333333336e12
    assert share(_view()) == pytest.approx(100.0 * JOBS * 3 * least / 0.5)
    assert collective(_view()) == pytest.approx(11.52)  # two 1 200 x 1 200 float32 Grams a job
    # a program at one bf16 pass would be held to the bf16 peak
    spans[:] = [_span(precision="default")]
    assert share(_view()) == pytest.approx(100.0 * 3 * least * 32.833333333333336 / 197.0 / 0.5)


def test_nothing_to_read_without_the_route(spans):
    """The parent commit: no span at the site, the one-chip route's spans, or
    no ``profiled_spans`` at all.  The readers return None and do not raise."""
    from heat_tpu import telemetry

    for found in ([], [{"site": "fuse:replay", "kind": "launch", "id": 1}],
                  [_span(route="cholqr2"), _span(route="householder")]):
        spans[:] = found
        assert [read(_view()) for read in _readers()] == [None, None]
    spans[:] = [_span()]
    view = _view()
    view["work"] = {}
    assert _readers()[0](view) is None and _readers()[1](view) == pytest.approx(11.52 / JOBS)
    del telemetry.profiled_spans
    assert [read(_view()) for read in _readers()] == [None, None]


def test_the_cell_loads_through_run_and_lists_its_metrics():
    import run

    loaded = run.load_cell(LIKE_CELL)
    assert loaded["cell"]["chips"] == 4 and loaded["own"]["checked_jobs"] == 1
    assert loaded["config"]["data"] == {"kind": "blobs", "rows": 6291456, "features": 1200, "centres": 8,
                                        "centre_scale": 10.0, "noise": 1.0}
    assert loaded["config"]["entry"] == "svd_tall" and loaded["config"]["reference"] == "svd_rows_plain"
    assert set(loaded["own"]["limits"]) == NUMBERS
    bench = _read(os.path.join(REPO, "BENCHMARK.json"))
    added = [m for m in bench["per_layer"] if m["name"] in NEW]
    assert [(m["layer"], m["moves"], m["source"], m["workloads"]) for m in added] == [
        ("kernels", "job_ms", "device_trace", [LIKE_CELL]), ("distribution", "job_ms", "program_counter", [LIKE_CELL])]
    read = {m["name"] for m in bench["per_layer"] if LIKE_CELL in m.get("workloads", [LIKE_CELL])}
    assert set(NEW) <= read and "a_passes_per_job" not in read
