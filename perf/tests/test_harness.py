"""The harness end to end at a tiny size on the CPU, through a tiny
configuration and cell that the test adds to a temporary copy as new files;
off the chip ``run.py`` as committed gives no result; a cell, a
configuration, a job entry and a per-layer metric added as new files are
found by name."""

import json
import os
import subprocess
import sys

import pytest

from conftest import PERF, REPO, result_lines, run_child

RUN = "sys.exit(run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0']))"


@pytest.mark.parametrize("cell,devices", [("tiny_kmeans_c1", 1), ("tiny_cdist_c1", 1), ("tiny_kmeans_c4", 4)])
def test_a_run_end_to_end(copy, cell, devices):
    proc = run_child(copy, RUN.format(cell=cell, seed=3_000_000_019), devices=devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert list(line)[-1] == "compared"  # the numbers compared come last
    assert line["correct"] is True, line["compared"]
    assert line["failed"] == 0 and line["attempted"] >= 1
    # the tail is reported in the cells it lists: those whose window holds hundreds of jobs
    tail = {"job_p95_ms"} if cell == "tiny_cdist_c1" else set()
    assert set(line["metrics"]) == {"job_ms", "setup_s"} | tail
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["count"] == devices and line["device"]["platform"] == "cpu"
    # each number beside its limit closes standard error
    tail = [l for l in proc.stderr.strip().splitlines() if l.startswith("compared ")]
    assert len(tail) == len(line["compared"])
    assert proc.stderr.strip().splitlines()[-1].startswith("compared ")


def test_same_seed_same_inputs(copy):
    body = """
import datagen, numpy as np, zlib
a = np.asarray(datagen.make({"kind": "blobs", "rows": 4100, "features": 32, "centres": 8, "centre_scale": 10.0, "noise": 1.0}, 2**31 + 12345, jax.devices()))
b = np.asarray(datagen.make({"kind": "blobs", "rows": 4100, "features": 32, "centres": 8, "centre_scale": 10.0, "noise": 1.0}, 2**31 + 12345, jax.devices()))
c = np.asarray(datagen.make({"kind": "blobs", "rows": 4100, "features": 32, "centres": 8, "centre_scale": 10.0, "noise": 1.0}, 12345, jax.devices()))
print(json.dumps({"same": bool((a == b).all()), "differs": bool((a != c).any()), "crc": zlib.crc32(a.tobytes())}))
"""
    one = result_lines(run_child(copy, body, devices=1))[-1]
    four = result_lines(run_child(copy, body, devices=4))[-1]
    assert one == four  # the same array, however many devices hold it
    assert one["same"] and one["differs"]


def test_off_the_chip_no_result():
    """The committed ``run.py``, unsteered, on the CPU: another exit code than
    0 and no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(PERF, "run.py"), "--workload", "kmeans_300_c1",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode != 0
    assert "needs a TPU" in proc.stderr
    assert not [l for l in proc.stdout.splitlines() if l.startswith("{")]


def test_fewer_chips_than_the_cell_asks(copy):
    body = """
import run as fresh, importlib
importlib.reload(fresh)   # the unsteered look for a chip
class D:
    platform = "tpu"; device_kind = "TPU v5 lite"
jax.devices = lambda: [D()]
try:
    fresh.require_chip(4, {"TPU v5 lite": {}})
except SystemExit as e:
    print(json.dumps({"refused": str(e)}))
try:
    D.device_kind = "TPU v9"
    fresh.require_chip(1, {"TPU v5 lite": {}})
except SystemExit as e:
    print(json.dumps({"refused": str(e)}))
"""
    lines = result_lines(run_child(copy, body))
    assert "needs 4 chip(s), jax found 1" in lines[0]["refused"]
    assert "no peaks on record" in lines[1]["refused"]


def test_new_files_are_found_by_name(copy, tmp_path):
    """A later PR's additions: a configuration, a cell, a job entry and a
    per-layer metric, each a new file plus an entry in ``BENCHMARK.json``;
    no file that was there is edited."""
    import shutil

    dst = str(tmp_path / "added")
    shutil.copytree(copy, dst, ignore=shutil.ignore_patterns(".jax_cache"))
    perf = os.path.join(dst, "perf")
    before = {}
    for root, _, files in os.walk(perf):
        for f in files:
            p = os.path.join(root, f)
            before[p] = open(p, "rb").read()

    with open(os.path.join(perf, "jobs", "colsum.py"), "w") as fh:
        fh.write('''
import numpy as np
def prepare(ht, config, x):
    return ht.array(x, split=0, copy=False)
def run(ht, config, state, job_index, seed):
    return {"sums": ht.sum(state, axis=0).larray}
def judge(config, x, outputs, seed):
    ref = np.asarray(x, dtype=np.float64).sum(0)
    return {"sum_err": float(np.max(np.abs(np.asarray(outputs["sums"], dtype=np.float64) - ref)))}
def work(config):
    d = config["data"]
    return {"bytes": d["rows"] * d["features"] * 4, "flops": d["rows"] * d["features"], "flops_peak": "bf16_tflops"}
''')
    with open(os.path.join(perf, "configs", "new-colsum.json"), "w") as fh:
        json.dump({
            "name": "new-colsum", "entry": "colsum", "reference": "none",
            "data": {"kind": "normal", "rows": 8192, "features": 16}, "job": {},
        }, fh)
    with open(os.path.join(perf, "workloads", "new_colsum_c1.json"), "w") as fh:
        json.dump({"name": "new_colsum_c1", "config": "new-colsum", "traffic": "closed_loop",
                   "checked_jobs": 2, "limits": {"sum_err": 1e-2}}, fh)
    with open(os.path.join(perf, "layer_metrics", "jobs_traced.py"), "w") as fh:
        fh.write("def read(run):\n    return float(run['trace']['jobs'])\n")
    with open(os.path.join(dst, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    bench["configs"].append({"name": "new-colsum", "source": "test", "file": "perf/configs/new-colsum.json", "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "new_colsum_c1", "config": "new-colsum", "traffic": "closed_loop", "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "jobs_traced", "unit": "count", "better": "higher", "source": "device_trace",
                               "layer": "estimators", "moves": "job_ms", "workloads": ["new_colsum_c1"]})
    with open(os.path.join(dst, "BENCHMARK.json"), "w") as fh:
        json.dump(bench, fh)

    proc = run_child(dst, RUN.format(cell="new_colsum_c1", seed=7))
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and set(line["compared"]) == {"sum_err"}

    # the new reader is found by name, reads its cell and is left out of the others
    body = """
import importlib
loaded = run.load_cell({cell!r})
entry = importlib.import_module("jobs." + loaded["config"]["entry"])
view = {{"trace": {{"jobs": 5, "window_s": 1.0, "busy_s": 0.5, "busy_s_min": 0.5, "launches": 10.0,
         "devices": 1, "collective_s_max": 0.0, "collective_exposed_s_max": 0.0}},
        "compiles_in_window": 0, "memory_peak_bytes": 0, "work": entry.work(loaded["config"]),
        "peaks": loaded["peaks"]["TPU v5 lite"], "chips": 1}}
print(json.dumps(run.layer_metrics(loaded, view)))
"""
    new = result_lines(run_child(dst, body.format(cell="new_colsum_c1")))[-1]
    old = result_lines(run_child(dst, body.format(cell="tiny_kmeans_c1")))[-1]
    assert new["jobs_traced"] == {"value": 5.0, "unit": "count"}
    assert "jobs_traced" not in old and "collective_exposed_pct" not in old
    # one chip: nothing to read for the collective metric; no memory count: left out
    assert set(old) == {"host_ms_per_job", "launches_per_job", "compiles_in_window", "roofline_pct", "device_idle_pct"}
    assert old["device_idle_pct"]["value"] == pytest.approx(50.0)

    for p, content in before.items():
        assert open(p, "rb").read() == content, f"{p} was edited"
