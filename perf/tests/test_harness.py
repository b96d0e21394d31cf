"""The harness end to end at a tiny size on the CPU, through a tiny
configuration and cell that the test adds to a temporary copy as new files;
off the chip ``run.py`` as committed gives no result; a cell, a
configuration, a job entry and a per-layer metric added as new files are
found by name."""

import functools
import json
import os
import subprocess
import sys

import pytest

from conftest import PERF, REPO, result_lines, run_child

RUN = "sys.exit(run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0']))"

#: the same run, with a look at jax's backends where ``run.main`` takes its
#: ``imports`` mark (the call of ``require_chip`` follows it at once) and where
#: it takes its ``backend`` mark (``datagen.make`` is the next thing it calls).
#: The CPU's backend is up in milliseconds, so the look for a chip also sleeps
#: ``SLOW_START_S``: a runtime's start that the line has to show taken out
SLOW_START_S = 0.4
WATCHED_RUN = """
import time
import datagen
from jax._src import xla_bridge
seen = {{}}
def _watch(module, name, mark, pause=0.0):
    inner = getattr(module, name)
    def watched(*args, **kwargs):
        if mark not in seen:
            seen[mark] = xla_bridge.backends_are_initialized()
            print("backends_initialised_at " + json.dumps(seen), file=sys.stderr)
        time.sleep(pause)
        return inner(*args, **kwargs)
    setattr(module, name, watched)
_watch(run, "require_chip", "imports", pause={pause})
_watch(datagen, "make", "backend")
sys.exit(run.main(['--workload', {cell!r}, '--seed', '{seed}', '--seconds', '0.5', '--trace', '0']))
"""

CELLS = [("tiny_kmeans_c1", 1), ("tiny_cdist_c1", 1), ("tiny_kmeans_c4", 4)]


@functools.cache
def ran(copy, cell, devices):
    """One watched run of the cell, shared by the tests that read it."""
    body = WATCHED_RUN.format(cell=cell, seed=3_000_000_019, pause=SLOW_START_S)
    return run_child(copy, body, devices=devices)


@pytest.mark.parametrize("cell,devices", CELLS)
def test_a_run_end_to_end(copy, cell, devices):
    proc = ran(copy, cell, devices)
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
    # set-up is process start to the first measured job LESS the backend's
    # start, which the line reports apart, beside the marks
    marks, backend_start_s = line["setup_marks_s"], line["backend_start_s"]
    assert list(marks) == ["imports", "backend", "data", "warm_up"]
    assert backend_start_s > SLOW_START_S
    assert backend_start_s == pytest.approx(marks["backend"] - marks["imports"], abs=1e-9)
    setup_s = line["metrics"]["setup_s"]["value"]
    assert setup_s < marks["warm_up"]
    # what follows the ``warm_up`` mark in set-up (a collection, the heap frozen) is short
    assert 0 <= setup_s - (marks["warm_up"] - backend_start_s) < 0.5
    # each number beside its limit closes standard error
    tail = [l for l in proc.stderr.strip().splitlines() if l.startswith("compared ")]
    assert len(tail) == len(line["compared"])
    assert proc.stderr.strip().splitlines()[-1].startswith("compared ")


@pytest.mark.parametrize("cell,devices", CELLS)
def test_the_backend_starts_in_its_own_stage(copy, cell, devices):
    """The order of the stages is part of ``setup_s``'s definition: no jax
    backend is up when the ``imports`` mark is taken, one is at the
    ``backend`` mark.  The runtime's start then lies whole in the stage that
    ``setup_s`` leaves out, in every cell alike."""
    proc = ran(copy, cell, devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    seen = [l.split(" ", 1)[1] for l in proc.stderr.splitlines() if l.startswith("backends_initialised_at ")]
    assert json.loads(seen[-1]) == {"imports": False, "backend": True}


def test_nothing_ahead_of_the_backend_stage_starts_a_backend():
    """What ``run.main`` imports and calls ahead of its ``imports`` mark, for
    every configuration of ``BENCHMARK.json`` (those later PRs add too):
    ``import heat_tpu``, the compile cache placed, ``import jax``, ``datagen``
    and the job entry.  A later PR that makes one of them bring the backend up
    fails here instead of moving the runtime's start into what is counted."""
    body = """
import importlib, json, os, sys
sys.path.insert(0, {perf!r}); sys.path.insert(1, {repo!r})
import run
with open(os.path.join({repo!r}, "BENCHMARK.json")) as fh:
    bench = json.load(fh)
entries = sorted({{run._read_json(os.path.join({repo!r}, c["file"]))["entry"] for c in bench["configs"]}})
import heat_tpu as ht
from heat_tpu.core._compile_cache import place_compile_cache
place_compile_cache()
import jax
import datagen
for entry in entries:
    importlib.import_module("jobs." + entry)
from jax._src import xla_bridge
print(json.dumps({{"entries": entries, "initialised": xla_bridge.backends_are_initialized()}}))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", body.format(perf=PERF, repo=REPO)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=600,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert len(got["entries"]) >= 4 and got["initialised"] is False


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


def test_sets_reads_the_stages_of_set_up():
    """``tools/sets.py``: a line's stages add up to what ``setup_s`` was with
    the backend's start in it, and two sets give medians, spreads and how far
    the medians lie apart."""
    sys.path.insert(0, os.path.join(PERF, "tools"))
    try:
        import sets
    finally:
        sys.path.remove(os.path.join(PERF, "tools"))
    line = {
        "setup_marks_s": {"imports": 5.5, "backend": 13.5, "data": 15.25, "warm_up": 16.75},
        "backend_start_s": 8.0,
        "metrics": {"setup_s": {"value": 8.875}},  # 0.125 s after the warm_up mark
    }
    got = sets.stages(line)
    assert got == {"imports": 5.5, "backend_start": 8.0, "data": 1.75, "warm_up": 1.5, "setup_s_with_backend": 16.875}
    both = sets.two_sets([[8.0, 9.0, 10.0, 11.0, 12.0, 13.0], [9.0, 9.0, 11.0, 11.0, 13.0, 13.0]])
    assert both["medians"] == [10.5, 11.0]
    assert both["spreads"] == pytest.approx([3.5 / 10.5, 4.0 / 11.0])
    assert both["medians_apart"] == pytest.approx(0.5 / 10.5)
