"""Shared helpers of the benchmark's own tests (CPU rehearsals; tier-1 does
not collect them):

    JAX_PLATFORMS=cpu python -m pytest perf/tests -q

Nothing here touches a chip.  The end-to-end tests copy ``perf/`` and
``BENCHMARK.json`` into a temporary directory, add a tiny configuration and
cell there as NEW files (``run.py`` has no size option), and run the copy in
a child process with the look for a chip steered from the test.
"""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

PERF = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(PERF)
if PERF not in sys.path:
    sys.path.insert(0, PERF)

#: as many rows a cluster as the one-chip cell has (37), far narrower rows
TINY = {
    "tiny-kmeans": ("kmeans-cityscapes-1chip", {"rows": 296, "features": 8192}),
    "tiny-kmeans-4": ("kmeans-cityscapes-4chip", {"rows": 296, "features": 8192}),
    "tiny-cdist": ("cdist-susy-1chip", {"rows": 2000}),
}
TINY_CELLS = {
    "tiny_kmeans_c1": ("tiny-kmeans", "kmeans_300_c1", 1),
    "tiny_kmeans_c4": ("tiny-kmeans-4", "kmeans_448_c4", 4),
    "tiny_cdist_c1": ("tiny-cdist", "cdist_40k_c1", 1),
}


def _dump(path, obj):
    with open(path, "w") as fh:
        json.dump(obj, fh, indent=1)


def make_copy(dst: str) -> str:
    """``perf/`` and ``BENCHMARK.json`` copied to ``dst``; tiny configurations
    and cells added as new files and new entries, nothing that was there
    edited.  Returns ``dst``."""
    shutil.copytree(PERF, os.path.join(dst, "perf"), ignore=shutil.ignore_patterns("__pycache__", "tests"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for name, (like, data) in TINY.items():
        with open(os.path.join(PERF, "configs", like + ".json")) as fh:
            conf = json.load(fh)
        conf["name"] = name
        conf["data"].update(data)
        conf["chips"] = "as the cell says"
        _dump(os.path.join(dst, "perf", "configs", name + ".json"), conf)
        bench["configs"].append({
            "name": name, "source": "test", "file": f"perf/configs/{name}.json",
            "reduced": ["rows"], "why": "a size a test can hold",
        })
    for cell, (conf, like, chips) in TINY_CELLS.items():
        with open(os.path.join(PERF, "workloads", like + ".json")) as fh:
            own = json.load(fh)
        own.update(name=cell, config=conf)
        traffic = own["traffic"]
        _dump(os.path.join(dst, "perf", "workloads", cell + ".json"), own)
        bench["workloads"].append(
            {"name": cell, "config": conf, "traffic": traffic, "chips": chips, "why": "test"}
        )
        # a metric that lists the cell this one is modelled on lists it too
        for m in bench["end_to_end"] + bench["per_layer"]:
            if like in m.get("workloads", []):
                m["workloads"].append(cell)
    _dump(os.path.join(dst, "BENCHMARK.json"), bench)
    return dst


#: the child's prologue: the copy's run.py with the look for a chip steered
PROLOGUE = """
import json, sys
sys.path.insert(0, {perf!r}); sys.path.insert(1, {repo!r})
import jax
import run
def _any_device(chips, peaks):
    devices = jax.devices()
    assert len(devices) == chips, (len(devices), chips)
    return devices
run.require_chip = _any_device
"""


def run_child(copy: str, body: str, devices: int = 1, timeout: int = 600):
    """Run ``body`` (python source) after :data:`PROLOGUE` in a child on
    ``devices`` virtual CPU devices.  Returns the CompletedProcess."""
    code = PROLOGUE.format(perf=os.path.join(copy, "perf"), repo=REPO) + body
    env = dict(os.environ)
    env.update(
        JAX_PLATFORMS="cpu",
        JAX_NUM_CPU_DEVICES=str(devices),
        JAX_COMPILATION_CACHE_DIR=os.path.join(copy, ".jax_cache"),
    )
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-c", code], cwd=copy, env=env, capture_output=True, text=True, timeout=timeout
    )


def result_lines(proc) -> list:
    """Every JSON object line of the child's standard output."""
    out = []
    for line in proc.stdout.splitlines():
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


@pytest.fixture(scope="session")
def copy(tmp_path_factory):
    return make_copy(str(tmp_path_factory.mktemp("perf_copy")))
