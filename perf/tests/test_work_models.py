"""The work models (each job entry's ``work(config)``, the one source of a
job's bytes and FLOPs) against numbers computed by hand, and the least times
that follow for the committed one-chip configurations."""

import importlib
import json
import os

import pytest

from conftest import PERF, REPO


def configs():
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    for c in bench["configs"]:
        with open(os.path.join(REPO, c["file"])) as fh:
            yield c["name"], json.load(fh)


def test_kmeans_by_hand():
    work = importlib.import_module("jobs.kmeans_fit").work
    got = work({"data": {"rows": 8_000_000, "features": 32}, "job": {"clusters": 8, "iterations": 30}})
    # 30 sweeps + 7 k-means++ passes + 1 final assignment = 38 reads of 1.024 GB;
    # the 1 KB of centres read and written in each sweep and read once more
    assert got["bytes"] == 38 * 1_024_000_000 + 61 * 1024 + 64_000_000 + 1024 + 4
    # 61 products of 2 * 8e6 * 32 * 8 FLOPs
    assert got["flops"] == 61 * 4_096_000_000
    wide = work({"data": {"rows": 300, "features": 6_291_456}, "job": {"clusters": 8, "iterations": 30}})
    x, centres = 300 * 6_291_456 * 4, 8 * 6_291_456 * 4
    assert wide["bytes"] == 38 * x + 62 * centres + 300 * 8 + 4
    assert wide["flops"] == 61 * 2 * 300 * 6_291_456 * 8


def test_cdist_by_hand():
    work = importlib.import_module("jobs.cdist").work
    got = work({"data": {"rows": 40_000, "features": 18}})
    assert got["bytes"] == 6_400_000_000 + 5_760_000
    assert got["flops"] == 3 * 1_600_000_000 * 18 + 1_600_000_000


@pytest.mark.parametrize("name,config", list(configs()))
def test_every_configuration_has_a_work_model(name, config):
    work = importlib.import_module("jobs." + config["entry"]).work(config)
    assert work["bytes"] > 0 and work["flops"] > 0
    assert "bytes_per_job" not in config["work_model"]  # one source: the function
    with open(os.path.join(PERF, "peaks.json")) as fh:
        peaks = json.load(fh)
    for kind in peaks.values():
        assert work["flops_peak"] in kind


def test_least_time_of_the_one_chip_cells():
    least = importlib.import_module("layer_metrics.roofline_pct").least_seconds
    with open(os.path.join(PERF, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    by_name = dict(configs())

    def of(name):
        config = by_name[name]
        work = importlib.import_module("jobs." + config["entry"]).work(config)
        return least({"work": work, "peaks": peaks, "chips": 1})

    # memory-bound: 38 reads of 7.55 GB and 62 of the 201 MB of centres, 365.6 ms
    assert of("kmeans-cityscapes-1chip") == pytest.approx(299.38e9 / 819e9, rel=1e-3)
    assert of("cdist-susy-1chip") == pytest.approx(6.40576e9 / 819e9, rel=1e-6)  # 7.82 ms
