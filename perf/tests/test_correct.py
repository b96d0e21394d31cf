"""``correct`` has been shown to fail: the control (the plain reference one
precision lower, in the program's place) at a size a test can hold, and a
run driven with the timed path broken underneath, once for each fault a
cell can have.  The look for a chip is skipped; the rest of the run is
``run.py``'s own."""

import json

import pytest

from conftest import result_lines, run_child

#: child body: the control's numbers and the float32 reference's, judged as a run's are
CONTROL = """
import importlib, datagen
loaded = run.load_cell({cell!r})
config, limits = loaded["config"], loaded["own"]["limits"]
entry = importlib.import_module("jobs." + config["entry"])
for seed in (11, 2**31 + 5, 977):
    x = datagen.make(config["data"], seed, jax.devices())
    numbers = entry.judge(config, x, entry.control(config, x, seed), seed)
    print(json.dumps({{"seed": seed, "over": sorted(n for n in limits if not numbers[n] <= limits[n])}}))
"""


@pytest.mark.parametrize("cell,must_fail", [
    ("tiny_kmeans_c1", {"inertia_rel", "centre_step"}),
    ("tiny_cdist_c1", {"dist_err_all", "dist_err_f64"}),
])
def test_the_control_is_not_correct(copy, cell, must_fail):
    lines = result_lines(run_child(copy, CONTROL.format(cell=cell)))
    assert len(lines) == 3
    for line in lines:
        assert must_fail <= set(line["over"]), line


#: child body: run the cell once sound, then once under each fault
FAULTS = """
import jax.numpy as jnp
import heat_tpu as ht
from heat_tpu.cluster.kmeans import KMeans
from heat_tpu.spatial import distance

def go(tag):
    print(json.dumps({{"tag": tag}}), flush=True)
    run.main(['--workload', {cell!r}, '--seed', '424243', '--seconds', '0.3', '--trace', '0'])

go("sound")
{faults}
"""

KMEANS_FAULTS = """
seg, fin = KMeans._fit_segment, KMeans._finalize

KMeans._fit_segment = staticmethod(lambda arr, tol, stop, carry: (stop, carry[1], carry[2]))
go("state_unchanged")
KMeans._fit_segment = staticmethod(lambda arr, tol, stop, carry: seg(arr[: arr.shape[0] // 2], tol, stop, carry))
go("half_rows_left_out")
KMeans._fit_segment = staticmethod(lambda arr, tol, stop, carry: seg(arr[: arr.shape[0] // len(jax.devices())], tol, stop, carry))
go("exchange_left_out")   # only the first chip's rows reach the centres
KMeans._fit_segment = seg

def one_label(arr, c):
    labels, inertia = fin(arr, c)
    return labels.at[17].set((labels[17] + 1) % c.shape[0]), inertia
KMeans._finalize = staticmethod(one_label)
go("label_altered")
KMeans._finalize = staticmethod(lambda arr, c: (fin(arr, c)[0], fin(arr, c)[1] * 1.001))
go("inertia_altered")
KMeans._finalize = fin
"""

CDIST_FAULTS = """
wrap = distance._wrap
distance._wrap = lambda x, garr, dtype: wrap(x, garr.at[garr.shape[0] // 2:].set(0.0), dtype)
go("half_rows_left_out")
distance._wrap = lambda x, garr, dtype: wrap(x, garr.at[1234, 77].add(1e-3), dtype)
go("entry_altered")
distance._wrap = wrap
"""


def verdicts(proc):
    out, tag = {}, None
    for line in result_lines(proc):
        if "tag" in line:
            tag = line["tag"]
        else:
            out[tag] = line
    return out


def over(line):
    return {n for n, c in line["compared"].items() if not c["value"] <= c["limit"]}


@pytest.mark.parametrize("cell,devices", [("tiny_kmeans_c1", 1), ("tiny_kmeans_c4", 4)])
def test_kmeans_faults_come_out_not_correct(copy, cell, devices):
    faults = KMEANS_FAULTS
    if devices == 1:  # one chip has no exchange to leave out
        faults = "\n".join(l for l in faults.splitlines() if "exchange_left_out" not in l and "len(jax.devices())" not in l)
    proc = run_child(copy, FAULTS.format(cell=cell, faults=faults), devices=devices)
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = verdicts(proc)
    assert got["sound"]["correct"] is True, got["sound"]["compared"]
    expect = {
        "state_unchanged": "centre_step",
        "half_rows_left_out": "centre_step",
        "label_altered": "label_gap",
        "inertia_altered": "inertia_rel",
    }
    if devices > 1:
        expect["exchange_left_out"] = "centre_step"
    for tag, number in expect.items():
        assert got[tag]["correct"] is False, tag
        assert number in over(got[tag]), (tag, got[tag]["compared"])


def test_cdist_faults_come_out_not_correct(copy):
    proc = run_child(copy, FAULTS.format(cell="tiny_cdist_c1", faults=CDIST_FAULTS))
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = verdicts(proc)
    assert got["sound"]["correct"] is True, got["sound"]["compared"]
    for tag in ("half_rows_left_out", "entry_altered"):
        assert got[tag]["correct"] is False, tag
        assert "dist_err_all" in over(got[tag])
