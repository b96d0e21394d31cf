"""The trace reduction: interval arithmetic on hand-made cases, and the
whole reduction on traces recorded on the chip (``data/``, TPU v5e, PR 24:
three seconds each of ``cdist_40k_c1`` and of KMeans fits at 8M x 32 on one
chip and 16M x 32 on four, a shape the benchmark tried first and has no cell
of any more; the reduction under test does not care) and on a hand-made
two-chip trace with a collective."""

import os

import pytest

import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_merge_total_clip():
    assert tr.merge([(3, 4), (0, 1), (0.5, 2), (2, 2)]) == [(0, 2), (3, 4)]
    assert tr.total([(0, 2), (3, 4)]) == 3
    assert tr.clip([(0, 2), (3, 5)], 1, 4) == [(1, 2), (3, 4)]


def test_subtract_and_gaps():
    a = [(0, 10)]
    b = [(1, 2), (4, 6), (9, 12)]
    assert tr.subtract(a, b) == [(0, 1), (2, 4), (6, 9)]
    assert tr.subtract([(0, 1), (5, 6)], [(0, 1)]) == [(5, 6)]
    assert tr.gaps([(1, 2), (4, 6)], 0, 7) == [(0, 1), (2, 4), (6, 7)]


def test_names():
    assert tr.is_collective("%all-reduce.3 = f32[8,32]{1,0} all-reduce(f32[8,32] %x)")
    assert tr.is_collective("%all-gather-start.1 = (f32[4]) all-gather-start(%y)")
    assert not tr.is_collective("%fusion.19 = f32[8] fusion(%all-reduce.3)")
    assert tr.is_container("%while.5 = (s32[]) while(%t)")
    assert not tr.is_container("%while_fusion.5 = f32[] fusion(%t)")


def hand_made():
    """Two chips, two jobs of one second each.  Chip 0: compute 0.1-0.5 and
    1.1-1.5, an all-reduce 0.4-0.7 (0.1 hidden under compute, 0.2 exposed).
    Chip 1: the same compute, all-reduce 0.5-0.9 (all 0.4 exposed)."""
    def dev(ar):
        return {
            "modules": [("jit_step(1)", 0.1, 0.9), ("jit_step(1)", 1.1, 1.5)],
            "ops": [
                ("%while.1 = () while()", 0.1, 0.9),
                ("%fusion.1 = f32[] fusion()", 0.1, 0.5),
                ("%all-reduce.1 = f32[] all-reduce()", *ar),
                ("%fusion.1 = f32[] fusion()", 1.1, 1.5),
            ],
            "async": [],
        }
    host = {"python": [
        ("perf_job", 0.0, 1.0), ("perf_job", 1.0, 2.0),
        ("PjitFunction(step)", 0.0, 0.1), ("np.asarray(jax.Array)", 1.5, 2.0),
    ]}
    return {"devices": {"/device:TPU:0": dev((0.4, 0.7)), "/device:TPU:1": dev((0.5, 0.9))}, "host": host}


def test_reduce_hand_made():
    s = tr.reduce(hand_made())
    assert s["jobs"] == 2 and s["devices"] == 2
    assert s["window_s"] == pytest.approx(2.0)
    # busy is the union of all operations, the while included: 0.1-0.9, 1.1-1.5
    assert s["busy_s"] == pytest.approx(1.2)
    assert s["launches"] == 2
    assert s["collective_s_max"] == pytest.approx(0.4)
    assert s["collective_exposed_s_max"] == pytest.approx(0.4)  # chip 1
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["PjitFunction(step)"] == pytest.approx(0.1)
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.5)
    assert gaps["perf_job"] == pytest.approx(0.2)  # 0.9-1.1: no host event but the job's
    ops = dict(s["breakdown"]["device_ops"])
    assert ops["fusion.1 = f32[] fusion()"] == pytest.approx(0.8)
    assert not any(n.startswith("while") for n in ops)


def test_reduce_nothing_to_read():
    empty = hand_made()
    empty["devices"] = {}
    assert tr.reduce(empty) is None
    nojob = hand_made()
    nojob["host"] = {"python": [("other", 0, 1)]}
    assert tr.reduce(nojob) is None


def test_recorded_kmeans_trace():
    s = tr.reduce(tr.load(os.path.join(DATA, "kmeans_8m_x32_1chip.xplane.pb.gz")))
    assert s["jobs"] == 19 and s["devices"] == 1
    assert s["window_s"] == pytest.approx(3.015229, abs=1e-5)
    assert s["busy_s"] == pytest.approx(2.751092, abs=1e-5)
    assert s["launches"] == 360  # 19 jobs of 19 programs, less one that began before the window
    assert s["collective_s_max"] == 0
    top = s["breakdown"]["device_ops"][0]
    assert top[0].startswith("multiply_reduce_fusion.2 = f32[8000000]") and top[1] == pytest.approx(0.720305, abs=1e-5)
    gaps = dict(s["breakdown"]["idle_gaps"])
    assert gaps["np.asarray(jax.Array)"] == pytest.approx(0.142183, abs=1e-5)
    assert sum(gaps.values()) == pytest.approx(s["window_s"] - s["busy_s"], abs=1e-6)


def test_recorded_cdist_trace():
    s = tr.reduce(tr.load(os.path.join(DATA, "cdist_40k_c1.xplane.pb.gz")))
    assert s["jobs"] == 35 and s["launches"] == 35
    assert s["busy_s"] / s["window_s"] == pytest.approx(0.98484, abs=1e-4)
    assert [n.split(" = ")[0] for n, _ in s["breakdown"]["device_ops"]] == ["multiply_reduce_fusion", "sqrt.1"]


def test_recorded_four_chip_trace():
    """A KMeans fit of 16M x 32 over four chips: the program all-gathers the
    whole of X onto every chip in each k-means++ step, and nothing else can
    run meanwhile."""
    s = tr.reduce(tr.load(os.path.join(DATA, "kmeans_16m_x32_4chips.xplane.pb.gz")))
    assert s["jobs"] == 5 and s["devices"] == 4
    assert s["launches"] == 100  # chip 0 sees every program, 20 a job
    assert s["busy_s"] == pytest.approx(3.361106, abs=1e-5)
    assert s["collective_s_max"] == pytest.approx(2.91055, abs=1e-4)
    assert s["collective_exposed_s_max"] / s["window_s"] == pytest.approx(0.8421, abs=1e-3)
    assert s["breakdown"]["device_ops"][0][0].startswith("all-gather.22 = f32[16000000,32]")
