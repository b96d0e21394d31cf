"""The readings the limits of a KMedians cell are set from, at the cell's own
size, in one process on the chip (``PERF.md`` section 2 has the rule):

    python perf/tools/limits_probe_kmedians.py --workload kmedians_300_c1 \\
        --first-seed <n> --seeds 12 --control-seeds 3

For each of ``--seeds`` seeds: the data, one job of the program through the
timed entry and the job's numbers against the plain reference (the lower
readings), with the sizes of the served clusters.  For each of the first
``--control-seeds`` of them also the upper readings, judged the same way: the
program with each fault of :data:`FAULTS` planted in its own code, the two of
:data:`UNSEEN` (recorded whichever side of the limits they fall: on blobs as
far apart as the cell's they label every row as the sound program does, and
tier-1 holds both on data where they differ,
``tests/test_kmedians_reference.py``), one label altered in the outputs, and
the job entry's ``control`` (the plain reference in bfloat16 in the program's
place).  One JSON line each on standard output, with the seconds the job
took.  Refuses to run off the chip, as ``run.py`` does.

A fault is a context manager that breaks one function the fit program looks
up when it is traced and mends it on the way out;
``perf/tests/test_kmedians_cell.py`` drives a run under each on the CPU.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if HERE not in sys.path:
    sys.path.insert(0, HERE)
    sys.path.insert(1, os.path.dirname(HERE))

from tools.limits_probe_spectral import _patched


@contextlib.contextmanager
def _medians(replacement):
    """Both routes of the medians replaced by ``replacement(arr, labels, k) ->
    (k, f)``: the kernel's entry and the rank bisection."""
    import jax.numpy as jnp

    from heat_tpu.cluster import kmedians
    from heat_tpu.core import _colmedian

    def counts_of(labels, k):
        return jnp.sum(labels[:, None] == jnp.arange(k)[None, :], axis=0, dtype=jnp.int32)

    def group_medians(arr, labels, k, **_):
        return replacement(arr, labels, k), counts_of(labels, k)

    def cluster_medians(arr, svals, fmin, fmax, onehot, counts, k, prev_pos=None):
        return replacement(arr, jnp.argmax(onehot, axis=1), k), prev_pos

    with _patched(_colmedian, "group_medians", group_medians), \
            _patched(kmedians, "_cluster_medians", cluster_medians):
        yield


def mean_for_median():
    """The centre update by the mean of a cluster's rows (KMeans' update)."""
    import jax.numpy as jnp

    def means(arr, labels, k):
        member = (labels[None, :] == jnp.arange(k)[:, None]).astype(arr.dtype)
        return jnp.matmul(member, arr, precision="highest") / jnp.maximum(member.sum(1), 1)[:, None]

    return _medians(means)


def _ranks(replacement):
    """The ranks of a cluster's two middle members (``core/_colmedian.py:
    _middle_ranks``, asked by both routes) replaced."""
    from heat_tpu.core import _colmedian

    return _patched(_colmedian, "_middle_ranks", replacement)


def lower_middle_alone():
    """The lower of the two middle members at an even count, not their mean."""
    return _ranks(lambda m: ((m + 1) // 2, (m + 1) // 2))


def one_row_left_out():
    """Every cluster's median without the cluster's largest member: the
    middle ranks of ``m - 1`` members."""
    return _ranks(lambda m: (m // 2, (m - 1) // 2 + 1))


def euclidean_assignment():
    """The assignment by squared Euclidean distance (the tree's before PR 36)."""
    import jax.numpy as jnp

    from heat_tpu.spatial import distance

    real = distance._pairwise_sum
    return _patched(distance, "_pairwise_sum", lambda xa, ya, term: real(xa, ya, jnp.square))


def l1_sums_in_bfloat16():
    """Float32 data, the L1 differences and sums taken and held in bfloat16."""
    import jax.numpy as jnp

    from heat_tpu.spatial import distance

    def pairwise(xa, ya, term):
        xb, yb = xa.astype(jnp.bfloat16), ya.astype(jnp.bfloat16)
        return jnp.stack(
            [jnp.sum(term(xb - yb[j][None, :]), axis=1, dtype=jnp.bfloat16) for j in range(ya.shape[0])], axis=1
        ).astype(xa.dtype)

    return _patched(distance, "_pairwise_sum", pairwise)


#: name -> (context manager, the number that must come out over its limit)
FAULTS = {
    "mean_for_median": (mean_for_median, "median_step"),
    "lower_middle_alone": (lower_middle_alone, "median_step"),
    "one_row_left_out": (one_row_left_out, "median_step"),
}

#: faults of the assignment that blobs this far apart cannot show: recorded,
#: not required to fail (tier-1 holds them on data where the labels differ)
UNSEEN = {
    "euclidean_assignment": euclidean_assignment,
    "l1_sums_in_bfloat16": l1_sums_in_bfloat16,
}


def one_label_altered(outputs: dict) -> dict:
    """The served outputs with the first row given another label."""
    labels = outputs["labels"]
    k = int(outputs["centres"].shape[0])
    return dict(outputs, labels=labels.at[0].set((labels[0] + 1) % k))


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    args = ap.parse_args(argv)

    loaded = run.load_cell(args.workload)
    config = loaded["config"]
    from heat_tpu.core._compile_cache import place_compile_cache

    place_compile_cache()
    import jax
    import numpy as np

    devices = run.require_chip(int(loaded["cell"]["chips"]), loaded["peaks"])
    import heat_tpu as ht

    import datagen

    entry = importlib.import_module("jobs." + config["entry"])

    def emit(seed, who, numbers, seconds=None, **more):
        line = {"cell": args.workload, "seed": seed, "who": who, "numbers": numbers, **more}
        if seconds is not None:
            line["job_s"] = seconds
        print(json.dumps(line), flush=True)

    def one_job(seed, state):
        jax.block_until_ready(entry.run(ht, config, state, -1, seed))  # compiles
        t0 = time.perf_counter()
        out = entry.run(ht, config, state, 0, seed)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        x = datagen.make(config["data"], seed, devices)
        state = entry.prepare(ht, config, x)
        jobs = {"program": one_job(seed, state)}
        if i < args.control_seeds:
            for name, (fault, _) in FAULTS.items():
                with fault():
                    jobs["fault:" + name] = one_job(seed, state)
            for name, fault in UNSEEN.items():
                with fault():
                    jobs["unseen:" + name] = one_job(seed, state)
            jobs["fault:one_label_altered"] = (one_label_altered(jobs["program"][0]), None)
        del state
        for who, (out, seconds) in jobs.items():
            sizes = np.bincount(np.asarray(out["labels"]), minlength=int(out["centres"].shape[0])).tolist()
            emit(seed, who, entry.judge(config, x, out, seed), seconds, cluster_sizes=sizes)
        if i < args.control_seeds:
            t0 = time.perf_counter()
            out = entry.control(config, x, seed)
            emit(seed, "control", entry.judge(config, x, out, seed), time.perf_counter() - t0)
        del jobs, x
    return 0


if __name__ == "__main__":
    sys.exit(main())
