"""The readings a limit of ``correct`` is set from, at the cell's own size,
in one process on the chip (``PERF.md`` section 2 has the rule):

    python perf/tools/limits_probe.py --workload <cell> --first-seed <n> \\
        --seeds 12 --control-seeds 3

For each of ``--seeds`` seeds: the data, one job of the program through the
timed entry, the numbers of its outputs against the plain reference (the
lower readings).  For each of the first ``--control-seeds`` of them also the
upper readings, judged the same way: the faults of :data:`FAULTS`, planted
in that job's outputs where the program produces them; the program's own
lower-precision paths of :data:`PROGRAM_CONTROLS`; and the job entry's
``control`` (the plain reference, one precision lower, in the program's
place; the program's executables are dropped first, so that it fits).  One
JSON line each on standard output.  Refuses to run off the chip, as
``run.py`` does.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))


def kmeans_faults(config, x, out, seed: int, chips: int) -> dict:
    """The served fit with its last sweep broken: the centres are the mean of
    only some of each cluster's rows (half of them; with several chips also
    the first chip's alone, which is what a left-out exchange serves), one
    label moved to the next cluster, the inertia off by a thousandth."""
    import jax
    import jax.numpy as jnp

    n, k = int(x.shape[0]), int(out["centres"].shape[0])

    @jax.jit
    def step_over(x, centres, rows):  # one Lloyd step that sees rows [0, rows) only
        d2 = jnp.stack([jnp.sum((x - centres[j]) ** 2, axis=1) for j in range(k)], axis=1)
        near = jnp.argmin(d2, axis=1)
        seen = jnp.arange(n) < rows
        new = []
        for j in range(k):
            mine = jnp.logical_and(near == j, seen)
            tot = jnp.sum(jnp.where(mine[:, None], x, 0.0), axis=0)
            new.append(jnp.where(jnp.sum(mine) > 0, tot / jnp.maximum(jnp.sum(mine), 1), centres[j]))
        return jnp.stack(new)

    row = int(seed) % n
    faults = {
        "half_rows_left_out": dict(out, centres=step_over(x, out["centres"], n // 2)),
        "one_label_altered": dict(out, labels=out["labels"].at[row].set((out["labels"][row] + 1) % k)),
        "inertia_altered": dict(out, inertia=out["inertia"] * 1.001),
    }
    if chips > 1:
        faults["exchange_left_out"] = dict(out, centres=step_over(x, out["centres"], n // chips))
    return faults


def cdist_program_controls(ht, config, state) -> dict:
    """The program's own lower-precision path, switched on: the quadratic
    expansion, whose product runs as one bf16 pass on the MXU."""
    return {"quadratic_expansion": {"distances": ht.spatial.cdist(state, state, quadratic_expansion=True).larray}}


#: by job entry
FAULTS = {"kmeans_fit": kmeans_faults}
PROGRAM_CONTROLS = {"cdist": cdist_program_controls}


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

    devices = run.require_chip(int(loaded["cell"]["chips"]), loaded["peaks"])
    import heat_tpu as ht

    import datagen

    entry = importlib.import_module("jobs." + config["entry"])

    def emit(seed, who, numbers):
        print(json.dumps({"cell": args.workload, "seed": seed, "who": who, "numbers": numbers}), flush=True)

    chips = int(loaded["cell"]["chips"])
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        x = datagen.make(config["data"], seed, devices)
        state = entry.prepare(ht, config, x)
        out = entry.run(ht, config, state, 0, seed)
        jax.block_until_ready(out)
        emit(seed, "program", entry.judge(config, x, out, seed))
        if i < args.control_seeds:
            if config["entry"] in FAULTS:
                faults = FAULTS[config["entry"]](config, x, out, seed, chips)
                for name in list(faults):
                    emit(seed, "fault:" + name, entry.judge(config, x, faults.pop(name), seed))
            del out  # one result at a time: two of cdist's do not fit
            if config["entry"] in PROGRAM_CONTROLS:
                others = PROGRAM_CONTROLS[config["entry"]](ht, config, state)
                for name in list(others):
                    emit(seed, "program:" + name, entry.judge(config, x, others.pop(name), seed))
            del state
            jax.clear_caches()
            emit(seed, "control", entry.judge(config, x, entry.control(config, x, seed), seed))
        out = state = None
        del x
    return 0


if __name__ == "__main__":
    sys.exit(main())
