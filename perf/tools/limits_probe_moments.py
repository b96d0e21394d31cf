"""The readings the limits of a moments cell are set from, at the cell's own
size, in one process on the chip (``PERF.md`` section 2 has the rule):

    python perf/tools/limits_probe_moments.py --workload moments_300_c1 \\
        --first-seed <n> --seeds 12 --control-seeds 3

For each of ``--seeds`` seeds: the data, one job of the program through the
timed entry and the job's four numbers against the plain reference (the lower
readings), with the largest and smallest column deviation and the largest
``abs(mean) / std`` of the data, which say what the numbers were read on.
For each of the first ``--control-seeds`` of them also the upper readings,
judged the same way: the program with each fault of :data:`FAULTS` planted in
its own code, the raw form ``E[x**2] - mean**2`` in float32 in its place
(:func:`raw_form`; recorded whichever side of the limits it falls, and no
fault on data whose means are small beside their deviations), and the job
entry's ``control`` (the plain reference in bfloat16 in the program's
place).  One JSON line each on standard output, with the seconds the job
took.  Refuses to run off the chip, as ``run.py`` does.

A fault is a context manager that breaks one function of the program
(``heat_tpu/core/statistics.py``) and mends it on the way out;
``perf/tests/test_moments_cell.py`` drives a run under each on the CPU.
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

from tools.limits_probe_spectral import _patched as _patched_one


@contextlib.contextmanager
def _patched(**replacements):
    """Functions of ``heat_tpu.core.statistics`` replaced by name, each by
    what its entry here makes of the real one; every compiled program is
    dropped on the way in and out (the programs look the functions up when
    they are traced)."""
    from heat_tpu.core import statistics

    with contextlib.ExitStack() as stack:
        for name, replacement in replacements.items():
            stack.enter_context(_patched_one(statistics, name, replacement(getattr(statistics, name))))
        yield


def one_row_left_out():
    """Both moments over all rows but the last."""
    return _patched(
        _mean=lambda real: lambda a, axis, keepdims: real(a[:-1], axis, keepdims),
        _var=lambda real: lambda a, axis, ddof, keepdims: real(a[:-1], axis, ddof, keepdims),
    )


def ddof_one():
    """The deviation with ``n - 1`` below the sum where the job says ``n``."""
    return _patched(_var=lambda real: lambda a, axis, ddof, keepdims: real(a, axis, 1, keepdims))


def variance_for_deviation():
    """``ht.std`` without its square root."""
    return _patched(
        _moment2=lambda real: lambda x, axis, ddof, kwargs, name, finalize: real(
            x, axis, ddof, kwargs, name, lambda r: r
        )
    )


def _sum_bf16(a, axis, keepdims):
    import jax.numpy as jnp

    return jnp.sum(a.astype(jnp.bfloat16), axis=axis, keepdims=keepdims, dtype=jnp.bfloat16)


def sums_in_bfloat16():
    """Float32 data, every sum over the rows taken and held in bfloat16."""
    import jax.numpy as jnp

    def mean(real):
        return lambda a, axis, keepdims: (_sum_bf16(a, axis, keepdims) / a.shape[axis]).astype(a.dtype)

    def var(real):
        def _var(a, axis, ddof, keepdims):
            mu = (_sum_bf16(a, axis, True) / a.shape[axis]).astype(a.dtype)
            return (_sum_bf16(jnp.square(a - mu), axis, keepdims) / (a.shape[axis] - ddof)).astype(a.dtype)

        return _var

    return _patched(_mean=mean, _var=var)


def raw_form():
    """No fault on every data set: the variance as ``E[x**2] - mean**2`` in
    float32, one pass and no subtraction before the squares.  It cancels
    where a mean is large beside its deviation; on other data it reads as
    rounding."""
    import jax.numpy as jnp

    def var(real):
        def _var(a, axis, ddof, keepdims):
            n = a.shape[axis]
            mu = jnp.mean(a, axis=axis, keepdims=keepdims)
            raw = jnp.mean(jnp.square(a), axis=axis, keepdims=keepdims) - jnp.square(mu)
            return jnp.maximum(raw, 0.0) * (n / (n - ddof))

        return _var

    return _patched(_var=var)


#: name -> (context manager, the number that must come out over its limit)
FAULTS = {
    "one_row_left_out": (one_row_left_out, "mean_err_all"),
    "ddof_one": (ddof_one, "std_rel_all"),
    "variance_for_deviation": (variance_for_deviation, "std_rel_all"),
    "sums_in_bfloat16": (sums_in_bfloat16, "mean_err_all"),
}


def data_stats(x) -> dict:
    """What the numbers are read on: the columns' deviations and how large a
    mean stands beside its deviation (float32 on the device)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def stats(x):
        mean = jnp.mean(x, axis=0)
        std = jnp.sqrt(jnp.mean(jnp.square(x - mean[None, :]), axis=0))
        return jnp.min(std), jnp.max(std), jnp.max(jnp.abs(mean) / std)

    lo, hi, ratio = stats(x)
    return {"std_min": float(lo), "std_max": float(hi), "mean_over_std_max": float(ratio)}


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

    def emit(seed, who, numbers, seconds=None):
        line = {"cell": args.workload, "seed": seed, "who": who, "numbers": numbers}
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
            with raw_form():
                jobs["program:raw_form"] = one_job(seed, state)
        del state
        for who, (out, seconds) in jobs.items():
            emit(seed, who, entry.judge(config, x, out, seed), seconds)
        emit(seed, "data", data_stats(x))
        if i < args.control_seeds:
            emit(seed, "control", entry.judge(config, x, entry.control(config, x, seed), seed))
        del jobs, x
    return 0


if __name__ == "__main__":
    sys.exit(main())
