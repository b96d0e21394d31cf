"""Job entry ``moments``: one job is the program's public ``ht.mean(X, axis)``
and then ``ht.std(X, axis)`` on the resident data, two calls back to back as
the reference harness's script makes and times them
(``benchmarks/statistical_moments/heat-cpu.py``).  Every job computes both
anew: nothing of one call or one job may be kept for the next.

The configuration's ``job`` block: ``functions`` (``["mean", "std"]``, the
calls in their order), ``axis`` and ``ddof``.
"""

from __future__ import annotations

import importlib


def _reference(config):
    return importlib.import_module(f"references.{config['reference']}")


def _job(config) -> tuple:
    job = config["job"]
    if list(job["functions"]) != ["mean", "std"] or int(job["axis"]) != 0:
        raise ValueError(f"jobs/moments.py runs mean then std along axis 0, not {job}")
    return int(job["axis"]), int(job["ddof"])


def prepare(ht, config, x):
    """Hand the benchmark's array to the program: a ``split=0`` DNDarray over
    the same buffers."""
    return ht.array(x, split=0, copy=False)


def run(ht, config, state, job_index: int, seed: int) -> dict:
    axis, ddof = _job(config)
    m = ht.mean(state, axis=axis)
    s = ht.std(state, axis=axis, ddof=ddof)
    # neither is waited for yet: the harness fences both
    return {"mean": m.larray, "std": s.larray}


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, seed, ddof=_job(config)[1])


def control(config, x, seed: int) -> dict:
    """The reference in the program's place, one precision below the
    configuration's float32: data and sums in bfloat16."""
    import jax.numpy as jnp

    return _reference(config).moments(x, jnp.bfloat16, ddof=_job(config)[1])


def work(config) -> dict:
    """Bytes and FLOPs one job needs, from its shapes, whoever implements it.

    X is read ONCE for each of the two public calls: a mean is one pass, and a
    deviation can be made in one pass too (merged single-pass moments, as the
    reference library's own ``heat/core/statistics.py:870-945`` does), but not
    in less, and the two calls may share nothing.  Counting two reads and not
    the three of a variance that makes its mean in a pass of its own is
    deliberate: such a program then reads about two thirds of this roofline,
    which is the room a one-pass variance has; counted as three, that variance
    would read over 100 % through no fault of its own.  Beside the reads: the
    two float32 results written.  FLOPs: one addition an element for the mean,
    three operations an element (subtract, multiply, add) for the deviation."""
    d = config["data"]
    n, f = int(d["rows"]), int(d["features"])
    return {
        "bytes": 2 * n * f * 4 + 2 * f * 4,
        "flops": n * f + 3 * n * f,
        # the table of peaks has no row for the vector units; held against the
        # MXU's the job's least time is the reads', as it would be on any unit
        "flops_peak": "bf16_tflops",
    }
