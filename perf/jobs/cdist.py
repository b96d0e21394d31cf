"""Job entry ``cdist``: one job is one call of the program's public
``ht.spatial.cdist(X, X)`` in its default exact form on the resident data,
as the reference harness times it (``benchmarks/distance_matrix/heat-cpu.py``).
The configuration's ``job`` block is empty: the default form is part of the
numerics (``quadratic_expansion=True`` is a different result).
"""

from __future__ import annotations

import importlib


def _reference(config):
    return importlib.import_module(f"references.{config['reference']}")


def prepare(ht, config, x):
    return ht.array(x, split=0, copy=False)


def run(ht, config, state, job_index: int, seed: int) -> dict:
    return {"distances": ht.spatial.cdist(state, state).larray}


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, seed)


def control(config, x, seed: int) -> dict:
    """The reference in the program's place in bfloat16, one precision below
    the configuration's float32."""
    import jax.numpy as jnp

    return {"distances": _reference(config).pairwise(x, jnp.bfloat16)}


def work(config) -> dict:
    """n*n float32 written plus the operands read once; three operations
    (subtract, multiply, add) for each of the f features of each pair, and
    one square root a pair."""
    d = config["data"]
    n, f = int(d["rows"]), int(d["features"])
    return {
        "bytes": n * n * 4 + 2 * n * f * 4,
        "flops": 3 * n * n * f + n * n,
        # the table of peaks has no row for the vector units; held against the
        # MXU's the job's least time is the write's, as it would be on any unit
        "flops_peak": "bf16_tflops",
    }
