"""Job entry ``svd_tall``: one job is one call of the program's public
``ht.linalg.svd(A)`` on the resident tall operand, the reduced SVD with its
vectors: U (rows x columns), S, V (columns x columns).  Every job factors A
anew: nothing of one job may be kept for the next.

The configuration's ``job`` block: ``full_matrices`` (false: the reduced SVD)
and ``compute_uv`` (true).
"""

from __future__ import annotations

import importlib


def _reference(config):
    return importlib.import_module(f"references.{config['reference']}")


def prepare(ht, config, x):
    """Hand the benchmark's array to the program: a ``split=0`` DNDarray over
    the same buffers (no second copy of the data on the chip)."""
    return ht.array(x, split=0, copy=False)


def run(ht, config, state, job_index: int, seed: int) -> dict:
    job = config["job"]
    if job["full_matrices"] or not job["compute_uv"]:
        raise ValueError(f"jobs/svd_tall.py runs the reduced SVD with its vectors, not {job}")
    u, s, v = ht.linalg.svd(state)
    # none is waited for yet: the harness fences all three
    return {"U": u.larray, "S": s.larray, "V": v.larray}


def judge(config, x, outputs: dict, seed: int) -> dict:
    return _reference(config).judge(x, outputs, seed)


def control(config, x, seed: int) -> dict:
    """The reference in the program's place, one precision below the
    configuration's float32: data rounded to bfloat16, U's product one
    bfloat16 pass."""
    import jax.numpy as jnp

    return _reference(config).svd(x, jnp.bfloat16)


def work(config) -> dict:
    """Bytes and FLOPs one job needs, from its shapes, whoever implements it.

    A is read TWICE and U written once: U's every row depends on all of A
    (through the factor of A's column space, R or the Gram), so no sound
    program forms U in the read that makes the factor, and two reads are the
    least.  Beside them the small factors: S and V written.

    FLOPs: two products of 2·m·n² each, the factor's (a Gram, or a QR's
    updates) and U's (A times an n x n matrix); the n x n work on R is
    n³-order and not counted.  They are held against ``bf16_tflops``, the
    peak of ONE bfloat16 pass: a program that makes its products in fewer
    passes than float32's six (three, or one where that were sound) can then
    never read over 100 % of this roofline, which would be
    ``impossible_reading`` and no fault of its own; against the six-pass
    ``f32_highest_tflops`` a three-pass program would read up to twice the
    truth.  ``factor_roofline_pct`` holds each pass to the peak of the
    precision its span states.  ``a_bytes`` is one read of A and
    ``pass_flops`` one 2·m·n² product, for that reader."""
    d = config["data"]
    m, n = int(d["rows"]), int(d["features"])
    a = m * n * 4
    return {
        "bytes": 2 * a + a + n * 4 + n * n * 4,
        "flops": 2 * (2 * m * n * n),
        "flops_peak": "bf16_tflops",
        "a_bytes": a,
        "pass_flops": 2 * m * n * n,
    }
