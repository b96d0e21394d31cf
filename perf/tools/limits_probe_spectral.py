"""The readings the limits of a spectral cell are set from, at the cell's own
size, in one process on the chip (``PERF.md`` section 2 has the rule):

    python perf/tools/limits_probe_spectral.py --workload spectral_40k_c1 \\
        --first-seed <n> --seeds 8 --control-seeds 2

For each of ``--seeds`` seeds: the data, one job of the program through the
timed entry, the plain reference's own fit (kept for the seed) and the job's
five numbers against it (the lower readings).  For each of the first
``--control-seeds`` of them also the upper readings, judged the same way:
the program with each fault of :data:`FAULTS` planted in its own code, the
program at the parent's numerics (:func:`parent_numerics`: every product of the linalg
policy at jax's default precision, one bf16 pass on the MXU), the program
with 30 Lanczos steps for 300 (no fault on this data, see
:func:`m_cut_to_10`) and with the similarity in its exact form, the
reference's own fit held to itself, and the job entry's ``control`` (the plain reference in bfloat16 in the program's
place).  One JSON line each on standard output, with the seconds the fit
took.  Refuses to run off the chip, as ``run.py`` does.

A fault is a context manager that breaks one function of the program and
mends it on the way out; ``perf/tests/test_spectral_cell.py`` drives a run
under each on the CPU.  The bf16 faults round a product's operands to
bfloat16 and accumulate in float32, which is what one MXU pass does, so they
read alike on the CPU and on the chip.
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


def _bf16_matmul(a, b):
    """One bf16 pass: operands rounded to bfloat16, products summed in float32."""
    import jax.numpy as jnp

    return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)


@contextlib.contextmanager
def _patched(module, name: str, replacement):
    """``module.name`` replaced, and every compiled program dropped on the way
    in and out (the programs close over the function when they are traced)."""
    import jax

    from heat_tpu.core import _compile

    real = getattr(module, name)
    setattr(module, name, replacement)
    _compile.clear_cache()
    jax.clear_caches()
    try:
        yield
    finally:
        setattr(module, name, real)
        _compile.clear_cache()
        jax.clear_caches()


def similarity_bf16_pass():
    """The similarity from the expansion whose product is one bf16 pass."""
    import jax.numpy as jnp

    from heat_tpu.spatial import distance

    def quadratic_d2(xa, ya, precision=None):
        x2 = jnp.sum(xa * xa, axis=-1, keepdims=True)
        y2 = jnp.sum(ya * ya, axis=-1, keepdims=True).swapaxes(-1, -2)
        return jnp.maximum(x2 + y2 - 2.0 * _bf16_matmul(xa, ya.swapaxes(-1, -2)), 0.0)

    return _patched(distance, "quadratic_d2", quadratic_d2)


def matvec_bf16_pass():
    """The Lanczos step's product with the operator as one bf16 pass."""
    from heat_tpu.core.linalg import solver

    return _patched(solver, "_matvec", lambda arr, w, precision: _bf16_matmul(arr, w))


def reorth_left_out():
    """No re-orthogonalisation: the plain three-term recurrence."""
    from heat_tpu.core.linalg import solver

    return _patched(solver, "_reorth", lambda V, w, precision: w)


def diagonal_not_zeroed():
    """Self-loops kept: every degree is one too large."""
    import jax.numpy as jnp

    from heat_tpu.graph import laplacian

    def adjacency(S, mode, key, val, weighted):
        return S.astype(jnp.float32)

    return _patched(laplacian, "_adjacency", adjacency)


def simple_for_norm_sym():
    """``D - A`` served where ``I - D^-1/2 A D^-1/2`` is asked for."""
    from heat_tpu.graph import laplacian

    return _patched(laplacian, "_norm_sym", laplacian._simple)


def m_cut(divisor: int):
    """``m // divisor`` Lanczos steps where the configuration says ``m``."""
    from heat_tpu.core.linalg import solver

    real = solver.lanczos
    return _patched(solver, "lanczos", lambda A, m, **kw: real(A, max(m // divisor, 1), **kw))


def m_cut_to_10():
    """10 Lanczos steps for 300: too few for 8 pairs.  (30 steps are NOT a
    fault on a clustered graph: its other eigenvalues crowd near 1, and the
    8 lowest pairs converge to float32 rounding within 30 steps; ``main``
    prints that reading beside the faults.)"""
    return m_cut(30)


def similarity_exact_form():
    """No fault: the similarity in its exact form (no product at all) where
    ``Spectral`` asks for the expansion.  What the expansion at float32
    products costs in the numbers, and what the exact form costs in time."""
    from heat_tpu.spatial import distance

    real = distance.rbf
    return _patched(distance, "rbf", lambda x, sigma, quadratic_expansion: real(x, sigma=sigma))


@contextlib.contextmanager
def parent_numerics():
    """The linalg policy at jax's default: what every product of the fit ran
    at before the solver and the similarity took ``basics._precision()``."""
    import heat_tpu as ht

    was = ht.linalg.get_matmul_precision()
    ht.linalg.set_matmul_precision("default")
    try:
        yield
    finally:
        ht.linalg.set_matmul_precision(was)


#: name -> (context manager, the number that must come out over its limit)
FAULTS = {
    "similarity_bf16_pass": (similarity_bf16_pass, "eig_residual"),
    "matvec_bf16_pass": (matvec_bf16_pass, "eig_residual"),
    "reorth_left_out": (reorth_left_out, "embedding_orth"),
    "m_cut_to_10": (m_cut_to_10, "eig_residual"),
    "diagonal_not_zeroed": (diagonal_not_zeroed, "eig_residual"),
    "simple_for_norm_sym": (simple_for_norm_sym, "eig_residual"),
}


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=8)
    ap.add_argument("--control-seeds", type=int, default=2)
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
    reference = entry._reference(config)

    def emit(seed, who, numbers, seconds=None):
        line = {"cell": args.workload, "seed": seed, "who": who, "numbers": numbers}
        if seconds is not None:
            line["fit_s"] = seconds
        print(json.dumps(line), flush=True)

    def one_job(seed, state):
        t0 = time.perf_counter()
        out = entry.run(ht, config, state, 0, seed)
        jax.block_until_ready(out)
        return out, time.perf_counter() - t0

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        x = datagen.make(config["data"], seed, devices)
        state = entry.prepare(ht, config, x)
        # every fit of the program first, its outputs kept (1.4 MB each): a
        # fit's own (n, n) array and the reference's graph do not both fit
        one_job(seed, state)
        fits = {"program": one_job(seed, state)}  # the second job: no compile in its time
        if i < args.control_seeds:
            for name, (fault, _) in FAULTS.items():
                with fault():
                    one_job(seed, state)
                    fits["fault:" + name] = one_job(seed, state)
            others = {"parent_numerics": parent_numerics, "m_cut_to_30": lambda: m_cut(10),
                      "similarity_exact_form": similarity_exact_form}
            for name, other in others.items():
                with other():
                    one_job(seed, state)
                    fits["program:" + name] = one_job(seed, state)
        del state
        for who, (out, seconds) in fits.items():
            emit(seed, who, entry.judge(config, x, out, seed), seconds)
        ref = reference.reference_fit(x, *entry._job(config))
        emit(seed, "reference", {"eigenvalues": [float(v) for v in ref["eigenvalues"]], "ncut": ref["ncut"]})
        if i < args.control_seeds:  # the reference held to itself: the floor of the numbers at this size
            emit(seed, "reference_itself", entry.judge(config, x, {k: ref[k] for k in ("labels", "embedding", "eigenvalues")}, seed))
        if i < args.control_seeds:  # bfloat16 throughout: its graph fits beside the reference's
            emit(seed, "control", entry.judge(config, x, entry.control(config, x, seed), seed))
        del ref, fits, x
        reference.forget()
    return 0


if __name__ == "__main__":
    sys.exit(main())
