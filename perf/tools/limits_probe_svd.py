"""The readings the limits of a tall SVD cell are set from, at the cell's own
size, in one process on the chip (``PERF.md`` section 2 has the rule):

    python perf/tools/limits_probe_svd.py --workload svd_300_c1 \\
        --first-seed <n> --seeds 12 --control-seeds 3

For each of ``--seeds`` seeds: the data, one job of the program through the
timed entry and the job's numbers against the plain reference (the lower
readings), with kappa(A) from the reference's singular values.  For each of
the first ``--control-seeds`` of them also the upper readings, judged the same
way: the program with each fault of :data:`FAULTS` planted in its own code,
and the job entry's ``control`` (the plain reference with the data in
bfloat16 in the program's place); ``--faults`` names which of them.  One
JSON line each on standard output, with the seconds the job took.  Refuses
to run off the chip, as ``run.py`` does.

A fault is a context manager that breaks one function of
``heat_tpu/core/linalg/qr.py`` that the SVD program looks up when it is
traced, and mends it on the way out; ``perf/tests/test_svd_cell.py`` drives
a run under each on the CPU.
"""

from __future__ import annotations

import argparse
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


def _qr():
    return importlib.import_module("heat_tpu.core.linalg.qr")


def tall_products_one_bf16_pass():
    """Every tall product of the route (the Grams, Q1's blocks, U) from
    operands rounded to bfloat16: one MXU pass, what ``precision="default"``
    gives on the chip."""
    import jax.numpy as jnp

    def dot(a, b, precision):
        return jnp.matmul(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), preferred_element_type=jnp.float32)

    return _patched(_qr(), "_tall_dot", dot)


def cholqr_without_second_pass():
    """CholeskyQR once: R and R⁻¹ from the Gram of A itself."""
    return _patched(_qr(), "_second_pass", lambda a, r1, r1inv, precision: (r1, r1inv))


def row_block_left_out():
    """The first block of rows left out of both Gram passes."""
    qr = _qr()
    real = qr._gram

    def gram(a, rinv, precision):
        rows = a[: min(qr.BLOCK_ROWS, a.shape[0])]
        q = rows if rinv is None else qr._tall_dot(rows, rinv, precision)
        return real(a, rinv, precision) - qr._tall_dot(q.T, q, precision)

    return _patched(qr, "_gram", gram)


def stale_w():
    """U formed with the first pass's inverse: ``W = R1⁻¹·U_R`` where R is
    ``R2·R1`` (R, and so S and V, are sound)."""
    qr = _qr()
    real = qr._second_pass

    def second(a, r1, r1inv, precision):
        r, _ = real(a, r1, r1inv, precision)
        return r, r1inv

    return _patched(qr, "_second_pass", second)


def v_from_r_one_bf16_pass():
    """V formed from R and U_R, ``V = Rᵀ·U_R·S⁻¹``, that product from operands
    rounded to bfloat16, in place of the small SVD's own V (U and S are the
    SVD's)."""
    import jax.numpy as jnp

    qr = _qr()
    real = qr._r_svd

    def small(r):
        ur, s, _ = real(r)
        rtu = jnp.matmul(r.T.astype(jnp.bfloat16), ur.astype(jnp.bfloat16), preferred_element_type=jnp.float32)
        return ur, s, rtu / s

    return _patched(qr, "_r_svd", small)


#: name -> (context manager, the number that must come out over its limit)
FAULTS = {
    "tall_products_one_bf16_pass": (tall_products_one_bf16_pass, "sv_rel"),
    "cholqr_without_second_pass": (cholqr_without_second_pass, "u_orth"),
    "row_block_left_out": (row_block_left_out, "sv_rel"),
    "stale_w": (stale_w, "u_orth"),
    "v_from_r_one_bf16_pass": (v_from_r_one_bf16_pass, "v_orth"),
}


def main(argv=None) -> int:
    import run

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--faults", nargs="*", default=sorted(FAULTS) + ["control"],
                    help="what to plant on the control seeds: names of FAULTS, and control")
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
    reference = importlib.import_module("references." + config["reference"])

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
        out, seconds = one_job(seed, state)
        s = reference.spectrum(x, jax.numpy.float32)[0]
        emit(seed, "program", entry.judge(config, x, out, seed), seconds, kappa=float(s[0] / s[-1]))
        del out
        if i < args.control_seeds:
            for name, (fault, _) in FAULTS.items():
                if name not in args.faults:
                    continue
                with fault():
                    out, seconds = one_job(seed, state)
                emit(seed, "fault:" + name, entry.judge(config, x, out, seed), seconds)
                del out
        del state
        if i < args.control_seeds and "control" in args.faults:
            t0 = time.perf_counter()
            out = entry.control(config, x, seed)
            emit(seed, "control", entry.judge(config, x, out, seed), time.perf_counter() - t0)
            del out
        del x
    return 0


if __name__ == "__main__":
    sys.exit(main())
