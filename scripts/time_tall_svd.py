"""Time ``ht.linalg.svd`` of a tall float32 operand held by one chip on each
of its routes, and hold the ``cholqr2`` route's fallback to its numbers: the
measurement behind ``core/linalg/qr.py:MIN_BYTES`` and ``KAPPA_MAX``.

    <chip tool> --chips 1 -- python scripts/time_tall_svd.py [--seed 0]

``sizes``: for 64 and 300 columns and row counts from 4 096 up, one
``ht.linalg.svd`` of a normal operand on the ``householder`` route (XLA's QR
of the whole operand, the fused chain) and on ``cholqr2`` (two Gram passes and
U = A·W), each the median of five calls after one that compiles.

``fallback``: at the benchmark's 6 291 456 x 300, operands on which the
``cholqr2`` program must take its blocked TSQR (columns scaled down to
κ = 1e6, a column repeated, a column of zeros) beside a sound one (normal
columns): the time of a call and, on the device by blocks of rows, the
largest entry of ``|UᵀU - I|`` and ``|A·V - U·diag(S)|_F / |A|_F``.

One JSON line a reading, a copy in ``chiprun_out/time_tall_svd.jsonl``.
Refuses to run without a TPU: a time from the CPU says nothing.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

ROWS = {64: (16_384, 65_536, 262_144, 1_048_576, 4_194_304), 300: (4_096, 16_384, 65_536, 262_144, 1_048_576)}
#: above these, the whole-operand form is not timed (its first call compiles
#: for half a minute and more, PR 21's smoke run)
HOUSEHOLDER_MAX_BYTES = 1 << 30
BIG = (6_291_456, 300)
REPEATS = 5


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from heat_tpu.core._compile_cache import place_compile_cache

    if jax.default_backend() != "tpu":
        print("no TPU: nothing timed", file=sys.stderr)
        return 1
    place_compile_cache()
    import heat_tpu as ht

    qr = importlib.import_module("heat_tpu.core.linalg.qr")
    comm = ht.XlaCommunication(jax.devices()[:1])
    os.makedirs("chiprun_out", exist_ok=True)
    sink = open(os.path.join("chiprun_out", "time_tall_svd.jsonl"), "w")

    def emit(line):
        print(json.dumps(line), flush=True)
        sink.write(json.dumps(line) + "\n")
        sink.flush()

    def timed(a):
        times = []
        for i in range(REPEATS + 1):
            t0 = time.perf_counter()
            out = ht.linalg.svd(a)
            jax.block_until_ready(out.U.larray)
            times.append(time.perf_counter() - t0)
            if i < REPEATS:
                del out
        return out, times[0], statistics.median(times[1:])

    def operand(m, n, seed):
        return ht.array(jax.random.normal(jax.random.PRNGKey(seed), (m, n), jnp.float32), split=0, comm=comm)

    for n, rows in ROWS.items():
        for m in rows:
            a = operand(m, n, args.seed + m)
            for route, floor in (("cholqr2", 0), ("householder", 1 << 62)):
                if route == "householder" and m * n * 4 > HOUSEHOLDER_MAX_BYTES:
                    continue
                qr.MIN_BYTES = floor
                assert qr.tall_route((m, n), jnp.float32) == route
                out, first, warm = timed(a)
                del out
                emit({"what": "sizes", "rows": m, "cols": n, "bytes": m * n * 4, "route": route,
                      "first_s": first, "warm_ms": 1e3 * warm})
            del a
    qr.MIN_BYTES = 0

    m, n = BIG
    block = 1 << 16

    @jax.jit
    def numbers(x, u, s, v):
        def step(i, acc):
            xb = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)
            ub = jax.lax.dynamic_slice_in_dim(u, i * block, block, 0)
            gram = jnp.matmul(ub.T, ub, precision="highest")
            res = jnp.matmul(xb, v, precision="highest") - ub * s
            return acc[0] + gram, acc[1] + jnp.sum(res * res), acc[2] + jnp.sum(xb * xb)

        g, r2, a2 = jax.lax.fori_loop(0, m // block, step, (jnp.zeros((n, n)), 0.0, 0.0))
        return jnp.max(jnp.abs(g - jnp.eye(n))), jnp.sqrt(r2 / a2), s[0] / s[-1]

    scale = jnp.logspace(0.0, -6.0, n, dtype=jnp.float32)
    kinds = {
        "normal": lambda x: x,
        "kappa_1e6": lambda x: x * scale,
        "column_repeated": lambda x: x.at[:, n - 1].set(x[:, 0]),
        "zero_column": lambda x: x.at[:, n // 2].set(0.0),
    }
    for kind, make in kinds.items():
        x = make(jax.random.normal(jax.random.PRNGKey(args.seed), (m, n), jnp.float32))
        a = ht.array(x, split=0, comm=comm, copy=False)
        out, first, warm = timed(a)
        u_orth, recon, kappa = (float(t) for t in numbers(x, out.U.larray, out.S.larray, out.V.larray))
        emit({"what": "fallback", "kind": kind, "rows": m, "cols": n, "first_s": first, "warm_ms": 1e3 * warm,
              "u_orth": u_orth, "recon_rel": recon, "kappa_served": kappa})
        del out, a, x
    sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
