"""Lasso benchmark (reference: benchmarks/lasso/heat-cpu.py — coordinate
descent on the eurad H5 set, 1 iteration, 10 trials).

A port of the reference's harness script, kept as the origin of the
benchmark's data and settings.  It prints wall time on whatever device it
runs on: under ``--devices N`` (a virtual CPU mesh) that checks the
distributed code path and is no rate.  The repo's benchmark is
``BENCHMARK.json`` + ``perf/``; its numbers are in ``PERF_LEDGER.jsonl``.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
from _common import bootstrap


def main():
    parser = argparse.ArgumentParser(description="heat_tpu lasso benchmark")
    parser.add_argument("--n", type=int, default=1_000_000, help="samples")
    parser.add_argument("--f", type=int, default=8, help="features")
    parser.add_argument("--iterations", type=int, default=1)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--h5", nargs=3, metavar=("PATH", "XDSET", "YDSET"), default=None)
    args = bootstrap(parser)

    import heat_tpu as ht

    if args.h5:
        x = ht.load_hdf5(args.h5[0], args.h5[1], split=0)
        y = ht.load_hdf5(args.h5[0], args.h5[2], split=0)
    else:
        rng = np.random.default_rng(0)
        w = rng.normal(size=args.f).astype(np.float32)
        xd = rng.normal(size=(args.n, args.f)).astype(np.float32)
        yd = xd @ w + 0.1 * rng.normal(size=args.n).astype(np.float32)
        x, y = ht.array(xd, split=0), ht.array(yd, split=0)

    est = ht.regression.Lasso(lam=0.1, max_iter=args.iterations, tol=-1.0)
    est.fit(x, y)  # warmup compile

    times = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        ht.regression.Lasso(lam=0.1, max_iter=args.iterations, tol=-1.0).fit(x, y)
        times.append(time.perf_counter() - t0)
    best = min(times)
    print(f"lasso: n={x.shape[0]} f={x.shape[1]} sweeps={args.iterations} "
          f"best={best:.3f}s → {args.iterations / best:.2f} sweeps/s")


if __name__ == "__main__":
    main()
