"""Pairwise-distance benchmark (reference: benchmarks/distance_matrix/
heat-cpu.py:1-34 — cdist on a SUSY H5 slice, 10 trials).

Prints the best trial's wall time and the bytes of the result matrix over
it.

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
    parser = argparse.ArgumentParser(description="heat_tpu cdist benchmark")
    parser.add_argument("--n", type=int, default=20_000, help="rows of X")
    parser.add_argument("--f", type=int, default=18, help="features (SUSY width)")
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--h5", nargs=2, metavar=("PATH", "DATASET"), default=None)
    args = bootstrap(parser)

    import heat_tpu as ht

    if args.h5:
        X = ht.load_hdf5(args.h5[0], args.h5[1], split=0)
    else:
        rng = np.random.default_rng(0)
        X = ht.array(rng.normal(size=(args.n, args.f)).astype(np.float32), split=0)

    d = ht.spatial.cdist(X, quadratic_expansion=True)  # warmup compile
    d.larray.block_until_ready()

    times = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        d = ht.spatial.cdist(X, quadratic_expansion=True)
        d.larray.block_until_ready()
        times.append(time.perf_counter() - t0)
    best = min(times)
    out_bytes = d.shape[0] * d.shape[1] * 4
    print(f"cdist: n={X.shape[0]} f={X.shape[1]} best={best:.3f}s "
          f"→ {out_bytes / best / 1e9:.2f} GB/s")


if __name__ == "__main__":
    main()
