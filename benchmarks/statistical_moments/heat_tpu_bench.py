"""Statistical-moments benchmark (reference: benchmarks/
statistical_moments/heat-cpu.py — mean/std along axis 0, 10 trials).

A port of the reference's harness script, kept as the origin of the
benchmark's data and settings: ``perf/configs/moments-cityscapes-1chip.json``
(cell ``moments_300_c1``) cites it for the job, ``ht.mean`` then ``ht.std``
along axis 0 of a ``split=0`` array.  It prints wall time on whatever device
it runs on: under ``--devices N`` (a virtual CPU mesh) that checks the
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
    parser = argparse.ArgumentParser(description="heat_tpu moments benchmark")
    parser.add_argument("--n", type=int, default=10_000_000)
    parser.add_argument("--f", type=int, default=8)
    parser.add_argument("--trials", type=int, default=3)
    args = bootstrap(parser)

    import heat_tpu as ht

    rng = np.random.default_rng(0)
    x = ht.array(rng.normal(size=(args.n, args.f)).astype(np.float32), split=0)

    ht.mean(x, axis=0).larray.block_until_ready()  # warmup
    ht.std(x, axis=0).larray.block_until_ready()

    times = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        m = ht.mean(x, axis=0)
        s = ht.std(x, axis=0)
        s.larray.block_until_ready()
        times.append(time.perf_counter() - t0)
    best = min(times)
    # one read of the data for each of the two calls is what the job requires;
    # the code reads it three times (ht.std makes its mean in a pass of its own)
    gb = x.nbytes * 2 / 1e9
    print(f"moments: n={args.n} f={args.f} best={best:.4f}s → {gb / best:.2f} GB/s")


if __name__ == "__main__":
    main()
