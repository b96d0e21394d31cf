"""KMeans benchmark (reference: benchmarks/kmeans/heat-cpu.py:1-34 —
10 trials of an 8-cluster, 30-iteration fit timed with perf_counter).

Synthetic blobs stand in for the cityscapes H5 input (config.json:1-7);
pass --h5 PATH DATASET to reproduce the reference's file-driven runs.

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
    parser = argparse.ArgumentParser(description="heat_tpu kmeans benchmark")
    parser.add_argument("--n", type=int, default=500_000, help="samples")
    parser.add_argument("--f", type=int, default=32, help="features")
    parser.add_argument("--clusters", type=int, default=8)
    parser.add_argument("--iterations", type=int, default=30)
    parser.add_argument("--trials", type=int, default=3)
    parser.add_argument("--h5", nargs=2, metavar=("PATH", "DATASET"), default=None)
    args = bootstrap(parser)

    import heat_tpu as ht

    if args.h5:
        data = ht.load_hdf5(args.h5[0], args.h5[1], split=0)
    else:
        rng = np.random.default_rng(0)
        centers = rng.normal(scale=10, size=(args.clusters, args.f))
        blobs = np.concatenate(
            [c + rng.normal(size=(args.n // args.clusters, args.f)) for c in centers]
        ).astype(np.float32)
        data = ht.array(blobs, split=0)

    km = ht.cluster.KMeans(
        n_clusters=args.clusters, init="probability_based", max_iter=args.iterations,
        tol=-1.0, random_state=1,
    )
    km.fit(data)  # warmup: compiles the fused step

    times = []
    for _ in range(args.trials):
        t0 = time.perf_counter()
        km = ht.cluster.KMeans(
            n_clusters=args.clusters, init="probability_based",
            max_iter=args.iterations, tol=-1.0, random_state=1,
        )
        km.fit(data)
        # fit is fully async (device scalars stay lazy): without this
        # readback fence the 1-device timing measures DISPATCH ONLY
        # (~150 us) and fabricates a 30x "scaling cliff" vs meshes whose
        # label resharding happens to synchronize (r4 scaling record)
        np.asarray(km.cluster_centers_.larray)
        times.append(time.perf_counter() - t0)
    best = min(times)
    print(f"kmeans: n={data.shape[0]} f={data.shape[1]} k={args.clusters} "
          f"iters={km.n_iter_} best={best:.3f}s → {km.n_iter_ / best:.2f} iter/s")


if __name__ == "__main__":
    main()
