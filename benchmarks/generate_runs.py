"""Scaling-run generator.

Reference: benchmarks/generate_jobscripts.py:11-181 — emits SLURM jobscripts
for strong/weak scaling over node×task grids.  The TPU analog emits plain
shell scripts sweeping mesh sizes: virtual CPU meshes for development (the
harnesses' ``--devices N`` flag, which configures jax_num_cpu_devices
before import) and, on real hardware, the device counts of the attached
slice (a TPU job owns its whole slice, so the reference's nodes×tasks grid
collapses to the mesh size).  Virtual devices share one host's cores: a
sweep over them checks that every mesh size runs, and its times are no
scaling curve.  The repo's benchmark is ``BENCHMARK.json`` + ``perf/``.
"""

from __future__ import annotations

import argparse
import json
import os
import stat

SUITES = {
    "kmeans": "benchmarks/kmeans/heat_tpu_bench.py",
    "distance_matrix": "benchmarks/distance_matrix/heat_tpu_bench.py",
    "lasso": "benchmarks/lasso/heat_tpu_bench.py",
    "statistical_moments": "benchmarks/statistical_moments/heat_tpu_bench.py",
    "attention": "benchmarks/attention/heat_tpu_bench.py",
}

STRONG_N = {"kmeans": 500_000, "distance_matrix": 20_000, "lasso": 1_000_000,
            "statistical_moments": 10_000_000, "attention": 16_384}

#: the workload-size flag per suite (attention scales the sequence length)
SIZE_FLAG = {"attention": "--seq"}


def main():
    parser = argparse.ArgumentParser(description="generate scaling-run scripts")
    parser.add_argument("--meshes", type=int, nargs="+", default=[1, 2, 4, 8])
    parser.add_argument("--mode", choices=["strong", "weak"], default="strong")
    parser.add_argument("--platform", choices=["cpu", "tpu"], default="cpu")
    parser.add_argument("--outdir", default="benchmarks/runs")
    args = parser.parse_args()

    os.makedirs(args.outdir, exist_ok=True)
    for suite, script in SUITES.items():
        lines = ["#!/bin/sh", "# generated by benchmarks/generate_runs.py", ""]
        for m in args.meshes:
            n = STRONG_N[suite] if args.mode == "strong" else STRONG_N[suite] * m // max(args.meshes)
            dev_flag = f" --devices {m}" if args.platform == "cpu" else ""
            lines.append(f"echo '== {suite} mesh={m} {args.mode} n={n} =='")
            size_flag = SIZE_FLAG.get(suite, "--n")
            lines.append(f"python {script} {size_flag} {n} --trials 3{dev_flag}")
        path = os.path.join(args.outdir, f"{suite}_{args.mode}_{args.platform}.sh")
        with open(path, "w") as f:
            f.write("\n".join(lines) + "\n")
        os.chmod(path, os.stat(path).st_mode | stat.S_IXUSR)
        print("wrote", path)


if __name__ == "__main__":
    main()
