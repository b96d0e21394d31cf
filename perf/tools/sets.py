"""Two sets of runs of one cell, the same seeds in both, and each metric's
spread (distance between the first and the third quartile as a share of the
median, ``statistics.quantiles(values, n=4)``), as the bounds in
``BENCHMARK.json`` are set from (about five times the widest):

    python perf/tools/sets.py --workload <cell> [--runs 6] [--traced 3] [--seconds S]

Every run is a child process of its own (this parent never touches jax, so
the child is the chip's one holder).  Result lines go to standard output and,
where that directory exists, to ``chiprun_out/sets_<cell>.jsonl``.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)


def spread(values):
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--traced", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--first-seed", type=int, default=2147483659)
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        bench = json.load(fh)
    seconds = args.seconds if args.seconds is not None else bench["run_seconds"]
    seeds = [args.first_seed + 1000003 * i for i in range(args.runs)]
    log = None
    if os.path.isdir(os.path.join(ROOT, "chiprun_out")):
        log = open(os.path.join(ROOT, "chiprun_out", f"sets_{args.workload}.jsonl"), "a")

    def one(seed, trace):
        cmd = bench["command"] + ["--workload", args.workload, "--seed", str(seed),
                                  "--seconds", str(seconds), "--trace", str(trace)]
        proc = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
        if proc.returncode != 0:
            sys.stderr.write(proc.stderr[-4000:])
            raise SystemExit(f"sets.py: run failed with code {proc.returncode}")
        line = json.loads(proc.stdout.strip().splitlines()[-1])
        line["seed"], line["trace"] = seed, trace
        text = json.dumps(line)
        print(text, flush=True)
        if log:
            log.write(text + "\n")
            log.flush()
        return line

    sets = []
    for s in range(2):
        sets.append([one(seed, 0) for seed in seeds])
    for i in range(args.traced):
        one(seeds[-1] + 17 * (i + 1), 1)
    summary = {"cell": args.workload, "seconds": seconds, "all_correct": all(l["correct"] for st in sets for l in st)}
    for name in sets[0][0]["metrics"]:
        per_set = [[l["metrics"][name]["value"] for l in st] for st in sets]
        summary[name] = {
            "medians": [statistics.median(v) for v in per_set],
            "spreads": [spread(v) for v in per_set],
            "first_run": per_set[0][0],
        }
    print(json.dumps({"summary": summary}), flush=True)
    if log:
        log.write(json.dumps({"summary": summary}) + "\n")
        log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
