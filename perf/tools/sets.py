"""Two sets of runs of one cell, the same seeds in both, and each metric's
spread (distance between the first and the third quartile as a share of the
median, ``statistics.quantiles(values, n=4)``), as the bounds in
``BENCHMARK.json`` are set from (about five times the widest):

    python perf/tools/sets.py --workload <cell> [--runs 6] [--traced 3] [--seconds S]

One run on a seed of its own goes ahead of the sets and counts in neither:
in a fresh checkout it is the one that compiles, as the driver keeps each
side's first run apart (``lead`` in the summary).  The summary then gives,
for every end-to-end metric and for every stage of set-up (``stages``, from
the lines' ``setup_marks_s`` and ``backend_start_s``; ``setup_s_with_backend``
is what ``setup_s`` was before PR 35), each set's median and spread and how
far apart the two medians lie as a share of the first: what ``setup_s``'s
bound is set from, by the rule in ``PERF.md`` section 2.

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


def stages(line: dict) -> dict:
    """Seconds each stage of set-up took in one untraced run, in their order;
    ``setup_s`` itself comes with the metrics."""
    marks, backend = line["setup_marks_s"], line["backend_start_s"]
    return {
        "imports": marks["imports"],
        "backend_start": backend,
        "data": marks["data"] - marks["backend"],
        "warm_up": marks["warm_up"] - marks["data"],
        "setup_s_with_backend": line["metrics"]["setup_s"]["value"] + backend,
    }


def two_sets(per_set: list) -> dict:
    medians = [statistics.median(v) for v in per_set]
    return {
        "medians": medians,
        "spreads": [spread(v) for v in per_set],
        "medians_apart": abs(medians[1] - medians[0]) / medians[0],
    }


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

    lead = one(args.first_seed - 7, 0)
    sets = [[one(seed, 0) for seed in seeds] for _ in range(2)]
    traced = [one(seeds[-1] + 17 * (i + 1), 1) for i in range(args.traced)]
    summary = {
        "cell": args.workload,
        "seconds": seconds,
        "all_correct": all(l["correct"] for l in [lead] + sets[0] + sets[1] + traced),
        "lead": {name: m["value"] for name, m in lead["metrics"].items()},
    }
    for name in lead["metrics"]:
        summary[name] = two_sets([[l["metrics"][name]["value"] for l in st] for st in sets])
    staged = [[stages(l) for l in st] for st in sets]
    summary["stages"] = {name: two_sets([[s[name] for s in st] for st in staged]) for name in staged[0][0]}
    print(json.dumps({"summary": summary}), flush=True)
    if log:
        log.write(json.dumps({"summary": summary}) + "\n")
        log.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
