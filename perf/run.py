"""The benchmark's one command:

    python perf/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, the parent of nothing.  It finds the cell in ``BENCHMARK.json``
and from it, by name, the configuration (``configs/``), the traffic mix
(``traffic/``), the cell's own file with the limits of ``correct``
(``workloads/``), the job entry (``jobs/``), the plain reference
(``references/``) and the per-layer readers (``layer_metrics/``); refuses to
run without the cell's chips; makes the data on the device from ``--seed``;
warms the cell's own shapes (set-up); runs jobs back to back for
``--seconds`` (the window); reads the peak memory; frees the program's
state; holds a sample of the window's own outputs to the plain reference
(``correct``); prints one JSON line.  ``README.md`` has the layout and how a
later PR adds to it without editing a file.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # process start, to within the interpreter's own start-up

import argparse
import gc
import importlib
import json
import os
import random
import shutil
import statistics
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
TRACE_DIR = os.path.join(ROOT, ".perf_trace")  # fixed, inside the checkout, git-ignored


class CompileCounter:
    """Compile requests and persistent-cache hits, from jax's own monitoring
    events (copied from ``chip_smoke.CompileCounter``)."""

    def __init__(self):
        import jax.monitoring

        self.requests = 0
        self.hits = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1


def _read_json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def load_cell(name: str) -> dict:
    """Everything that belongs to the cell, found by the names in
    ``BENCHMARK.json``."""
    bench = _read_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"perf/run.py: no cell {name!r} in BENCHMARK.json (cells: {sorted(cells)})")
    cell = cells[name]
    conf_entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    return {
        "bench": bench,
        "cell": cell,
        "config": _read_json(os.path.join(ROOT, conf_entry["file"])),
        "traffic": _read_json(os.path.join(HERE, "traffic", cell["traffic"] + ".json")),
        "own": _read_json(os.path.join(HERE, "workloads", name + ".json")),
        "peaks": _read_json(os.path.join(HERE, "peaks.json")),
    }


def require_chip(chips: int, peaks: dict):
    """The cell's devices, or no run: a TPU, exactly the cell's number of
    chips, of a kind whose peaks are on record."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(
            f"perf/run.py: needs a TPU, jax found platform {devices[0].platform!r}; "
            "no number is measured off the chip"
        )
    if len(devices) != chips:
        raise SystemExit(f"perf/run.py: the cell needs {chips} chip(s), jax found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks:
        raise SystemExit(
            f"perf/run.py: no peaks on record for device kind {kind!r} "
            f"(known: {sorted(peaks)}): add it to perf/peaks.json with its source"
        )
    return devices


def memory_bytes(devices) -> tuple:
    """``(in use, reserved)`` on the fullest device: the allocator's peak of
    bytes in use (arrays that live on the chip: the data, the results), and,
    apart from it, what the allocator holds reserved for the loaded programs'
    temporaries (on the TPU a program's scratch space is not counted as in
    use).  Two readings, never added: the first is ``memory_peak_bytes``.
    0 where the backend keeps no such count, as the CPU's does not."""
    stats = [d.memory_stats() or {} for d in devices]
    in_use = max(int(s.get("peak_bytes_in_use", 0)) for s in stats)
    reserved = max(int(s.get("peak_bytes_reserved", s.get("bytes_reserved", 0))) for s in stats)
    return in_use, reserved


def run_window(entry, ht, config, state, seed: int, seconds: float, keep: int):
    """The one traffic generator, a closed loop of one client: jobs back to
    back until ``seconds`` have passed; the job in flight at the deadline is
    finished and counted.  Every job is fenced
    on all its outputs, and its result released before the next starts,
    save a sample kept for the comparison: the last job, and ``keep - 1``
    earlier ones drawn from the seed (a reservoir, so that the draw needs no
    count of the jobs beforehand).  Returns the jobs' own wall times, the
    window's wall time and the kept ``(index, outputs)``."""
    import jax

    rng = random.Random(seed)
    kept, times = [], []
    start = time.perf_counter()
    deadline = start + seconds
    i = 0
    while True:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("perf_job"):
            out = entry.run(ht, config, state, i, seed)
            jax.block_until_ready(out)
        t1 = time.perf_counter()
        times.append(t1 - t0)
        last = (i, out)
        del out
        i += 1
        if t1 >= deadline:
            break
        # not the last job: its result leaves before the next job starts,
        # unless the draw keeps it
        if keep > 1:
            if len(kept) < keep - 1:
                kept.append(last)
            else:
                j = rng.randrange(i)
                if j < keep - 1:
                    kept[j] = last
        last = None
    return times, t1 - start, sorted(kept + [last], key=lambda p: p[0])


def layer_metrics(loaded: dict, view: dict) -> dict:
    """Every per-layer metric of ``BENCHMARK.json`` that lists this cell (or
    lists none), read by its own reader; one that finds nothing is left out."""
    out = {}
    cell = loaded["cell"]["name"]
    for m in loaded["bench"]["per_layer"]:
        if "workloads" in m and cell not in m["workloads"]:
            continue
        value = importlib.import_module("layer_metrics." + m["name"]).read(view)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if HERE not in sys.path:
        sys.path.insert(0, HERE)
    if ROOT not in sys.path:
        sys.path.insert(1, ROOT)
    loaded = load_cell(args.workload)
    config, traffic, own = loaded["config"], loaded["traffic"], loaded["own"]
    chips = int(loaded["cell"]["chips"])

    import heat_tpu as ht
    from heat_tpu.core._compile_cache import place_compile_cache

    place_compile_cache()
    import jax

    import datagen

    entry = importlib.import_module("jobs." + config["entry"])
    marks = {"imports": time.perf_counter() - _T0}  # seconds since process start
    # the backend's start, timed by itself: the first ``jax.devices()`` brings
    # up the TPU runtime, 5-16 s in which no line of the repo runs and which
    # swings by seconds between two runs of one call.  Nothing before this
    # stage initialises a backend (``tests/test_harness.py`` holds the order),
    # so the runtime's start lies whole in what ``setup_s`` leaves out
    devices = require_chip(chips, loaded["peaks"])
    counter = CompileCounter()
    marks["backend"] = time.perf_counter() - _T0
    backend_start_s = marks["backend"] - marks["imports"]

    # ---- set-up: data on the device from the seed, the cell's shapes warmed
    x = datagen.make(config["data"], args.seed, devices)
    state = entry.prepare(ht, config, x)
    del x  # the program holds its own; the reference makes it again from the seed
    jax.block_until_ready(getattr(state, "larray", state))
    marks["data"] = time.perf_counter() - _T0
    for warm in range(int(traffic["warmup_jobs"])):
        jax.block_until_ready(entry.run(ht, config, state, -1 - warm, args.seed))
    marks["warm_up"] = time.perf_counter() - _T0
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(traffic["trace_seconds"]))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=options)
    compiles0 = counter.requests
    # the interpreter's heap as it stands (jax's imports, mostly) is set apart
    # from the collector: a full collection inside the window then walks only
    # what the jobs themselves have left behind
    gc.collect()
    gc.freeze()
    full_collections0 = gc.get_stats()[2]["collections"]
    # process start to the first measured job, less the backend's start
    setup_s = (time.perf_counter() - _T0) - backend_start_s

    # ---- the window
    times, window_s, kept = run_window(
        entry, ht, config, state, args.seed, seconds, int(own["checked_jobs"])
    )
    compiles = counter.requests - compiles0
    full_collections = gc.get_stats()[2]["collections"] - full_collections0
    if args.trace:
        jax.profiler.stop_trace()
    peak, reserved = memory_bytes(devices)

    # ---- the program's state and its loaded programs (with their scratch
    # space) go; the sample of its outputs stays
    del state
    jax.clear_caches()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
        "memory_reserved_bytes": reserved,  # the programs' scratch, apart; the driver does not read it
    }
    result = {"correct": False, "attempted": len(times), "failed": 0}
    if args.trace:
        import trace_reduce

        summary = trace_reduce.reduce(trace_reduce.load(trace_reduce.find_trace(TRACE_DIR)))
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        if summary is None:
            raise SystemExit("perf/run.py: the trace shows no operation on the device in the window")
        view = {
            "trace": summary,
            "compiles_in_window": compiles,
            "memory_peak_bytes": peak,
            "work": entry.work(config),
            "peaks": loaded["peaks"][device["kind"]],
            "chips": chips,
        }
        result["metrics"] = layer_metrics(loaded, view)
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = summary["breakdown"]
    else:
        values = {
            "job_ms": window_s / len(times) * 1e3,
            "job_p95_ms": (statistics.quantiles(times, n=20)[18] if len(times) > 1 else times[0]) * 1e3,
            "setup_s": setup_s,
        }
        # an end-to-end metric that lists its cells is reported in those alone
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in loaded["bench"]["end_to_end"]
            if args.workload in m.get("workloads", [args.workload])
        }
        # for whoever reads a far-off run: the window's slowest jobs, by index
        slowest = sorted(range(len(times)), key=lambda i: -times[i])[:3]
        result["slowest_jobs"] = [[i, times[i] * 1e3] for i in slowest]
        result["job_median_ms"] = statistics.median(times) * 1e3
        result["full_gc_in_window"] = full_collections
        # where set-up went: seconds since process start at the end of each
        # stage, and the one stage that ``setup_s`` does not count
        result["setup_marks_s"] = marks
        result["backend_start_s"] = backend_start_s
    result["device"] = device

    # ---- correct: the window's own outputs against the plain reference
    x = datagen.make(config["data"], args.seed, devices)
    limits = own["limits"]
    worst = {}
    for index, out in kept:
        for name, value in entry.judge(config, x, out, args.seed + index).items():
            value = float(value)
            if value != value:  # a NaN is over every limit
                value = float("inf")
            worst[name] = max(worst.get(name, 0.0), value)
    result["jobs_compared"] = len(kept)
    del kept, x
    compared = {name: {"value": worst[name], "limit": limits[name]} for name in sorted(limits)}
    result["correct"] = all(c["value"] <= c["limit"] for c in compared.values())
    result["compared"] = compared  # last in the line

    print(json.dumps(result), flush=True)
    for name, c in compared.items():
        verdict = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} {c['value']:.6g} limit {c['limit']:.6g} {verdict}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
