"""Where a benchmark cell's ``setup_s`` goes, from the program's own start-up
record: one untraced run of ``perf/run.py`` in this process, then the account.

    python scripts/startup_account.py --workload <cell> --seed <n> --seconds <s>

``perf/run.py`` marks the end of its four stages from outside (``imports``,
``backend``, ``data``, ``warm_up``: ``setup_marks_s`` in its line);
``telemetry.startup()`` holds what the package's import statements took and
every program's trace, lower and compile-or-load, on the same clock.  This
lays the second over the first: for each stage the seconds the package's
import, tracing and lowering, and the backend's compile-or-load cover, and
what is left (the interpreter's and ``run.py``'s own imports; the data's and
the warm-up jobs' device time and dispatch).  The result line of the run is
printed as ``run.py`` printed it, then one JSON line with the account; the
operator's text (``telemetry.startup_report()``) and the account go to
``chiprun_out/startup_<cell>.txt``.  What the record costs is timed here too:
a thousand calls of the compile listener and of an import stamp.
"""

import contextlib
import io
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "perf"))
sys.path.insert(1, ROOT)


def account(records, t0: float, marks: dict, backend_start_s: float) -> dict:
    """``records`` (``telemetry.startup()``) laid over the stages whose ends
    since ``t0`` ``marks`` gives."""
    from heat_tpu.telemetry._core import covered_s

    def pairs(*sites):
        return [(r["ts"], r["ts"] + r["dur"]) for r in records if r["site"] in sites]

    parts = {
        "import_s": pairs("import:heat_tpu"),
        "trace_lower_s": pairs("compile:trace", "compile:lower"),
        "compile_load_s": pairs("compile:backend"),
    }
    stages, lo = {}, t0
    for name, end in marks.items():
        hi = t0 + end
        row = {k: covered_s(v, lo, hi) for k, v in parts.items()}
        # tracing, lowering and loading nest in nothing of each other's, and none runs during the import
        row["rest_s"] = (hi - lo) - sum(row.values())
        row["stage_s"] = hi - lo
        row["programs"] = sum(1 for a, _ in parts["compile_load_s"] if lo <= a < hi)
        stages[name], lo = row, hi
    end = t0 + marks["warm_up"]
    total = {k: covered_s(v, t0, end) for k, v in parts.items()}
    total["programs"] = sum(1 for a, _ in parts["compile_load_s"] if a < end)
    total["accounted_s"] = total["import_s"] + total["trace_lower_s"] + total["compile_load_s"]
    total["to_warm_up_less_backend_s"] = marks["warm_up"] - backend_start_s
    return {"stages": stages, "total": total}


def record_costs(n: int = 1000) -> dict:
    """Seconds one call costs: the compile listener (into a scratch record,
    nothing recording) and an import stamp as the ``__init__`` files make it."""
    from heat_tpu.core import _compile
    from heat_tpu.telemetry import _core

    kept, _core._startup = _core._startup, []
    try:
        t = time.perf_counter()
        for _ in range(n):
            _compile._on_compile_stage("/jax/core/compile/jaxpr_trace_duration", 0.001, fun_name="jit(x)")
            _core._startup.clear()
        listener = (time.perf_counter() - t) / n
    finally:
        _core._startup = kept
    stamps = []

    def _done(what, _now=time.monotonic, _add=stamps.append):
        _add((what, _now()))

    t = time.perf_counter()
    for _ in range(n):
        _done("x")
    stamp = (time.perf_counter() - t) / n
    return {"listener_call_s": listener, "import_stamp_s": stamp}


def main() -> int:
    import run  # perf/run.py: its _T0 is this process's start, to within what ran above

    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run.main(sys.argv[1:] + ["--trace", "0"])
    sys.stdout.write(out.getvalue())
    if rc:
        return rc
    line = json.loads([l for l in out.getvalue().splitlines() if l.startswith("{")][-1])
    cell = sys.argv[sys.argv.index("--workload") + 1]

    from heat_tpu import telemetry

    records = telemetry.startup()
    acc = account(records, run._T0, line["setup_marks_s"], line["backend_start_s"])
    acc.update(
        cell=cell,
        setup_s=line["metrics"]["setup_s"]["value"],
        job_ms=line["metrics"]["job_ms"]["value"],
        costs=record_costs(),
        records=len(records),
    )
    print(json.dumps({"startup_account": acc}), flush=True)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", f"startup_{cell}.txt"), "w") as fh:
        fh.write(telemetry.startup_report() + "\n\n" + json.dumps(acc, indent=1) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
