"""``dispatch_ms_per_job`` (ms, program_span) - layer: op engine.  Moves ``job_ms``.

Total duration of the program's ``launch`` spans in the traced window, over
the jobs traced: host time spent issuing compiled programs (a first call's
span holds trace, lower and compile, but every shape is warmed in set-up).
"""

from layer_metrics.dispatches_per_job import window_spans


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    return sum(e["dur"] for e in spans if e["kind"] == "launch") / run["trace"]["jobs"] * 1e3
