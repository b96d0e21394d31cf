"""``dispatches_per_job`` (count, program_counter) - layer: op engine.  Moves ``job_ms``.

Launches of a compiled program that the program counted itself: the spans of
kind ``launch`` that ``heat_tpu.telemetry`` recorded during the traced window
(every counted dispatch goes through ``core/_compile.launch``, one span
each), over the jobs traced.  Beside ``launches_per_job``, which counts the
device's module events: the difference is the eager ``jax.numpy`` programs
no counter of the program sees.  Nothing to read where the program keeps no
such record (before the spans came) or recorded nothing.
"""


def window_spans():
    """The spans the program recorded during the most recent profiler trace,
    or None where it has none to give."""
    try:
        from heat_tpu import telemetry
    except ImportError:
        return None
    read = getattr(telemetry, "profiled_spans", None)
    return (read() or None) if read is not None else None


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    return sum(1 for e in spans if e["kind"] == "launch") / run["trace"]["jobs"]
