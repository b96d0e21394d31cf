"""``import_s`` (s, program_span) - layer: package.  Moves ``setup_s``.

What ``import heat_tpu`` took: the duration of the record ``import:heat_tpu``
in the program's start-up record (``heat_tpu.telemetry.startup()``: the
package stamps the clock after each import statement of its two ``__init__``
files, and jax reports every program's trace, lower and compile-or-load time;
kept with telemetry disabled).  With the three metrics beside it, the first
to look inside ``setup_s``.

All four read the records that began before the traced window did (the first
span of ``telemetry.profiled_spans()``, same clock): what the process did in
set-up, ``datagen``'s one program and the eager ``jax.numpy`` programs
included; what the comparison compiles after the window is left out.  Nothing
to read where the program keeps no such record (before it came), recorded
nothing, or no window was traced.
"""

from layer_metrics.dispatches_per_job import window_spans


def setup_records():
    """The start-up records that began before the traced window, or None
    where there is nothing to read."""
    spans = window_spans()
    if spans is None:
        return None
    from heat_tpu import telemetry

    read = getattr(telemetry, "startup", None)
    if read is None:
        return None
    start = min(e["ts"] for e in spans)
    return [r for r in read() if r["ts"] < start] or None


def covered_s(records, sites) -> float:
    """Seconds the records at ``sites`` cover together: the union of their
    intervals, so that a trace nested in another (a ``jax.numpy`` function
    traced inside a program's trace reports its own duration) counts once."""
    covered, end = 0.0, float("-inf")
    for a, b in sorted((r["ts"], r["ts"] + r["dur"]) for r in records if r["site"] in sites):
        if b > end:
            covered += b - max(a, end)
            end = b
    return covered


def read(run):
    records = setup_records()
    if records is None or run["trace"] is None:
        return None
    roots = [r["dur"] for r in records if r["site"] == "import:heat_tpu"]
    return sum(roots) if roots else None
