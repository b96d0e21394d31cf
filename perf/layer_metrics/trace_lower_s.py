"""``trace_lower_s`` (s, program_span) - layer: op engine.  Moves ``setup_s``.

Seconds of set-up in which jax traced a program to a jaxpr or lowered one to
a module: the union of the intervals of the records ``compile:trace`` and
``compile:lower`` of the program's start-up record that began before the
traced window (``import_s.setup_records``).  Python's and MLIR's work ahead
of the compile cache: a warm cache does not shorten it.
"""

from layer_metrics.import_s import covered_s, setup_records


def read(run):
    records = setup_records()
    if records is None or run["trace"] is None:
        return None
    return covered_s(records, ("compile:trace", "compile:lower"))
