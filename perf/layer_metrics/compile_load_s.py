"""``compile_load_s`` (s, program_span) - layer: op engine.  Moves ``setup_s``.

Seconds of set-up inside jax's backend-compile stage: the union of the
intervals of the records ``compile:backend`` of the program's start-up record
that began before the traced window (``import_s.setup_records``).  With a
warm persistent cache this is the cache's reads and the executables'
loading; with a cold one, the compiler.
"""

from layer_metrics.import_s import covered_s, setup_records


def read(run):
    records = setup_records()
    if records is None or run["trace"] is None:
        return None
    return covered_s(records, ("compile:backend",))
