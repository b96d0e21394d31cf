"""``a_passes_per_job`` (count, program_counter) - layer: linalg.  Moves ``job_ms``.

Reads of the tall operand that the SVD programs issued in the traced window,
over the jobs traced: the sum of the ``a_passes`` field of the launch spans
at ``jitted:linalg.svd`` (``core/linalg/svd.py`` states of each program how
many times it reads A: two Gram passes and U's on the route ``cholqr2``;
``tests/test_tpu_compile.py`` holds the field to the program compiled for the
chip).  The job entry's ``work`` counts two reads; what this reads above that
is what a program that forms its factor in one pass would take off
``job_ms``.  Nothing to read where the program records no such field.
"""

from layer_metrics.dispatches_per_job import window_spans

SITE = "jitted:linalg.svd"


def passes(run):
    """``[(a_passes, precision), ...]`` of the window's SVD spans; None
    without any."""
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    found = [(e["a_passes"], e.get("precision")) for e in spans if e["site"] == SITE and "a_passes" in e]
    return found or None


def read(run):
    found = passes(run)
    return None if found is None else sum(p for p, _ in found) / run["trace"]["jobs"]
