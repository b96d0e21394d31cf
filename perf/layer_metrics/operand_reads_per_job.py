"""``operand_reads_per_job`` (count, program_counter) - layer: array ops.  Moves ``job_ms``.

Reads of the resident operand that the array layer's statistics programs
issued in the traced window, over the jobs traced: the sum of the ``reads``
field of the launch spans at ``jitted:stat.*`` (``core/statistics.py`` says of
each program how many times it reads its operand: ``stat.mean`` 1,
``stat.moment2`` 1 on the form ``one_pass`` and 2 on ``two_pass``;
``tests/test_tpu_compile.py`` holds the field to the program compiled for the
chip).  The job entry's ``work`` counts one read a
public call; what this reads above that is what a pass saved would take off
``job_ms``.  Nothing to read where the program records no such field.
"""

from layer_metrics.dispatches_per_job import window_spans

SITES = "jitted:stat."


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    reads = [e["reads"] for e in spans if e["site"].startswith(SITES) and "reads" in e]
    return sum(reads) / run["trace"]["jobs"] if reads else None
