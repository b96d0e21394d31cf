"""``x_passes_per_job`` (count, program_counter) - layer: estimators.  Moves ``job_ms``.

Reads of the resident data that the KMedians fit programs issued in the
traced window, over the jobs traced: the sum of the ``x_passes`` field of the
launch spans at ``jit:kmedians.`` (``cluster/kmedians.py`` says of each fit
how many times its program reads X: two a sweep and one for the last
assignment on the route ``column_select``; ``tests/test_tpu_compile.py`` holds
the field to the program compiled for the chip).  The job entry's ``work``
counts one read a sweep and one more; what this reads above that is what a
fused sweep would take off ``job_ms``.  Nothing to read where the program
records no such field.
"""

from layer_metrics.dispatches_per_job import window_spans

SITES = "jit:kmedians."


def passes(run):
    """The ``x_passes`` of the window's fit spans, summed; None without any."""
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    found = [e["x_passes"] for e in spans if e["site"].startswith(SITES) and "x_passes" in e]
    return sum(found) if found else None


def read(run):
    total = passes(run)
    return None if total is None else total / run["trace"]["jobs"]
