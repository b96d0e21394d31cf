"""``solver_steps_per_job`` (count, program_counter) - layer: solvers.  Moves ``job_ms``.

Lanczos steps the program issued in the traced window, over the jobs traced:
the sum of the ``steps`` field of the launch spans at ``jit:lanczos.segment``
(``core/linalg/solver.py:lanczos`` records how many steps each segment
runs).  ``n_lanczos - 1`` says the loop ran whole; the count of the spans
beside it (``dispatches_per_job``) says in how many segments.  Nothing to
read where the program records no such span.
"""

from layer_metrics.dispatches_per_job import window_spans

SITE = "jit:lanczos.segment"


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    steps = [e["steps"] for e in spans if e["site"] == SITE and "steps" in e]
    return sum(steps) / run["trace"]["jobs"] if steps else None
