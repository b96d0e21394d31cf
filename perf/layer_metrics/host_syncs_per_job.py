"""``host_syncs_per_job`` (count, program_counter) - layer: estimators.  Moves ``job_ms``.

Blocking device-to-host reads in the traced window, over the jobs traced:
the program's spans of kind ``sync`` (``telemetry.host_read``, the one way
the estimators read a device scalar back).
"""

from layer_metrics.dispatches_per_job import window_spans


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    return sum(1 for e in spans if e["kind"] == "sync") / run["trace"]["jobs"]
