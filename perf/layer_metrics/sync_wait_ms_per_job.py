"""``sync_wait_ms_per_job`` (ms, program_span) - layer: device.  Moves ``job_ms``.

Total duration of the program's ``sync`` spans in the traced window, over the
jobs traced: how long the program's thread waited for the chip inside its
own host reads (the harness's fence on the job's outputs is not the
program's and is not in it).
"""

from layer_metrics.dispatches_per_job import window_spans


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    return sum(e["dur"] for e in spans if e["kind"] == "sync") / run["trace"]["jobs"] * 1e3
