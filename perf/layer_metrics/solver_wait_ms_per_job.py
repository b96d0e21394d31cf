"""``solver_wait_ms_per_job`` (ms, program_span) - layer: solvers.  Moves ``job_ms``.

Total duration of the ``sync:spectral.tridiag`` spans in the traced window,
over the jobs traced: how long the host waited, in the read of the
tridiagonal T, for the similarity, the Laplacian and the Lanczos steps
together (they are issued without a wait between them).  Nothing to read
where the program records no such span.
"""

from layer_metrics.dispatches_per_job import window_spans

SITE = "sync:spectral.tridiag"


def site_ms_per_job(run, site: str):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    durs = [e["dur"] for e in spans if e["site"] == site]
    return sum(durs) / run["trace"]["jobs"] * 1e3 if durs else None


def read(run):
    return site_ms_per_job(run, SITE)
