"""``host_ms_per_job`` (ms, device_trace) - layer: estimators.  Moves ``job_ms``.

The part of a job in which no operation ran on the chip: the traced window
less the device-busy time inside it (mean over the chips), over the jobs
traced.  It is what the estimators' host code, the dispatches and the fences
cost a job; spans inside the program are the tracing issue's.
"""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return (t["window_s"] - t["busy_s"]) / t["jobs"] * 1e3
