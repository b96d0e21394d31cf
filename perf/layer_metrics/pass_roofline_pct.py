"""``pass_roofline_pct`` (%, device_trace) - layer: kernels.  Moves ``job_ms``.

How near to the chip's bandwidth the passes over X run that the program DOES
make: the passes the traced window's fit spans state (``x_passes``, see
``x_passes_per_job``) x the bytes of X (from the job entry's ``work``: its
``x_bytes``) over the peak bandwidth, over the device-busy time of the traced
window.  Beside ``roofline_pct``, which holds the job to the passes it NEEDS.
It names no kernel, so a fused sweep leaves it alive; it cannot pass 100 while
``x_passes`` is honest.  Nothing to read where the program records no such
field or the work model states no ``x_bytes``.
"""

from layer_metrics.x_passes_per_job import passes


def read(run):
    t, total = run["trace"], passes(run)
    x_bytes = run["work"].get("x_bytes")
    if total is None or not x_bytes or t["busy_s"] <= 0:
        return None
    least = total * x_bytes / run["chips"] / (run["peaks"]["hbm_gb_per_sec"] * 1e9)
    return 100.0 * least / t["busy_s"]
