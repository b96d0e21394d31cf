"""``roofline_pct`` (%, device_trace) - layer: kernels.  Moves ``job_ms``.

Jobs traced x the least time one chip needs for its share of a job (the
larger of bytes over peak bandwidth and FLOPs over peak FLOP/s, from the
job entry's ``work(config)`` and ``peaks.json``) over the device-busy time of
the traced window.  The work model counts what the algorithm needs, so the
share cannot pass 100 % by a change of formulation.
"""


def read(run):
    t = run["trace"]
    if t is None or t["busy_s"] <= 0:
        return None
    return 100.0 * t["jobs"] * least_seconds(run) / t["busy_s"]


def least_seconds(run):
    work, peaks, chips = run["work"], run["peaks"], run["chips"]
    by_bytes = work["bytes"] / chips / (peaks["hbm_gb_per_sec"] * 1e9)
    by_flops = work["flops"] / chips / (peaks[work["flops_peak"]] * 1e12)
    return max(by_bytes, by_flops)
