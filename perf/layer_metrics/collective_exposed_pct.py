"""``collective_exposed_pct`` (%, device_trace) - layer: distribution.  Moves ``job_ms``.

Time in which a collective operation ran on a chip while no compute
operation did, over the traced window, on the worst chip.  Nothing to read
on one chip or in a trace without collectives.
"""


def read(run):
    t = run["trace"]
    if t is None or t["devices"] < 2 or t["collective_s_max"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s_max"] / t["window_s"]
