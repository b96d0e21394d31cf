"""``device_idle_pct`` (%, device_trace) - layer: device.  Moves ``job_ms``.

1 - the union of the device's operation intervals over the traced window,
on the idlest chip.
"""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return 100.0 * (1.0 - t["busy_s_min"] / t["window_s"])
