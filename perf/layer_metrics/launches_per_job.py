"""``launches_per_job`` (count, device_trace) - layer: op engine.  Moves ``job_ms``.

Executions of a compiled program on the device (one event each on the
device's ``XLA Modules`` line) on the chip that saw most of them (small
unsharded programs run on one chip only), over the jobs traced.
Counted from the trace because the program's own counter
(``telemetry.counting_dispatches``) passes by the estimators' bare
``jax.jit`` programs.
"""


def read(run):
    t = run["trace"]
    if t is None:
        return None
    return t["launches"] / t["jobs"]
