"""``entry_self_ms_per_job`` (ms, program_span) - layer: estimators.  Moves ``job_ms``.

Self time of the program's ``entry`` and ``other`` spans in the traced
window (duration less what their direct children cover,
``telemetry.self_times``), over the jobs traced: the estimators' own Python
between launches and host reads, with the eager ``jax.numpy`` programs they
issue outside any ``launch`` span.
"""

from layer_metrics.dispatches_per_job import window_spans


def read(run):
    spans = window_spans()
    if spans is None or run["trace"] is None:
        return None
    from heat_tpu import telemetry

    own = telemetry.self_times(spans)
    return sum(own[e["id"]] for e in spans if e["kind"] in ("entry", "other")) / run["trace"]["jobs"] * 1e3
