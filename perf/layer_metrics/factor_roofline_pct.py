"""``factor_roofline_pct`` (%, device_trace) - layer: kernels.  Moves ``job_ms``.

How near to its roofline each pass over A runs that the SVD programs DO make:
for every pass the traced window's ``jitted:linalg.svd`` spans state
(``a_passes``, see ``a_passes_per_job``), the least time of one pass, the
larger of A's bytes over the peak bandwidth and one 2·m·n² product over the
peak of the span's ``precision`` (``highest``, which jax also names
``float32``: ``f32_highest_tflops``, six bfloat16 passes; ``high``: three
passes, ``bf16_tflops`` / 3; ``default``: one pass, ``bf16_tflops``), summed and divided by the
device-busy time of the window.  The job entry's ``work`` gives ``a_bytes``
and ``pass_flops``.  It names no kernel, so another implementation of the
same passes leaves it alive; it cannot pass 100 while ``a_passes`` and
``precision`` are honest.  Nothing to read where the program records no such
field or the work model states no ``a_bytes``.
"""

from layer_metrics.a_passes_per_job import passes


#: bfloat16 passes of one product at each of jax's names of a precision
THREE_PASSES = ("high", "bfloat16_3x", "tensorfloat32")
SIX_PASSES = ("highest", "float32")


def peak_tflops(peaks, precision):
    """The peak one product runs at, in TFLOP/s, at the span's precision."""
    if precision in SIX_PASSES:
        return peaks["f32_highest_tflops"]
    if precision in THREE_PASSES:
        return peaks["bf16_tflops"] / 3.0
    return peaks["bf16_tflops"]


def read(run):
    t, found = run["trace"], passes(run)
    work, peaks = run["work"], run["peaks"]
    if found is None or not work.get("a_bytes") or t["busy_s"] <= 0:
        return None
    by_bytes = work["a_bytes"] / run["chips"] / (peaks["hbm_gb_per_sec"] * 1e9)
    least = sum(
        count * max(by_bytes, work["pass_flops"] / run["chips"] / (peak_tflops(peaks, precision) * 1e12))
        for count, precision in found
    )
    return 100.0 * least / t["busy_s"]
