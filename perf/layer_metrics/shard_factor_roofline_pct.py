"""``shard_factor_roofline_pct`` (%, device_trace) - layer: kernels.  Moves ``job_ms``.

How near to its roofline each pass over a chip's shard of A runs that the
row-sharded SVD program makes: for every pass the traced window's
``jitted:linalg.svd`` spans on the route ``cholqr2_rows`` state
(``a_passes``), the least time of one pass on one chip, the larger of one
shard's read of A (``a_bytes`` over the chips, at the peak bandwidth) and
one 2·m·n² product over the chips at the peak of the span's ``precision``
(``factor_roofline_pct.peak_tflops``), summed and divided by the window's
device-busy time (the chips' mean).  The job entry's ``work`` gives
``a_bytes`` and ``pass_flops``.  The n x n work between the passes (two
Choleskys, two triangular inverses and R's SVD, replicated on every chip)
and the two all-reduces of the Grams are in the busy time and not in the
least time: what the share leaves below 100 is theirs and the passes' own
distance from the peak.  Nothing to read where the program records no such
span (a program without the route) or the work model states no ``a_bytes``.
"""

from layer_metrics.dispatches_per_job import window_spans
from layer_metrics.factor_roofline_pct import peak_tflops

SITE, ROUTE = "jitted:linalg.svd", "cholqr2_rows"


def row_spans():
    """The window's SVD launch spans on the row-sharded route, or None."""
    spans = window_spans()
    if spans is None:
        return None
    found = [e for e in spans if e.get("site") == SITE and e.get("route") == ROUTE and "a_passes" in e]
    return found or None


def read(run):
    t, found = run["trace"], row_spans()
    work, peaks, chips = run["work"], run["peaks"], run["chips"]
    if found is None or t is None or not work.get("a_bytes") or t["busy_s"] <= 0:
        return None
    by_bytes = work["a_bytes"] / chips / (peaks["hbm_gb_per_sec"] * 1e9)
    least = sum(
        e["a_passes"] * max(by_bytes, work["pass_flops"] / chips / (peak_tflops(peaks, e.get("precision")) * 1e12))
        for e in found
    )
    return 100.0 * least / t["busy_s"]
