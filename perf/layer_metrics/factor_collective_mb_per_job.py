"""``factor_collective_mb_per_job`` (MB, program_counter) - layer: distribution.  Moves ``job_ms``.

Bytes a chip hands the collectives of the row-sharded factorization, per
job: the sum of the ``collective_bytes`` field of the traced window's
``jitted:linalg.svd`` launch spans on the route ``cholqr2_rows`` (the two
n x n float32 Grams each chip all-reduces on the sound branch,
``qr.route_fields``; ``tests/test_tpu_compile.py`` holds the field to the
program compiled for the chip), in 10^6 bytes, over the jobs traced.  What
a program that all-gathered a shard or summed more than the Grams would
move reads above it.  Nothing to read where the program records no such
field.
"""

from layer_metrics.shard_factor_roofline_pct import row_spans


def read(run):
    found = row_spans()
    if found is None or run["trace"] is None or not all("collective_bytes" in e for e in found):
        return None
    return sum(e["collective_bytes"] for e in found) / 1e6 / run["trace"]["jobs"]
