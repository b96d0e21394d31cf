"""``peak_hbm_gib`` (GiB, program_counter) - layer: device.  Moves ``job_ms``.

``memory_stats()["peak_bytes_in_use"]`` of the fullest chip, read when the
window has closed and before the reference runs.
"""


def read(run):
    peak = run["memory_peak_bytes"]
    return None if not peak else peak / 2**30
