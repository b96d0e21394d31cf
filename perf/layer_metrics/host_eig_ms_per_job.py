"""``host_eig_ms_per_job`` (ms, program_span) - layer: solvers.  Moves ``job_ms``.

Total duration of the ``spectral:eigh`` spans in the traced window, over the
jobs traced: the host's ``numpy.linalg.eigh`` of the (m, m) tridiagonal,
during which the chip has nothing to do.  Nothing to read where the program
records no such span.
"""

from layer_metrics.solver_wait_ms_per_job import site_ms_per_job

SITE = "spectral:eigh"


def read(run):
    return site_ms_per_job(run, SITE)
