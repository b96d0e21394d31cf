"""``compiles_in_window`` (count, program_counter) - layer: op engine.  Moves ``job_ms``.

Compile requests that jax's monitoring events counted between the first and
the last measured job (``/jax/compilation_cache/compile_requests_use_cache``,
as ``chip_smoke.CompileCounter``).  Should read 0: every shape is warmed in
set-up.
"""


def read(run):
    return float(run["compiles_in_window"])
