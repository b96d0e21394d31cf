"""``programs_in_setup`` (count, program_counter) - layer: op engine.  Moves ``setup_s``.

Programs compiled or loaded before the traced window: the records
``compile:backend`` of the program's start-up record that began before it
(``import_s.setup_records``).  Each costs a trace, a lowering and a read of
the cache, whatever it computes: ``jitted()``'s and the estimators' own, the
eager ``jax.numpy`` programs around them, ``datagen``'s one.
"""

from layer_metrics.import_s import setup_records


def read(run):
    records = setup_records()
    if records is None or run["trace"] is None:
        return None
    return float(sum(1 for r in records if r["site"] == "compile:backend"))
