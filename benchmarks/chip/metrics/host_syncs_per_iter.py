"""Blocking device-to-host reads per window iteration: the program's
``gwtf.loss_sync`` spans (one per dispatch chunk, where
``IterationResult.host_syncs`` counts them), from the traced run
(``program_spans.py``)."""
from benchmarks.chip.program_spans import of


def read(rec):
    n = of(rec).get("span_n", {}).get("gwtf.loss_sync")
    return None if n is None else n / rec.iterations
