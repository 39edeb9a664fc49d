"""Host milliseconds per window iteration in the program's planning span
``gwtf.plan`` (``policy.plan()`` and the job assignment), from the
traced run (``program_spans.py``)."""
from benchmarks.chip.program_spans import of


def read(rec):
    s = of(rec).get("span_s", {}).get("gwtf.plan")
    return None if s is None else 1000.0 * s / rec.iterations
