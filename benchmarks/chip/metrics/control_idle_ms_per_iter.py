"""Device-idle milliseconds per window iteration whose gap falls, by its
midpoint, in the program's control spans: churn sampling, planning,
crash resolution and the commit (``program_spans.CONTROL``)."""
from benchmarks.chip.program_spans import CONTROL, of


def read(rec):
    idle = of(rec).get("idle_s")
    if idle is None:
        return None
    return 1000.0 * sum(idle.get(n, 0.0) for n in CONTROL) / rec.iterations
