"""Device-idle milliseconds per window iteration whose gap falls, by its
midpoint, in the program's numeric pass ``gwtf.execute`` or a span
nested in it: feed, dispatch, repair, gradient sums, the loss sync and
the update (``program_spans.EXECUTE``)."""
from benchmarks.chip.program_spans import EXECUTE, of


def read(rec):
    idle = of(rec).get("idle_s")
    if idle is None:
        return None
    return 1000.0 * sum(idle.get(n, 0.0) for n in EXECUTE) / rec.iterations
