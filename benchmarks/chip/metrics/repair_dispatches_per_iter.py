"""Stage repairs per iteration: forward recomputes plus backward replays
(``IterationResult``)."""


def read(rec):
    return (rec.fwd_recomputes + rec.bwd_replays) / rec.iterations
