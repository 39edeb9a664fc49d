"""Microbatches that completed per iteration (``IterationResult``)."""


def read(rec):
    return rec.completed / rec.iterations
