"""Seconds from the first line of the benchmark to the start of the
window: imports, device start, building the trainer and its weights,
and the checked steps that warm every program the window runs."""


def read(rec):
    return rec.setup_s
