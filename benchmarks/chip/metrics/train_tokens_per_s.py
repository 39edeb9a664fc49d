"""Tokens of the microbatches completed in the window over the window's
length, from its start to the end of its last iteration's update."""


def read(rec):
    return rec.completed * rec.tokens_per_mb / rec.window_s
