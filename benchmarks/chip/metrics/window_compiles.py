"""Programs lowered inside the window (each a compile or a cache fetch);
a sound warm-up leaves none."""


def read(rec):
    return rec.window_lowered
