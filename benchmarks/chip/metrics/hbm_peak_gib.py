"""Peak device memory in use after the window (``peak_bytes_in_use``),
in GiB."""


def read(rec):
    return rec.peak_bytes / 2 ** 30 if rec.peak_bytes else None
