"""Seconds of tracing, lowering and compiling in set-up, from JAX's own
compile events (cache fetches included)."""


def read(rec):
    return rec.setup_compile_s
