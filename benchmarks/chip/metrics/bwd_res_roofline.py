"""Roofline share of the stage backward programs (``bwd_res_impl``) in
the device trace, against the backward work of the completed
microbatches and the backward replays (``work.py``)."""
from benchmarks.chip.work import roofline


def read(rec):
    return roofline(rec, "bwd_res_impl", "bwd")
