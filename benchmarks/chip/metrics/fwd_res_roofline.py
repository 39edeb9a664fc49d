"""Roofline share of the stage forward programs (``fwd_res_impl``) in
the device trace, against the forward work of the completed
microbatches (``work.py``)."""
from benchmarks.chip.work import roofline


def read(rec):
    return roofline(rec, "fwd_res_impl", "fwd")
