"""Percent of the chip's bf16 peak that the whole step reaches: forward
and backward FLOPs of the completed microbatches (``work.py``; repairs
and recomputes do not count) over the window's seconds."""
from benchmarks.chip.work import train_flops_per_token


def read(rec):
    flops = rec.completed * rec.tokens_per_mb * train_flops_per_token(rec)
    return 100.0 * flops / (rec.window_s * rec.chips
                            * rec.peaks["bf16_flops_per_s"])
