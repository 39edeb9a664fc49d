"""Work the staged step needs over a window, from the shapes alone.

A stage's forward over one microbatch costs its layers' forward FLOPs
(``families/<family>.counts``) for every token; its backward twice that.
Bytes are the lower bound the algorithm must move: each stage's
parameters read once per iteration (and, for the backward, its
gradients written once), and each microbatch's boundary activations in
and out.  Forward repairs run another program and are not counted here;
each backward replay is one more microbatch-stage of backward work.
"""
from __future__ import annotations


def train_flops_per_token(rec) -> float:
    """Forward and backward FLOPs per trained token (3x the forward)."""
    c = rec.counts
    return 3.0 * (sum(rec.stage_layers) * c["layer_flops"] + c["head_flops"])


def stage_pass(rec, direction: str):
    """(FLOPs, bytes) of all ``fwd`` or ``bwd`` stage work in the window."""
    c, tok = rec.counts, rec.tokens_per_mb
    S = len(rec.stage_layers)
    layers = sum(rec.stage_layers)
    mb_stages = rec.completed * S
    per_layer_flops = c["layer_flops"] * tok
    flops = rec.completed * layers * per_layer_flops
    params = rec.iterations * layers * c["layer_param_bytes"]
    if direction == "bwd":
        mb_stages += rec.bwd_replays
        flops = 2.0 * (flops + rec.bwd_replays * layers / S
                       * per_layer_flops)
        params *= 2
    return flops, params + mb_stages * 2 * tok * c["act_bytes"]


def roofline(rec, program: str, direction: str):
    """Percent of the chip's roofline that ``program``'s device time
    reaches: the least time the work could take (FLOPs over the bf16
    peak or bytes over the memory bandwidth, whichever is longer) over
    the measured time.  None where the trace has no such program."""
    t = sum(s for n, s in rec.trace.get("program_s", {}).items()
            if program in n)
    if not t:
        return None
    flops, nbytes = stage_pass(rec, direction)
    least = max(flops / (rec.chips * rec.peaks["bf16_flops_per_s"]),
                nbytes / (rec.chips * rec.peaks["hbm_bytes_per_s"]))
    return 100.0 * least / t
