"""Work the staged step needs over a window, from the shapes alone.

A stage's forward over one microbatch costs its layers' forward FLOPs
(``families/<family>.counts``) for every token; its backward twice that.
Bytes are the lower bound the algorithm must move: each stage's
parameters read once per iteration (and, for the backward, its
gradients written once), and each microbatch's boundary activations in
and out.  Forward repairs run another program and are not counted here;
each backward replay is charged the mean stage's backward work, since
the trace does not tell which stage replayed.

For a family whose layers are all alike, ``rec.counts`` gives one
layer's FLOPs and parameter bytes and ``rec.stage_layers`` the layers
of each stage.  For a kinded family (``reference.py``) the counts are
dicts keyed by kind and ``rec.stage_kinds`` gives each stage's
``{kind: layers}``; FLOPs and bytes are summed kind by kind.
"""
from __future__ import annotations


def _layer_work(rec):
    """``(layers, forward FLOPs per token, parameter bytes)`` of each
    layer kind over all stages; one triple where all layers are alike."""
    c = rec.counts
    kinds = getattr(rec, "stage_kinds", None)
    if kinds is None:
        return [(sum(rec.stage_layers), c["layer_flops"],
                 c["layer_param_bytes"])]
    n = {}
    for stage in kinds:
        for k, layers in stage.items():
            n[k] = n.get(k, 0) + layers
    return [(n[k], c["layer_flops"][k], c["layer_param_bytes"][k])
            for k in sorted(n)]


def train_flops_per_token(rec) -> float:
    """Forward and backward FLOPs per trained token (3x the forward)."""
    layers = sum(n * f for n, f, _ in _layer_work(rec))
    return 3.0 * (layers + rec.counts["head_flops"])


def stage_pass(rec, direction: str):
    """(FLOPs, bytes) of all ``fwd`` or ``bwd`` stage work in the window."""
    c, tok = rec.counts, rec.tokens_per_mb
    S = len(rec.stage_layers)
    work = _layer_work(rec)
    mb_stages = rec.completed * S
    flops = sum(rec.completed * n * (f * tok) for n, f, _ in work)
    params = sum(rec.iterations * n * b for n, _, b in work)
    if direction == "bwd":
        mb_stages += rec.bwd_replays
        flops = 2.0 * (flops + sum(rec.bwd_replays * n / S * (f * tok)
                                   for n, f, _ in work))
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
