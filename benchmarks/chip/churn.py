"""Crash-and-return churn traces, drawn from the seed.

Each relay crashes with probability ``rate`` per iteration, at a uniform
time inside it, and is back at the start of the next iteration.  Counts
are stratified so that every seed does the same amount of repair: in
each block of ``block`` iterations exactly ``round(rate * relays *
block)`` crashes fall on (iteration, relay) slots drawn without
replacement.  The first iterations can carry fixed crash patterns
(``check_crashes``) so that the steps the reference checks include a
forward repair and a backward repair on every seed.

The trace is a list of ``(iteration, kind, node_id[, when])`` events, as
the program's ``TraceChurn`` replays them; in each iteration rejoins
come before crashes.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np

_ORDER = {"rejoin": 0, "crash": 1}


def crash_and_return(relays_by_stage: Dict[int, Sequence[int]], *,
                     rate: float, block: int, horizon: int,
                     check_crashes: Sequence[dict] = (),
                     rng: np.random.Generator) -> List[tuple]:
    """Events for iterations ``0 .. len(check_crashes) + horizon - 1``.

    ``check_crashes[i]`` crashes ``relays`` relays ("all_but_one" or a
    count) of ``stage`` (a number, or "drawn" from the seed) at time
    ``when`` of iteration ``i``; an empty dict leaves it clean.  The
    stratified stream starts after them."""
    events: List[tuple] = []

    def crash(it, nid, when):
        events.append((it, "crash", int(nid), float(when)))
        events.append((it + 1, "rejoin", int(nid), 0.0))

    stages = sorted(relays_by_stage)
    for it, spec in enumerate(check_crashes):
        if not spec:
            continue
        s = (stages[int(rng.integers(len(stages)))]
             if spec["stage"] == "drawn" else int(spec["stage"]))
        pool = list(relays_by_stage[s])
        k = (len(pool) - 1 if spec["relays"] == "all_but_one"
             else int(spec["relays"]))
        for nid in rng.choice(pool, size=k, replace=False):
            crash(it, nid, spec["when"])
    relays = [r for s in stages for r in relays_by_stage[s]]
    per_block = int(round(rate * len(relays) * block))
    start = len(check_crashes)
    for b0 in range(start, start + horizon, block):
        slots = np.sort(rng.choice(block * len(relays), size=per_block,
                                   replace=False))
        for slot, when in zip(slots, rng.uniform(size=per_block)):
            crash(b0 + int(slot) // len(relays),
                  relays[int(slot) % len(relays)], when)
    events.sort(key=lambda e: (e[0], _ORDER[e[1]]))
    return events
