"""The crash-and-return trace generator."""
import numpy as np

from benchmarks.chip.churn import crash_and_return

RELAYS = {s: list(range(2 + 4 * s, 6 + 4 * s)) for s in range(4)}
CHECK = [{"stage": "drawn", "relays": "all_but_one", "when": 0.01},
         {"stage": 0, "relays": "all_but_one", "when": 0.5}]


def _trace(seed, **kw):
    args = dict(rate=0.1, block=10, horizon=200, check_crashes=CHECK)
    args.update(kw)
    return crash_and_return(RELAYS, rng=np.random.default_rng(seed), **args)


def test_same_seed_same_trace_and_seeds_differ():
    assert _trace(2 ** 31 + 3) == _trace(2 ** 31 + 3)
    assert _trace(1) != _trace(2)


def test_every_crash_rejoins_the_next_iteration():
    ev = _trace(7)
    crashes = [(it, nid) for it, kind, nid, _ in ev if kind == "crash"]
    rejoins = {(it, nid) for it, kind, nid, _ in ev if kind == "rejoin"}
    assert crashes
    assert all((it + 1, nid) in rejoins for it, nid in crashes)
    assert len(rejoins) == len(crashes)


def test_rejoins_come_before_crashes_within_an_iteration():
    ev = _trace(8)
    for it in {e[0] for e in ev}:
        kinds = [e[1] for e in ev if e[0] == it]
        assert kinds == sorted(kinds, key={"rejoin": 0, "crash": 1}.get)


def test_every_block_holds_the_same_number_of_crashes():
    ev = _trace(9, check_crashes=())
    per_block = {}
    for it, kind, nid, when in ev:
        if kind == "crash":
            per_block[it // 10] = per_block.get(it // 10, 0) + 1
            assert 0.0 <= when < 1.0
    assert set(per_block.values()) == {16}        # 0.1 x 16 relays x 10
    assert len(per_block) == 20


def test_check_crashes_leave_one_relay_of_the_stage():
    ev = _trace(10)
    first = [(nid, when) for it, kind, nid, when in ev
             if kind == "crash" and it == 0]
    stages = {s for nid, _ in first for s, rs in RELAYS.items() if nid in rs}
    assert len(first) == 3 and len(stages) == 1
    assert all(when == 0.01 for _, when in first)
    second = [nid for it, kind, nid, when in ev
              if kind == "crash" and it == 1 and when == 0.5]
    assert len(second) == 3 and set(second) <= set(RELAYS[0])
