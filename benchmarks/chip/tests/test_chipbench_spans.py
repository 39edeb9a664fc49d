"""The program's ``gwtf.*`` spans: recorded by a real iteration at CPU
size and read back through the benchmark's extraction; reduced on a
hand-made trace with known answers and on a small trace recorded on a
TPU v5e (`fixtures/`, a slice of a traced ``gpt300m-churn10`` window as
``program_spans.extract`` lists it); and read by the five metric
readers."""
import gzip
import importlib
import json
from pathlib import Path
from types import SimpleNamespace

import jax
import pytest

from benchmarks.chip import harness, program_spans

from conftest import tiny_spec

FIXTURE = (Path(__file__).parent / "fixtures"
           / "trace_gpt300m-churn10-spans.json.gz")
SEED = 2 ** 31 + 29

TABLE = {"gwtf.iteration", "gwtf.churn", "gwtf.plan", "gwtf.resolve",
         "gwtf.execute", "gwtf.chunk", "gwtf.feed", "gwtf.forward",
         "gwtf.backward", "gwtf.head", "gwtf.accumulate", "gwtf.loss_sync",
         "gwtf.update", "gwtf.commit"}


def _traced_iterations(workload, tmp_path, batched=True, n=2):
    """``n`` traced iterations of the cell at CPU size (the first two of
    a churn cell each carry a forced crash); their results and the
    program's spans."""
    spec = tiny_spec(workload)
    trainer, shards = harness.build(spec, SEED)
    trainer.batch_microbatches = batched
    results = []
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(n):
                results.append(trainer.iteration(
                    {dn: sh.microbatches() for dn, sh in shards.items()}))
    ex = program_spans.extract(str(tmp_path))
    spans = [(n_, s, s + d) for n_, s, d in ex["spans"]
             if n_.startswith(program_spans.PREFIX)]
    return results, spans


def _inside(spans, outer, inner):
    """Each ``inner`` span lies in some ``outer`` span."""
    outs = [(s, e) for n, s, e in spans if n == outer]
    ins = [(s, e) for n, s, e in spans if n == inner]
    return bool(ins) and all(any(a <= s and e <= b for a, b in outs)
                             for s, e in ins)


@pytest.mark.parametrize("workload,batched", [
    ("gpt300m-churn0", True), ("gpt300m-churn10", True),
    ("gpt300m-churn10", False)], ids=["churn0", "churn10", "per-mb"])
def test_iteration_records_every_span(workload, batched, tmp_path):
    results, spans = _traced_iterations(workload, tmp_path, batched)
    names = {n for n, _, _ in spans}
    churn = workload.endswith("churn10")
    want = TABLE if batched else TABLE - {"gwtf.forward", "gwtf.backward",
                                          "gwtf.head"}
    assert want <= names
    # a forced crash dispatches lost work under gwtf.repair (the
    # per-microbatch path dispatches it inline, inside its chunk)
    assert ("gwtf.repair" in names) == (churn and batched)
    for inner in ("gwtf.churn", "gwtf.plan", "gwtf.resolve",
                  "gwtf.execute", "gwtf.commit"):
        assert _inside(spans, "gwtf.iteration", inner), inner
    assert _inside(spans, "gwtf.execute", "gwtf.chunk")
    assert _inside(spans, "gwtf.execute", "gwtf.update")
    assert _inside(spans, "gwtf.chunk", "gwtf.loss_sync")
    if churn and batched:
        assert _inside(spans, "gwtf.chunk", "gwtf.repair")
    counts = {k: sum(1 for n, _, _ in spans if n == k)
              for k in ("gwtf.iteration", "gwtf.chunk", "gwtf.loss_sync")}
    assert counts["gwtf.iteration"] == len(results)
    syncs = sum(r.host_syncs for r in results)
    assert syncs == counts["gwtf.chunk"] == counts["gwtf.loss_sync"]
    completed = sum(r.completed for r in results)
    if batched:
        # CPU size stacks every microbatch of a data node into one chunk
        assert 0 < syncs < completed
    else:
        assert syncs == completed


# ---------------------------------------------------------------------------
# The reduction, by hand
# ---------------------------------------------------------------------------

HAND = {
    "spans": [
        ["window", 10, 100],
        ["gwtf.commit", 4, 8],          # the iteration before the window
        ["iteration", 12, 98],
        ["gwtf.iteration", 13, 96],
        ["gwtf.plan", 13, 7],
        ["gwtf.execute", 20, 80],
        ["gwtf.chunk", 20, 50],
        ["gwtf.feed", 20, 5],
        ["gwtf.loss_sync", 60, 10],
        ["gwtf.chunk", 70, 20],
        ["gwtf.loss_sync", 86, 2],
        ["gwtf.update", 90, 10],
        ["gwtf.commit", 100, 9],
    ],
    "ops": {"/device:TPU:0": [[25, 35], [72, 13], [92, 6]]},
    "programs": [],
}


def test_hand_made_spans():
    r = program_spans.reduce(HAND)
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(54e-9)
    # the commit that starts before the window counts only its part
    # inside it, and is not one of the window's spans
    assert r["span_s"]["gwtf.commit"] == pytest.approx((2 + 9) * 1e-9)
    assert r["span_s"]["gwtf.iteration"] == pytest.approx(96e-9)
    assert r["span_n"] == {"gwtf.iteration": 1, "gwtf.plan": 1,
                           "gwtf.execute": 1, "gwtf.chunk": 2,
                           "gwtf.feed": 1, "gwtf.loss_sync": 2,
                           "gwtf.update": 1, "gwtf.commit": 1}
    # gaps [10,25) mid 17.5 plan; [60,72) mid 66 loss_sync; [85,92) mid
    # 88.5 the second chunk's self time; [98,110) mid 104 commit
    assert r["idle_s"] == pytest.approx({
        "gwtf.plan": 15e-9, "gwtf.loss_sync": 12e-9, "gwtf.chunk": 7e-9,
        "gwtf.commit": 12e-9})
    assert sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"] - r["busy_s"])
    assert r["gaps"][0] == ["gwtf.plan", pytest.approx(15e-9)]


def test_gap_outside_every_program_span_keeps_the_bench_name():
    ex = dict(HAND, ops={"/device:TPU:0": [[10, 2], [12.6, 97.4]]})
    r = program_spans.reduce(ex)
    # [12,12.6): inside bench.iteration, before gwtf.iteration starts
    assert r["idle_s"] == pytest.approx({"iteration": 0.6e-9})


def test_innermost_is_the_deepest_open_span():
    spans = [(0, 10, "a"), (2, 4, "b"), (5, 9, "c"), (6, 7, "d")]
    assert program_spans._innermost(spans, [1, 3, 4.5, 6.5, 8, 9.5, 11]) \
        == ["a", "b", "a", "d", "c", "a", "none"]


def test_no_program_spans_reads_nothing():
    bench_only = dict(HAND, spans=[s for s in HAND["spans"]
                                   if not s[0].startswith("gwtf.")])
    assert program_spans.reduce(bench_only) == {}
    assert program_spans.reduce(dict(HAND, ops={})) == {}
    assert program_spans.reduce(dict(HAND, spans=HAND["spans"][1:])) == {}


# ---------------------------------------------------------------------------
# The readers
# ---------------------------------------------------------------------------

def _rec(**kw):
    rec = SimpleNamespace(iterations=4, trace={"window_s": 1.0})
    rec.__dict__.update(kw)
    return rec


SPANS = {
    "span_s": {"gwtf.plan": 0.010, "gwtf.resolve": 0.002},
    "span_n": {"gwtf.loss_sync": 32},
    "idle_s": {"gwtf.plan": 0.040, "gwtf.churn": 0.004,
               "gwtf.resolve": 0.008, "gwtf.commit": 0.004,
               "gwtf.feed": 0.020, "gwtf.chunk": 0.010,
               "gwtf.update": 0.006, "gwtf.loss_sync": 0.004,
               "gwtf.iteration": 0.100, "data": 1.0},
}
READERS = {"plan_ms_per_iter": 2.5, "resolve_ms_per_iter": 0.5,
           "host_syncs_per_iter": 8.0, "control_idle_ms_per_iter": 14.0,
           "execute_idle_ms_per_iter": 10.0}


@pytest.mark.parametrize("name", sorted(READERS))
def test_reader(name):
    read = importlib.import_module(f"benchmarks.chip.metrics.{name}").read
    assert read(_rec(program_spans=SPANS)) == pytest.approx(READERS[name])
    # untraced, and traced on a program without the spans
    assert read(_rec(trace={})) is None
    assert read(_rec(program_spans={})) is None


# ---------------------------------------------------------------------------
# The chip trace
# ---------------------------------------------------------------------------

def test_recorded_chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        ex = json.load(f)
    r = program_spans.reduce(ex)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] + sum(r["idle_s"].values()) == pytest.approx(
        r["window_s"], rel=1e-9)
    n = r["span_n"]
    assert n["gwtf.loss_sync"] == n["gwtf.chunk"] > 0
    assert n["gwtf.repair"] > 0
    assert TABLE <= set(n)
    gwtf_idle = sum(s for k, s in r["idle_s"].items()
                    if k.startswith("gwtf."))
    assert gwtf_idle > 0.5 * (r["window_s"] - r["busy_s"])
