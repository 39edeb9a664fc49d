"""The trace reduction, on a hand-made trace with known answers and on a
small trace recorded on a TPU v5e (`fixtures/`, a slice of a traced
``gpt300m-churn0`` window as ``tracing.extract`` lists it)."""
import gzip
import json
from pathlib import Path

import pytest

from benchmarks.chip import tracing

FIXTURE = Path(__file__).parent / "fixtures" / "trace_gpt300m-churn0.json.gz"

HAND = {
    "spans": [["window", 0, 100], ["data", 0, 10], ["iteration", 10, 85],
              ["sync", 95, 5]],
    "ops": {"/device:TPU:0": [[12, 8], [15, 10], [40, 20], [70, 5],
                              [90, 30]]},
    "programs": [["jit_fwd_res_impl", 12, 13], ["jit_bwd_res_impl", 40, 20],
                 ["jit_fwd_res_impl", 70, 5], ["jit__lambda", 90, 30],
                 ["jit_fwd_res_impl", 150, 5]],
}


def test_hand_made_trace():
    r = tracing.reduce(HAND)
    # ops merge to [12,25) [40,60) [70,75) [90,100) inside the window
    assert r["window_s"] == pytest.approx(100e-9)
    assert r["busy_s"] == pytest.approx(48e-9)
    # the program outside the window does not count
    assert r["program_s"] == pytest.approx({
        "jit_fwd_res_impl": 18e-9, "jit_bwd_res_impl": 20e-9,
        "jit__lambda": 30e-9})
    assert r["device_ops"][0] == ["jit__lambda", pytest.approx(30e-9)]
    # gaps: [0,12) data+iteration (mid 6: data), [25,40) [60,70) [75,90)
    # iteration
    idle = dict((n, s) for n, s in r["idle_gaps"])
    assert idle["all iteration"] == pytest.approx(40e-9)
    assert idle["all data"] == pytest.approx(12e-9)
    assert r["idle_gaps"][0][0] == "all iteration"
    assert [n for n, _ in r["idle_gaps"]][2:4] == ["one iteration",
                                                   "one iteration"]


def test_no_window_or_no_device_reads_nothing():
    assert tracing.reduce(dict(HAND, spans=HAND["spans"][1:])) == {}
    assert tracing.reduce(dict(HAND, ops={})) == {}


def test_program_names_drop_the_execution_suffix():
    assert tracing.program_name("jit_fwd_res_impl(12)") == "jit_fwd_res_impl"
    assert tracing.program_name("jit__lambda") == "jit__lambda"


def test_recorded_chip_trace():
    with gzip.open(FIXTURE, "rt") as f:
        ex = json.load(f)
    r = tracing.reduce(ex)
    assert 0 < r["busy_s"] < r["window_s"]
    gaps = sum(s for n, s in r["idle_gaps"] if n.startswith("all "))
    assert r["busy_s"] + gaps == pytest.approx(r["window_s"], rel=1e-9)
    names = set(r["program_s"])
    assert {"jit_fwd_res_impl", "jit_bwd_res_impl"} <= names
    labels = {n.split(" ", 1)[1] for n, _ in r["idle_gaps"]}
    assert labels <= {"data", "iteration", "sync", "none"}
    assert len(r["device_ops"]) <= 10 and len(r["idle_gaps"]) <= 10
