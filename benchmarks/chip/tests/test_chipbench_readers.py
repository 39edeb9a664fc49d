"""Every metric reader on one fixed record of a homogeneous family, made
by ``harness.record`` from the committed cells and the recorded
``gpt300m-churn10`` trace (``fixtures/``), against the values pinned
here.  The record's counters are fixed by hand to match the traced
slice: one iteration, 8 microbatches completed, 4 repairs."""
import functools
import gzip
import importlib
import json
from pathlib import Path
from types import SimpleNamespace

import pytest

from benchmarks.chip import harness, program_spans, tracing
from benchmarks.chip.peaks import peaks

FIXTURE = (Path(__file__).parent / "fixtures"
           / "trace_gpt300m-churn10-spans.json.gz")

SHARED = {
    "train_tokens_per_s": 32768.0, "setup_s": 21.25, "compile_s": 6.5,
    "window_compiles": 0, "data_ms_per_iter": 9.765625,
    "completed_per_iter": 8.0, "repair_dispatches_per_iter": 4.0,
    "device_idle_share": 17.077179010374767, "hbm_peak_gib": 9.9033203125,
    "plan_ms_per_iter": 8.56699, "resolve_ms_per_iter": 0.309011,
    "host_syncs_per_iter": 8.0, "control_idle_ms_per_iter": 0.319502,
    "execute_idle_ms_per_iter": 65.3429139999995,
}
PINNED = {
    "gpt300m-churn10": dict(SHARED, fwd_res_roofline=24.601223179022913,
                            bwd_res_roofline=42.373078377084056,
                            step_mfu=26.065866282103553),
    "mamba2-churn0": dict(SHARED, fwd_res_roofline=11.84606555186779,
                          bwd_res_roofline=20.403630357590437,
                          step_mfu=13.932004558879187),
}


@functools.lru_cache(maxsize=None)
def _rec(workload):
    n = SimpleNamespace(iterations=1, completed=8, launched=8, dropped=0,
                        fwd_recomputes=1, bwd_replays=3,
                        data_s=0.009765625)
    rec = harness.record(harness.load_spec(workload), n, window_s=0.5,
                         setup_s=21.25, setup_compile_s=6.5,
                         window_lowered=0, peak_bytes=10633609216, chips=1,
                         peaks=peaks("TPU v5 lite"))
    with gzip.open(FIXTURE, "rt") as f:
        ex = json.load(f)
    rec.trace = tracing.reduce(ex)
    rec.program_spans = program_spans.reduce(ex)
    return rec


def test_every_metric_of_the_benchmark_is_pinned():
    bench = json.loads((harness.ROOT / "BENCHMARK.json").read_text())
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert all(set(v) == names for v in PINNED.values())


@pytest.mark.parametrize("workload,name", [
    (w, n) for w, vals in PINNED.items() for n in sorted(vals)])
def test_reader_returns_the_pinned_value(workload, name):
    read = importlib.import_module(f"benchmarks.chip.metrics.{name}").read
    assert read(_rec(workload)) == PINNED[workload][name]
