"""The check that decides ``correct``, driven through a whole run at CPU
size with the chip gate skipped: a sound run is correct, and each fault
a training cell can have, planted under the timed path, comes out not
correct.  The fp8 control fails the committed limits."""
import time

import jax
import pytest

from benchmarks.chip import harness, tracing
from benchmarks.chip.peaks import peaks
from benchmarks.chip.reference import readings

from conftest import tiny_spec

SEED = 2 ** 31 + 17


def _run(workload):
    spec = tiny_spec(workload)
    return harness.run_cell(spec, SEED, 0.5, False, jax.devices(),
                            time.perf_counter(), peaks("TPU v5 lite"),
                            harness.CompileClock())


@pytest.mark.parametrize("workload", ["gpt300m-churn10", "mamba2-churn0"])
def test_sound_run_is_correct(workload):
    out = _run(workload)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert set(out["metrics"]) == {"train_tokens_per_s", "setup_s"}


def _state_unchanged(mp):
    from repro.core.runtime.trainer import RuntimeTrainer

    mp.setattr(RuntimeTrainer, "_apply_update", lambda self, *a: None)


def _half_batch(mp):
    """Half of each step's completed microbatches left out of the
    numeric pass, the mean taken over the rest, while the iteration
    still reports them completed."""
    from repro.core.runtime.trainer import RuntimeTrainer

    orig = RuntimeTrainer._execute

    def half(self, res, wire=None):
        full = res.completed
        res.completed = full[::2]
        try:
            return orig(self, res, wire)
        finally:
            res.completed = full

    mp.setattr(RuntimeTrainer, "_execute", half)


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch],
                         ids=["state_unchanged", "half_batch"])
def test_fault_comes_out_not_correct(fault, monkeypatch):
    fault(monkeypatch)
    out = _run("gpt300m-churn0")
    assert out["correct"] is False
    gaps = [out["checks"][k] for k in tiny_spec("gpt300m-churn0").limits]
    assert any(not isinstance(c["value"], float) or c["value"] > c["limit"]
               for c in gaps)


@pytest.mark.parametrize("workload", ["gpt300m-churn0", "mamba2-churn0"])
def test_fp8_control_fails_the_limits(workload):
    spec = tiny_spec(workload)
    trainer, shards = harness.build(spec, SEED)
    start = harness.give_weights(trainer, spec, SEED)
    checked = harness.checked_steps(trainer, shards, spec, start,
                                    tracing.Spans(False))
    ref = harness.reference_run(spec, SEED, checked)
    control = harness.reference_run(spec, SEED, checked, precision="fp8")
    sound = readings(checked["program"], ref)
    low = readings(control, ref)
    keys = spec.limits
    assert all(sound[k] <= spec.limits[k] for k in keys), sound
    assert any(low[k] > spec.limits[k] for k in keys), low


def test_no_tpu_exits_nonzero_without_a_result(tmp_path):
    import os
    import shutil
    import subprocess
    import sys

    root = harness.ROOT
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "benchmarks/chip/run.py", "--workload",
           "gpt300m-churn0", "--seed", "1", "--seconds", "1", "--trace", "0"]
    p = subprocess.run(cmd, cwd=root, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    # a checkout holding only BENCHMARK.json and the benchmark's files
    shutil.copy(root / "BENCHMARK.json", tmp_path)
    shutil.copytree(root / "benchmarks" / "chip",
                    tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run(cmd, cwd=tmp_path, env=env, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0 and p.stdout.strip() == ""
