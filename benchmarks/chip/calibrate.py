#!/usr/bin/env python3
"""Readings that the limits of ``limits/<cell>.json`` are set from.

    python3 benchmarks/chip/calibrate.py --workload gpt300m-churn0 \
        --seeds 1-12 --control-seeds 1-4 --fault-seeds 1-3

Runs on the chip, at the cell's own sizes, in one process.  For every
seed it drives the program through the cell's checked steps exactly as a
benchmark run's set-up does, frees it, and reads the program against the
float32 reference (the lower readings).  On the control seeds it also
reads the fp8 control against the reference, and on the fault seeds two
faults planted in the reference: half of each step's batch left out, and
a state left unchanged (the upper readings).  Prints one JSON line per
reading and a summary; the benchmark's own runs never run this.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
KEYS = ("loss1_gap", "loss_gap", "grad_gap", "change_gap",
        "grad_median_gap", "change_median_gap")


def _seeds(text):
    out = []
    for part in text.split(","):
        lo, _, hi = part.partition("-")
        out += list(range(int(lo), int(hi or lo) + 1))
    return out


def _unchanged(harness, spec, seed, checked):
    """The fault "a step that returns its state unchanged", planted in
    the reference: no update (learning rate 0), so every step's loss is
    the first weights' loss, and no first moment and no change."""
    opt = dict(spec.traffic["optimizer"], lr=0.0)
    spec0 = SimpleNamespace(**vars(spec))
    spec0.traffic = dict(spec.traffic, optimizer=opt)
    run = harness.reference_run(spec0, seed, checked)
    run["grad_norms"] = {k: 0.0 for k in run["grad_norms"]}
    run["change_norms"] = {k: 0.0 for k in run["change_norms"]}
    return run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", default="1-12")
    ap.add_argument("--control-seeds", default="1-4")
    ap.add_argument("--fault-seeds", default="1-3")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if jax.devices()[0].platform != "tpu":
        print("calibrate.py: no TPU; nothing was run", file=sys.stderr)
        return 3

    from repro.core.runtime import cache

    from benchmarks.chip import harness, tracing
    from benchmarks.chip.reference import readings

    spec = harness.load_spec(args.workload, ROOT)
    control, fault = set(_seeds(args.control_seeds)), set(
        _seeds(args.fault_seeds))
    rows = []
    for seed in _seeds(args.seeds):
        t0 = time.perf_counter()
        trainer, shards = harness.build(spec, seed)
        start = harness.give_weights(trainer, spec, seed)
        checked = harness.checked_steps(trainer, shards, spec, start,
                                        tracing.Spans(False))
        struct = harness.structural(spec, checked)
        del trainer, shards, start
        cache.initial_params.cache_clear()
        gc.collect()
        ref = harness.reference_run(spec, seed, checked)
        kinds = {"program": checked["program"]}
        if seed in control:
            kinds["fp8_control"] = harness.reference_run(
                spec, seed, checked, precision="fp8")
        if seed in fault:
            kinds["half_batch"] = harness.reference_run(
                spec, seed, checked, half_batch=True)
            kinds["state_unchanged"] = _unchanged(harness, spec, seed,
                                                  checked)
        for kind, run in kinds.items():
            row = {"seed": seed, "kind": kind, **readings(run, ref),
                   "structural": {k: c["value"] for k, c in struct.items()},
                   "losses": run["losses"], "ref_losses": ref["losses"],
                   "seconds": time.perf_counter() - t0}
            rows.append(row)
            print(json.dumps(row), flush=True)
        del checked, ref, kinds
        gc.collect()
    summary = {}
    for kind in ("program", "fp8_control", "half_batch", "state_unchanged"):
        sel = [r for r in rows if r["kind"] == kind]
        if sel:
            agg = max if kind == "program" else min
            summary[kind] = {k: agg(r[k] for r in sel) for k in KEYS}
            summary[kind]["seeds"] = len(sel)
    print(json.dumps({"summary": summary,
                      "device": jax.devices()[0].device_kind,
                      "seconds": time.perf_counter() - T_PROCESS}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
