#!/usr/bin/env python3
"""Chip benchmark of the staged GWTF trainer.

    python3 benchmarks/chip/run.py --workload gpt300m-churn0 --seed 7 \
        --seconds 20 --trace 0

Runs one cell of ``BENCHMARK.json`` on the accelerator of the machine it
is started on: set-up (timed as ``setup_s`` from the first line of this
file to the start of the window), whole training iterations for
``--seconds`` seconds, then the float32 reference check.  The last line
of standard output is one JSON object with ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, last, ``checks``: each compared
number beside its limit, which also end standard error.  ``--trace 1``
profiles the window and reports the per-layer metrics and a
``breakdown`` instead of the end-to-end ones.

Exits non-zero, and prints no result, where JAX finds no TPU or fewer
chips than the cell asks for; it never falls back to the CPU.  JAX's
persistent compilation cache lives in ``.jax_cache/`` at the root of the
checkout, whatever the environment names.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from benchmarks.chip.harness import CompileClock, load_spec, run_cell
    from benchmarks.chip.peaks import peaks

    spec = load_spec(args.workload, ROOT)
    devices = jax.devices()
    chips = spec.workload["chips"]
    if devices[0].platform != "tpu":
        print(f"run.py: no TPU (JAX platform is {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 3
    if len(devices) < chips:
        print(f"run.py: {args.workload} needs {chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 3
    pk = peaks(devices[0].device_kind)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    clock = CompileClock()
    trace_dir = tempfile.mkdtemp(prefix="gwtf_bench_trace_") \
        if args.trace else None
    try:
        out = run_cell(spec, args.seed, args.seconds, bool(args.trace),
                       devices[:chips], T_PROCESS, pk, clock, trace_dir)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
