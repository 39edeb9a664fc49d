"""Benchmark harness: one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # quick pass
    PYTHONPATH=src python -m benchmarks.run --full     # paper-scale reps

Prints ``name,value,derived`` CSV rows (plus human-readable tables).
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale repetition counts (25 reps)")
    ap.add_argument("--only", default=None,
                    help="comma list: crash_llama,crash_gpt,node_addition,"
                         "optimal,flow,convergence,ablation")
    args = ap.parse_args()
    reps = 25 if args.full else 3
    only = set(args.only.split(",")) if args.only else None

    from benchmarks import (bench_ablation, bench_convergence,
                            bench_crash_gpt, bench_crash_llama, bench_flow,
                            bench_node_addition, bench_optimal)

    suites = [
        ("crash_llama", lambda: bench_crash_llama.run(reps=reps)),
        ("crash_gpt", lambda: bench_crash_gpt.run(reps=reps)),
        ("node_addition", lambda: bench_node_addition.run(
            reps=max(2, reps // 2))),
        ("optimal", lambda: bench_optimal.run(reps=reps)),
        ("flow", lambda: bench_flow.run(reps=max(3, reps))),
        ("convergence", lambda: bench_convergence.run(
            iterations=40 if args.full else 15)),
        ("ablation", lambda: bench_ablation.run(reps=max(4, reps // 2))),
    ]

    all_rows = []
    for name, fn in suites:
        if only and name not in only:
            continue
        t0 = time.time()
        rows = fn()
        all_rows += rows
        print(f"[{name}: {time.time()-t0:.1f}s]")

    print("\n# name,value,derived")
    for row in all_rows:
        print(row)


if __name__ == "__main__":
    main()
