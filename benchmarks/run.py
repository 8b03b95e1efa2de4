"""Benchmark orchestrator — one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only NAME]

Output contract: ``name,us_per_call,derived`` CSV lines.
"""

import argparse
import sys
import time
import traceback


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None)
    args = ap.parse_args()

    from benchmarks import (
        bench_ablation_ers,
        bench_ablation_scale,
        bench_error_measure,
        bench_renoise_error,
        bench_solver_quality,
    )

    suites = {
        "solver_quality": bench_solver_quality.run,   # Tables 1/2/3/6
        "ablation_ers": bench_ablation_ers.run,       # Tables 4/5
        "ablation_scale": bench_ablation_scale.run,   # Figs 5/6
        "error_measure": bench_error_measure.run,     # Fig 3
        "renoise_error": bench_renoise_error.run,     # Appendix C
    }
    if args.only and args.only not in suites:
        print(f"unknown suite {args.only!r}; available: {sorted(suites)}")
        sys.exit(2)
    failed = []
    for name, fn in suites.items():
        if args.only and name != args.only:
            continue
        print(f"# === {name} ===", flush=True)
        t0 = time.perf_counter()
        try:
            fn()
        except Exception:
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} done in {time.perf_counter() - t0:.1f}s", flush=True)
    if failed:
        print(f"# FAILED suites: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    main()
