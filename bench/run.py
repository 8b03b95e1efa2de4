"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cells are listed in ``BENCHMARK.json`` and described by
``bench/workloads/<cell>.json``.  The run fails before any work unless JAX
finds the TPU chips the cell asks for, of a kind ``bench/peaks.json``
knows.  It builds the cell's model with weights drawn from ``--seed``,
warms every program and shape the cell's traffic uses, measures for
``--seconds``, checks a sample of the window's answers against a float32
reference, and prints one JSON object as the last line of standard
output: ``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics, read from
a profiler trace of the window), ``device``, and ``compared`` (each number
the check compared, with its limit).

``--rehearse`` runs the same path on the CPU at the smoke sizes, for
tests: its result never names a TPU and is never a measurement.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true", help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        print("the program (src/repro) is not in this checkout", file=sys.stderr)
        return 2
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import harness

    try:
        line = harness.run_cell(
            args.workload, args.seed, args.seconds, bool(args.trace),
            rehearse=args.rehearse, t0=T0,
        )
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 1
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
