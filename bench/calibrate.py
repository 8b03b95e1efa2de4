"""Readings that a cell's correctness limit is set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,3 --seconds 8 \
        [--control-seeds 3]

For each seed, in one process: one run of the cell's own entry at its own
sizes and load (a short window), then its answers held to the float32
reference by the run's own check (``check.run``, ``check.correct``): the
program's reading is the worst sampled row's relative error.  On the first
``--control-seeds`` seeds the control goes through the same check: the
reference computed with float8 matrix products, put in the place of the
program's sampled answers (``check.control``).  Each seed's line gives both
readings and whether the check passed each; the last line sums them up:
the largest program reading (the lower end of the limit), the smallest
control reading (the upper end), and whether every program run was correct
and every control not.  Benchmark runs never run the control.

``--rehearse`` does the same on the CPU at the smoke sizes.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench import check, harness

    seeds = [int(s) for s in args.seeds.split(",")]
    program, control, verdicts = [], [], []
    for i, seed in enumerate(seeds):
        try:
            record = harness.measure(args.workload, seed, args.seconds, False,
                                     rehearse=args.rehearse)
        except harness.NoChip as e:
            print(f"no result: {e}", file=sys.stderr)
            return 1
        params = record.pop("params")
        cfg, spec = record["config"], record["workload"]["check"]
        compared = check.run(record, params, cfg, spec, seed, args.rehearse)
        line = {"seed": seed, "program": compared["x0_relerr_max"]["value"],
                "program_correct": check.correct(compared),
                "limit": compared["x0_relerr_max"]["limit"],
                "setup_s": record["setup_s"], "attempted": record["attempted"]}
        program.append(line["program"])
        verdicts.append(line["program_correct"])
        if i < args.control_seeds:
            swapped = check.control(record, params, cfg, spec, seed)
            compared = check.run(swapped, params, cfg, spec, seed, args.rehearse)
            line["control"] = compared["x0_relerr_max"]["value"]
            line["control_correct"] = check.correct(compared)
            control.append(line["control"])
            verdicts.append(not line["control_correct"])
        del params
        print(json.dumps(line), flush=True)
    read = [p for p in program if p is not None]
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "lower": max(read) if read else None,
                      "upper": min(control) if control else None,
                      "control_seeds": len(control), "as_expected": all(verdicts)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
