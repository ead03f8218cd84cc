"""Readings that the limits of `correct` are set from.

    python3 bench/tools/calibrate.py --workload <name> --seeds 1 2 3 ... \
        --seconds <s> [--control high]

Runs the cell on each seed in this one process, as `bench/run.py` does, and
prints for each the numbers compared (the program's readings: the lower
end of a limit) and the same numbers with the plain reference computed at
the control precision put in the program's place (the upper end).  It
needs the chip, like the benchmark; it is not one of the benchmark's runs.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--control", nargs="+", default=["high"])
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from bench.harness import check
    from bench.harness.core import run

    rows = []
    for seed in args.seeds:
        got = {}

        def on_done(r, compared):
            got["program"] = {c["name"]: c["value"] for c in compared}
            got["gaps"] = check.gaps(r, r.answers)
            for prec in args.control:
                got["control_" + prec] = check.gaps(
                    r, check.control_answers(r, prec))

        t0 = time.time()
        out = run(args.workload, seed, args.seconds, False, on_done=on_done)
        row = {"seed": seed, "correct": out["correct"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "wall_s": time.time() - t0, **got}
        print(json.dumps(row), flush=True)
        rows.append(row)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(rows, f, indent=1)


if __name__ == "__main__":
    main()
