"""The on-chip benchmark: one run of one cell.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Prints, as the last line of standard output, one JSON object with
`correct`, `attempted`, `failed`, `metrics`, `device` (and, traced,
`breakdown`), and last the numbers compared with their limits; the same
numbers close standard error.  With `--trace 0` the metrics are the cell's
end-to-end metrics, with `--trace 1` its per-layer metrics, read from a
profiler trace of the window.  Exits non-zero, printing no result, when JAX
finds no TPU or fewer chips than the cell asks for.
"""

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench.harness.core import NoChip, run

    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start=T_START)
    except NoChip as e:
        print(str(e), file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
