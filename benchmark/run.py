#!/usr/bin/env python3
"""The benchmark's command:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, which holds the cell's chips.  Prints the result as the
last line of standard output (one JSON object), and each number that
decided ``correct`` beside its limit as the last lines of standard
error.  Exits non-zero, with no result, when JAX finds no TPU or fewer
chips than the cell asks for, or when the program is not in the
checkout.
"""

import time

T_START = time.perf_counter()   # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return p.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    from benchmark import harness

    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = harness.load_cell(ROOT, args.workload, manifest)
    seconds = (float(manifest["run_seconds"]) if args.seconds is None
               else args.seconds)
    try:
        result = harness.run_cell(cell, args.seed, seconds,
                                  bool(args.trace), T_START)
    except harness.NoDevice as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    sys.stderr.flush()              # the compared numbers, then the line
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
