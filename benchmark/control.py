#!/usr/bin/env python3
"""Readings that set the limits, taken on the chip at the cell's own
size (not part of a benchmark run):

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3

For each seed the reference follows the cell's first steps three ways
on the same batches -- exact (float32), the CONTROL (activations and
weights held in int8: the nearest precision below the configuration's
bfloat16) and the half-batch FAULT (the second half of every batch
left out, the mean taken over the rest) -- and prints the comparison's
numbers of each against the exact one, one JSON line per seed, with
whether it passes the cell's limits.  What they read, and that the
control PASSES the limits sound runs need, is in PERF.md section 4 and
its first open question.
"""

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="1,2,3")
    args = p.parse_args(argv)
    from benchmark import compare, harness, traffic
    from eksml_tpu.utils.compile_cache import enable_persistent_cache

    enable_persistent_cache()
    cell = harness.load_cell(ROOT, args.workload)
    try:
        harness.check_device(cell.chips)
    except harness.NoDevice as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    limits = cell.workload["limits"]
    follow = int(cell.workload["follow_steps"])
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        batches = harness.first_batches(cell, seed, follow)
        rows = cell.hyper["global_batch"]

        def run(**kw):
            return cell.task.reference_steps(
                cell.spec, cell.hyper, traffic.effective_seed(seed),
                batches, **kw)

        exact = run()
        line = {"workload": cell.name, "seed": seed, "loss": exact["loss"]}
        readings = {"control_int8": {"precision": "int8"},
                    "fault_half_batch": {"rows": list(range(rows // 2))}}
        for name, kw in readings.items():
            values, where = compare.numbers(run(**kw), exact,
                                            cell.task.extra_numbers)
            ok, _ = compare.judge(values, limits)
            line[name] = {"numbers": values, "worst_leaf": where,
                          "passes_limits": ok}
        line["seconds"] = time.perf_counter() - t0
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
