"""Operating-point sweep: run bench.py across configurations and bank
the results as one artifact.

Sweeps the perf-relevant axes the optimized chart exposes — ROIAlign
backend (Pallas vs XLA), precision, remat — each as a separate
``bench.py`` subprocess so a crashed configuration can't take the
others down.  A chip belongs to one process at a time, so the runs are
strictly sequential and THIS parent must never import jax (it does
not: ``eksml_tpu.fsio`` is jax-free) — a parent holding the chip would
make every child fail.  ``bench.py`` measures chips only; there is no
CPU mode of the sweep.

Usage::

    python tools/bench_sweep.py --out artifacts/bench_sweep.json \
        [--steps 20]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

CONFIGS = [
    # (name, extra argv, config KEY=VALUEs) — first entry is the
    # headline operating point (full auto: pallas fwd+bwd on a TPU)
    ("pallas_bf16", ["--roi-backend", "auto"], []),
    ("xla_bf16", ["--roi-backend", "xla", "--roi-bwd", "xla"], []),
    # backward-kernel isolation pair: pallas fwd fixed, bwd varies
    ("pallas_bf16_bwdxla", ["--roi-backend", "pallas",
                            "--roi-bwd", "xla"], []),
    ("pallas_bf16_bwdpallas", ["--roi-backend", "pallas",
                               "--roi-bwd", "pallas"], []),
    ("pallas_bf16_remat", ["--roi-backend", "auto", "--remat"], []),
    ("pallas_f32", ["--roi-backend", "auto",
                    "--precision", "float32"], []),
    # the optimized chart's landscape bucket (PREPROC.BUCKETS): the
    # canvas ~all landscape COCO images train at — quantifies the
    # bucketed-padding win over the 1344 square above
    ("pallas_bf16_bucket", ["--roi-backend", "auto",
                            "--pad-hw", "832", "1344"], []),
    # legacy f32 host-normalized ingest (PREPROC.DEVICE_NORMALIZE off)
    ("pallas_bf16_f32ingest", ["--roi-backend", "auto"],
     ["PREPROC.DEVICE_NORMALIZE=False"]),
]

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from eksml_tpu.fsio import atomic_write_json  # noqa: E402


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--out", default="artifacts/bench_sweep.json")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--timeout", type=float, default=0,
                   help="per-configuration wall clock budget (s); "
                        "0 = none [%(default)s]")
    args = p.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    results = []
    for name, extra, config in CONFIGS:
        # --single: each sweep row measures exactly its named operating
        # point — bench.py's default is now the escalation ladder
        cmd = [sys.executable, os.path.join(repo, "bench.py"),
               "--single", "--steps", str(args.steps)] + extra
        if config:
            cmd += ["--config"] + config
        t0 = time.time()
        entry = {"config": name}
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=args.timeout or None, cwd=repo)
            line = out.stdout.strip().splitlines()[-1] if out.stdout \
                else ""
            entry.update(json.loads(line))
        except subprocess.TimeoutExpired:
            entry["error"] = f"timeout after {args.timeout:.0f}s"
        except (json.JSONDecodeError, IndexError):
            entry["error"] = "no JSON line"
            entry["stderr_tail"] = out.stderr.splitlines()[-3:]
        entry["wall_s"] = round(time.time() - t0, 1)
        results.append(entry)
        print(f"{name}: "
              f"{entry.get('value', entry.get('error'))}", file=sys.stderr)

    payload = {"sweep": results}
    print(json.dumps(payload))
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    atomic_write_json(args.out, payload)


if __name__ == "__main__":
    main()
