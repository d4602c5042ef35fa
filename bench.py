"""Benchmark: Mask-RCNN R50-FPN training throughput + MFU on TPU.

Runs the real jitted train step (forward + backward + SGD update) on
synthetic COCO-shaped data.  Default mode is a cheap-first LADDER of
operating points — 512px/batch-1, the 832x1344 bucket canvas, then the
optimized-chart headline (bf16, batch 4 per chip, 1344 px padded
images; reference charts/maskrcnn-optimized/templates/maskrcnn.yaml:63,72
and the PREPROC.MAX_SIZE the charts train at) — banking every rung that
succeeds to artifacts/ before escalating.  ``--single`` benches exactly
the requested point (A/B and sweep mode).

Prints ONE JSON line:
    {"metric": ..., "value": N, "unit": "images/sec/chip",
     "vs_baseline": N, "mfu": ..., ...}

One process, one ``jax.devices()`` call in the main thread.  A run that
fails — no device, a device that is not in ``PEAK_FLOPS``, a refused
compile, a failed rung — still prints its diagnostic JSON line (with
``"status": "error"`` or a ``ladder_abort`` record) and exits non-zero.
Choose the CPU with ``JAX_PLATFORMS=cpu`` in the shell; a CPU is not in
the peaks table, so that run is an error too: this script measures
chips only.

The reference publishes no numbers (BASELINE.md), so ``vs_baseline``
is reported against the public TensorPack-era V100 figure of
~20 img/s/GPU at batch 4 fp16 — the closest apples-to-apples anchor
for the hardware the reference targets (2× p3.16xlarge).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

from eksml_tpu.fsio import atomic_write_json, atomic_write_text

# Approximate per-V100 throughput of the reference's optimized stack
# (aws-samples mask-rcnn-tensorflow, fp16, batch 4). Used only to give
# vs_baseline a denominator; the reference repo itself publishes none.
V100_IMAGES_PER_SEC = 20.0

# bf16 peak of the chips this targets, keyed by jax ``device_kind``.
# A device that is not here is an error, never a default.
PEAK_FLOPS = {
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v4": 275e12,
    "TPU v6 lite": 918e12,   # v6e/Trillium
}


def peak_flops_for(device_kind: str) -> float:
    if device_kind not in PEAK_FLOPS:
        raise ValueError(
            f"no peak FLOP/s for device kind {device_kind!r} (known: "
            f"{sorted(PEAK_FLOPS)}); bench.py measures chips only")
    return PEAK_FLOPS[device_kind]


def _emit(payload: dict) -> None:
    print(json.dumps(payload))


def _is_hbm_oom(e: BaseException) -> bool:
    """XLA:TPU compile-time out-of-memory (an operating-point problem,
    retryable with remat).  A bare RESOURCE_EXHAUSTED is NOT enough:
    gRPC quota/message-size errors carry the same status (and messages
    like 'Failed to allocate request buffer') and must not trigger a
    remat-degraded headline — require an HBM-specific marker."""
    msg = str(e)
    return ("Ran out of memory in memory space hbm" in msg
            or ("RESOURCE_EXHAUSTED" in msg and "hbm" in msg.lower()))


LAST_GOOD = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts", "bench_last_good.json")

# THE canonical banked_at stamp format
TS_FMT = "%Y-%m-%dT%H:%M:%SZ"


def utcnow() -> str:
    return time.strftime(TS_FMT, time.gmtime())


def is_hardware(diag: dict, key: str = "device_kind") -> bool:
    """THE hardware-evidence gate: a measurement may only be banked as
    hardware evidence when its device field names a real accelerator.
    Tolerates explicit null device fields (a run that died before
    device init)."""
    return ((diag or {}).get(key) or "").lower() not in ("", "cpu",
                                                         "host")


def _bank(path: str, diag: dict) -> None:
    """Persist a successful result (timestamped) under artifacts/."""
    try:
        rec = dict(diag)
        rec["banked_at"] = utcnow()
        os.makedirs(os.path.dirname(path), exist_ok=True)
        atomic_write_json(path, rec)
    except OSError as e:
        print(f"bench: could not bank {path}: {e}", file=sys.stderr)


def _bank_last_good(diag: dict) -> None:
    _bank(LAST_GOOD, diag)


def main(argv=None):
    p = argparse.ArgumentParser(description="eksml_tpu throughput bench")

    def positive_int(s):
        v = int(s)
        if v < 1:
            raise argparse.ArgumentTypeError(
                "must be >= 1 (the first call compiles and must stay "
                "out of timing)")
        return v

    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--warmup", type=positive_int, default=3)
    p.add_argument("--single", action="store_true",
                   help="run exactly the operating point given by "
                        "--image-size/--pad-hw/--batch-size (A/B and "
                        "sweep mode).  Default is the LADDER: cheap "
                        "point first, banking each rung, then escalate "
                        "to the 1344px/batch-4 headline")
    p.add_argument("--batch-size", type=int, default=4)
    # chart operating point: PREPROC.MAX_SIZE=1344 (config.py), the
    # shape the v5e-32 north star is defined at — NOT a smaller proxy
    p.add_argument("--image-size", type=int, default=1344)
    p.add_argument("--pad-hw", type=int, nargs=2, default=None,
                   metavar=("H", "W"),
                   help="bench a rectangular PREPROC.BUCKETS canvas "
                        "(e.g. 832 1344) instead of the square "
                        "--image-size pad")
    p.add_argument("--precision", default="bfloat16",
                   choices=["bfloat16", "float32"])
    # nargs="?"/const=1 keeps the legacy bare `--remat` spelling while
    # exposing the per-change A/B form (`--remat 0`, `--remat 1`)
    p.add_argument("--remat", type=int, nargs="?", const=1, default=0,
                   choices=(0, 1),
                   help="rematerialize backbone/FPN (TRAIN.REMAT); "
                        "A/B switch (0/1, bare flag = 1)")
    p.add_argument("--param-dtype", default="float32",
                   choices=["float32", "bfloat16"],
                   help="param + optimizer-state storage dtype "
                        "(TRAIN.PARAM_DTYPE); bfloat16 halves the "
                        "state HBM — the 1344/b8 memory plan")
    p.add_argument("--sharding", default="replicated",
                   choices=["replicated", "fsdp", "tensor", "2d"],
                   help="sharding plan for the measured train step "
                        "(eksml_tpu/parallel/sharding.py): fsdp "
                        "shards params+optimizer state over the fsdp "
                        "mesh axis, tensor shards the FPN/head "
                        "weights' output features over the model "
                        "axis, 2d composes both — all gathered "
                        "just-in-time in the step; per-device state "
                        "bytes land in the result JSON either way")
    p.add_argument("--fsdp-axis", type=int, default=0,
                   help="fsdp axis size for --sharding fsdp/2d "
                        "(0 = all devices of one slice; under 2d, "
                        "the rest of the slice after --model-axis)")
    p.add_argument("--model-axis", type=int, default=0,
                   help="model axis size for --sharding tensor/2d "
                        "(0 = all devices of one slice under tensor; "
                        "2d needs it set explicitly)")
    p.add_argument("--num-slices", type=int, default=0,
                   help="slice count for the measured mesh "
                        "(TPU.NUM_SLICES); 0 = auto — hardware slice "
                        "groups always win, the flag only pins "
                        "emulated/CPU splits [%(default)s]")
    p.add_argument("--exchange", default="flat",
                   choices=["flat", "hierarchical"],
                   help="cross-slice gradient exchange "
                        "(TRAIN.SHARDING.EXCHANGE): hierarchical = "
                        "in-slice reduce-scatter on ICI, DCN "
                        "all-reduce of the partials, in-slice "
                        "all-gather back; inert at one slice "
                        "[%(default)s]")
    p.add_argument("--prefetch", type=int, default=-1,
                   choices=(-1, 0, 1),
                   help="input-pipeline A/B: -1 = one device-resident "
                        "batch (legacy, measures pure step time); 0 = "
                        "synchronous host->device transfer every step; "
                        "1 = async double-buffered DevicePrefetcher "
                        "(overlaps the transfer with compute)")
    p.add_argument("--roi-backend", default="auto",
                   choices=["auto", "pallas", "xla"],
                   help="A/B switch for the ROIAlign kernel "
                        "(sets EKSML_ROI_BACKEND)")
    p.add_argument("--roi-bwd", default="auto",
                   choices=["auto", "pallas", "xla"],
                   help="A/B switch for the ROIAlign BACKWARD kernel "
                        "(sets EKSML_ROI_BWD; only matters when the "
                        "pallas forward is active)")
    p.add_argument("--profile", type=int, default=0, metavar="N",
                   help="capture a jax.profiler trace of N timed steps "
                        "into ./profile/")
    p.add_argument("--config", nargs="*", default=[],
                   help="KEY=VALUE overrides")
    args = p.parse_args(argv)

    # ladder mode dictates its own operating points — refuse silently
    # ignored point flags rather than bench something the caller did
    # not ask for (use --single to pin a point)
    if not args.single:
        ignored = [f for f in ("--image-size", "--batch-size")
                   if getattr(args, f[2:].replace("-", "_"))
                   != p.get_default(f[2:].replace("-", "_"))]
        if args.pad_hw is not None:
            ignored.append("--pad-hw")
        if args.profile:
            ignored.append("--profile")
        if ignored:
            p.error(f"{', '.join(ignored)} only apply with --single; "
                    "default mode runs the fixed cheap-first ladder")

    os.environ["EKSML_ROI_BACKEND"] = args.roi_backend
    os.environ["EKSML_ROI_BWD"] = args.roi_bwd

    diag = {
        "metric": "maskrcnn_r50fpn_train_throughput",
        "value": 0.0,
        "unit": "images/sec/chip",
        "vs_baseline": 0.0,
        "batch_size": args.batch_size,
        "image_size": (tuple(args.pad_hw) if args.pad_hw
                       else args.image_size),
        "precision": args.precision,
        "roi_backend": args.roi_backend,
        "roi_bwd": args.roi_bwd,
    }

    try:
        if args.single:
            _run_with_remat(args, diag)
        else:
            run_ladder(args, diag)
    except Exception as e:  # noqa: BLE001 — diagnostic line must land
        import traceback

        diag["error"] = f"{type(e).__name__}: {e}"
        diag["trace_tail"] = "".join(
            traceback.format_exception(type(e), e, e.__traceback__)
        ).splitlines()[-3:]
    # explicit machine-readable health; a run in which anything failed
    # (the whole run, or one rung of the ladder) exits non-zero
    failed = bool(diag.get("error") or diag.get("ladder_abort"))
    diag.setdefault("status", "error" if diag.get("error") else "ok")
    _emit(diag)
    return 1 if failed else 0


def _run_with_remat(args, diag: dict) -> None:
    """run(); on HBM OOM (an operating-point problem) retry once with
    backbone/FPN remat (TRAIN.REMAT — the knob the optimized chart
    exposes) and record that the point needed it (``remat_fallback``,
    ``pre_remat_error``)."""
    import traceback

    # the retry run happens OUTSIDE the except block: run() reaches
    # the sharded step's collectives (storage_grads), and a collective
    # under an exception handler is a host-local entry the
    # collective-order checker rightly rejects — only the raising host
    # would enter it
    retry = False
    try:
        run(args, diag)
    except Exception as e:  # noqa: BLE001
        if not (_is_hbm_oom(e) and not args.remat):
            # bench is a per-host measurement CLI: a raise here ends
            # THIS host's run and its JSON line records the failure —
            # no fleet is left blocking in the retry's collectives
            raise  # eksml-lint: disable=collective-order
        print("bench: HBM OOM at this operating point; retrying "
              "with TRAIN.REMAT=True", file=sys.stderr)
        # snapshot the failure, then DROP the traceback before the
        # rerun: the failed attempt's params/opt_state/batch HBM
        # buffers live in its frames, and holding them through the
        # retry would shave hundreds of MB off a compile that is
        # already within ~0.5G of capacity
        err_msg = f"{type(e).__name__}: {e}"
        traceback.clear_frames(e.__traceback__)
        args.remat = True
        diag["remat_fallback"] = True
        diag["pre_remat_error"] = err_msg.splitlines()[0][:200]
        retry = True
    if retry:
        run(args, diag)


# Cheap-first escalation ladder.  Each rung is a
# real operating point of the charts: 512px is the convergence-rung
# canvas, 832x1344 is the PREPROC.BUCKETS rectangular canvas, 1344 sq
# batch 4 is the optimized-chart headline the north star is defined at.
# Rung 0 is a forward-only microbench: the cheapest compile, run
# before anything that pays a backward-pass compile.
RUNGS = (
    {"name": "micro_256_b1_fwd", "image_size": 256, "pad_hw": None,
     "batch_size": 1, "forward_only": True, "steps": 3, "warmup": 1},
    {"name": "512_b1", "image_size": 512, "pad_hw": None,
     "batch_size": 1},
    {"name": "832x1344_b4", "image_size": 1344, "pad_hw": (832, 1344),
     "batch_size": 4},
    {"name": "1344_b4", "image_size": 1344, "pad_hw": None,
     "batch_size": 4},
    # the batch-8 memory plan: remat + bf16
    # param/optimizer storage buy the HBM for b8 at the flagship
    # canvas — the operating point the bucketed 832x1344 rung (13.08
    # img/s/chip) says has headroom over the b4 headline
    {"name": "1344_b8_remat", "image_size": 1344, "pad_hw": None,
     "batch_size": 8, "remat": True, "param_dtype": "bfloat16"},
)
# rungs whose success counts as "the headline point ran" — the b4
# flagship and the b8 memory-plan point are both production-legal
HEADLINE_RUNGS = ("1344_b4", "1344_b8_remat")


def run_ladder(args, diag: dict) -> None:
    """Run RUNGS cheapest-first, banking each success to
    artifacts/bench_rung_<name>.json (and bench_last_good.json via
    run()) BEFORE attempting the next.  The ladder stops at the first
    rung that fails, and main() then exits non-zero.  The emitted
    headline line carries
    the most expensive rung that succeeded, plus a per-rung summary."""
    import traceback

    # EKSML_BENCH_RUNGS=name[,name…] subsets the ladder — the CPU
    # integration drive runs the REAL rung loop on one cheap rung with
    # shrunken --config widths instead of faking run()
    keep = os.environ.get("EKSML_BENCH_RUNGS", "")
    if keep:
        names = [t.strip() for t in keep.split(",") if t.strip()]
        known = {r["name"] for r in RUNGS}
        bad = [n for n in names if n not in known]
        if not names:
            raise ValueError(
                f"EKSML_BENCH_RUNGS={keep!r} contains no rung names "
                f"(known: {sorted(known)})")
        if bad:
            # every requested name must resolve — a typo silently
            # dropping the headline rung must fail loudly, not bench
            # a subset the caller didn't ask for
            raise ValueError(
                f"EKSML_BENCH_RUNGS={keep!r}: unknown rung(s) {bad} "
                f"(known: {sorted(known)})")
        rungs = [r for r in RUNGS if r["name"] in names]
    else:
        rungs = list(RUNGS)

    rung_summaries = []
    best = None
    carry_remat = args.remat
    for rung in rungs:
        ra = argparse.Namespace(**vars(args))
        ra.image_size = rung["image_size"]
        ra.pad_hw = rung["pad_hw"]
        ra.batch_size = rung["batch_size"]
        ra.profile = 0  # profiling is a --single concern (harvest)
        # rung 0 overrides: forward-only and tiny step counts — the
        # whole point is banking a number before the first backward
        # compile finishes elsewhere on the ladder
        ra.forward_only = rung.get("forward_only", False)
        if rung.get("steps"):
            ra.steps = rung["steps"]
        if rung.get("warmup"):
            ra.warmup = rung["warmup"]
        # once a rung needed remat, every LARGER rung starts with it
        # instead of re-paying a doomed non-remat compile.
        # A rung can also REQUIRE remat / bf16 params (the b8 memory
        # plan ships as one pre-planned operating point).
        ra.remat = 1 if (carry_remat or rung.get("remat")) else 0
        ra.param_dtype = rung.get("param_dtype", args.param_dtype)
        rdiag = {
            "metric": ("maskrcnn_r50fpn_fwd_microbench"
                       if ra.forward_only else diag["metric"]),
            "value": 0.0,
            "unit": diag["unit"],
            "vs_baseline": 0.0,
            "operating_point": rung["name"],
            "batch_size": ra.batch_size,
            "image_size": (tuple(ra.pad_hw) if ra.pad_hw
                           else ra.image_size),
            "precision": args.precision,
            "roi_backend": args.roi_backend,
            "roi_bwd": args.roi_bwd,
        }
        if ra.forward_only:
            rdiag["forward_only"] = True
        try:
            _run_with_remat(ra, rdiag)
        except Exception as e:  # noqa: BLE001 — bank what we have
            err = f"{type(e).__name__}: {e}"
            print(f"bench: rung {rung['name']} failed: "
                  f"{err.splitlines()[0][:200]}", file=sys.stderr)
            rung_summaries.append({"rung": rung["name"], "value": 0.0,
                                   "error": err.splitlines()[0][:200]})
            diag["ladder_abort"] = {
                "rung": rung["name"],
                "error": err.splitlines()[0][:200],
                "trace_tail": "".join(traceback.format_exception(
                    type(e), e, e.__traceback__)).splitlines()[-3:],
            }
            break
        best = rdiag  # later rungs are strictly more headline-like
        carry_remat = carry_remat or ra.remat
        rung_summaries.append({
            "rung": rung["name"],
            **{k: rdiag.get(k) for k in (
                "value", "step_time_ms", "mfu", "remat_fallback")}})
        # hardware evidence only AND nonzero (the exact gate
        # _bank_last_good uses: a hardware run landing 0.0 must not
        # bank a zero rung artifact): a CPU smoke of the
        # ladder must not clobber banked TPU rung files
        if rdiag["value"] > 0 and is_hardware(rdiag):
            _bank(os.path.join(os.path.dirname(LAST_GOOD),
                               f"bench_rung_{rung['name']}.json"),
                  rdiag)
    if best is not None:
        diag.update(best)
        diag["headline_point"] = (
            best.get("operating_point") in HEADLINE_RUNGS)
    else:
        # no rung landed: surface the failure at top level so the
        # recorded line is self-diagnosing
        abort = diag.get("ladder_abort", {})
        diag["error"] = abort.get("error", "ladder: no rung ran")
        diag["trace_tail"] = abort.get("trace_tail", [])
    diag["rungs"] = rung_summaries


def _bank_attribution(step, diag: dict) -> None:
    """--profile companion artifacts: the compiled
    HLO text and its instruction→component attribution land next to the
    trace, so ``tools/trace_summary.py --attribution`` can name every
    fusion the trace times.  Best-effort: a failure here must never
    destroy the measured result."""
    import sys as _sys

    try:
        hlo = step.as_text()  # AOT-compiled executable only
    except Exception as e:  # noqa: BLE001 — jit fallback has no text
        print(f"bench: no compiled HLO for attribution ({e})",
              file=_sys.stderr)
        return
    try:
        from eksml_tpu.profiling import write_attribution_artifact

        os.makedirs("profile", exist_ok=True)
        atomic_write_text(os.path.join("profile", "hlo.txt"), hlo)
        payload = write_attribution_artifact(
            hlo, os.path.join("profile", "attribution.json"),
            extra={"operating_point": diag.get("operating_point"),
                   "image_size": diag.get("image_size"),
                   "batch_size": diag.get("batch_size")})
        table = payload["component_table"]
        diag["component_pct"] = table["component_pct"]
        diag["component_other_pct"] = table["other_pct"]
        print("bench: attribution banked to profile/attribution.json "
              f"(modeled other {table['other_pct']}%)",
              file=_sys.stderr)
    except Exception as e:  # noqa: BLE001 — diagnostics only
        print(f"bench: attribution failed: {e}", file=_sys.stderr)


def run(args, diag: dict) -> None:
    import jax

    # the device first, in the main thread, before anything is built:
    # no device, or one without a row in PEAK_FLOPS, ends the run here
    devices = jax.devices()
    n_dev = len(devices)
    dev_kind = devices[0].device_kind
    diag["device_kind"] = dev_kind
    diag["n_devices"] = n_dev
    peak = peak_flops_for(dev_kind)

    # persistent compile cache: the 1344-px train-step compile is
    # minutes of XLA work — pay it once per (program, cache directory)
    from eksml_tpu.utils.compile_cache import enable_persistent_cache

    diag["compile_cache"] = enable_persistent_cache()

    import jax.numpy as jnp
    import numpy as np
    import optax

    from eksml_tpu.config import config as cfg
    from eksml_tpu.data.loader import make_synthetic_batch
    from eksml_tpu.models import MaskRCNN
    from eksml_tpu.train import make_optimizer

    shape = tuple(args.pad_hw) if args.pad_hw else args.image_size
    size = max(args.pad_hw) if args.pad_hw else args.image_size
    cfg.freeze(False)
    cfg.TRAIN.PRECISION = args.precision
    cfg.TRAIN.REMAT = bool(args.remat)
    cfg.TRAIN.PARAM_DTYPE = getattr(args, "param_dtype", "float32")
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = args.batch_size
    cfg.TRAIN.SHARDING.STRATEGY = getattr(args, "sharding",
                                          "replicated")
    cfg.TRAIN.SHARDING.FSDP_AXIS_SIZE = getattr(args, "fsdp_axis", 0)
    cfg.TRAIN.SHARDING.MODEL_AXIS_SIZE = getattr(args, "model_axis", 0)
    cfg.TRAIN.SHARDING.EXCHANGE = getattr(args, "exchange", "flat")
    cfg.PREPROC.MAX_SIZE = size
    cfg.PREPROC.TRAIN_SHORT_EDGE_SIZE = (size, size)
    cfg.update_args(args.config)
    cfg.freeze()
    # the config is the single source of truth for the measured plan:
    # a --config TRAIN.SHARDING.* override lands AFTER the flags above
    # and must actually select the plan (keying off the flag alone
    # would bank a "fsdp" JSON line measured on the replicated path)
    sharding = str(cfg.TRAIN.SHARDING.STRATEGY)
    if sharding != "replicated":
        if getattr(args, "forward_only", False):
            raise ValueError(f"sharding={sharding} measures the full "
                             "train step (params+optimizer shards); "
                             "drop --forward-only")
        if getattr(args, "prefetch", -1) >= 0:
            raise ValueError("sharding and --prefetch are separate "
                             "A/Bs; run them in separate invocations")
    # Validate AFTER update_args so a sweep overriding the strides is
    # checked against the strides it actually runs with.
    coarsest = max(cfg.FPN.ANCHOR_STRIDES)
    for d in (args.pad_hw or [args.image_size]):
        if d % coarsest:
            raise ValueError(
                f"pad dim {d} must be divisible by the coarsest FPN "
                f"stride ({coarsest}): anchor grids are computed at "
                "H//stride and must match the conv feature maps")

    # cfg, not the flags: a --config override may have shadowed the
    # batch/precision flags above (the PR 6/7 re-derivation rule; the
    # banner and every consumer below must describe what is measured).
    # The diag fields are corrected HERE, before any consumer — the
    # --profile attribution artifact banks diag["batch_size"] mid-run
    batch_per_chip = int(cfg.TRAIN.BATCH_SIZE_PER_CHIP)
    diag["batch_size"] = batch_per_chip
    diag["precision"] = str(cfg.TRAIN.PRECISION)
    print(f"bench: {n_dev}x {dev_kind}, batch={batch_per_chip}, "
          f"image={shape}, {cfg.TRAIN.PRECISION}, "
          f"roi={args.roi_backend}", file=sys.stderr)

    fwd_only = getattr(args, "forward_only", False)
    model = MaskRCNN.from_config(cfg)

    # sharding plan for the measured step (--sharding): replicated
    # keeps the historical no-mesh jit path untouched (banked numbers
    # stay comparable); fsdp builds the (data, fsdp, model) mesh and
    # threads the plan's shardings through init and the step
    plan = None
    if sharding != "replicated":
        from eksml_tpu.parallel import build_mesh
        from eksml_tpu.parallel.mesh import slice_groups
        from eksml_tpu.parallel.sharding import ShardingPlan, plan_mesh

        # the plan must see the real slice topology: with the config
        # default NUM_SLICES=1, --fsdp-axis 0 on multislice hardware
        # would resolve to ALL devices and straddle the DCN hop.
        # Hardware slice groups always win; --num-slices only pins
        # emulated/CPU splits (virtual devices carry no slice info)
        groups = slice_groups(devices)
        num_slices = (len(groups) if groups
                      else max(1, getattr(args, "num_slices", 0)))
        if num_slices > 1:
            cfg.freeze(False)
            cfg.TPU.NUM_SLICES = num_slices
            cfg.freeze()
        mesh_shape, mesh_axes = plan_mesh(cfg, n_devices=n_dev)
        mesh = build_mesh(mesh_shape, mesh_axes, devices,
                          num_slices=num_slices)
        plan = ShardingPlan.from_config(cfg, mesh)
        diag["sharding"] = plan.describe()
        # consumers must never have to assume one slice: the JSON
        # line (and every banked artifact derived from it) carries
        # the slice topology the step actually ran on
        diag["num_slices"] = num_slices
        diag["slice_devices"] = n_dev // max(1, num_slices)

    # input-pipeline A/B (--prefetch): a small pool of DISTINCT host
    # batches cycled through the step loop, so transfer modes measure
    # real per-step H2D traffic instead of a cached resident buffer
    prefetch = getattr(args, "prefetch", -1)
    host_batches = None
    if prefetch >= 0:
        host_batches = [
            {k: v for k, v in make_synthetic_batch(
                cfg, batch_size=batch_per_chip, image_size=shape,
                seed=s).items() if k not in ("image_scale", "image_id")}
            for s in range(4)]
        batch = jax.device_put(host_batches[0])
    else:
        # the plan path runs ONE global program over every device, so
        # the host batch carries batch_size rows PER CHIP (the
        # trainer's TRAIN.BATCH_SIZE_PER_CHIP semantics — the batch
        # axis must divide over data×fsdp); the historical no-plan
        # path keeps batch_size total rows on one device
        global_bs = batch_per_chip * (n_dev if plan is not None else 1)
        batch = make_synthetic_batch(cfg, batch_size=global_bs,
                                     image_size=shape)
        batch = {k: jnp.asarray(v) for k, v in batch.items()
                 if k not in ("image_scale", "image_id")}

    rng = jax.random.PRNGKey(0)
    t0 = time.time()

    def init_fn(r, b):
        return model.init(r, b, r)["params"]

    if plan is not None:
        batch = jax.device_put(batch, plan.batch_sharding())
        params, param_sh = plan.init_sharded(init_fn, rng, batch)
    else:
        params = jax.jit(init_fn)(rng, batch)
    from eksml_tpu.train import cast_params_for_storage

    params = cast_params_for_storage(params, cfg.TRAIN.PARAM_DTYPE)
    if not fwd_only:
        # the micro rung never touches the optimizer — skip allocating
        # param-tree-sized momentum buffers on the device exactly where
        # per-cycle latency matters most
        tx, _ = make_optimizer(cfg)
        if plan is not None:
            opt_state, opt_sh = plan.init_sharded(tx.init, params,
                                                  deterministic=True)
        else:
            opt_state = tx.init(params)
        # the per-device state cost of the active plan — what the
        # fsdp-vs-replicated A/B is actually about (the same numbers
        # the trainer's eksml_train_*_bytes gauges publish)
        from eksml_tpu.parallel.sharding import tree_bytes_per_device

        diag["param_bytes_per_device"] = tree_bytes_per_device(params)
        diag["opt_state_bytes_per_device"] = tree_bytes_per_device(
            opt_state)
    print(f"bench: init in {time.time() - t0:.1f}s", file=sys.stderr)

    # per-step batch source for the transfer A/B modes
    prefetcher = None
    if prefetch < 0:
        def next_batch():
            return batch
    elif prefetch == 0:
        import itertools

        host_it = itertools.cycle(host_batches)

        def next_batch():
            # synchronous transfer on the step critical path — the
            # baseline the prefetcher is measured against
            b = jax.device_put(next(host_it))
            jax.block_until_ready(b)
            return b
    else:
        import itertools

        from eksml_tpu.data.loader import DevicePrefetcher

        prefetcher = DevicePrefetcher(itertools.cycle(host_batches),
                                      jax.device_put)

        def next_batch():
            return next(prefetcher)

    if fwd_only:
        # rung-0 microbench: time the forward losses alone — no grad,
        # no optimizer, no donated buffers — so the compile is a
        # fraction of the train step's.  Clearly labeled: metric name and the
        # forward_only field both say what was measured.
        def forward_step(params, batch, rng):
            losses = model.apply({"params": params}, batch, rng)
            return losses["total_loss"]

        step = jax.jit(forward_step)
        lower_args = (params, batch, rng)

        def run_step(i):
            return step(params, next_batch(),
                        jax.random.fold_in(rng, i))
    else:
        # ONE step construction with profiling/predict.py (which
        # AOT-prices this exact program) — see make_synthetic_train_step
        from eksml_tpu.train import make_synthetic_train_step

        step = make_synthetic_train_step(
            model, tx, plan,
            param_sh if plan is not None else None,
            opt_sh if plan is not None else None)
        lower_args = (params, opt_state, batch, rng)

        def run_step(i):
            nonlocal params, opt_state
            params, opt_state, loss = step(params, opt_state,
                                           next_batch(),
                                           jax.random.fold_in(rng, i))
            return loss

    # compiled-HLO FLOPs per step → MFU.  cost_analysis counts the
    # actual fused program, a better estimate than a hand model of the
    # architecture.  The AOT executable REPLACES the jit dispatch
    # (compiling once, not twice); a refused compile raises here.
    # The try/finally closes the prefetcher on EVERY exit: an HBM OOM
    # here must not leak the transfer thread + its queued device
    # batches into _run_with_remat's retry compile (which runs within
    # ~0.5G of capacity by definition).
    flops_per_step = None
    try:
        compiled = step.lower(*lower_args).compile()
        step = compiled
        try:
            cost = compiled.cost_analysis()
            if isinstance(cost, (list, tuple)):
                cost = cost[0] if cost else None
            if cost:
                flops_per_step = float(cost.get("flops", 0.0)) or None
        except Exception as e:  # noqa: BLE001 — MFU is best-effort
            print(f"bench: cost_analysis unavailable: {e}",
                  file=sys.stderr)

        t0 = time.time()
        for i in range(args.warmup):
            loss = run_step(i)
        jax.block_until_ready(loss)
        print(f"bench: compile+warmup in {time.time() - t0:.1f}s "
              f"(loss={float(loss):.3f})", file=sys.stderr)

        t0 = time.time()
        for i in range(args.steps):
            loss = run_step(100 + i)
        jax.block_until_ready(loss)
        dt = time.time() - t0

        if args.profile:
            # separate profiled segment AFTER timing — trace
            # serialization must not pollute the headline
            # images/sec/chip or mfu
            jax.profiler.start_trace("profile")
            for i in range(args.profile):
                loss = run_step(500 + i)
            jax.block_until_ready(loss)
            jax.profiler.stop_trace()
            print("bench: trace written to ./profile/", file=sys.stderr)
            _bank_attribution(step, diag)
    finally:
        if prefetcher is not None:
            # time the step loop spent BLOCKED on the next device
            # batch — ~0 means the transfer fully overlapped compute
            diag["prefetch_wait_ms"] = round(
                prefetcher.wait_ms_ewma or 0.0, 2)
            prefetcher.close()

    assert np.isfinite(float(loss)), f"non-finite loss {float(loss)}"
    # under a plan each step consumes batch_size rows on EVERY chip;
    # the legacy path's step is batch_size rows total
    imgs_per_step = batch_per_chip * (n_dev if plan is not None else 1)
    imgs_per_sec = args.steps * imgs_per_step / dt
    per_chip = imgs_per_sec / max(1, n_dev)
    step_ms = dt / args.steps * 1000

    diag["value"] = round(per_chip, 3)
    diag["prefetch"] = prefetch
    diag["param_dtype"] = cfg.TRAIN.PARAM_DTYPE
    # predicted step time rides NEXT TO the measurement (ISSUE 7): a
    # real hardware round self-calibrates the roofline model the
    # hermetic gate (tools/perf_gate.py) runs on between chip runs.
    # AFTER the timed loop on purpose — parsing a flagship-scale HLO
    # text costs seconds.  EKSML_BENCH_PREDICT=0 opts out.
    # never on forward-only programs: the fields carry train-step
    # semantics everywhere (calibration), and a fwd-only
    # prediction under the same names is a trap for every consumer
    # that forgets the forward_only filter
    if not fwd_only and os.environ.get("EKSML_BENCH_PREDICT") != "0":
        try:
            from eksml_tpu.profiling import predict as _predict

            # cfg, not the flags: TRAIN.PRECISION / TPU.NUM_SLICES
            # re-derive after --config overrides and slice detection
            # (the sharding re-derivation rule above) — the wrong
            # peak-flops row or link bandwidth would bank a badly
            # scaled self-calibration point
            pred = _predict.predict_for_compiled(
                compiled.as_text(), device_kind=dev_kind,
                mesh_shape=(dict(plan.mesh.shape)
                            if plan is not None else {}),
                precision=str(cfg.TRAIN.PRECISION),
                num_slices=int(cfg.TPU.NUM_SLICES),
                exchange=str(cfg.TRAIN.SHARDING.EXCHANGE))
            diag["predicted_step_time_ms"] = \
                pred["predicted_step_time_ms"]
            diag["predicted_sections_ms"] = pred["sections_ms"]
            # the per-link split (ISSUE 19): ici/dcn/exposed ms from
            # the replica_groups-exact pricing, so a hardware round
            # banks the link-level prediction next to the measurement
            diag["predicted_comms_ms"] = pred.get("comms_ms")
            # the memory plan (ISSUE 20): liveness-predicted peak HBM
            # + headroom against the chip's capacity, next to the
            # measurement the same way — a hardware round's
            # memory_stats() peak calibrates this model
            hbm = pred.get("hbm") or {}
            cap = hbm.get("capacity") or {}
            diag["predicted_peak_hbm_bytes"] = \
                hbm.get("peak_hbm_bytes")
            diag["predicted_hbm_headroom_bytes"] = \
                cap.get("headroom_bytes")
            diag["predicted_target"] = pred["target"]
        except Exception as e:  # noqa: BLE001 — prediction is advisory
            print(f"bench: step-time prediction unavailable: {e}",
                  file=sys.stderr)
    # a forward-only number must not be ratioed against the
    # train-throughput anchor — leave vs_baseline at 0 for the micro
    # rung (its value/mfu stand on their own, clearly labeled)
    diag["vs_baseline"] = (0.0 if fwd_only else
                           round(per_chip / V100_IMAGES_PER_SEC, 3))
    diag["step_time_ms"] = round(step_ms, 1)
    if flops_per_step:
        mfu = flops_per_step / (dt / args.steps) / (peak * n_dev)
        diag["mfu"] = round(mfu, 4)
        diag["tflops_per_step"] = round(flops_per_step / 1e12, 2)
    # bank HARDWARE evidence only: a CPU smoke overwriting the banked
    # TPU number would defeat the feature (the stale record a failure
    # cites must be a real accelerator measurement).  The fwd-only
    # micro rung is excluded too — last_good is TRAIN-step evidence,
    # and a forward-only images/sec clobbering it would inflate every
    # later stale citation (its own rung file still banks via the
    # ladder).
    if diag["value"] > 0 and is_hardware(diag) and not fwd_only:
        _bank_last_good(diag)


if __name__ == "__main__":
    sys.exit(main())
