#!/usr/bin/env python3
"""Chip smoke: the training main path, once, on the attached TPU.

    python chip_smoke.py               # one chip: device, kernel, train, resume
    python chip_smoke.py --phases kernel  # one chip: device, then only these
    python chip_smoke.py --four-chips  # four chips: 1-device vs (4,1) mesh only
    python chip_smoke.py --rehearse    # control flow at smoke widths, any
                                       # platform; never prints "ok": true

ONE process drives everything through the entry points a user calls:
``Trainer(cfg, logdir).fit(...)`` fed by ``DetectionLoader`` over
``SyntheticDataset`` — what ``python -m eksml_tpu.train --synthetic``
does — for Mask-RCNN R50-FPN at the config defaults (the published
widths), 1344², bf16, batch 4 per chip, ``TRAIN.REMAT=False``, random
weights from ``--seed``.  Each phase prints one JSON object on its own
line; a phase that fails ends the run with ``{"ok": false, ...}`` as
the last line and a non-zero exit.  On success the last line is exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``.

No TPU (``JAX_PLATFORMS=cpu``, or a machine without a chip) fails in
the ``device`` phase.  ``--rehearse`` exists for the sandbox: it shrinks
the model to ``config.SMOKE_OVERRIDES``, runs the kernel check in
Pallas interpret mode, skips the TPU-only assertions, reports
``"ok": false, "rehearsal": true`` and exits 3 when every phase passed.

The compile cache follows ``eksml_tpu/utils/compile_cache.py``:
``JAX_COMPILATION_CACHE_DIR`` when set, else ``<checkout>/.jax_cache``.
A second run in the same chip call therefore reports a cache hit for
the train step.
"""

from __future__ import annotations

import argparse
import gc
import itertools
import json
import math
import os
import shutil
import sys
import tempfile
import time
import traceback

REHEARSAL_EXIT = 3

# What the train phase runs besides the config defaults (R50-FPN, FPN
# 256, fc head 1024, mask head 256, RPN 2000→1000, 512 ROIs/image,
# masks on): the optimized chart's operating point, remat off.
TRAIN_OVERRIDES = (
    "DATA.SYNTHETIC=True", "PREPROC.MAX_SIZE=1344",
    "PREPROC.TRAIN_SHORT_EDGE_SIZE=(1344,1344)",
    "TRAIN.PRECISION=bfloat16", "TRAIN.REMAT=False",
    "TRAIN.SHARDING.STRATEGY=replicated",
    "TRAIN.LOG_PERIOD=1", "TRAIN.CHECKPOINT_PERIOD=1",
    "TRAIN.MAX_EPOCHS=1", "TRAIN.EVAL_PERIOD=0",
    # the smoke owns no port: two trainers in one process, and the
    # driver may run other things on the machine
    "TELEMETRY.PORT=0",
)
GLOBAL_BATCH = 4

# kernel phase: max |kernel - reference| as a fraction of max
# |reference|.  The reference is the XLA gather formulation evaluated
# in float32 on the same (dtype-rounded) inputs: the kernel computes in
# f32 at HIGHEST MXU precision (or, forward over bf16 strips, its
# three-term equivalent) and rounds only its output, so what is left is
# output rounding (2^-8 in bf16) and, in the backward, the order in
# which many ROIs accumulate into one strip.
KERNEL_FWD_TOL = 1e-2
KERNEL_BWD_TOL = 2e-2
KERNEL_TIMED_CALLS = 10
# four-chip phase: relative |loss_4 - loss_1| / |loss_1|.  Step 1 runs
# identical params on identical data (reduction order differs); later
# steps also carry discrete flips in proposal sampling / NMS.
LOSS_TOL_FIRST = 2e-2
LOSS_TOL_LATER = 1e-1


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def result_line(ok: bool, device: dict | None, **extra) -> dict:
    """The LAST line.  On success exactly ``ok`` + ``device``."""
    if ok:
        return {"ok": True, "device": device}
    return {"ok": False, "device": device, **extra}


ONE_CHIP_PHASES = ("kernel", "train", "resume")


def phases_for(args) -> tuple:
    if args.four_chips:
        return ("device", "four_chips")
    return ("device",) + tuple(
        p for p in ONE_CHIP_PHASES if p in (args.phases or ONE_CHIP_PHASES))


# ---------------------------------------------------------------- device


def phase_device(args) -> dict:
    """``jax.devices()`` in the main thread: no deadline thread, no
    retry.  Platform must be ``tpu`` (any platform under --rehearse)."""
    import jax

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    import jaxlib

    try:
        from importlib.metadata import version

        libtpu = version("libtpu")
    except Exception:  # noqa: BLE001 — reporting only
        libtpu = "unknown"
    emit({"phase": "device", **device, "jax": jax.__version__,
          "jaxlib": jaxlib.__version__, "libtpu": libtpu,
          "rehearsal": bool(args.rehearse)})
    return device


def check_device(args, device: dict) -> None:
    if not args.rehearse and device["platform"] != "tpu":
        raise RuntimeError(
            f"no TPU: jax.devices()[0].platform is "
            f"{device['platform']!r}")
    want = 4 if args.four_chips else 1
    if device["count"] < want or (not args.rehearse
                                  and device["count"] != want):
        raise RuntimeError(f"need {want} device(s), jax sees "
                           f"{device['count']}")


# ---------------------------------------------------------------- kernel


def median_call_ms(fn, *args) -> float:
    """Median host-clock time of a call that ends in
    ``block_until_ready``, compiled and warmed first."""
    import statistics

    import jax

    jax.block_until_ready(fn(*args))
    times = []
    for _ in range(KERNEL_TIMED_CALLS):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(1e3 * (time.perf_counter() - t0))
    return round(statistics.median(times), 3)


def phase_kernel(args) -> None:
    """Pallas ROIAlign forward and backward against the XLA gather
    formulation (same tile-fit level assignment) at the benchmark
    cells' shapes: batch 4, C=256, four FPN levels of a 1344² canvas,
    the mask head's 128 ROIs × 14² and the box head's 512 ROIs × 7² an
    image; then each kernel alone on the clock (``fwd_ms``, ``bwd_ms``:
    median of ``KERNEL_TIMED_CALLS`` calls that end in
    ``block_until_ready``; on a chip only)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from eksml_tpu.ops.pallas import (TILE,
                                      pallas_batched_multilevel_roi_align,
                                      sublane_align)
    from eksml_tpu.ops.roi_align import (assign_fpn_levels_tile_fit,
                                         batched_multilevel_roi_align)

    strides = (4, 8, 16, 32)
    if args.rehearse:
        img, c, b = 128, 8, 1
        cases = [("mask", 6, 14, jnp.float32), ("box", 8, 7, jnp.float32)]
    else:
        img, c, b = 1344, 256, 4
        cases = [("mask", 128, 14, jnp.bfloat16),
                 ("mask", 128, 14, jnp.float32),
                 ("box", 512, 7, jnp.bfloat16),
                 ("box", 512, 7, jnp.float32)]
    interpret = bool(args.rehearse)
    rng = np.random.RandomState(args.seed)

    def roi_sides(roi_set, shape):
        if roi_set == "log_uniform":
            # boxes from 16 px to most of the image: every level and
            # every strip count of the backward is hit
            return np.exp(rng.uniform(np.log(16), np.log(img * 0.9),
                                      shape))
        # anchor-sized: what a fresh RPN proposes (32-64 px anchors at
        # three aspect ratios) — the benchmark cells' distribution
        side = np.exp(rng.uniform(np.log(32), np.log(64), shape[:-1]))
        ratio = rng.choice([0.5, 1.0, 2.0], shape[:-1])
        return np.stack([side * np.sqrt(ratio), side / np.sqrt(ratio)],
                        -1)

    for roi_set, (head, n, out_size, dtype) in itertools.product(
            ("log_uniform", "anchor"), cases):
        feats = tuple(jnp.asarray(
            rng.randn(b, img // s, img // s, c), dtype) for s in strides)
        side = roi_sides(roi_set, (b, n, 2))
        xy = rng.uniform(0, 1, (b, n, 2)) * (img - 1 - side)
        rois = jnp.asarray(np.concatenate([xy, xy + side], -1),
                           jnp.float32)
        g = jnp.asarray(rng.randn(b, n, out_size, out_size, c), dtype)
        levels = assign_fpn_levels_tile_fit(
            rois.reshape(b * n, 4), strides, len(feats), TILE,
            min_level=2, align=sublane_align(dtype)).reshape(b, n)

        def kernel(fs, r):
            return pallas_batched_multilevel_roi_align(
                fs, r, strides, out_size, 2, 2, interpret)

        def reference(fs, r):
            return batched_multilevel_roi_align(
                fs, r, strides, out_size, 2, 2, levels=levels)

        def fwd_bwd(fn, cast):
            def run(fs, r, gg):
                out, vjp = jax.vjp(lambda f: fn(f, r),
                                   jax.tree.map(cast, fs))
                return out, vjp(cast(gg))[0]
            return jax.block_until_ready(jax.jit(run)(feats, rois, g))

        def as_f32(x):
            return x.astype(jnp.float32)

        t0 = time.perf_counter()
        out_k, grads_k = fwd_bwd(kernel, lambda x: x)
        out_r, grads_r = fwd_bwd(reference, as_f32)
        seconds = time.perf_counter() - t0

        def rel_err(a, r):
            a = np.asarray(a, np.float32)
            r = np.asarray(r, np.float32)
            if not np.isfinite(a).all():
                return float("inf")
            return float(np.abs(a - r).max()
                         / max(float(np.abs(r).max()), 1e-6))

        observed = {}
        if dtype == jnp.bfloat16 and head == "mask":
            # observation, not asserted: the XLA formulation evaluated
            # IN bf16 also carries its ROI coordinates in bf16
            # (ops/roi_align.py roi_align), which is what non-kernel
            # bf16 runs compute
            out_x, _ = fwd_bwd(reference, lambda x: x)
            observed["xla_bf16_fwd_rel_err_vs_f32"] = rel_err(out_x,
                                                              out_r)
        fwd_err = rel_err(out_k, out_r)
        bwd_err = max(rel_err(a, r) for a, r in zip(grads_k, grads_r))
        del out_r, grads_r, out_k, grads_k
        if not interpret:
            def bwd_alone(fs, r, gg):
                # the forward is dead code under a vjp whose primal
                # output nobody reads
                return jax.vjp(lambda f: kernel(f, r), fs)[1](gg)[0]

            observed["fwd_ms"] = median_call_ms(jax.jit(kernel), feats,
                                                rois)
            observed["bwd_ms"] = median_call_ms(jax.jit(bwd_alone), feats,
                                                rois, g)
        used = sorted(set(np.asarray(levels).ravel().tolist()))
        emit({"phase": "kernel", "rois_set": roi_set, "head": head,
              "rois": n,
              "out_size": out_size, "dtype": np.dtype(dtype).name,
              "channels": c, "levels_hit": used, "interpret": interpret,
              "fwd_rel_err": fwd_err, "fwd_tol": KERNEL_FWD_TOL,
              "bwd_rel_err": bwd_err, "bwd_tol": KERNEL_BWD_TOL,
              "seconds": round(seconds, 2), **observed})
        if not (fwd_err <= KERNEL_FWD_TOL and bwd_err <= KERNEL_BWD_TOL):
            raise AssertionError(
                f"ROIAlign kernel disagrees with the XLA formulation "
                f"({head} head, {np.dtype(dtype).name}, {roi_set} "
                f"ROIs): fwd "
                f"{fwd_err:.3g}, bwd {bwd_err:.3g}")


# ------------------------------------------------------- train + resume


class CacheCounter:
    """Counts jax's persistent-cache hit/miss monitoring events."""

    def __init__(self):
        import jax

        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def make_config(args, logdir: str, mesh_shape, batch_per_chip: int,
                num_chips: int, total_steps: int):
    """Reset the global config to its defaults (``args.config_defaults``,
    snapshotted by main), apply the smoke's overrides, finalize."""
    from eksml_tpu import config as config_mod

    cfg = config_mod.config
    cfg.freeze(False)
    cfg.from_dict(args.config_defaults)
    overrides = list(TRAIN_OVERRIDES)
    if args.rehearse:
        overrides = ([o for o in overrides
                      if not o.startswith("PREPROC.")]
                     + list(config_mod.SMOKE_OVERRIDES)
                     + ["TRAIN.PRECISION=float32"])
    overrides += [
        f"TRAIN.LOGDIR={logdir}", f"TRAIN.SEED={args.seed}",
        f"TRAIN.BATCH_SIZE_PER_CHIP={batch_per_chip}",
        f"TRAIN.NUM_CHIPS={num_chips}",
        f"TRAIN.STEPS_PER_EPOCH={total_steps}",
        f"TPU.MESH_SHAPE={tuple(mesh_shape)}".replace(" ", ""),
    ]
    cfg.update_args(overrides)
    return config_mod.finalize_configs(is_training=True)


def run_trainer(args, cfg, logdir: str, total_steps: int,
                cache: CacheCounter, inspect=None) -> dict:
    """One ``Trainer(cfg, logdir).fit`` over the synthetic loader, as
    ``eksml_tpu.train.main`` wires it.  ``inspect(trainer, state,
    host_batch)`` runs before the trainer is closed, with the last
    batch the loader produced."""
    import jax
    import numpy as np

    from eksml_tpu.data import DetectionLoader, SyntheticDataset
    from eksml_tpu.train import Trainer

    trainer = Trainer(cfg, logdir)
    obs = {}
    try:
        local_chips = sum(d.process_index == jax.process_index()
                          for d in trainer.mesh.devices.flat)
        records = SyntheticDataset(
            num_images=64, height=cfg.PREPROC.MAX_SIZE,
            width=cfg.PREPROC.MAX_SIZE,
            num_classes=cfg.DATA.NUM_CLASSES, seed=args.seed).records()
        loader = DetectionLoader(
            records, cfg, cfg.TRAIN.BATCH_SIZE_PER_CHIP * local_chips,
            is_training=True, num_hosts=1, host_id=0,
            seed=cfg.TRAIN.SEED, with_masks=cfg.MODE_MASK,
            ledger_dir=logdir, num_slices=int(cfg.TPU.NUM_SLICES))

        # observe (not alter) the trainer's first-step compile window
        inner = trainer._step_fn_with_prediction

        def timed(*a):
            h0, m0, t0 = cache.hits, cache.misses, time.perf_counter()
            try:
                return inner(*a)
            finally:
                obs["step_fn_with_prediction_seconds"] = round(
                    time.perf_counter() - t0, 2)
                obs["train_step_cache_hits"] = cache.hits - h0
                obs["train_step_cache_misses"] = cache.misses - m0

        trainer._step_fn_with_prediction = timed
        batches = loader.batches(None)
        last = {}

        def remembered():
            for last["batch"] in batches:
                yield last["batch"]

        try:
            state = trainer.fit(remembered(), total_steps,
                                data_health=loader.health)
        finally:
            batches.close()
        obs["step"] = int(np.asarray(state.step))
        obs["aot_compile_seconds"] = (
            None if trainer.aot_compile_seconds is None
            else round(trainer.aot_compile_seconds, 2))
        if inspect is not None:
            obs.update(inspect(trainer, state, last["batch"]))
    finally:
        trainer.ckpt.close()
    return obs


def read_metrics(logdir: str) -> list:
    with open(os.path.join(logdir, "metrics.jsonl")) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    return [r for r in rows if "total_loss" in r]


def check_losses(rows, steps) -> list:
    by_step = {int(r["step"]): r for r in rows}
    losses = [by_step[s]["total_loss"] for s in steps]
    if not all(math.isfinite(v) for v in losses):
        raise AssertionError(f"non-finite loss among {losses}")
    return losses


def phase_train(args, device: dict, logdir: str,
                cache: CacheCounter) -> None:
    import jax

    from eksml_tpu._native import bridge_report
    from eksml_tpu.ops.pallas import pallas_roi_align_supported

    n = args.steps
    cfg = make_config(args, logdir, (1, 1), GLOBAL_BATCH, 1, n)
    on_tpu = device["platform"] == "tpu"

    def inspect(trainer, state, host_batch):
        _, compiled = trainer.aot_step
        text = compiled.as_text()
        leaves = jax.tree.leaves(state.params)
        platforms = sorted({d.platform for leaf in leaves
                            for d in leaf.devices()})
        out = {"kernel_call_sites": text.count("tpu_custom_call"),
               "state_platforms": platforms,
               "compiled_hlo_bytes": len(text)}
        mem = compiled.memory_analysis()
        if mem is not None:
            out["compiler_memory_bytes"] = {
                k: int(getattr(mem, k)) for k in (
                    "argument_size_in_bytes", "output_size_in_bytes",
                    "temp_size_in_bytes",
                    "generated_code_size_in_bytes")}
        # steady step time of the trainer's own executable: three more
        # steps on one resident batch, each ended by block_until_ready
        batch = trainer._globalize_batch(host_batch)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            state, metrics = compiled(state, batch)
            jax.block_until_ready(metrics["total_loss"])
            times.append((time.perf_counter() - t0) * 1e3)
        out["steady_step_ms"] = [round(t, 2) for t in times]
        stats = jax.local_devices()[0].memory_stats() or {}
        out["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        out["memory_stats"] = {k: v for k, v in sorted(stats.items())
                               if isinstance(v, (int, float))}
        pred = trainer.prediction
        if pred is not None:
            out["predicted"] = {
                "target": pred["target"],
                "step_time_ms": pred["predicted_step_time_ms"],
                "peak_hbm_bytes": (pred.get("hbm") or {}).get(
                    "peak_hbm_bytes")}
        return out

    obs = run_trainer(args, cfg, logdir, n, cache, inspect)
    rows = read_metrics(logdir)
    losses = check_losses(rows, range(1, n + 1))
    emit({"phase": "train", "model": "maskrcnn-r50-fpn",
          "widths": "smoke" if args.rehearse else "config defaults",
          "max_size": cfg.PREPROC.MAX_SIZE,
          "precision": cfg.TRAIN.PRECISION,
          "batch_per_chip": cfg.TRAIN.BATCH_SIZE_PER_CHIP,
          "remat": bool(cfg.TRAIN.REMAT),
          "roi_kernel_selected": pallas_roi_align_supported(),
          "steps": n, "losses": losses,
          "fit_step_time_ms": [r.get("step_time_ms") for r in rows],
          "compile_cache_dir": args.cache_dir,
          "train_step_cache": ("hit" if obs["train_step_cache_hits"]
                               and not obs["train_step_cache_misses"]
                               else "miss"),
          "native_bridges": bridge_report(), **obs})
    if obs["step"] != n:
        raise AssertionError(f"step counter {obs['step']} != {n}")
    if bool(cfg.TRAIN.REMAT):
        raise AssertionError("TRAIN.REMAT came out True; asked False")
    if on_tpu:
        if obs["kernel_call_sites"] < 1:
            raise AssertionError(
                "compiled train step holds no tpu_custom_call: the "
                "Pallas ROIAlign kernel is not in the program")
        if obs["state_platforms"] != ["tpu"]:
            raise AssertionError(
                f"state lives on {obs['state_platforms']}, not the TPU")
        if not obs["peak_bytes_in_use"]:
            raise AssertionError("memory_stats() gave no peak bytes")


def phase_resume(args, logdir: str, cache: CacheCounter) -> None:
    """A fresh ``Trainer`` on the same logdir restores the checkpoint
    the train phase wrote and takes one more step (donated state +
    Orbax restore on a real device)."""
    n = args.steps
    cfg = make_config(args, logdir, (1, 1), GLOBAL_BATCH, 1, n + 1)
    obs = run_trainer(args, cfg, logdir, n + 1, cache)
    rows = read_metrics(logdir)
    steps = [int(r["step"]) for r in rows]
    loss = check_losses(rows, [n + 1])[0]
    emit({"phase": "resume", "restored_step": n, "steps_logged": steps,
          "loss": loss, **obs})
    if obs["step"] != n + 1:
        raise AssertionError(f"resumed trainer ended at step "
                             f"{obs['step']}, expected {n + 1}")
    if steps != list(range(1, n + 2)):
        raise AssertionError(
            f"resume did not continue from step {n}: logged {steps}")


# ------------------------------------------------------------ four chips


def phase_four_chips(args, device: dict, workdir: str,
                     cache: CacheCounter) -> None:
    """The same global batch and seed through ``Trainer`` on a 1-device
    mesh and on a (4, 1) data-parallel mesh, replicated strategy."""
    import jax

    n = args.steps
    runs = {}
    for name, mesh_shape, per_chip, chips in (
            ("one", (1, 1), GLOBAL_BATCH, 1),
            ("four", (4, 1), GLOBAL_BATCH // 4, 4)):
        logdir = os.path.join(workdir, name)
        cfg = make_config(args, logdir, mesh_shape, per_chip, chips, n)

        def inspect(trainer, state, host_batch):
            _, compiled = trainer.aot_step
            text = compiled.as_text()
            leaves = jax.tree.leaves(state.params)
            param_devs = sorted({len(leaf.devices()) for leaf in leaves})
            replicated = all(leaf.sharding.is_fully_replicated
                             for leaf in leaves)
            batch = trainer._globalize_batch(host_batch)
            shard_devs = sorted({s.device.id for s in
                                 batch["images"].addressable_shards})
            shard_rows = sorted({s.data.shape[0] for s in
                                 batch["images"].addressable_shards})
            return {"mesh": dict(trainer.mesh.shape),
                    "all_reduce_sites": text.count("all-reduce"),
                    "kernel_call_sites": text.count("tpu_custom_call"),
                    "param_device_counts": param_devs,
                    "params_replicated": replicated,
                    "batch_shard_devices": shard_devs,
                    "batch_shard_rows": shard_rows}

        obs = run_trainer(args, cfg, logdir, n, cache, inspect)
        gc.collect()  # the finished trainer's device buffers go now
        obs["losses"] = check_losses(read_metrics(logdir),
                                     range(1, n + 1))
        runs[name] = obs
        emit({"phase": "four_chips", "run": name, **obs})

    one, four = runs["one"], runs["four"]
    rel = [abs(a - b) / max(abs(b), 1e-9)
           for a, b in zip(four["losses"], one["losses"])]
    emit({"phase": "four_chips", "run": "compare", "loss_rel_diff": rel,
          "tol_first": LOSS_TOL_FIRST, "tol_later": LOSS_TOL_LATER})
    if len(four["batch_shard_devices"]) != 4:
        raise AssertionError(
            f"batch shards sit on {four['batch_shard_devices']}, "
            "not four distinct devices")
    if four["param_device_counts"] != [4] or not four["params_replicated"]:
        raise AssertionError(
            f"parameters are not replicated on all four devices: "
            f"{four['param_device_counts']}")
    if four["all_reduce_sites"] < 1:
        raise AssertionError("compiled 4-device step has no all-reduce")
    if four["step"] != n or one["step"] != n:
        raise AssertionError("step counters did not advance")
    if rel[0] > LOSS_TOL_FIRST or any(r > LOSS_TOL_LATER
                                      for r in rel[1:]):
        raise AssertionError(
            f"4-device losses {four['losses']} disagree with the "
            f"1-device run {one['losses']} (rel {rel})")


# ------------------------------------------------------------------ main


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the 1-device vs (4,1)-mesh comparison "
                        "(needs four chips)")
    p.add_argument("--rehearse", action="store_true",
                   help="smoke widths, any platform, interpret-mode "
                        "kernel; reports ok:false and exits "
                        f"{REHEARSAL_EXIT} when every phase passed")
    p.add_argument("--phases", type=lambda v: tuple(v.split(",")),
                   help="one chip: run only these of "
                        f"{','.join(ONE_CHIP_PHASES)} (after device)")
    p.add_argument("--steps", type=int, default=5,
                   help="train steps per trainer run [%(default)s]")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    unknown = set(args.phases or ()) - set(ONE_CHIP_PHASES)
    if unknown:
        p.error(f"--phases: unknown {sorted(unknown)}")
    return args


def main(argv=None) -> int:
    args = parse_args(argv)
    device = None
    phase = "import"
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    t_start = time.perf_counter()
    try:
        # the one compile-cache rule, before the first compile
        from eksml_tpu import config as config_mod
        from eksml_tpu.utils.compile_cache import enable_persistent_cache

        args.cache_dir = enable_persistent_cache()
        args.config_defaults = config_mod.config.to_dict()
        cache = CacheCounter()
        for phase in phases_for(args):
            t0 = time.perf_counter()
            if phase == "device":
                device = phase_device(args)
                check_device(args, device)
            elif phase == "kernel":
                phase_kernel(args)
            elif phase == "train":
                phase_train(args, device, os.path.join(workdir, "run"),
                            cache)
            elif phase == "resume":
                phase_resume(args, os.path.join(workdir, "run"), cache)
            elif phase == "four_chips":
                phase_four_chips(args, device, workdir, cache)
            emit({"phase_done": phase,
                  "seconds": round(time.perf_counter() - t0, 2)})
    except Exception as e:  # noqa: BLE001 — reported, then non-zero
        traceback.print_exc()
        emit(result_line(False, device, phase=phase,
                         error=f"{type(e).__name__}: {e}"[:2000]))
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
    emit({"total_seconds": round(time.perf_counter() - t_start, 2)})
    if args.rehearse:
        emit(result_line(False, device, rehearsal=True,
                         phases=list(phases_for(args))))
        return REHEARSAL_EXIT
    emit(result_line(True, device))
    return 0


if __name__ == "__main__":
    sys.exit(main())
