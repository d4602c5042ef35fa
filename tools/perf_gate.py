"""Hermetic predicted-step-time perf gate — no TPU needed.

Chip measurements are rare, so ``tools/bench_gate.py`` seldom has
anything fresh to gate on.  This tool gates what CAN be produced on
every CI box: AOT-lower the real train step for a named TPU target under
``JAX_PLATFORMS=cpu``, price the compiled HLO with the roofline model
(``eksml_tpu/profiling/predict.py``), and compare the predicted step
time — per component and total — against the banked prediction
baseline.

- **bank**: ``artifacts/perf_pred_<rung>_<strategy>_<precision>.json``
  — one baseline per rung geometry × sharding strategy × precision.
  ``--update-baseline`` (re)banks fresh predictions (run it once when
  a prediction-moving change is INTENDED, and commit the diff).
- **gate**: a fresh prediction regressing more than
  ``--max-regress-pct`` vs its banked baseline FAILs with a
  component-attributed message ("backbone-bwd predicted +34%"), never
  a bare number.  A big component regression hidden by an unrelated
  win fails too (compare_predictions).
- **calibration**: every run reports the model's honesty — one scale
  factor per rung fitted against the banked r5 hardware artifacts
  (``artifacts/roi_ab_r5.json``, ``bench_rung_1344_b4.json``), with
  the cross-rung spread printed as ``model_error_pct``.  A banked
  rung that carries predicted next to measured joins the fit.

The model is lowered at the SMOKE channel widths (config
SMOKE_OVERRIDES) so a CI box compiles each geometry in tens of
seconds; the canvas/batch — what decides program structure and
relative cost — are the real rung geometry.  Absolute milliseconds are
therefore model-scale, not hardware-scale; the gate only ever compares
prediction RATIOS, and the calibration section quantifies how far
ratios can be trusted.

Usage::

    # CI gate (CPU-only, bounded): 2 geometries x 4 strategies
    # (replicated, fsdp, tensor, 2d — the sharded lowerings price
    # their fsdp-/model-axis collectives)
    python tools/perf_gate.py

    # accept an intended prediction change / first-time banking
    python tools/perf_gate.py --update-baseline

    # calibration report only (no lowering — pure artifact math)
    python tools/perf_gate.py --calibrate-only
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from eksml_tpu.fsio import atomic_write_json, atomic_write_text  # noqa: E402

# Rung geometries the predictor lowers (canvas × batch, plus the knobs
# a rung pre-plans — named as the banked ``bench_rung_<name>.json``
# records are, so a measured rung pairs with its prediction by name).
PRED_RUNGS: Dict[str, Dict[str, Any]] = {
    "128_b1": {"image_size": 128, "batch_size": 1},
    "256_b1": {"image_size": 256, "batch_size": 1},
    "512_b1": {"image_size": 512, "batch_size": 1},
    "512_b4": {"image_size": 512, "batch_size": 4},
    "832x1344_b4": {"pad_hw": (832, 1344), "batch_size": 4},
    "1344_b4": {"image_size": 1344, "batch_size": 4},
    "1344_b8_remat": {"image_size": 1344, "batch_size": 8,
                      "remat": True, "param_dtype": "bfloat16"},
    # multi-slice rungs: a slice is internally fsdp x model (the 2D
    # layout), slices exchange only gradients over DCN.  Lowered with
    # the hierarchical exchange and priced BOTH ways from the same
    # HLO — the rung FAILs unless hierarchical is strictly faster
    # than the flat DCN ring (the win this gate exists to gate).
    # "strategies" restricts the plan: a slice axis only means
    # anything composed with a sharded in-slice layout.
    "128_b1_s2": {"image_size": 128, "batch_size": 1,
                  "num_slices": 2, "strategies": ("2d",)},
    "128_b1_s4": {"image_size": 128, "batch_size": 1,
                  "num_slices": 4, "strategies": ("2d",)},
}

#: the CI default: two cheap geometries × every executable strategy
#: plus the two multi-slice rungs (2d-only) — ~10 tiny-model
#: compiles, bounded minutes on one CPU core (the tensor/2d rungs
#: price the model-axis collectives hermetically; the _s2/_s4 rungs
#: price the cross-slice DCN exchange hierarchical-vs-flat)
DEFAULT_RUNGS = "128_b1,256_b1,128_b1_s2,128_b1_s4"
DEFAULT_STRATEGIES = "replicated,fsdp,tensor,2d"

# Serving (bucket, batch) rungs priced by --serve: the PREDICT step
# the serving engine's AOT cache warms (eksml_tpu/serve/engine.py),
# lowered at SMOKE widths like the training rungs — CI gets a
# per-bucket predicted-latency verdict with no hardware.
# Names mirror the serve bucket schedule at smoke geometry.
SERVE_PRED_RUNGS: Dict[str, Dict[str, Any]] = {
    "serve_128x128_b1": {"pad_hw": (128, 128), "batch_size": 1},
    "serve_128x128_b4": {"pad_hw": (128, 128), "batch_size": 4},
}

DEFAULT_SERVE_RUNGS = "serve_128x128_b1,serve_128x128_b4"


def pred_key(rung: str, strategy: str, precision: str) -> str:
    return f"{rung}_{strategy}_{precision}"


def baseline_path(bank_dir: str, key: str) -> str:
    return os.path.join(bank_dir, f"perf_pred_{key}.json")


def _utcnow() -> str:
    return time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime())


def _load_json(path: str) -> Optional[Dict]:
    # ONE loader with the calibration pairing (predict.load_json)
    from eksml_tpu.profiling.predict import load_json

    return load_json(path)


def _rung_config(rung: str, precision: str, config_overrides):
    """Global config → the rung's geometry at SMOKE widths, finalized.

    Mutates the process-global config (the CLI owns the process); tests
    go through the fresh_config fixture instead and call
    predict.lower_train_step directly."""
    from eksml_tpu.config import (SMOKE_OVERRIDES, config,
                                  finalize_configs)

    spec = PRED_RUNGS[rung]
    size = (max(spec["pad_hw"]) if spec.get("pad_hw")
            else spec["image_size"])
    config.freeze(False)
    config.update_args(SMOKE_OVERRIDES)
    config.TRAIN.PRECISION = precision
    config.TRAIN.REMAT = bool(spec.get("remat", False))
    config.TRAIN.PARAM_DTYPE = spec.get("param_dtype", "float32")
    config.TRAIN.BATCH_SIZE_PER_CHIP = spec["batch_size"]
    config.PREPROC.MAX_SIZE = size
    config.PREPROC.TRAIN_SHORT_EDGE_SIZE = (size, size)
    config.update_args(config_overrides or [])
    return finalize_configs(is_training=True)


def axis_widths(mesh_shape: Dict[str, Any]) -> Dict[str, int]:
    """Resolved (fsdp, model) widths of a lowered rung's mesh — the
    verdict-row field that keeps a 2d rung from being confused with
    its 1D siblings in the bank (same rung name, same strategy
    string, different shard widths).  A mesh with a ``slice`` axis
    adds a ``slices`` column; single-slice rows keep the historical
    two-key shape (banked artifacts and their consumers pin it)."""
    widths = {"fsdp": int((mesh_shape or {}).get("fsdp", 1)),
              "model": int((mesh_shape or {}).get("model", 1))}
    slices = int((mesh_shape or {}).get("slice", 1))
    if slices > 1:
        widths["slices"] = slices
    return widths


def row_axis_widths(rec: Dict[str, Any]) -> Optional[Dict[str, int]]:
    """Widths for a verdict row, derived from the ``mesh_shape`` the
    record already banks (no second copy to drift) — None for serve
    predict records (no training mesh) and pre-mesh_shape banks."""
    if rec.get("kind") == "predict" or "mesh_shape" not in rec:
        return None
    return axis_widths(rec["mesh_shape"])


def predict_rung(rung: str, strategy: str, precision: str,
                 target: str, fsdp_axis: int = 2, model_axis: int = 2,
                 config_overrides=None) -> Dict[str, Any]:
    """Lower one rung × strategy and price it for ``target`` —
    the fresh-prediction record the gate compares and banks."""
    from eksml_tpu.profiling import predict as P

    spec = PRED_RUNGS[rung]
    cfg = _rung_config(rung, precision, config_overrides)
    # cfg wins over the flag: a --config TRAIN.PRECISION override
    # changed the lowered program, and pricing/keying it as the flag
    # precision would overwrite the wrong baseline (the lint's
    # config-drift rule)
    precision = str(cfg.TRAIN.PRECISION)
    num_slices = int(spec.get("num_slices", 1))
    exchange = "hierarchical" if num_slices > 1 else "flat"
    t0 = time.time()
    hlo, meta = P.lower_train_step(
        cfg, batch_size=spec["batch_size"],
        image_size=spec.get("image_size"),
        pad_hw=spec.get("pad_hw"), strategy=strategy,
        fsdp_axis=fsdp_axis, model_axis=model_axis,
        num_slices=num_slices, exchange=exchange)
    slice_devices = (meta["slice_devices"] if num_slices > 1
                     else None)
    pred = P.predict_from_hlo(hlo, target=target, precision=precision,
                              comm_sizes=meta["comm_sizes"],
                              slice_devices=slice_devices,
                              exchange=exchange,
                              input_groups=meta["input_groups"])
    rec = dict(pred)
    rec.update({
        "rung": rung,
        "key": pred_key(rung, strategy, precision),
        "strategy": strategy,
        "geometry": {k: meta[k] for k in ("batch_size", "image_size",
                                          "remat", "param_dtype")},
        "mesh_shape": meta["mesh_shape"],
        # the widths disclaimer: absolute ms are model-scale (smoke
        # channel widths unless the caller overrode them) — gate on
        # ratios, read the calibration section for trust bounds
        "model_widths": "smoke",
        "lower_seconds": round(time.time() - t0, 1),
        "banked_at": _utcnow(),
    })
    if num_slices > 1:
        # price the SAME compiled program as one flat ring at the
        # slowest link — the counterfactual the hierarchical exchange
        # is gated against (it must be strictly faster, gate_one)
        flat = P.predict_from_hlo(
            hlo, target=target, precision=precision,
            comm_sizes=meta["comm_sizes"],
            slice_devices=slice_devices, exchange="flat")
        rec.update({
            "num_slices": num_slices,
            "slice_devices": meta["slice_devices"],
            "exchange": exchange,
            "flat_predicted_step_time_ms":
                flat["predicted_step_time_ms"],
        })
    return rec


def _serve_rung_config(rung: str, precision: str, config_overrides):
    """Global config → the serve rung's inference geometry at SMOKE
    widths, finalized for inference (``is_training=False`` — the
    server's own finalize call)."""
    from eksml_tpu.config import (SMOKE_OVERRIDES, config,
                                  finalize_configs)

    spec = SERVE_PRED_RUNGS[rung]
    size = max(spec["pad_hw"])
    config.freeze(False)
    config.update_args(SMOKE_OVERRIDES)
    config.TRAIN.PRECISION = precision
    config.PREPROC.MAX_SIZE = size
    config.PREPROC.TEST_SHORT_EDGE_SIZE = min(spec["pad_hw"])
    config.TEST.EVAL_BATCH_SIZE = spec["batch_size"]
    config.update_args(config_overrides or [])
    return finalize_configs(is_training=False)


def predict_serve_rung(rung: str, precision: str, target: str,
                       config_overrides=None) -> Dict[str, Any]:
    """Lower one serving (bucket, batch) rung's PREDICT step and
    price it for ``target`` — the per-bucket predicted-latency record
    the --serve gate compares and banks."""
    from eksml_tpu.profiling import predict as P

    spec = SERVE_PRED_RUNGS[rung]
    cfg = _serve_rung_config(rung, precision, config_overrides)
    # cfg wins over the flag (the lint's config-drift rule): a
    # --config TRAIN.PRECISION override changed the lowered program
    precision = str(cfg.TRAIN.PRECISION)
    t0 = time.time()
    hlo, meta = P.lower_predict_step(
        cfg, batch_size=spec["batch_size"], pad_hw=spec["pad_hw"])
    pred = P.predict_from_hlo(hlo, target=target, precision=precision,
                              comm_sizes=meta["comm_sizes"],
                              input_groups=meta["input_groups"])
    rec = dict(pred)
    rec.update({
        "rung": rung,
        "key": f"{rung}_{precision}",
        "kind": "predict",
        "geometry": {k: meta[k] for k in ("batch_size", "pad_hw",
                                          "device_normalize")},
        # the serving SLO framing of the same number: predicted
        # device time for ONE dispatched (bucket, batch) executable
        "predicted_latency_ms": pred["predicted_step_time_ms"],
        "predicted_latency_per_image_ms": round(
            pred["predicted_step_time_ms"] / spec["batch_size"], 4),
        "model_widths": "smoke",
        "lower_seconds": round(time.time() - t0, 1),
        "banked_at": _utcnow(),
    })
    return rec


def hbm_columns(rec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """The memory verdict columns a prediction record contributes to
    its gate row — None for pre-observatory records (no ``hbm``)."""
    hbm = rec.get("hbm") or {}
    if not hbm.get("peak_hbm_bytes"):
        return None
    cap = hbm.get("capacity") or {}
    return {
        "peak_hbm_bytes": hbm["peak_hbm_bytes"],
        "headroom_bytes": cap.get("headroom_bytes"),
        "utilization_pct": cap.get("utilization_pct"),
        "fits": bool(cap.get("fits", True)),
    }


def hbm_regression_error(fresh: Dict, base: Dict,
                         max_regress_pct: float
                         ) -> Optional[str]:
    """Peak-HBM regression beyond the bound → the FAIL message naming
    the component whose live-at-peak bytes grew most; None when in
    bounds or either record predates the observatory."""
    fh = fresh.get("hbm") or {}
    bh = base.get("hbm") or {}
    fp, bp = fh.get("peak_hbm_bytes"), bh.get("peak_hbm_bytes")
    if not fp or not bp:
        return None
    pct = 100.0 * (float(fp) / float(bp) - 1.0)
    if pct <= max_regress_pct:
        return None
    fc = fh.get("live_at_peak_by_component") or {}
    bc = bh.get("live_at_peak_by_component") or {}
    worst = max(set(fc) | set(bc) or {"other"},
                key=lambda k: fc.get(k, 0) - bc.get(k, 0))
    return (f"predicted peak HBM regressed +{pct:.1f}% "
            f"({bp} -> {fp} bytes, bound {max_regress_pct}%); worst "
            f"component {worst}: live-at-peak {bc.get(worst, 0)} -> "
            f"{fc.get(worst, 0)} bytes")


def hbm_cross_rows(fresh_records: List[Dict[str, Any]]
                   ) -> List[Dict[str, Any]]:
    """The sharding cross-gate: at the same rung geometry, the 2d
    lowering's predicted peak HBM must be STRICTLY below replicated's
    (params+optimizer+grads divide over fsdp x model while per-device
    activations match — PR 15's measured 19.2% storage claim as a
    hermetic invariant).  One verdict row per rung where this run
    lowered both strategies."""
    by_rung: Dict[str, Dict[str, Dict]] = {}
    for rec in fresh_records:
        rung, strat = rec.get("rung"), rec.get("strategy")
        if rung and strat in ("replicated", "2d"):
            by_rung.setdefault(rung, {})[strat] = rec
    rows: List[Dict[str, Any]] = []
    for rung in sorted(by_rung):
        pair = by_rung[rung]
        if "replicated" not in pair or "2d" not in pair:
            continue
        rp = ((pair["replicated"].get("hbm") or {})
              .get("peak_hbm_bytes"))
        dp = ((pair["2d"].get("hbm") or {}).get("peak_hbm_bytes"))
        if not rp or not dp:
            continue
        row: Dict[str, Any] = {
            "key": f"{rung}_hbm_cross_strategy",
            "check": "2d predicted peak strictly below replicated",
            "replicated_peak_hbm_bytes": rp,
            "2d_peak_hbm_bytes": dp,
            "peak_ratio_pct": round(100.0 * dp / rp, 2),
            "gate": "PASS" if dp < rp else "FAIL",
        }
        if row["gate"] == "FAIL":
            row["error"] = (
                f"at rung {rung} the 2d lowering's predicted peak HBM "
                f"({dp} bytes) is not strictly below replicated's "
                f"({rp} bytes) — sharding stopped paying for its "
                f"per-device memory plan")
        rows.append(row)
    return rows


def gate_one(fresh: Dict, bank_dir: str, max_regress_pct: float,
             allow_missing_baseline: bool) -> Dict[str, Any]:
    """Fresh prediction vs its banked baseline → one result row."""
    from eksml_tpu.profiling.memory import top_components
    from eksml_tpu.profiling.predict import compare_predictions

    path = baseline_path(bank_dir, fresh["key"])
    base = _load_json(path)
    row: Dict[str, Any] = {
        "key": fresh["key"],
        "predicted_step_time_ms": fresh["predicted_step_time_ms"],
        "sections_ms": fresh["sections_ms"],
        "baseline_path": os.path.relpath(path, REPO),
    }
    if fresh.get("comms_ms") is not None:
        # the per-link communication columns (ISSUE 19): predicted
        # ici/dcn/exposed ms ride every verdict row so a comms move
        # is visible at the link level, not just inside the total
        row["comms_ms"] = fresh["comms_ms"]
    widths = row_axis_widths(fresh)
    if widths is not None:
        # resolved shard widths ride every verdict row: a 2d rung and
        # its 1D siblings share rung names, and the bank must never
        # let one masquerade as the other
        row["axis_widths"] = widths
    flat_ms = fresh.get("flat_predicted_step_time_ms")
    if flat_ms is not None:
        # the multi-slice rung's reason to exist: under the banked
        # DCN calibration the hierarchical exchange must be strictly
        # faster than pricing the same program as one flat ring at
        # the slowest link — equal-or-slower means the three-phase
        # schedule is not paying for itself
        row["flat_predicted_step_time_ms"] = flat_ms
        if fresh["predicted_step_time_ms"] >= flat_ms:
            row["gate"] = "FAIL"
            row["error"] = (
                f"hierarchical exchange predicted "
                f"{fresh['predicted_step_time_ms']}ms is not "
                f"strictly faster than the flat DCN ring "
                f"({flat_ms}ms) at num_slices="
                f"{fresh.get('num_slices')} — the exchange pricing "
                f"or the staged collectives regressed")
            return row
    mem = hbm_columns(fresh)
    if mem is not None:
        # the memory verdict columns (ISSUE 20) ride every row; the
        # capacity half needs no baseline — a rung that does not fit
        # the chip FAILs naming its top live-at-peak components
        row["hbm"] = mem
        if not mem["fits"]:
            cap = (fresh["hbm"].get("capacity") or {})
            row["gate"] = "FAIL"
            row["error"] = row["hbm"]["error"] = (
                f"predicted peak HBM {mem['peak_hbm_bytes']} bytes "
                f"exceeds {fresh.get('target', '?')} capacity "
                f"{cap.get('hbm_bytes')} bytes — top live-at-peak: "
                f"{top_components(fresh['hbm'])}")
            return row
    if base is not None:
        base_widths = row_axis_widths(base)
        if (widths is not None and base_widths is not None
                and widths != base_widths):
            # pred_key excludes the widths, so a lowering at other
            # --fsdp-axis/--model-axis values lands under the SAME
            # baseline file — comparing their times would be a bogus
            # verdict about nothing; fail naming both layouts
            row["gate"] = "FAIL"
            row["baseline_axis_widths"] = base_widths
            row["error"] = (
                f"axis widths mismatch: fresh lowering is "
                f"fsdp={widths['fsdp']} x model={widths['model']} but "
                f"the banked baseline is fsdp={base_widths['fsdp']} x "
                f"model={base_widths['model']} — pass the matching "
                f"--fsdp-axis/--model-axis, or re-bank with "
                f"--update-baseline if the new widths are intended")
            return row
    if base is None:
        row["gate"] = "PASS" if allow_missing_baseline else "FAIL"
        row["error"] = (
            f"no banked baseline at {path} — run tools/perf_gate.py "
            "--update-baseline once and commit the artifact"
        ) if not allow_missing_baseline else None
        row["note"] = "missing baseline"
        return row
    ok, verdict = compare_predictions(fresh, base,
                                      max_regress_pct=max_regress_pct)
    row["gate"] = "PASS" if ok else "FAIL"
    row["verdict"] = verdict
    if not ok:
        row["error"] = verdict.get("error")
    if mem is not None and (base.get("hbm") or {}).get(
            "peak_hbm_bytes"):
        # the regression half of the memory verdict: baseline peak +
        # delta always ride the columns; beyond the bound the row
        # FAILs naming the component whose live-at-peak bytes grew
        # most (time error — the pinned message — stays primary when
        # both regress)
        base_peak = base["hbm"]["peak_hbm_bytes"]
        row["hbm"]["baseline_peak_hbm_bytes"] = base_peak
        row["hbm"]["peak_regress_pct"] = round(
            100.0 * (float(mem["peak_hbm_bytes"]) / float(base_peak)
                     - 1.0), 2)
        mem_err = hbm_regression_error(fresh, base, max_regress_pct)
        if mem_err:
            row["gate"] = "FAIL"
            row["hbm"]["error"] = mem_err
            if not row.get("error"):
                row["error"] = mem_err
    return row


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rungs", default=DEFAULT_RUNGS,
                   help=f"comma list of {sorted(PRED_RUNGS)} "
                        f"[%(default)s]")
    p.add_argument("--strategies", default=DEFAULT_STRATEGIES,
                   help="comma list of sharding strategies to lower "
                        "(replicated, fsdp, tensor, 2d) "
                        "[%(default)s]")
    p.add_argument("--target", default="v5e",
                   help="chip spec the roofline prices for "
                        "(predict.CHIP_SPECS) [%(default)s]")
    p.add_argument("--precision", default="bfloat16",
                   choices=["bfloat16", "float32"])
    p.add_argument("--fsdp-axis", type=int, default=2,
                   help="fsdp axis size for the fsdp/2d lowerings "
                        "(host-platform virtual devices) [%(default)s]")
    p.add_argument("--model-axis", type=int, default=2,
                   help="model axis size for the tensor/2d lowerings "
                        "(host-platform virtual devices) [%(default)s]")
    p.add_argument("--bank-dir",
                   default=os.path.join(REPO, "artifacts"),
                   help="where perf_pred_*.json baselines live")
    p.add_argument("--fresh-dir", default=None,
                   help="also write fresh predictions here (e.g. for "
                        "bench_gate --predicted); default: only the "
                        "verdict JSON carries them")
    p.add_argument("--max-regress-pct", type=float, default=10.0)
    p.add_argument("--update-baseline", action="store_true",
                   help="(re)bank fresh predictions as the baseline "
                        "instead of gating against it")
    p.add_argument("--allow-missing-baseline", action="store_true")
    p.add_argument("--calibrate-only", action="store_true",
                   help="skip lowering; print the calibration report "
                        "from banked artifacts (pure JSON math)")
    p.add_argument("--serve", action="store_true",
                   help="gate the SERVING predict step instead of the "
                        "train step: lower each (bucket, batch) rung "
                        "of the serve engine's AOT cache and price "
                        "its latency (perf_pred_serve_* baselines)")
    p.add_argument("--serve-rungs", default=DEFAULT_SERVE_RUNGS,
                   help=f"comma list of {sorted(SERVE_PRED_RUNGS)} "
                        f"[%(default)s]")
    p.add_argument("--out", default=None,
                   help="write the verdict JSON here too")
    p.add_argument("--config", nargs="*", default=[],
                   help="KEY=VALUE config overrides applied on top of "
                        "the rung geometry (synthetic-regression "
                        "probes, width experiments)")
    args = p.parse_args(argv)

    # hermetic by construction: this tool only compiles — it must
    # never touch a TPU backend, even on a TPU host.  The environment
    # is set before jax is imported (the fsdp lowering needs >=2
    # host-platform devices and XLA reads the flag at backend init).
    # --calibrate-only never compiles, so it skips the jax import
    # entirely (it is pure JSON math).
    if not args.calibrate_only:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        flags = os.environ.get("XLA_FLAGS", "")
        if "xla_force_host_platform_device_count" not in flags:
            # the 2d lowering shards over fsdp x model jointly — the
            # host platform must carry the axis PRODUCT, times the
            # widest slice count any requested rung lowers at
            max_slices = max(
                [1] + [int(PRED_RUNGS[r.strip()].get("num_slices", 1))
                       for r in args.rungs.split(",")
                       if r.strip() in PRED_RUNGS])
            n_virtual = max(2, args.fsdp_axis, args.model_axis,
                            args.fsdp_axis * args.model_axis
                            * max_slices)
            os.environ["XLA_FLAGS"] = (
                flags + f" --xla_force_host_platform_device_count="
                        f"{n_virtual}").strip()
    from eksml_tpu.profiling.predict import calibrate, calibration_points

    verdict: Dict[str, Any] = {
        "target": args.target,
        "precision": args.precision,
        "max_regress_pct": args.max_regress_pct,
        "model_widths": "smoke",
        "results": [],
    }

    ok = True
    run_precision = args.precision
    if not args.calibrate_only:
        if args.serve:
            verdict["mode"] = "serve"
            rungs = [r.strip() for r in args.serve_rungs.split(",")
                     if r.strip()]
            bad = [r for r in rungs if r not in SERVE_PRED_RUNGS]
            if bad:
                p.error(f"unknown serve rung(s) {bad}; known: "
                        f"{sorted(SERVE_PRED_RUNGS)}")
            # one (rung,) pseudo-strategy axis: the predict program
            # has no sharding strategy — serving is per-replica
            plan = [(rung, None) for rung in rungs]
        else:
            rungs = [r.strip() for r in args.rungs.split(",")
                     if r.strip()]
            strategies = [s.strip() for s in args.strategies.split(",")
                          if s.strip()]
            bad = [r for r in rungs if r not in PRED_RUNGS]
            if bad:
                p.error(f"unknown rung(s) {bad}; known: "
                        f"{sorted(PRED_RUNGS)}")
            # a rung may restrict its strategy axis (the multi-slice
            # rungs only mean anything over a sharded in-slice
            # layout) — absent the key, every requested strategy runs
            plan = [(rung, strategy) for rung in rungs
                    for strategy in strategies
                    if strategy in PRED_RUNGS[rung].get("strategies",
                                                        strategies)]
        fresh_records: List[Dict[str, Any]] = []
        for rung, strategy in plan:
            print(f"perf_gate: lowering {rung}"
                  + (f" x {strategy}" if strategy else " (serve)")
                  + " ...", file=sys.stderr)
            if strategy is None:
                fresh = predict_serve_rung(
                    rung, args.precision, args.target,
                    config_overrides=args.config)
            else:
                fresh = predict_rung(
                    rung, strategy, args.precision, args.target,
                    fsdp_axis=args.fsdp_axis,
                    model_axis=args.model_axis,
                    config_overrides=args.config)
            # the record's key, NOT pred_key(..., args.precision):
            # a --config TRAIN.PRECISION override re-keyed the
            # record, and writing it under the flag's key would
            # overwrite the wrong baseline file
            key = fresh["key"]
            fresh_records.append(fresh)
            run_precision = fresh["precision"]
            print(f"perf_gate: {key}: predicted "
                  f"{fresh['predicted_step_time_ms']}ms "
                  f"(lowered in {fresh['lower_seconds']}s)",
                  file=sys.stderr)
            if args.fresh_dir:
                os.makedirs(args.fresh_dir, exist_ok=True)
                # atomic: bench_gate --predicted may poll this
                # dir while we lower the next rung
                atomic_write_json(os.path.join(
                    args.fresh_dir, f"perf_pred_{key}.json"),
                    fresh)
            if args.update_baseline:
                os.makedirs(args.bank_dir, exist_ok=True)
                path = baseline_path(args.bank_dir, key)
                atomic_write_json(path, fresh)
                banked_row = {
                    "key": key, "gate": "BANKED",
                    "predicted_step_time_ms":
                        fresh["predicted_step_time_ms"],
                    "sections_ms": fresh["sections_ms"],
                    "baseline_path": os.path.relpath(path, REPO)}
                if fresh.get("comms_ms") is not None:
                    banked_row["comms_ms"] = fresh["comms_ms"]
                widths = row_axis_widths(fresh)
                if widths is not None:
                    banked_row["axis_widths"] = widths
                if "flat_predicted_step_time_ms" in fresh:
                    banked_row["flat_predicted_step_time_ms"] = (
                        fresh["flat_predicted_step_time_ms"])
                mem = hbm_columns(fresh)
                if mem is not None:
                    banked_row["hbm"] = mem
                verdict["results"].append(banked_row)
            else:
                row = gate_one(fresh, args.bank_dir,
                               args.max_regress_pct,
                               args.allow_missing_baseline)
                ok = ok and row["gate"] != "FAIL"
                verdict["results"].append(row)
        if not args.serve:
            # the sharding memory cross-gate (2d strictly below
            # replicated at the same rung) runs in BOTH modes —
            # --update-baseline must never bank a violating pair
            for row in hbm_cross_rows(fresh_records):
                ok = ok and row["gate"] != "FAIL"
                verdict["results"].append(row)

    # the honesty check rides every run: how far can the model's
    # ratios be trusted, per the banked hardware evidence.
    # run_precision, not the flag: a --config TRAIN.PRECISION
    # override re-keyed the records, and the header/calibration must
    # describe the precision that was actually lowered
    verdict["precision"] = run_precision
    verdict["calibration"] = calibrate(
        calibration_points(args.bank_dir, precision=run_precision))

    verdict["gate"] = "PASS" if ok else "FAIL"
    payload = json.dumps(verdict, indent=1)
    print(payload)
    if args.out:
        atomic_write_text(args.out, payload)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
