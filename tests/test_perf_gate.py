"""The hermetic predicted-step-time gate (ISSUE 7).

Three layers, cheapest first:
- roofline math + comparison/calibration logic on hand-rolled HLO and
  synthetic prediction records (no jax, milliseconds);
- the committed calibration evidence: the model fitted against the
  REAL banked r5 hardware artifacts, with the reported model error
  pinned — regenerating the prediction bank with a drifted model
  fails here rather than silently shipping a different honesty claim;
- one real CPU lowering of the smoke-width train step (the same
  program tools/perf_gate.py gates on every CI round), plus
  slow-marked fsdp/synthetic-regression drives for the chaos rung.
"""

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from eksml_tpu.profiling import predict as P
from tools import bench_gate, perf_gate

# ---- chip specs ------------------------------------------------------


def test_chip_spec_lookup():
    spec = P.chip_spec("v5e")
    assert spec["peak_flops"]["bfloat16"] == 197e12
    assert spec["hbm_bytes_per_sec"] > 0
    assert spec["ici_bytes_per_sec"] > 0
    with pytest.raises(ValueError) as e:
        P.chip_spec("v99")
    assert "v5e" in str(e.value)  # the error names the valid targets
    assert P.target_for_device_kind("TPU v5 lite") == "v5e"
    for unknown in ("cpu", None, "TPU v9"):
        with pytest.raises(ValueError, match="no chip spec"):
            P.target_for_device_kind(unknown)


# ---- roofline on a hand-rolled module --------------------------------

HLO_FIXTURE = """\
HloModule jit_step, entry_computation_layout={()->f32[8]{0}}

ENTRY %main.9 (Arg_0.1: f32[1024,1024]) -> f32[1024,1024] {
  %Arg_0.1 = f32[1024,1024]{1,0} parameter(0)
  %convolution.2 = f32[1024,1024]{1,0} convolution(f32[1024,1024]{1,0} %Arg_0.1, f32[1024,1024]{1,0} %Arg_0.1), window={size=1x1}, dim_labels=bf01_oi01->bf01, metadata={op_name="jit(step)/jvp(MaskRCNN)/backbone/group0/conv"}
  %all-reduce.3 = f32[1024,1024]{1,0} all-reduce(f32[1024,1024]{1,0} %convolution.2), replica_groups={}, to_apply=%add.1
  %multiply.4 = f32[1024,1024]{1,0} multiply(f32[1024,1024]{1,0} %all-reduce.3, f32[1024,1024]{1,0} %all-reduce.3), metadata={op_name="jit(step)/optimizer/mul"}
  ROOT %copy.8 = f32[1024,1024]{1,0} copy(f32[1024,1024]{1,0} %multiply.4), metadata={op_name="jit(step)/optimizer/copy"}
}
"""


def test_predict_from_hlo_sections_and_comm_scaling():
    one = P.predict_from_hlo(HLO_FIXTURE, target="v5e",
                             precision="float32",
                             comm_sizes={"all-reduce": 1})
    two = P.predict_from_hlo(HLO_FIXTURE, target="v5e",
                             precision="float32",
                             comm_sizes={"all-reduce": 2})
    # structure: named components, sections sum to the total
    assert set(one["components_ms"]) >= {"backbone", "allreduce",
                                         "optimizer"}
    for pred in (one, two):
        assert pred["predicted_step_time_ms"] > 0
        # sections are rounded independently of the total: 4dp each
        assert (pytest.approx(pred["predicted_step_time_ms"],
                              abs=1e-3)
                == sum(pred["sections_ms"].values()))
    # the comms term scales with the participant count: at k=1 a ring
    # moves nothing, at k=2 the all-reduce pays its payload over ICI
    assert (two["sections_ms"]["comms"]
            > one["sections_ms"]["comms"])
    assert two["predicted_step_time_ms"] > one["predicted_step_time_ms"]
    # component_costs separates link traffic from HBM traffic
    costs = one["component_costs"]
    assert costs["allreduce"]["collective_bytes"] > 0
    assert costs["backbone"]["flops"] > 0
    # determinism: the same HLO prices identically (the PASS-on-rerun
    # half of the gate's contract)
    again = P.predict_from_hlo(HLO_FIXTURE, target="v5e",
                               precision="float32",
                               comm_sizes={"all-reduce": 1})
    assert again == one


def test_predict_precision_picks_peak():
    # the conv is flop-bound at these shapes: halving peak flops
    # (float32 MXU rate) must raise the predicted time
    bf16 = P.predict_from_hlo(HLO_FIXTURE, precision="bfloat16",
                              comm_sizes={"all-reduce": 1})
    f32 = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                             comm_sizes={"all-reduce": 1})
    assert (f32["components_ms"]["backbone"]
            > bf16["components_ms"]["backbone"])


def test_async_collective_opcode_coverage():
    """Every collective family's async halves are covered: the -start
    is priced as link traffic, the -done is structural (pricing its
    full output shape would double every async collective)."""
    from eksml_tpu.profiling import attribution as A

    for fam in ("all-reduce", "all-gather", "reduce-scatter",
                "collective-permute", "all-to-all"):
        assert A.is_collective_opcode(fam), fam
        assert A.is_collective_opcode(fam + "-start"), fam
        assert fam + "-done" in A._CONTAINER_OPS, fam


def test_dcn_bound_collective_pricing():
    """A ring wider than one slice rides the DCN NIC: the same
    collective prices slower than the ICI-bound single-slice case."""
    ici = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                             comm_sizes={"all-reduce": 4})
    dcn = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                             comm_sizes={"all-reduce": 4},
                             slice_devices=2)
    assert (dcn["sections_ms"]["comms"] > ici["sections_ms"]["comms"])


def test_predict_for_compiled_single_entry_point():
    """The one pricing path trainer and bench share: target from the
    device kind, comm sizes from the mesh, and DCN once the ring spans
    more devices than one slice holds."""
    one_slice = P.predict_for_compiled(
        HLO_FIXTURE, device_kind="TPU v5 lite",
        mesh_shape={"data": 4, "fsdp": 1, "model": 1},
        precision="float32", num_slices=1)
    assert one_slice["target"] == "v5e"
    assert one_slice["comm_sizes"]["all-reduce"] == 4
    # 2 slices x 2 devices: the 4-wide all-reduce crosses the slice
    # boundary and prices against the DCN NIC
    two_slice = P.predict_for_compiled(
        HLO_FIXTURE, device_kind="TPU v5e",
        mesh_shape={"data": 4, "fsdp": 1, "model": 1},
        precision="float32", num_slices=2)
    assert two_slice["target"] == "v5e"
    assert (two_slice["sections_ms"]["comms"]
            > one_slice["sections_ms"]["comms"])


@pytest.mark.parametrize("kind", ["cpu", None, "TPU v9 (unreleased)"])
def test_predict_for_compiled_unknown_device_kind_is_an_error(kind):
    """A program is priced for the chip it ran on or not at all: an
    unknown ``device_kind`` (a CPU included) raises instead of
    borrowing the v5e row."""
    with pytest.raises(ValueError, match="no chip spec"):
        P.predict_for_compiled(HLO_FIXTURE, device_kind=kind,
                               mesh_shape={"data": 1},
                               precision="bfloat16")


def test_comm_sizes_for_mesh():
    sizes = P.comm_sizes_for_mesh({"data": 4, "fsdp": 2, "model": 1})
    assert sizes["all-gather"] == 2
    assert sizes["reduce-scatter"] == 2
    assert sizes["all-reduce"] == 8
    # no mesh → single device → every ring factor degenerates to 0
    empty = P.comm_sizes_for_mesh({})
    assert empty["all-reduce"] == 1 and empty["all-gather"] == 1
    # model-axis collectives (ISSUE 15): the layout moves ride the
    # STORAGE axes (fsdp × model), the gradient all-reduce rides every
    # replica (batch rows span all three axes)
    tensor = P.comm_sizes_for_mesh({"data": 4, "model": 2})
    assert tensor["all-gather"] == 2
    assert tensor["reduce-scatter"] == 2
    assert tensor["all-reduce"] == 8
    twod = P.comm_sizes_for_mesh({"data": 1, "fsdp": 4, "model": 2})
    assert twod["all-gather"] == 8
    assert twod["reduce-scatter"] == 8
    assert twod["all-reduce"] == 8


# ---- comparison (the gate's FAIL logic) ------------------------------


def _pred(total, components):
    return {"predicted_step_time_ms": total,
            "components_ms": dict(components),
            "sections_ms": {}}


def test_compare_predictions_pass_and_total_regression():
    base = _pred(100.0, {"backbone": 60.0, "roi-bwd": 30.0,
                         "optimizer": 10.0})
    ok, v = P.compare_predictions(base, base, max_regress_pct=10.0)
    assert ok and v["total_regress_pct"] == 0.0
    fresh = _pred(125.0, {"backbone": 60.0, "roi-bwd": 55.0,
                          "optimizer": 10.0})
    ok, v = P.compare_predictions(fresh, base, max_regress_pct=10.0)
    assert not ok
    # the FAIL is component-attributed, never a bare number
    assert "roi-bwd" in v["error"] and "+83.3%" in v["error"]
    assert v["total_regress_pct"] == 25.0


def test_compare_predictions_masked_component_regression():
    """A big component regressing behind an unrelated win must fail:
    total +4% but roi-bwd +66% is a real regression a bare total
    would wave through."""
    base = _pred(100.0, {"backbone": 60.0, "roi-bwd": 30.0,
                         "optimizer": 10.0})
    fresh = _pred(104.0, {"backbone": 44.0, "roi-bwd": 50.0,
                          "optimizer": 10.0})
    ok, v = P.compare_predictions(fresh, base, max_regress_pct=10.0)
    assert not ok and "roi-bwd" in v["error"]
    assert "masked" in v["error"]


def test_compare_predictions_new_component_masked():
    """A brand-new ≥5%-share component has no baseline ratio, so the
    2x-bound check can't see it — it must still fail as a masked
    regression when the total hides it."""
    base = _pred(100.0, {"a": 50.0, "b": 50.0})
    fresh = _pred(99.0, {"a": 40.0, "b": 50.0, "new-comp": 9.0})
    ok, v = P.compare_predictions(fresh, base, max_regress_pct=10.0)
    assert not ok and "new-comp" in v["error"]
    assert "masked" in v["error"]
    # a sub-share new component stays advisory
    tiny = _pred(99.0, {"a": 45.0, "b": 50.0, "new-comp": 4.0})
    ok, _ = P.compare_predictions(tiny, base, max_regress_pct=10.0)
    assert ok


def test_compare_predictions_exploding_small_component():
    """A component with a TINY baseline exploding to a real share must
    fail even when the total hides it — the share test judges by
    max(baseline, fresh), not the baseline alone."""
    base = _pred(100.0, {"a": 92.0, "comms": 0.5, "opt": 7.5})
    fresh = _pred(100.5, {"a": 84.5, "comms": 8.5, "opt": 7.5})
    ok, v = P.compare_predictions(fresh, base, max_regress_pct=10.0)
    assert not ok and "comms" in v["error"]
    assert "masked" in v["error"]


def test_compare_predictions_rejects_zero_baseline():
    ok, v = P.compare_predictions(_pred(10.0, {}), _pred(0.0, {}),
                                  max_regress_pct=10.0)
    assert not ok and "rebank" in v["error"]


# ---- calibration math ------------------------------------------------


def test_calibrate_consistent_scales_mean_zero_error():
    pts = [{"rung": "a", "measured_ms": 200.0, "predicted_ms": 2.0,
            "measured_source": "x"},
           {"rung": "b", "measured_ms": 400.0, "predicted_ms": 4.0,
            "measured_source": "y"}]
    cal = P.calibrate(pts)
    assert cal["scale"] == 100.0
    assert cal["model_error_pct"] == 0.0


def test_calibrate_reports_spread_as_model_error():
    pts = [{"rung": "a", "measured_ms": 100.0, "predicted_ms": 1.0,
            "measured_source": "x"},
           {"rung": "b", "measured_ms": 121.0, "predicted_ms": 1.0,
            "measured_source": "y"}]
    cal = P.calibrate(pts)
    # geomean scale = 110.0, each point deviates ~+-10%
    assert cal["scale"] == 110.0
    assert cal["model_error_pct"] == 10.0
    assert len(cal["points"]) == 2
    empty = P.calibrate([])
    assert empty["model_error_pct"] is None and "note" in empty


def test_calibrate_fits_width_groups_separately():
    """Smoke-width banked predictions and measured-width embedded
    predictions carry a known channel-width scale gap — each group
    gets its own fit, and model_error_pct reports only within-group
    spread (the gap must never masquerade as model error)."""
    pts = [{"rung": "a", "measured_ms": 200.0, "predicted_ms": 2.0,
            "measured_source": "x", "fit_group": "smoke"},
           {"rung": "b", "measured_ms": 400.0, "predicted_ms": 4.0,
            "measured_source": "y", "fit_group": "smoke"},
           {"rung": "a", "measured_ms": 100.0, "predicted_ms": 95.0,
            "measured_source": "z", "fit_group": "measured"}]
    cal = P.calibrate(pts)
    assert cal["scale"] == 100.0  # the smoke-bank fit, unpolluted
    assert cal["scales"]["measured"] == pytest.approx(1.05, abs=0.01)
    assert cal["model_error_pct"] == 0.0  # within-group only
    assert {p["fit_group"] for p in cal["points"]} == {"smoke",
                                                       "measured"}


def test_update_baseline_writes_under_record_key(tmp_path,
                                                 monkeypatch):
    """--update-baseline banks under the RECORD's key (cfg-derived
    precision), never the --precision flag's: a --config
    TRAIN.PRECISION probe must not overwrite the other precision's
    baseline file."""
    rec = _pred(50.0, {"backbone": 50.0})
    rec["key"] = "128_b1_replicated_float32"
    rec["precision"] = "float32"
    rec["sections_ms"] = {}
    rec["lower_seconds"] = 0.1
    monkeypatch.setattr(perf_gate, "predict_rung",
                        lambda *a, **k: dict(rec))
    rc = perf_gate.main(["--rungs", "128_b1",
                         "--strategies", "replicated",
                         "--update-baseline",
                         "--bank-dir", str(tmp_path)])
    assert rc == 0
    assert (tmp_path / "perf_pred_128_b1_replicated_float32.json"
            ).exists()
    assert not (tmp_path / "perf_pred_128_b1_replicated_bfloat16"
                           ".json").exists()


def test_calibration_points_glob_route_filters(tmp_path):
    """Self-calibrating rung artifacts pair via the glob route —
    except forward-only micro rungs (dispatch-overhead-dominated,
    the comparability rule) and error rounds."""
    rec = {"operating_point": "512_b1", "step_time_ms": 100.0,
           "predicted_step_time_ms": 10.0, "status": "ok"}
    (tmp_path / "bench_rung_512_b1.json").write_text(json.dumps(rec))
    (tmp_path / "bench_rung_micro.json").write_text(json.dumps(
        {**rec, "operating_point": "micro", "forward_only": True}))
    (tmp_path / "bench_rung_err.json").write_text(json.dumps(
        {**rec, "operating_point": "err", "status": "error"}))
    pts = P.calibration_points(str(tmp_path))
    assert [p["rung"] for p in pts] == ["512_b1"]
    assert pts[0]["predicted_source"] == "embedded"


def test_calibration_points_no_double_count(tmp_path):
    """A pinned flat source that now carries its own embedded
    prediction is paired ONCE (glob route, measured width) — not
    again against the banked smoke-width prediction."""
    rec = {"operating_point": "1344_b4", "step_time_ms": 377.0,
           "predicted_step_time_ms": 37.0, "status": "ok"}
    (tmp_path / "bench_rung_1344_b4.json").write_text(json.dumps(rec))
    _write_pred(
        tmp_path / "perf_pred_1344_b4_replicated_bfloat16.json",
        "1344_b4_replicated_bfloat16", 5.0, {})
    pts = P.calibration_points(str(tmp_path))
    assert len(pts) == 1 and pts[0]["predicted_source"] == "embedded"


# Pinned by the committed artifacts (perf_pred_{512_b4,1344_b4}_
# replicated_bfloat16.json vs roi_ab_r5.json + bench_rung_1344_b4
# .json) — regenerate via `python tools/perf_gate.py
# --calibrate-only`.  The number is honest and LARGE on purpose: at
# the 512 canvas the hardware runs at 0.066 MFU (fixed-cost NMS/host
# overhead dominates) while the roofline assumes peak, so the
# 512-vs-1344 scale factors spread 3.3x vs 0.9x.  The gate therefore
# only ever compares prediction RATIOS of the SAME geometry; this pin
# is the published bound on cross-geometry trust, and it tightens
# automatically as self-calibrating hardware rounds land.
PINNED_MODEL_ERROR_PCT = 138.71


def test_calibration_pins_committed_r5_artifacts():
    """THE honesty pin: the model fitted against the committed r5
    hardware evidence (roi_ab_r5.json 512/b4 + 1344/b4, the
    bench_rung_1344_b4 headline) must report exactly the model error
    the banked predictions imply.  Rebanking the prediction artifacts
    with a changed model moves this number — update the pin
    CONSCIOUSLY, it is the repo's published trust bound on every
    predicted-step-time claim."""
    art = os.path.join(REPO, "artifacts")
    points = P.calibration_points(art)
    # two r5 A/B runs + the banked headline rung pair up
    assert len(points) >= 3, points
    rungs = {p["rung"] for p in points}
    assert {"512_b4", "1344_b4"} <= rungs
    cal = P.calibrate(points)
    assert cal["scale"] is not None and cal["scale"] > 0
    assert cal["model_error_pct"] == pytest.approx(
        PINNED_MODEL_ERROR_PCT, abs=0.01), cal


# ---- gate plumbing over a tmp bank (no lowering) ---------------------


def _write_pred(path, key, total, components, banked_at=None):
    import time

    rec = _pred(total, components)
    rec["key"] = key
    rec["banked_at"] = banked_at or time.strftime(
        "%Y-%m-%dT%H:%M:%SZ", time.gmtime())
    with open(path, "w") as f:
        json.dump(rec, f)


def test_gate_one_missing_baseline_policy(tmp_path):
    fresh = _pred(10.0, {"backbone": 10.0})
    fresh["key"] = "128_b1_replicated_bfloat16"
    row = perf_gate.gate_one(fresh, str(tmp_path), 10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "FAIL" and "--update-baseline" in row["error"]
    row = perf_gate.gate_one(fresh, str(tmp_path), 10.0,
                             allow_missing_baseline=True)
    assert row["gate"] == "PASS" and row["note"] == "missing baseline"


def test_synthetic_regression_fails_component_attributed(tmp_path):
    """The acceptance shape on artifact level: a banked baseline, a
    fresh prediction whose roi component grew 50% — the gate FAILs
    naming the component, and an unchanged re-run PASSes."""
    key = "512_b1_replicated_bfloat16"
    _write_pred(tmp_path / f"perf_pred_{key}.json", key, 100.0,
                {"backbone": 60.0, "roi-bwd": 30.0, "optimizer": 10.0})
    fresh = _pred(100.0, {"backbone": 60.0, "roi-bwd": 30.0,
                          "optimizer": 10.0})
    fresh["key"] = key
    row = perf_gate.gate_one(fresh, str(tmp_path), 10.0, False)
    assert row["gate"] == "PASS"
    worse = _pred(115.0, {"backbone": 60.0, "roi-bwd": 45.0,
                          "optimizer": 10.0})
    worse["key"] = key
    row = perf_gate.gate_one(worse, str(tmp_path), 10.0, False)
    assert row["gate"] == "FAIL"
    assert "roi-bwd" in row["error"], row


# ---- a bench line's status field + bench_gate --predicted ------------


def test_usable_measurement_honors_status_field():
    line = {"value": 10.0, "step_time_ms": 400.0}
    assert bench_gate.usable_measurement(line) is line
    err = {"value": 10.0, "step_time_ms": 400.0, "status": "error"}
    assert bench_gate.usable_measurement(err) is None
    # an error line still falls back to a healthy last_good
    err["last_good"] = {"value": 9.0, "step_time_ms": 410.0}
    assert bench_gate.usable_measurement(err)["step_time_ms"] == 410.0


def _bank_round_file(path, line):
    with open(path, "w") as f:
        json.dump({"n": 1, "cmd": "python measure.py", "rc": 0,
                   "tail": json.dumps(line) + "\n"}, f)


def test_freshest_round_is_error(tmp_path):
    good = {"metric": "m", "value": 10.0, "step_time_ms": 400.0,
            "status": "ok"}
    err = {"metric": "m", "value": 0.0, "status": "error",
           "last_good": dict(good)}
    _bank_round_file(tmp_path / "BENCH_r01.json", good)
    _bank_round_file(tmp_path / "BENCH_r02.json", err)
    pat = str(tmp_path / "BENCH_r*.json")
    assert bench_gate.freshest_round_is_error(pat).endswith(
        "BENCH_r02.json")
    # newest round healthy → measured evidence wins
    _bank_round_file(tmp_path / "BENCH_r03.json", good)
    assert bench_gate.freshest_round_is_error(pat) is None


def test_bench_gate_predicted_mode_cli(tmp_path, capsys):
    """End to end: every banked round is an error round (the r01–r05
    reality) → --predicted gates on the prediction bank, names its
    evidence source, PASSes on unchanged predictions and FAILs
    component-attributed on a regressed one."""
    err = {"metric": "m", "value": 0.0, "status": "error",
           "last_good": {"value": 10.0, "step_time_ms": 400.0}}
    _bank_round_file(tmp_path / "BENCH_r01.json", err)
    fresh_line = tmp_path / "fresh.json"
    fresh_line.write_text(json.dumps(
        {"metric": "m", "value": 0.0, "status": "error"}) + "\n")

    key = "128_b1_replicated_bfloat16"
    bank = tmp_path / "bank"
    bank.mkdir()
    _write_pred(bank / f"perf_pred_{key}.json", key, 100.0,
                {"backbone": 70.0, "optimizer": 30.0})
    freshd = tmp_path / "perf_fresh"
    freshd.mkdir()
    _write_pred(freshd / f"perf_pred_{key}.json", key, 101.0,
                {"backbone": 71.0, "optimizer": 30.0})

    args = ["--fresh", str(fresh_line),
            "--bank", str(tmp_path / "BENCH_r*.json"),
            "--predicted",
            "--pred-fresh", str(freshd / "perf_pred_*.json"),
            "--pred-bank", str(bank)]
    rc = bench_gate.main(args)
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["gate"] == "PASS"
    assert out["evidence_source"] == "predicted"
    assert out["measured_error_round"] == "BENCH_r01.json"

    # regress the backbone prediction 40% → FAIL naming it
    _write_pred(freshd / f"perf_pred_{key}.json", key, 128.0,
                {"backbone": 98.0, "optimizer": 30.0})
    rc = bench_gate.main(args)
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["gate"] == "FAIL"
    assert "backbone" in out["results"][0]["error"]

    # a STALE fresh artifact (leftover from an earlier round) must
    # FAIL as stale, not gate this change with last week's prediction
    _write_pred(freshd / f"perf_pred_{key}.json", key, 101.0,
                {"backbone": 71.0, "optimizer": 30.0},
                banked_at="2020-01-01T00:00:00Z")
    rc = bench_gate.main(args)
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and "stale" not in out  # row-level error
    assert "old" in out["results"][0]["error"]

    # no fresh predictions at all must FAIL loudly, not skip silently
    for f in freshd.glob("*.json"):
        f.unlink()
    rc = bench_gate.main(args)
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and "perf_gate.py" in out["error"]


def test_bench_gate_predicted_defers_to_real_measurement(tmp_path,
                                                         capsys):
    """--predicted must NOT override real hardware evidence: with the
    newest banked round healthy, the measured trajectory gates."""
    good = {"metric": "m", "value": 10.0, "step_time_ms": 400.0}
    _bank_round_file(tmp_path / "BENCH_r01.json", good)
    fresh_line = tmp_path / "fresh.json"
    fresh_line.write_text(json.dumps(
        {"metric": "m", "value": 10.0, "step_time_ms": 405.0}) + "\n")
    rc = bench_gate.main(["--fresh", str(fresh_line),
                          "--bank", str(tmp_path / "BENCH_r*.json"),
                          "--predicted"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["evidence_source"] == "measured"


def test_bench_gate_predicted_fires_without_fresh_line(tmp_path,
                                                       capsys):
    """A fresh output with NO metric line at all (bench crashed before
    emitting) is strictly less evidence than an error line — the
    predicted path must take over, not a doomed measured gate."""
    err = {"metric": "m", "value": 0.0, "status": "error"}
    _bank_round_file(tmp_path / "BENCH_r01.json", err)
    fresh_line = tmp_path / "fresh.json"
    fresh_line.write_text("Traceback (most recent call last): ...\n")
    key = "128_b1_replicated_bfloat16"
    bank = tmp_path / "bank"
    bank.mkdir()
    _write_pred(bank / f"perf_pred_{key}.json", key, 100.0,
                {"backbone": 100.0})
    freshd = tmp_path / "perf_fresh"
    freshd.mkdir()
    _write_pred(freshd / f"perf_pred_{key}.json", key, 100.0,
                {"backbone": 100.0})
    rc = bench_gate.main(["--fresh", str(fresh_line),
                          "--bank", str(tmp_path / "BENCH_r*.json"),
                          "--predicted",
                          "--pred-fresh",
                          str(freshd / "perf_pred_*.json"),
                          "--pred-bank", str(bank)])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["evidence_source"] == "predicted"


def test_bench_gate_predicted_defers_to_fresh_measurement(tmp_path,
                                                          capsys):
    """A fresh HEALTHY line gates measured even when every banked
    round is an error round: the hardware window's real measurement is
    the round's strongest evidence and can show host-side regressions
    the roofline model cannot see — --predicted must not discard it."""
    err = {"metric": "m", "value": 0.0, "status": "error",
           "last_good": {"value": 10.0, "step_time_ms": 400.0}}
    _bank_round_file(tmp_path / "BENCH_r01.json", err)
    fresh_line = tmp_path / "fresh.json"
    fresh_line.write_text(json.dumps(
        {"metric": "m", "value": 10.0, "step_time_ms": 405.0}) + "\n")
    rc = bench_gate.main(["--fresh", str(fresh_line),
                          "--bank", str(tmp_path / "BENCH_r*.json"),
                          "--predicted"])
    out = json.loads(capsys.readouterr().out)
    # gates vs the banked round's last_good carry (405 vs 400: PASS)
    assert rc == 0 and out["evidence_source"] == "measured"
    # and a fresh 30% regression FAILs on the measured path, not the
    # prediction bank
    fresh_line.write_text(json.dumps(
        {"metric": "m", "value": 7.0, "step_time_ms": 520.0}) + "\n")
    rc = bench_gate.main(["--fresh", str(fresh_line),
                          "--bank", str(tmp_path / "BENCH_r*.json"),
                          "--predicted"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["evidence_source"] == "measured"


# ---- run_report degradation ------------------------------------------


def test_run_report_predicted_section_degrades(tmp_path):
    from tools.run_report import _predicted_section

    lines = "\n".join(_predicted_section(str(tmp_path)))
    assert "perf_gate.py" in lines  # pointer, not an error
    # with the repo bank present the table renders
    lines = "\n".join(_predicted_section(
        os.path.join(REPO, "artifacts")))
    assert "| key | predicted ms |" in lines or "No `perf_pred_" \
        in lines


# ---- the real lowering (the program CI gates every round) ------------


@pytest.fixture(scope="module")
def tiny_lowering():
    """ONE smoke-width 128/b1 replicated lowering shared by the real-
    program assertions below (the compile is the expensive part).
    Module-scoped, so it saves/restores the global config by hand
    instead of using the function-scoped fresh_config fixture."""
    from eksml_tpu import config as config_mod
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs

    saved = config_mod.config.to_dict()
    config_mod.config.freeze(False)
    config_mod.config.update_args(SMOKE_OVERRIDES)
    config_mod.config.TRAIN.BATCH_SIZE_PER_CHIP = 1
    config_mod.config.TRAIN.PRECISION = "bfloat16"
    cfg = finalize_configs(is_training=True)
    try:
        hlo, meta = P.lower_train_step(cfg, batch_size=1,
                                       image_size=128,
                                       strategy="replicated")
    finally:
        config_mod.config.freeze(False)
        config_mod.config.from_dict(saved)
        config_mod.config.freeze()
    return hlo, meta


def test_real_train_step_prediction(tiny_lowering):
    """The gate's actual program: predicted time positive, components
    named (backbone/roi/optimizer all present), sections sum to the
    total, and pricing is deterministic."""
    hlo, meta = tiny_lowering
    pred = P.predict_from_hlo(hlo, target="v5e",
                              precision="bfloat16",
                              comm_sizes=meta["comm_sizes"])
    assert pred["predicted_step_time_ms"] > 0
    comps = set(pred["components_ms"])
    for needed in ("backbone", "optimizer", "roi-fwd", "roi-bwd"):
        assert needed in comps, sorted(comps)
    # sections are rounded independently of the total: 4dp each
    assert (pytest.approx(pred["predicted_step_time_ms"], abs=1e-3)
            == sum(pred["sections_ms"].values()))
    # single-device program: no collectives, comms 0 — the comms term
    # only enters through a sharded plan (fsdp test below)
    assert pred["sections_ms"]["comms"] == 0.0
    again = P.predict_from_hlo(hlo, target="v5e",
                               precision="bfloat16",
                               comm_sizes=meta["comm_sizes"])
    assert again == pred


def test_real_prediction_vs_committed_baseline(tiny_lowering):
    """Fresh tiny-geometry prediction vs the COMMITTED bank: the
    unchanged program must PASS the gate — this is the tier-1 rerun
    half of the acceptance (FAIL-on-regression is driven on artifact
    level above and by the slow synthetic-regression drive below)."""
    hlo, meta = tiny_lowering
    pred = P.predict_from_hlo(hlo, target="v5e",
                              precision="bfloat16",
                              comm_sizes=meta["comm_sizes"])
    pred = dict(pred)
    pred["key"] = "128_b1_replicated_bfloat16"
    row = perf_gate.gate_one(pred, os.path.join(REPO, "artifacts"),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS", row


@pytest.mark.slow
def test_fsdp_lowering_prices_comms(fresh_config):
    """fsdp plan → the compiled program carries the all-gather /
    grad-reduction collectives and the comms term is priced from the
    plan's axis sizes."""
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs

    cfg = fresh_config
    cfg.update_args(SMOKE_OVERRIDES)
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.PRECISION = "bfloat16"
    cfg = finalize_configs(is_training=True)
    hlo, meta = P.lower_train_step(cfg, batch_size=1, image_size=128,
                                   strategy="fsdp", fsdp_axis=2)
    assert meta["mesh_shape"] == {"data": 1, "fsdp": 2, "model": 1}
    assert meta["comm_sizes"]["all-gather"] == 2
    pred = P.predict_from_hlo(hlo, target="v5e",
                              precision="bfloat16",
                              comm_sizes=meta["comm_sizes"])
    assert pred["sections_ms"]["comms"] > 0, pred["sections_ms"]
    assert pred["totals"]["collective_bytes"] > 0


@pytest.mark.slow
@pytest.mark.parametrize("strategy,axes,widths", [
    ("tensor", (1, 1, 2), {"fsdp": 1, "model": 2}),
    ("2d", (1, 2, 2), {"fsdp": 2, "model": 2}),
])
def test_tensor_2d_lowerings_price_model_axis(fresh_config, strategy,
                                              axes, widths):
    """ISSUE 15: the tensor/2d lowerings carry model-axis collectives
    in the compiled HLO, the comm sizes ride the storage axes, and
    the axis_widths helper resolves the (fsdp, model) widths the
    verdict rows carry."""
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs

    cfg = fresh_config
    cfg.update_args(SMOKE_OVERRIDES)
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.PRECISION = "bfloat16"
    cfg = finalize_configs(is_training=True)
    hlo, meta = P.lower_train_step(cfg, batch_size=1, image_size=128,
                                   strategy=strategy, fsdp_axis=2,
                                   model_axis=2)
    assert meta["mesh_shape"] == dict(
        zip(("data", "fsdp", "model"), axes))
    assert meta["comm_sizes"]["all-gather"] == (
        widths["fsdp"] * widths["model"])
    assert perf_gate.axis_widths(meta["mesh_shape"]) == widths
    pred = P.predict_from_hlo(hlo, target="v5e",
                              precision="bfloat16",
                              comm_sizes=meta["comm_sizes"])
    assert pred["sections_ms"]["comms"] > 0, pred["sections_ms"]
    assert pred["totals"]["collective_bytes"] > 0


def test_gate_rows_carry_axis_widths(tmp_path):
    """A 2d verdict row can't be confused with its 1D siblings: the
    resolved (fsdp, model) widths ride the gate row, derived from the
    mesh_shape the record already banks (no second stored copy)."""
    fresh = {"key": "128_b1_2d_bfloat16",
             "predicted_step_time_ms": 5.0,
             "sections_ms": {"fwd": 5.0},
             "components_ms": {"backbone": 5.0},
             "mesh_shape": {"data": 1, "fsdp": 4, "model": 2}}
    with open(tmp_path / "perf_pred_128_b1_2d_bfloat16.json",
              "w") as f:
        json.dump(fresh, f)
    row = perf_gate.gate_one(fresh, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS"
    assert row["axis_widths"] == {"fsdp": 4, "model": 2}
    # a record without a mesh (serve predict / pre-mesh_shape banks)
    # stays renderable, just without the widths field
    legacy = {k: v for k, v in fresh.items() if k != "mesh_shape"}
    row = perf_gate.gate_one(legacy, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS" and "axis_widths" not in row
    assert perf_gate.row_axis_widths(
        {"kind": "predict", "mesh_shape": {}}) is None


def test_gate_fails_on_axis_width_mismatch(tmp_path):
    """pred_key excludes the shard widths, so a lowering at other
    --fsdp-axis/--model-axis values lands under the SAME baseline
    file — the gate must refuse the comparison naming both layouts,
    never emit a bogus time verdict."""
    base = {"key": "128_b1_2d_bfloat16",
            "predicted_step_time_ms": 5.0,
            "sections_ms": {"fwd": 5.0},
            "components_ms": {"backbone": 5.0},
            "mesh_shape": {"data": 1, "fsdp": 2, "model": 4}}
    with open(tmp_path / "perf_pred_128_b1_2d_bfloat16.json",
              "w") as f:
        json.dump(base, f)
    fresh = dict(base)
    fresh["mesh_shape"] = {"data": 1, "fsdp": 4, "model": 2}
    row = perf_gate.gate_one(fresh, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "FAIL"
    assert "axis widths mismatch" in row["error"]
    assert row["axis_widths"] == {"fsdp": 4, "model": 2}
    assert row["baseline_axis_widths"] == {"fsdp": 2, "model": 4}
    # matching widths still gate normally
    row = perf_gate.gate_one(dict(base), str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS"


@pytest.mark.slow
def test_synthetic_regression_real_lowering(tmp_path, fresh_config):
    """The full acceptance drive: bank the tiny geometry, re-lower
    with doubled FPN channel width (a real compiled-program change) —
    the prediction rises and the gate FAILs naming the regressing
    component."""
    from eksml_tpu.config import SMOKE_OVERRIDES, finalize_configs

    cfg = fresh_config
    cfg.update_args(SMOKE_OVERRIDES)
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.PRECISION = "bfloat16"
    cfg = finalize_configs(is_training=True)
    hlo, meta = P.lower_train_step(cfg, batch_size=1, image_size=128,
                                   strategy="replicated")
    base = dict(P.predict_from_hlo(hlo, comm_sizes=meta["comm_sizes"]))
    key = "128_b1_replicated_bfloat16"
    base["key"] = key
    with open(tmp_path / f"perf_pred_{key}.json", "w") as f:
        json.dump(base, f)

    cfg.freeze(False)
    cfg.FPN.NUM_CHANNEL = 64  # 2x width: conv trunk + roi heads grow
    cfg = finalize_configs(is_training=True)
    hlo2, meta2 = P.lower_train_step(cfg, batch_size=1,
                                     image_size=128,
                                     strategy="replicated")
    worse = dict(P.predict_from_hlo(hlo2,
                                    comm_sizes=meta2["comm_sizes"]))
    worse["key"] = key
    assert (worse["predicted_step_time_ms"]
            > base["predicted_step_time_ms"])
    row = perf_gate.gate_one(worse, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "FAIL"
    # the message names a regressing component, not a bare number
    assert any(c in row["error"] for c in
               ("roi", "fpn", "backbone", "rpn")), row["error"]


# ---- serving gate (--serve, ISSUE 14) --------------------------------


@pytest.fixture(scope="module")
def serve_lowering():
    """ONE serve-rung lowering (b1 at the 128 smoke bucket) shared by
    the serve-gate tests — module-scoped like tiny_lowering so the
    compile is paid once.  predict_serve_rung mutates the global
    config (the CLI owns the process), so snapshot/restore here."""
    from eksml_tpu import config as config_mod

    saved = config_mod.config.to_dict()
    try:
        fresh = perf_gate.predict_serve_rung(
            "serve_128x128_b1", "bfloat16", "v5e")
        probe = perf_gate.predict_serve_rung(
            "serve_128x128_b1", "bfloat16", "v5e",
            config_overrides=["FPN.NUM_CHANNEL=64"])
    finally:
        config_mod.config.freeze(False)
        config_mod.config.from_dict(saved)
        config_mod.config.freeze()
    return fresh, probe


def test_serve_rung_prices_predict_step(serve_lowering):
    """--serve lowers the SERVING predict program (no bwd, no
    optimizer, no collectives) and frames the number as per-bucket
    latency."""
    fresh, _ = serve_lowering
    assert fresh["key"] == "serve_128x128_b1_bfloat16"
    assert fresh["kind"] == "predict"
    assert fresh["predicted_latency_ms"] == \
        fresh["predicted_step_time_ms"] > 0
    assert fresh["predicted_latency_per_image_ms"] == pytest.approx(
        fresh["predicted_latency_ms"], abs=1e-3)  # batch 1
    # inference program: forward-only, nothing rides bwd/optimizer/
    # comms
    s = fresh["sections_ms"]
    assert s["bwd"] == 0.0 and s["optimizer"] == 0.0
    assert s["comms"] == 0.0
    assert "backbone" in fresh["components_ms"]
    assert fresh["geometry"]["pad_hw"] == [128, 128]


def test_serve_rung_vs_committed_baseline_and_probe(serve_lowering):
    """Fresh serve lowering PASSes against the committed
    perf_pred_serve_* bank; the injected FPN.NUM_CHANNEL probe FAILs
    with a component-attributed message — the rc=1 acceptance
    criterion, pinned at artifact level."""
    fresh, probe = serve_lowering
    bank = os.path.join(REPO, "artifacts")
    row = perf_gate.gate_one(fresh, bank, max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS", row
    row2 = perf_gate.gate_one(probe, bank, max_regress_pct=10.0,
                              allow_missing_baseline=False)
    assert row2["gate"] == "FAIL"
    assert "regressed" in row2["error"]
    # the message names the worst component, never a bare number
    assert "predicted +" in row2["error"]


# ---- multi-slice hierarchical exchange pricing (ISSUE 18) -----------


def test_comm_sizes_for_mesh_slice_axis():
    """A slice axis multiplies the gradient all-reduce (batch rows
    ride every mesh axis, slice included) but NOT the layout moves —
    all-gather/reduce-scatter stay in-slice storage traffic.  Meshes
    without the axis price exactly as before (the committed bank)."""
    ms = P.comm_sizes_for_mesh({"slice": 2, "data": 1, "fsdp": 2,
                                "model": 2})
    assert ms["all-gather"] == 4
    assert ms["reduce-scatter"] == 4
    assert ms["all-reduce"] == 8
    assert ms["all-to-all"] == 8
    # no slice key: bit-identical to the historical values
    assert (P.comm_sizes_for_mesh({"data": 1, "fsdp": 2, "model": 2})
            ["all-reduce"] == 4)


def test_hierarchical_three_phase_price():
    """The satellite fix: a cross-slice all-reduce under the
    hierarchical exchange prices as ICI reduce-scatter + DCN
    all-reduce of the 1/per partials + ICI all-gather — strictly
    below the flat ring at DCN speed, and degenerating to it at
    per-slice device count 1."""
    spec = P.chip_spec("v5e")
    ici = float(spec["ici_bytes_per_sec"])
    dcn = float(spec["dcn_bytes_per_sec"])
    nbytes, k, per = 1e9, 8, 4
    s = k // per
    hier = P.hierarchical_allreduce_seconds(nbytes, k, per, ici, dcn)
    expect = (nbytes * (per - 1) / per / ici
              + (nbytes / per) * 2.0 * (s - 1) / s / dcn
              + nbytes * (per - 1) / per / ici)
    assert hier == pytest.approx(expect, rel=1e-12)
    flat = nbytes * 2.0 * (k - 1) / k / dcn
    assert hier < flat
    # per=1: no in-slice phase exists — the "hierarchy" IS the flat
    # ring over the slices
    assert (P.hierarchical_allreduce_seconds(nbytes, 4, 1, ici, dcn)
            == pytest.approx(nbytes * 2.0 * 3 / 4 / dcn, rel=1e-12))


def test_predict_from_hlo_exchange_modes():
    """exchange= reshapes ONLY the cross-slice all-reduce price:
    hierarchical beats flat on the same HLO, and at a single slice
    (slice_devices=None) both spellings are bit-identical — the
    committed single-slice bank must never move."""
    flat = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                              comm_sizes={"all-reduce": 4},
                              slice_devices=2, exchange="flat")
    hier = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                              comm_sizes={"all-reduce": 4},
                              slice_devices=2, exchange="hierarchical")
    assert (hier["sections_ms"]["comms"]
            < flat["sections_ms"]["comms"])
    one_flat = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                                  comm_sizes={"all-reduce": 4})
    one_hier = P.predict_from_hlo(HLO_FIXTURE, precision="float32",
                                  comm_sizes={"all-reduce": 4},
                                  exchange="hierarchical")
    assert one_flat == one_hier


def test_predict_for_compiled_threads_exchange():
    mesh = {"slice": 2, "data": 2, "fsdp": 1, "model": 1}
    flat = P.predict_for_compiled(HLO_FIXTURE, device_kind="TPU v5e",
                                  mesh_shape=mesh, precision="float32",
                                  num_slices=2)
    hier = P.predict_for_compiled(HLO_FIXTURE, device_kind="TPU v5e",
                                  mesh_shape=mesh, precision="float32",
                                  num_slices=2,
                                  exchange="hierarchical")
    assert (hier["sections_ms"]["comms"]
            < flat["sections_ms"]["comms"])


def test_axis_widths_slices_column():
    """The slices column generalizes the verdict rows — but ONLY for
    meshes that carry a slice axis; single-slice rows keep the
    two-key shape every banked artifact and its consumers pin."""
    assert perf_gate.axis_widths({"data": 1, "fsdp": 4, "model": 2}) \
        == {"fsdp": 4, "model": 2}
    assert perf_gate.axis_widths(
        {"slice": 2, "data": 1, "fsdp": 2, "model": 2}) \
        == {"fsdp": 2, "model": 2, "slices": 2}
    assert perf_gate.axis_widths({"slice": 1, "data": 8}) \
        == {"fsdp": 1, "model": 1}


def test_multislice_rung_specs_restrict_strategies():
    for rung, slices in (("128_b1_s2", 2), ("128_b1_s4", 4)):
        spec = perf_gate.PRED_RUNGS[rung]
        assert spec["num_slices"] == slices
        assert spec["strategies"] == ("2d",)
    # the CI default includes both multislice rungs
    for rung in ("128_b1_s2", "128_b1_s4"):
        assert rung in perf_gate.DEFAULT_RUNGS.split(",")


def test_gate_fails_unless_hierarchical_beats_flat(tmp_path):
    """A multi-slice row carries the flat counterfactual price, and
    the gate FAILs when hierarchical is not strictly faster — the win
    this rung exists to prove."""
    fresh = {"key": "128_b1_s2_2d_bfloat16",
             "predicted_step_time_ms": 5.0,
             "sections_ms": {"fwd": 4.0, "comms": 1.0},
             "components_ms": {"backbone": 5.0},
             "mesh_shape": {"slice": 2, "data": 1, "fsdp": 2,
                            "model": 2},
             "num_slices": 2,
             "flat_predicted_step_time_ms": 8.0}
    with open(tmp_path / "perf_pred_128_b1_s2_2d_bfloat16.json",
              "w") as f:
        json.dump(fresh, f)
    row = perf_gate.gate_one(fresh, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS"
    assert row["axis_widths"] == {"fsdp": 2, "model": 2, "slices": 2}
    assert row["flat_predicted_step_time_ms"] == 8.0
    slower = dict(fresh)
    slower["flat_predicted_step_time_ms"] = 5.0  # equal: not a win
    row = perf_gate.gate_one(slower, str(tmp_path),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "FAIL"
    assert "not strictly faster" in row["error"]


@pytest.mark.slow
def test_multislice_prediction_vs_committed_baseline(fresh_config):
    """The hermetic acceptance drive at 2 slices: predict_rung lowers
    the hierarchical 2d program over a (2, 1, 2, 2) slice mesh,
    prices it both ways, beats the flat counterfactual, and PASSes
    against the COMMITTED bank."""
    rec = perf_gate.predict_rung("128_b1_s2", "2d", "bfloat16", "v5e")
    assert rec["mesh_shape"] == {"slice": 2, "data": 1, "fsdp": 2,
                                 "model": 2}
    assert rec["num_slices"] == 2 and rec["slice_devices"] == 4
    assert rec["exchange"] == "hierarchical"
    assert (rec["predicted_step_time_ms"]
            < rec["flat_predicted_step_time_ms"])
    row = perf_gate.gate_one(rec, os.path.join(REPO, "artifacts"),
                             max_regress_pct=10.0,
                             allow_missing_baseline=False)
    assert row["gate"] == "PASS", row
