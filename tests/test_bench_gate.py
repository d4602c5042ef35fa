"""tools/bench_gate.py: the banked-trajectory regression gate.

This IS the tier-1 CPU-smoke invocation (ISSUE 5 satellite): the gate
logic runs against synthetic banked rounds on every CI pass, so a
broken comparison never waits for a hardware window to surface.
"""

import json
import os

from tools.bench_gate import (extract_metric_line, gate, load_bank,
                              main, usable_measurement)


def _line(value=10.0, step_ms=400.0, **extra):
    d = {"metric": "maskrcnn_r50fpn_train_throughput",
         "value": value, "unit": "images/sec/chip",
         "step_time_ms": step_ms}
    d.update(extra)
    return d


def _bank_file(path, line, noise_before=True):
    """A driver-wrapped banked round: stdout tail with the metric
    line last (the real BENCH_r*.json shape)."""
    tail = ""
    if noise_before:
        tail += "INFO compile done\n{\"not\": \"a metric line\"}\n"
    tail += json.dumps(line) + "\n"
    with open(path, "w") as f:
        json.dump({"n": 1, "cmd": "python measure.py", "rc": 0,
                   "tail": tail}, f)


def test_extract_and_usable_measurement():
    text = "noise\n" + json.dumps(_line(step_ms=100.0)) + "\n" \
        + json.dumps(_line(step_ms=200.0)) + "\n"
    m = extract_metric_line(text)
    assert m["step_time_ms"] == 200.0  # last line wins
    assert usable_measurement(m) is m
    # error line: value 0 → falls back to last_good
    err = _line(value=0.0)
    err.pop("step_time_ms")
    err["last_good"] = _line(value=9.5, step_ms=410.0)
    assert usable_measurement(err)["step_time_ms"] == 410.0
    assert usable_measurement({"value": 0.0}) is None
    assert usable_measurement(None) is None
    # step_time_ms of 0 is no measurement either: as a baseline it
    # would divide the gate by zero, as a fresh line trivially pass
    assert usable_measurement(_line(step_ms=0.0)) is None
    assert usable_measurement(_line(step_ms=None)) is None


def test_load_bank_orders_rounds_and_skips_unusable(tmp_path):
    _bank_file(tmp_path / "BENCH_r01.json", _line(step_ms=500.0))
    # r02: hard failure, no metric line at all
    with open(tmp_path / "BENCH_r02.json", "w") as f:
        json.dump({"n": 2, "cmd": "x", "rc": 1,
                   "tail": "Traceback (most recent call last):\n"}, f)
    err = _line(value=0.0, step_ms=None)
    err["last_good"] = _line(value=10.0, step_ms=450.0)
    _bank_file(tmp_path / "BENCH_r03.json", err)
    bank = load_bank(str(tmp_path / "BENCH_r*.json"))
    assert [os.path.basename(p) for p, _ in bank] == [
        "BENCH_r01.json", "BENCH_r03.json"]
    assert bank[-1][1]["step_time_ms"] == 450.0  # last_good fallback


def test_load_bank_orders_rounds_numerically(tmp_path):
    """r100 must order AFTER r99 — lexicographic glob order would pin
    the gate's baseline at r99 forever once rounds outgrow the zero
    padding."""
    _bank_file(tmp_path / "BENCH_r99.json", _line(step_ms=500.0))
    _bank_file(tmp_path / "BENCH_r100.json", _line(step_ms=450.0))
    bank = load_bank(str(tmp_path / "BENCH_r*.json"))
    assert [os.path.basename(p) for p, _ in bank] == [
        "BENCH_r99.json", "BENCH_r100.json"]
    assert bank[-1][1]["step_time_ms"] == 450.0  # newest = baseline


def test_gate_passes_within_bound_and_fails_on_regression(tmp_path):
    _bank_file(tmp_path / "BENCH_r01.json", _line(step_ms=500.0))
    _bank_file(tmp_path / "BENCH_r02.json", _line(step_ms=400.0))
    bank = load_bank(str(tmp_path / "BENCH_r*.json"))
    # +5% vs the NEWEST round: pass
    ok, v = gate(_line(step_ms=420.0), bank, max_regress_pct=10.0)
    assert ok and v["step_time_regress_pct"] == 5.0
    assert v["baseline"]["path"].endswith("BENCH_r02.json")
    # +25%: fail, naming the baseline
    ok, v = gate(_line(step_ms=500.0), bank, max_regress_pct=10.0)
    assert not ok and "regressed 25.0%" in v["error"]
    assert "BENCH_r02.json" in v["error"]


def test_gate_fails_on_throughput_drop(tmp_path):
    _bank_file(tmp_path / "BENCH_r01.json",
               _line(value=10.0, step_ms=400.0))
    bank = load_bank(str(tmp_path / "BENCH_r*.json"))
    # step time fine but per-chip throughput collapsed (e.g. a chip
    # fell out of the mesh): the cross-check catches it
    ok, v = gate(_line(value=5.0, step_ms=400.0), bank,
                 max_regress_pct=10.0)
    assert not ok and "throughput dropped 50.0%" in v["error"]


def test_gate_fails_on_fresh_error_line(tmp_path):
    _bank_file(tmp_path / "BENCH_r01.json", _line(step_ms=400.0))
    bank = load_bank(str(tmp_path / "BENCH_r*.json"))
    err = _line(value=0.0)
    err["last_good"] = _line(step_ms=400.0)  # must NOT rescue fresh
    ok, v = gate(err, bank, max_regress_pct=10.0)
    assert not ok and "no usable measurement" in v["error"]
    ok, v = gate(None, bank, max_regress_pct=10.0)
    assert not ok


def test_gate_missing_baseline_policy(tmp_path):
    ok, v = gate(_line(), [], max_regress_pct=10.0)
    assert not ok and v["note"] == "no usable banked baseline"
    ok, _ = gate(_line(), [], max_regress_pct=10.0,
                 allow_missing_baseline=True)
    assert ok


def test_cli_end_to_end(tmp_path, capsys):
    _bank_file(tmp_path / "BENCH_r01.json", _line(step_ms=400.0))
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_line(step_ms=405.0)) + "\n")
    rc = main(["--fresh", str(fresh),
               "--bank", str(tmp_path / "BENCH_r*.json"),
               "--max-regress-pct", "10"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 0 and out["gate"] == "PASS"
    fresh.write_text(json.dumps(_line(step_ms=480.0)) + "\n")
    rc = main(["--fresh", str(fresh),
               "--bank", str(tmp_path / "BENCH_r*.json"),
               "--max-regress-pct", "10"])
    out = json.loads(capsys.readouterr().out)
    assert rc == 1 and out["gate"] == "FAIL"


def test_cli_on_this_repos_empty_bank(tmp_path):
    """The old driver rounds (all 0.0) were deleted with the harness
    that produced them, so the committed BENCH_r*.json bank is empty
    until the benchmark PR lands.  The CLI must then refuse a fresh
    line for want of a baseline — not pass it silently — unless the
    caller says a missing baseline is expected."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert load_bank(os.path.join(repo, "BENCH_r*.json")) == []
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps(_line(step_ms=400.0)) + "\n")
    script = os.path.join(repo, "tools", "bench_gate.py")
    strict = subprocess.run([sys.executable, script, "--fresh",
                             str(fresh)], capture_output=True, text=True)
    assert strict.returncode == 1, strict.stdout
    assert json.loads(strict.stdout)["gate"] == "FAIL"
    first = subprocess.run([sys.executable, script, "--fresh",
                            str(fresh), "--allow-missing-baseline"],
                           capture_output=True, text=True)
    assert first.returncode == 0, first.stdout
