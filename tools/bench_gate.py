"""Gate a fresh bench JSON line against the banked trajectory.

A round is banked as one ``BENCH_r<NN>.json`` (a wrapper's record of
a run's stdout: ``{"n", "cmd", "rc", "tail"}``; the tree holds none
today, and the harness that printed such lines is gone: ROADMAP D1),
but nothing
ever COMPARED a new measurement against that trajectory — a step-time
regression only surfaced when a human eyeballed the numbers.  This
tool is the missing regression gate:

- the **bank** is every ``BENCH_r*.json`` (newest = highest round);
  each file's ``tail`` is scanned for its last ``{"metric": ...}``
  line.  Error lines (``value == 0``) fall back to the line's
  ``last_good`` snapshot where an older record carries one.
- the **fresh** measurement is a bench JSON line (or the raw stdout
  that ends in one) from a file or stdin.
- the gate FAILS (exit 1) when fresh ``step_time_ms`` exceeds the
  newest usable banked step time by more than ``--max-regress-pct``
  (or when throughput ``value`` drops by more than the same bound,
  when both carry it).  A fresh error line fails too — a gate that
  passes on "the bench crashed" is not a gate.
- ``--predicted``: when the FRESHEST banked round is itself an error
  round (``status: "error"``), delegate
  to the hermetic predicted-step-time bank (``tools/perf_gate.py``)
  instead of skipping silently; the verdict's ``evidence_source``
  names which trajectory gated the change.

Usage::

    ... | python tools/bench_gate.py --fresh - --max-regress-pct 10
    python tools/bench_gate.py --fresh bench_out.json \
        --bank 'BENCH_r*.json' --allow-missing-baseline

The CPU-smoke half lives in tests/test_bench_gate.py (tier-1): it
drives this gate over synthetic banked files, so the comparison logic
is exercised on every CI run without touching hardware.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

# a usable measurement needs a positive throughput and a step time —
# the two numbers the gate compares
METRIC_LINE_RE = re.compile(r'^\s*\{"metric"')


def extract_metric_line(text: str) -> Optional[Dict]:
    """Last ``{"metric": ...}`` JSON object in ``text`` (a run
    prints exactly one as its final line; banked files wrap whole
    stdout)."""
    last = None
    for line in text.splitlines():
        if METRIC_LINE_RE.match(line):
            try:
                last = json.loads(line)
            except json.JSONDecodeError:
                continue
    return last


def usable_measurement(line: Optional[Dict]) -> Optional[Dict]:
    """The comparable core of a bench line: the line itself when it
    carries a real measurement, else its ``last_good`` snapshot (the
    stale-but-honest fallback of a run that could not reach
    hardware), else None."""
    if not isinstance(line, dict):
        return None

    def _ok(d: Dict) -> bool:
        # an explicit error mark wins over whatever numbers rode
        # along (every line carries a status since ISSUE 7);
        # both compared numbers must also be real: a step_time_ms of
        # 0 would divide the gate by zero as a baseline and trivially
        # PASS as a fresh line — "the bench crashed" must fail
        return (d.get("status") != "error"
                and (d.get("value", 0) or 0) > 0
                and (d.get("step_time_ms", 0) or 0) > 0)

    if _ok(line):
        return line
    lg = line.get("last_good")
    if isinstance(lg, dict) and _ok(lg):
        return lg
    return None


def _round_key(path: str) -> Tuple:
    """Sort key = the integer round parsed from the filename, so
    BENCH_r100 orders AFTER BENCH_r99 (lexicographic glob order would
    pin the baseline at r99 forever once rounds outgrow the zero
    padding); non-matching names fall back to plain name order."""
    m = re.search(r"_r(\d+)", os.path.basename(path))
    return (0, int(m.group(1)), path) if m else (1, 0, path)


def load_bank(pattern: str) -> List[Tuple[str, Dict]]:
    """[(path, usable measurement)] for every banked round that has
    one, in round order (numeric — BENCH_r99 < BENCH_r100)."""
    out = []
    for path in sorted(glob.glob(pattern), key=_round_key):
        try:
            with open(path) as f:
                payload = json.load(f)
        except (json.JSONDecodeError, OSError):
            continue
        text = payload.get("tail", "") if isinstance(payload, dict) \
            else ""
        m = usable_measurement(extract_metric_line(text))
        if m is not None:
            out.append((path, m))
    return out


def freshest_round_is_error(pattern: str) -> Optional[str]:
    """Path of the newest banked round when its OWN metric line is an
    error line (usable only via last_good, or not at all); None when
    the newest round carries a real measurement or no round exists.

    This is the --predicted trigger: five straight error rounds mean
    the measured trajectory is frozen, and gating fresh CPU rounds
    against a stale last_good carry proves nothing about THIS change.
    """
    paths = sorted(glob.glob(pattern), key=_round_key)
    if not paths:
        return None
    newest = paths[-1]
    try:
        with open(newest) as f:
            payload = json.load(f)
    except (json.JSONDecodeError, OSError):
        return newest
    text = payload.get("tail", "") if isinstance(payload, dict) else ""
    line = extract_metric_line(text)
    m = usable_measurement(line)
    if m is None or m is not line:
        return newest
    return None


def _pred_age_hours(rec: Dict) -> Optional[float]:
    """Hours since the prediction record's ``banked_at`` stamp; None
    when the stamp is missing or unparseable."""
    import calendar
    import time

    try:
        t = calendar.timegm(time.strptime(rec.get("banked_at", ""),
                                          "%Y-%m-%dT%H:%M:%SZ"))
    except (TypeError, ValueError):
        return None
    return (time.time() - t) / 3600.0


def gate_predicted(fresh_glob: str, bank_dir: str,
                   max_regress_pct: float,
                   max_age_hours: float = 24.0) -> Tuple[bool, Dict]:
    """Predicted-step-time gating: fresh prediction artifacts (a
    tools/perf_gate.py run's --fresh-dir output) vs the banked
    ``perf_pred_*.json`` baselines.  Used when the measured trajectory
    has no fresh evidence to offer (error round) — the verdict names
    its evidence source so a PASS can never masquerade as a hardware
    measurement."""
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    try:
        from tools.perf_gate import gate_one
    except ImportError:  # script mode: tools/ is sys.path[0]
        from perf_gate import gate_one

    verdict: Dict = {"evidence_source": "predicted",
                     "max_regress_pct": max_regress_pct,
                     "results": []}
    fresh_paths = sorted(glob.glob(fresh_glob))
    if not fresh_paths:
        verdict["error"] = (
            f"--predicted: no fresh prediction artifacts match "
            f"{fresh_glob!r} — run `python tools/perf_gate.py "
            f"--fresh-dir <dir>` first (the gate must not silently "
            "skip)")
        return False, verdict
    ok = True
    for path in fresh_paths:
        try:
            with open(path) as f:
                fresh = json.load(f)
        except (json.JSONDecodeError, OSError) as e:
            verdict["results"].append({"fresh": path,
                                       "gate": "FAIL",
                                       "error": repr(e)})
            ok = False
            continue
        fresh.setdefault("key", os.path.splitext(
            os.path.basename(path))[0].replace("perf_pred_", ""))
        # leftovers from an earlier round must not gate THIS change:
        # a stale fresh artifact passing silently is a green verdict
        # for a prediction that was never computed
        age = _pred_age_hours(fresh)
        if age is None or age > max_age_hours:
            verdict["results"].append({
                "key": fresh["key"], "gate": "FAIL",
                "error": (
                    f"fresh prediction {path} is "
                    f"{'unstamped' if age is None else f'{age:.1f}h old'}"
                    f" (limit {max_age_hours}h) — re-run `python "
                    "tools/perf_gate.py --fresh-dir <dir>` for this "
                    "change")})
            ok = False
            continue
        # ONE gating path + row schema with tools/perf_gate.py
        row = gate_one(fresh, bank_dir, max_regress_pct,
                       allow_missing_baseline=False)
        verdict["results"].append(row)
        ok = ok and row["gate"] != "FAIL"
    return ok, verdict


def gate(fresh: Optional[Dict], bank: List[Tuple[str, Dict]],
         max_regress_pct: float,
         allow_missing_baseline: bool = False) -> Tuple[bool, Dict]:
    """(ok, verdict).  The baseline is the NEWEST usable banked round
    — the gate answers "did this change regress the trajectory", not
    "is this the best number ever banked" (the best-ever number is
    reported for context)."""
    verdict: Dict = {"max_regress_pct": max_regress_pct}
    fresh_m = usable_measurement(fresh)
    if fresh_m is None or fresh_m is not fresh:
        # an error line (or one only usable via last_good) is not a
        # fresh measurement of THIS change
        verdict["error"] = ("fresh bench line carries no usable "
                            "measurement (value<=0, missing "
                            "step_time_ms, or error payload)")
        verdict["fresh"] = fresh
        return False, verdict
    verdict["fresh"] = {k: fresh_m.get(k)
                        for k in ("value", "step_time_ms", "unit")}
    if not bank:
        verdict["baseline"] = None
        verdict["note"] = "no usable banked baseline"
        return allow_missing_baseline, verdict
    base_path, base = bank[-1]
    best = min(bank, key=lambda pm: pm[1]["step_time_ms"])
    verdict["baseline"] = {"path": base_path,
                           "value": base.get("value"),
                           "step_time_ms": base["step_time_ms"]}
    verdict["best_banked"] = {"path": best[0],
                              "step_time_ms": best[1]["step_time_ms"]}
    limit = float(base["step_time_ms"]) * (1 + max_regress_pct / 100.0)
    step_regress_pct = (float(fresh_m["step_time_ms"])
                        / float(base["step_time_ms"]) - 1) * 100.0
    verdict["step_time_regress_pct"] = round(step_regress_pct, 2)
    ok = float(fresh_m["step_time_ms"]) <= limit
    if not ok:
        verdict["error"] = (
            f"step_time_ms regressed {step_regress_pct:.1f}% vs "
            f"{base_path} ({fresh_m['step_time_ms']} > "
            f"{base['step_time_ms']} +{max_regress_pct}%)")
        return False, verdict
    # throughput cross-check when both sides carry it (value is
    # images/sec/chip — a DROP is the regression direction)
    if (base.get("value") or 0) > 0 and (fresh_m.get("value") or 0) > 0:
        tp_drop_pct = (1 - float(fresh_m["value"])
                       / float(base["value"])) * 100.0
        verdict["throughput_drop_pct"] = round(tp_drop_pct, 2)
        if tp_drop_pct > max_regress_pct:
            verdict["error"] = (
                f"throughput dropped {tp_drop_pct:.1f}% vs "
                f"{base_path} ({fresh_m['value']} < {base['value']} "
                f"-{max_regress_pct}%)")
            return False, verdict
    return True, verdict


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--fresh", required=True,
                   help="fresh bench JSON line / a run's stdout "
                        "(file path, or '-' for stdin)")
    p.add_argument("--bank", default=None,
                   help="glob of banked rounds (default: "
                        "BENCH_r*.json next to this repo's root)")
    p.add_argument("--max-regress-pct", type=float, default=10.0,
                   help="max tolerated step-time increase (and "
                        "throughput drop) in percent [%(default)s]")
    p.add_argument("--allow-missing-baseline", action="store_true",
                   help="exit 0 when no banked round carries a "
                        "usable measurement (first round on new "
                        "hardware)")
    p.add_argument("--predicted", action="store_true",
                   help="when the FRESHEST banked round is an error "
                        "round (the r01-r05 reality), gate on the "
                        "predicted-step-time bank instead of a stale "
                        "last_good carry — fresh predictions from "
                        "--pred-fresh vs artifacts/perf_pred_*.json")
    p.add_argument("--pred-fresh", default=None,
                   help="glob of fresh prediction artifacts (a "
                        "tools/perf_gate.py --fresh-dir run) "
                        "[<repo>/artifacts/perf_fresh/perf_pred_*"
                        ".json]")
    p.add_argument("--pred-bank", default=None,
                   help="prediction-baseline dir "
                        "[<repo>/artifacts]")
    p.add_argument("--pred-max-age-hours", type=float, default=24.0,
                   help="fresh prediction artifacts older than this "
                        "FAIL as stale (leftovers from an earlier "
                        "round must not gate this change) "
                        "[%(default)s]")
    args = p.parse_args(argv)

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

    if args.fresh == "-":
        text = sys.stdin.read()
    else:
        with open(args.fresh) as f:
            text = f.read()
    fresh = extract_metric_line(text)

    pattern = args.bank
    if pattern is None:
        pattern = os.path.join(repo, "BENCH_r*.json")

    # --predicted: with the freshest banked round itself an error
    # round AND no fresh measurement either, the measured trajectory
    # is frozen and a fresh error line proves nothing new — delegate
    # to the hermetic prediction bank, and SAY which evidence gated
    # the change.  A fresh HEALTHY line always gates measured: a
    # hardware window's real measurement is the strongest evidence of
    # the round and can show host-side regressions the roofline model
    # cannot see.
    error_round = freshest_round_is_error(pattern)
    if (args.predicted and error_round is not None
            and (fresh is None
                 or usable_measurement(fresh) is not fresh)):
        print(f"bench_gate: freshest banked round {error_round} is "
              "an error round and the fresh line carries no "
              "measurement — gating on PREDICTED step time "
              "(tools/perf_gate.py bank), not measured hardware "
              "evidence", file=sys.stderr)
        ok, verdict = gate_predicted(
            args.pred_fresh or os.path.join(
                repo, "artifacts", "perf_fresh", "perf_pred_*.json"),
            args.pred_bank or os.path.join(repo, "artifacts"),
            args.max_regress_pct,
            max_age_hours=args.pred_max_age_hours)
        verdict["measured_error_round"] = os.path.basename(error_round)
    else:
        if args.predicted:
            why = ("the fresh line carries a real measurement"
                   if error_round is not None
                   else "the freshest banked round carries a real "
                        "measurement")
            print(f"bench_gate: {why} — gating on MEASURED evidence "
                  "(--predicted only takes over when both are error "
                  "rounds)", file=sys.stderr)
        bank = load_bank(pattern)
        ok, verdict = gate(fresh, bank, args.max_regress_pct,
                           allow_missing_baseline=args
                           .allow_missing_baseline)
        verdict["evidence_source"] = "measured"
    verdict["gate"] = "PASS" if ok else "FAIL"
    print(json.dumps(verdict, indent=1))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
