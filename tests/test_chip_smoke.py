"""chip_smoke.py's contract, as far as a machine without a chip can
show it: no TPU → non-zero exit and ``"ok": false``; the last-line
schema; ``--four-chips`` runs only its phase (rehearsed here on four
virtual CPU devices at smoke widths, which never prints ``ok: true``).
"""

import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _run(argv, cwd=ROOT, script=SCRIPT, env=None, timeout=900):
    full_env = dict(os.environ, JAX_PLATFORMS="cpu")
    full_env.pop("XLA_FLAGS", None)
    full_env.update(env or {})
    out = subprocess.run([sys.executable, script] + argv, cwd=cwd,
                         env=full_env, capture_output=True, text=True,
                         timeout=timeout)
    lines = [l for l in out.stdout.splitlines() if l.strip()]
    return out, lines


def test_no_tpu_exits_nonzero_with_ok_false():
    out, lines = _run([])
    assert out.returncode not in (0, chip_smoke.REHEARSAL_EXIT)
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["phase"] == "device"
    assert last["device"]["platform"] == "cpu"
    assert not any('"ok": true' in l for l in lines)
    # nothing ran past the device phase
    assert [json.loads(l).get("phase") for l in lines[:-1]] == ["device"]


def test_alone_in_a_directory_exits_nonzero(tmp_path):
    """The script without the program fails (and reports no result)."""
    shutil.copy(SCRIPT, tmp_path / "chip_smoke.py")
    out, lines = _run([], cwd=str(tmp_path),
                      script=str(tmp_path / "chip_smoke.py"),
                      env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert json.loads(lines[-1])["ok"] is False
    assert "eksml_tpu" in lines[-1]


def test_last_line_schema():
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    ok = chip_smoke.result_line(True, device)
    assert json.dumps(ok) == (
        '{"ok": true, "device": {"platform": "tpu", '
        '"kind": "TPU v5 lite", "count": 1}}')
    bad = chip_smoke.result_line(False, device, phase="train",
                                 error="boom")
    assert bad["ok"] is False and bad["phase"] == "train"


@pytest.mark.parametrize("argv,phases", [
    ([], ("device", "kernel", "train", "resume")),
    (["--four-chips"], ("device", "four_chips")),
    (["--phases", "kernel"], ("device", "kernel")),
    (["--phases", "resume,kernel"], ("device", "kernel", "resume")),
])
def test_phase_selection(argv, phases):
    assert chip_smoke.phases_for(chip_smoke.parse_args(argv)) == phases


def test_four_chips_rehearsal_runs_only_its_phase(tmp_path):
    """Rehearsal 2 of the on-chip-measurement guide, kept as a test:
    the 1-device vs (4,1)-mesh comparison on four virtual CPU devices.
    Batch shards land on four devices, parameters are replicated, the
    4-device step holds an all-reduce, losses agree — and the run never
    claims ``ok: true`` (exit code 3 = rehearsal passed)."""
    out, lines = _run(
        ["--four-chips", "--rehearse", "--steps", "2"],
        env={"XLA_FLAGS": "--xla_force_host_platform_device_count=4",
             "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cache")})
    assert out.returncode == chip_smoke.REHEARSAL_EXIT, out.stderr[-2000:]
    rows = [json.loads(l) for l in lines]
    assert {r["phase"] for r in rows if "phase" in r} == {
        "device", "four_chips"}
    last = rows[-1]
    assert last["ok"] is False and last["rehearsal"] is True
    assert last["device"]["count"] == 4
    four = next(r for r in rows if r.get("run") == "four")
    assert four["batch_shard_devices"] == [0, 1, 2, 3]
    assert four["params_replicated"] and four["all_reduce_sites"] >= 1
    compare = next(r for r in rows if r.get("run") == "compare")
    assert max(compare["loss_rel_diff"]) <= chip_smoke.LOSS_TOL_FIRST
