"""Unit rungs of the chaos ladder (eksml_tpu/resilience/).

The subprocess rungs — SIGTERM-graceful, corrupt-latest-fallback,
NaN-rollback against a real ``python -m eksml_tpu.train`` — live in
tests/test_fault_tolerance.py (marked ``chaos`` + ``slow``); these are
the fast in-tier-1 halves: each pillar's mechanism exercised directly,
no model compile.  tools/chaos_matrix.sh runs both layers.
"""

import json
import os
import threading
import time

import jax.numpy as jnp
import numpy as np
import pytest

from eksml_tpu.resilience import (DivergenceSentinel, HangWatchdog,
                                  PreemptedError, PreemptionHandler,
                                  integrity, retry_call)
from eksml_tpu.resilience.sentinel import (OK, ROLLBACK, WATCH,
                                           DivergenceError)

pytestmark = pytest.mark.chaos


# ---- hang watchdog ---------------------------------------------------


def test_watchdog_fires_on_stall_and_names_the_phase(tmp_path):
    """A deliberately stalled step must produce a report naming the
    stalled phase and step, with a stack for every live thread."""
    wd = HangWatchdog(0.3, report_dir=str(tmp_path),
                      first_beat_factor=1.0).start()
    try:
        wd.beat("train_step", 7)
        # the "hang": no further beats, until the second report or a
        # generous cap (a loaded machine schedules the watchdog late)
        give_up = time.monotonic() + 10.0
        while wd.fires < 2 and time.monotonic() < give_up:
            time.sleep(0.05)
    finally:
        wd.stop()
    assert wd.fires >= 2, "persistent hang must re-report every deadline"
    report = open(wd.reports[0]).read()
    assert "stalled phase: train_step" in report
    assert "step: 7" in report
    # per-thread stacks: the main thread (stalled in sleep) plus the
    # watchdog's own thread are both live
    assert "MainThread" in report
    assert "eksml-hang-watchdog" in report
    assert "in test_watchdog_fires_on_stall_and_names_the_phase" in report


def test_watchdog_quiet_while_heartbeat_flows(tmp_path):
    wd = HangWatchdog(0.5, report_dir=str(tmp_path),
                      first_beat_factor=1.0).start()
    try:
        for i in range(8):
            wd.beat("train_step", i)
            time.sleep(0.1)
    finally:
        wd.stop()
    assert wd.fires == 0
    assert not [p for p in os.listdir(tmp_path)
                if p.startswith("hang_report")]


def test_watchdog_first_deadline_stretched_for_compile(tmp_path):
    """Step 1 includes the XLA compile: until the fit loop declares the
    compile done, the deadline is deadline*first_beat_factor — and
    ordinary beats must NOT end the stretch (the loop beats
    milliseconds before the multi-minute compiling call)."""
    wd = HangWatchdog(0.2, report_dir=str(tmp_path),
                      first_beat_factor=50.0).start()
    try:
        wd.beat("globalize_batch", 0)
        wd.beat("train_step", 1)  # beats precede the compiling call...
        time.sleep(0.7)           # ...which runs >deadline, <<stretched
        assert wd.fires == 0, \
            "a beat must not cancel the compile headroom"
        wd.end_compile_headroom()  # first jitted step returned
        time.sleep(0.7)
    finally:
        wd.stop()
    assert wd.fires >= 1


def test_watchdog_report_includes_provider_sections(tmp_path):
    """Registered report providers (the data loader's health surface)
    must land in the hang report — and a crashing provider must be
    contained, never suppress the report itself."""
    wd = HangWatchdog(0.2, report_dir=str(tmp_path),
                      first_beat_factor=1.0)
    wd.add_report_provider(
        "data pipeline", lambda: "queue depth: 3\nquarantined: 1")
    wd.add_report_provider("broken provider", lambda: 1 / 0)
    with wd:
        wd.beat("next_batch", 5)
        time.sleep(0.6)
    assert wd.reports
    report = open(wd.reports[0]).read()
    assert "--- data pipeline ---" in report
    assert "queue depth: 3" in report and "quarantined: 1" in report
    assert "report provider failed" in report
    assert "stalled phase: next_batch" in report


def test_watchdog_on_hang_escalation(tmp_path):
    fired = []
    wd = HangWatchdog(0.2, report_dir=str(tmp_path), first_beat_factor=1.0,
                      on_hang=lambda n, phase: fired.append((n, phase)))
    with wd:
        wd.beat("eval", 3)
        time.sleep(0.6)
    assert fired and fired[0] == (1, "eval")


# ---- divergence sentinel ---------------------------------------------


def test_sentinel_patience_then_rollback():
    s = DivergenceSentinel(patience=3, max_rollbacks=2)
    assert s.observe(1, 0.7) == OK
    assert s.observe(2, float("nan")) == WATCH
    assert s.observe(3, float("inf")) == WATCH
    assert s.observe(4, float("nan")) == ROLLBACK
    assert s.first_bad_step == 2


def test_sentinel_finite_observation_resets_patience():
    s = DivergenceSentinel(patience=2, max_rollbacks=2)
    assert s.observe(1, float("nan")) == WATCH
    assert s.observe(2, 0.5) == OK  # recovered: a blip, not divergence
    assert s.observe(3, float("nan")) == WATCH
    assert s.observe(4, float("nan")) == ROLLBACK


def test_sentinel_blocks_save_while_nonfinite():
    s = DivergenceSentinel(patience=5, max_rollbacks=1)
    assert s.allows_save()  # nothing observed yet
    s.observe(1, 1.0)
    assert s.allows_save()
    s.observe(2, float("nan"))
    assert not s.allows_save(), \
        "non-finite state must never reach ckpt.save"
    s.observe(3, 2.0)
    assert s.allows_save()


def test_sentinel_rollback_budget_exhaustion_is_diagnostic():
    s = DivergenceSentinel(patience=1, max_rollbacks=1)
    s.observe(5, float("nan"))
    s.register_rollback(5, 4)
    s.observe(7, float("nan"))
    with pytest.raises(DivergenceError) as ei:
        s.register_rollback(7, 4)
    msg = str(ei.value)
    assert "MAX_ROLLBACKS" in msg and "5->4" in msg
    assert "first non-finite loss at step" in msg


# ---- checkpoint integrity + fallback ---------------------------------


def _save_steps(tmp_path, steps=(1, 2, 3), digest=False):
    from eksml_tpu.utils import CheckpointManager

    ckpt = CheckpointManager(str(tmp_path / "run"), digest=digest)
    state = {"w": jnp.arange(8, dtype=jnp.float32),
             "step": jnp.asarray(0)}
    for s in steps:
        state = {"w": state["w"] + 1.0, "step": jnp.asarray(s)}
        assert ckpt.save(s, state)
    ckpt.wait()
    return ckpt, state


def _step_files(ckpt, step):
    out = []
    for base, _d, files in os.walk(os.path.join(ckpt.directory, str(step))):
        out += [os.path.join(base, f) for f in files]
    return sorted(out)


def test_manifests_written_after_commit(tmp_path):
    ckpt, _ = _save_steps(tmp_path, digest=True)
    assert integrity.list_manifest_steps(ckpt.directory) == [1, 2, 3]
    ok, reason = integrity.verify_step(ckpt.directory, 3)
    assert ok and "verified against manifest" in reason
    manifest = json.load(
        open(integrity.manifest_path(ckpt.directory, 3)))
    assert manifest["files"], "manifest must enumerate the step's files"
    assert all("sha256" in e for e in manifest["files"].values())


def test_topology_manifest_lifecycle_prune_and_quarantine(tmp_path):
    """Elastic topology (ISSUE 10): a manager constructed with a
    topology descriptor persists it per step next to the integrity
    manifest, prune drops it with the step, and quarantine removes it
    alongside the integrity manifest."""
    from eksml_tpu.utils import CheckpointManager

    topo = {"mesh_shape": [8, 1], "mesh_axes": ["data", "model"],
            "num_slices": 1, "strategy": "replicated",
            "fsdp_axis_size": 1, "num_devices": 8, "process_count": 1}
    ckpt = CheckpointManager(str(tmp_path / "run"), topology=topo)
    state = {"w": jnp.arange(8, dtype=jnp.float32)}
    for s in (1, 2, 3):
        assert ckpt.save(s, state)
    ckpt.wait()
    for s in (1, 2, 3):
        assert integrity.read_topology_manifest(
            ckpt.directory, s) is not None
    # prune follows the integrity manifests
    integrity.prune_manifests(ckpt.directory, keep_steps=[2, 3])
    assert integrity.read_topology_manifest(ckpt.directory, 1) is None
    assert integrity.read_topology_manifest(
        ckpt.directory, 2) is not None
    # quarantine drops the step's topology manifest with it
    integrity.quarantine_step(ckpt.directory, 3)
    assert integrity.read_topology_manifest(ckpt.directory, 3) is None
    assert not os.path.exists(
        integrity.topology_manifest_path(ckpt.directory, 3))
    ckpt.close()


def test_manifestless_checkpoint_restores_without_topology(tmp_path):
    """Back-compat: a manager WITHOUT a topology descriptor (library
    consumers) writes no topology manifest, and a topology-aware
    manager restores a pre-elastic checkpoint (no manifest = no
    evidence = no mismatch) without resharding or raising."""
    from eksml_tpu.utils import CheckpointManager

    ckpt, state = _save_steps(tmp_path)  # no topology passed
    assert not os.path.exists(
        integrity.topology_manifest_path(ckpt.directory, 3))
    ckpt.close()
    topo = {"mesh_shape": [8, 1], "mesh_axes": ["data", "model"],
            "num_slices": 1, "strategy": "replicated",
            "fsdp_axis_size": 1, "num_devices": 8, "process_count": 1}
    aware = CheckpointManager(str(tmp_path / "run"), topology=topo)
    out, step = aware.restore_with_fallback(state)
    assert step == 3 and float(out["w"][0]) == float(state["w"][0])
    aware.close()


def test_truncated_file_fails_verification(tmp_path):
    ckpt, _ = _save_steps(tmp_path)
    victim = _step_files(ckpt, 3)[0]
    open(victim, "w").close()  # truncate to 0 bytes
    ok, reason = integrity.verify_step(ckpt.directory, 3)
    assert not ok and "truncated" in reason


def test_transient_io_error_during_verification_is_retried(
        tmp_path, monkeypatch):
    """An NFS blip while *verifying* a manifest-listed file is
    evidence about the MOUNT, not the step's bytes: retry and verify —
    neither crash the relaunch nor hand the caller a false corruption
    verdict (which would quarantine a good checkpoint)."""
    import errno

    ckpt, _ = _save_steps(tmp_path)
    victim = _step_files(ckpt, 3)[0]
    real_getsize = os.path.getsize
    fails = {"left": 2}

    def flaky_getsize(path):
        if path == victim and fails["left"] > 0:
            fails["left"] -= 1
            raise OSError(errno.EIO, "Input/output error", path)
        return real_getsize(path)

    monkeypatch.setattr(os.path, "getsize", flaky_getsize)
    ok, reason = integrity.verify_step(ckpt.directory, 3)
    assert ok and "verified" in reason


def test_persistent_io_error_during_verification_raises_not_quarantines(
        tmp_path, monkeypatch):
    """A mount outage mid-verification must crash the relaunch (the
    orchestrator retries later) rather than return a corruption
    verdict — quarantining on unreachable-file evidence would let one
    outage destroy every good checkpoint newest-first."""
    import errno

    ckpt, _ = _save_steps(tmp_path)
    victim = _step_files(ckpt, 3)[0]
    real_getsize = os.path.getsize

    def dead_mount_getsize(path):
        if path == victim:
            raise OSError(errno.ESTALE, "Stale file handle", path)
        return real_getsize(path)

    monkeypatch.setattr(os.path, "getsize", dead_mount_getsize)
    with pytest.raises(RuntimeError, match="verifying checkpoint"):
        integrity.verify_step(ckpt.directory, 3)
    # the step dir was NOT quarantined out of the digit namespace
    assert os.path.isdir(os.path.join(ckpt.directory, "3"))


def test_restore_walks_back_past_corrupt_latest(tmp_path):
    """Chaos rung (b), in-process half: truncate + delete files inside
    the latest committed step — restore_with_fallback must land on the
    previous good step and quarantine the bad one so a re-save at that
    step commits cleanly."""
    ckpt, state = _save_steps(tmp_path)
    files = _step_files(ckpt, 3)
    open(files[0], "w").close()
    if len(files) > 1:
        os.remove(files[1])

    got = ckpt.restore_with_fallback(state)
    assert got is not None, "fallback must not give up while good steps exist"
    restored, step = got
    assert step == 2
    np.testing.assert_allclose(np.asarray(restored["w"]),
                               np.arange(8, dtype=np.float32) + 2.0)
    # the corrupt dir left the digit namespace (quarantined) ...
    assert ckpt.latest_step() == 2
    assert any(p.startswith("3.corrupt") for p in
               os.listdir(ckpt.directory))
    # ... so the re-run of step 3 can commit
    assert ckpt.save(3, {"w": restored["w"] + 1.0,
                         "step": jnp.asarray(3)})
    ckpt.wait()
    assert ckpt.restore_with_fallback(state)[1] == 3


def test_digest_catches_silent_bitflip(tmp_path):
    """Same-size corruption passes the size check; only the sha256
    manifest (RESILIENCE.CHECKPOINT_DIGEST) can catch it."""
    ckpt, state = _save_steps(tmp_path, digest=True)
    victim = max(_step_files(ckpt, 3), key=os.path.getsize)
    data = bytearray(open(victim, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(victim, "wb").write(bytes(data))
    ok, reason = integrity.verify_step(ckpt.directory, 3)
    assert not ok and "sha256" in reason


def test_missing_manifest_is_not_fatal(tmp_path):
    """A step committed right before the writer died has no manifest;
    it must still restore (structural check only) — rejecting it would
    discard real progress."""
    ckpt, state = _save_steps(tmp_path)
    os.remove(integrity.manifest_path(ckpt.directory, 3))
    ok, reason = integrity.verify_step(ckpt.directory, 3)
    assert ok and "no manifest" in reason
    got = ckpt.restore_with_fallback(state)
    assert got is not None and got[1] == 3


def test_all_steps_corrupt_returns_none(tmp_path):
    ckpt, state = _save_steps(tmp_path, steps=(1, 2))
    for s in (1, 2):
        for f in _step_files(ckpt, s):
            os.remove(f)
    assert ckpt.restore_with_fallback(state) is None


def test_verified_step_that_fails_restore_raises_not_quarantines(
        tmp_path, monkeypatch):
    """A step that verifies intact against its manifest but fails to
    deserialize is a SYSTEMATIC failure (changed state structure /
    sharding), not corruption: walking back would quarantine every
    good checkpoint one by one and silently restart from scratch —
    the worst possible outcome for the asset this layer protects."""
    ckpt, state = _save_steps(tmp_path)

    def broken_restore(state_like, step=None):
        raise ValueError("structure mismatch")

    monkeypatch.setattr(ckpt, "restore", broken_restore)
    with pytest.raises(RuntimeError, match="refusing to quarantine"):
        ckpt.restore_with_fallback(state)
    # every checkpoint is still in place, nothing renamed
    assert ckpt.all_steps() == [1, 2, 3]
    assert not [p for p in os.listdir(ckpt.directory)
                if "corrupt" in p]


def test_unverified_step_that_fails_restore_is_quarantined(
        tmp_path, monkeypatch):
    """Without a manifest there is no intactness evidence, so a failed
    restore IS the corruption signal (kill between commit and manifest
    write) — walk back."""
    ckpt, state = _save_steps(tmp_path)
    os.remove(integrity.manifest_path(ckpt.directory, 3))

    real_restore = ckpt.restore

    def flaky_restore(state_like, step=None):
        if step == 3:
            raise ValueError("truncated tensorstore")
        return real_restore(state_like, step)

    monkeypatch.setattr(ckpt, "restore", flaky_restore)
    got = ckpt.restore_with_fallback(state)
    assert got is not None and got[1] == 2
    assert any(p.startswith("3.corrupt")
               for p in os.listdir(ckpt.directory))


# ---- graceful preemption (in-process mechanism) ----------------------


def test_preemption_handler_flag_and_exit_code():
    import signal

    h = PreemptionHandler(exit_code=77).install()
    try:
        assert not h.requested
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.time() + 5
        while not h.requested and time.time() < deadline:
            time.sleep(0.01)
        assert h.requested
        # single-process agreement is the local flag, any step
        assert h.should_checkpoint(step=13)
        err = h.preempted(13)
        assert isinstance(err, SystemExit)  # clean interpreter exit
        assert isinstance(err, PreemptedError)
        assert err.code == 77 and err.step == 13
    finally:
        h.uninstall()


def test_preemption_install_is_main_thread_only():
    out = {}

    def worker():
        h = PreemptionHandler()
        h.install()  # must not raise, must not install
        out["installed"] = h._installed

    t = threading.Thread(target=worker)
    t.start()
    t.join()
    assert out["installed"] is False


# ---- retry/backoff ---------------------------------------------------


def test_retry_succeeds_after_transient_failures():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise ConnectionError("connection refused")
        return "up"

    slept = []
    assert retry_call(flaky, attempts=5, backoff_sec=0.5,
                      describe="rendezvous",
                      sleep=slept.append) == "up"
    assert len(calls) == 3
    assert slept == [0.5, 1.0], "exponential backoff between attempts"


def test_retry_runs_cleanup_between_attempts():
    cleanups = []

    def always_down():
        raise ConnectionError("refused")

    with pytest.raises(RuntimeError):
        retry_call(always_down, attempts=3, backoff_sec=0.0,
                   describe="x", cleanup=lambda: cleanups.append(1),
                   sleep=lambda _t: None)
    assert len(cleanups) == 2  # between attempts, not after the last


def test_retry_exhaustion_is_one_actionable_error():
    with pytest.raises(RuntimeError) as ei:
        retry_call(lambda: (_ for _ in ()).throw(
            ConnectionError("connection refused")),
            attempts=3, backoff_sec=0.0, describe="rendezvous with c:1234",
            sleep=lambda _t: None)
    msg = str(ei.value)
    assert "rendezvous with c:1234" in msg
    assert "3 attempt" in msg and "connection refused" in msg
    assert isinstance(ei.value.__cause__, ConnectionError)
