"""Fault injection: the chaos ladder against a real subprocess trainer.

SURVEY.md §5.3: the reference has NO fault injection anywhere and
restartPolicy Never — a dead rank means rerun by hand.  Our contract
is JobSet maxRestarts + Orbax auto-resume PLUS the in-process
resilience layer (eksml_tpu/resilience/); each rung here drives a real
``python -m eksml_tpu.train`` process through one failure mode:

  sigkill-resume      SIGKILL mid-run (no atexit, no flush — a TPU
                      preemption that missed its grace window); the
                      relaunch resumes from the last COMMITTED step.
  sigterm-graceful    SIGTERM (the grace window k8s actually gives);
                      the trainer forces a checkpoint at the next step
                      boundary and exits the documented resumable code,
                      so the relaunch loses at most the in-flight step.
  corrupt-latest      files inside the newest committed step dir are
                      truncated/deleted (a kill mid-flush on NFS); the
                      relaunch walks back to the previous good step
                      instead of crashing.
  nan-rollback        params poisoned with NaN mid-run (divergence);
                      the sentinel refuses to checkpoint the poison,
                      rolls back to the last good step, and the run
                      still completes.
  elastic-resume      SIGTERM at one topology, relaunch at another
                      (8 chips fsdp(8) → 4 chips fsdp(4) → back to 8,
                      global batch held): each crossing reshards the
                      restore onto the freshly-derived mesh
                      (checkpoint_resharded event + saved→current
                      diff) and the loss stream continues from the
                      forced checkpoint (ISSUE 10).
  proc-capacity-wave  the autoscaling operator (tools/eksml_operator)
                      drives an UNATTENDED 8→4→8 capacity wave for
                      two full cycles: a file capacity provider flips,
                      the operator's pure policy decides, and every
                      transition rides the forced-checkpoint path
                      (SIGTERM → exit 77 → relaunch at the decided
                      topology, elastic resume resharding); the loss
                      stream stays continuous throughout and the
                      merged goodput ledger attributes the
                      between-relaunch downtime (ISSUE 16).

Data-ingest rungs (eksml_tpu/data/robust.py, ISSUE 2):

  data-corrupt-jpeg   a truncated JPEG on the shared filesystem is
                      quarantined + substituted; the run continues.
  data-missing-file   a partially-staged (absent) image likewise.
  data-eio-recover    an injected transient EIO (NFS blip) retries
                      and recovers with ZERO quarantine trace.
  data-broken-pool    a decode worker dies (OOM kill); the affected
                      batch is re-read inline (quarantine only on
                      real decode evidence), the pool rebuilt once.
  proc-data-chaos     all three data faults in ONE 20-step on-disk
                      training run: completes with unchanged batch
                      shapes; the ledger lists exactly the two
                      permanent failures.
  proc-data-breaker   quarantine fraction forced above
                      MAX_QUARANTINE_FRAC: the run aborts with an
                      actionable error naming the ledger path.

Observability rungs (eksml_tpu/telemetry/, ISSUEs 5 and 13):

  debugz-profile      GET /debugz/profile?steps=N against a live
                      trainer with span tracing enabled: the capture
                      artifact lands as valid Chrome-trace JSON,
                      trace_summary --merge renders the timeline
                      naming dominant spans, and losses stay
                      bit-identical with tracing on.
  goodput-preempt     SIGTERM mid-run + relaunch: the cross-restart
                      goodput ledger reports nonzero downtime and
                      checkpoint_restore buckets and a ratio
                      consistent with the rung's wall-clock;
                      eksml_goodput_ratio scrapes live mid-run.

Subprocess rungs are ``chaos`` + ``slow`` (each launches 1-2
subprocess trainers; the module-shared compile cache keeps the total
to ONE tiny XLA compile); the in-process data rungs are ``chaos``
only.  tools/chaos_matrix.sh runs the ladder with a per-rung summary;
the fast unit halves live in tests/test_resilience.py and
tests/test_data_robust.py.
"""

import json
import math
import os
import signal
import subprocess
import sys
import time

import pytest

from conftest import TINY_MODEL_OVERRIDES

TINY = TINY_MODEL_OVERRIDES + [
    "TRAIN.STEPS_PER_EPOCH=2", "TRAIN.MAX_EPOCHS=3",  # 6 total steps
    "TRAIN.CHECKPOINT_PERIOD=1",                      # ckpt every 2 steps
    "TRAIN.LOG_PERIOD=1", "TRAIN.SYNC_CHECK_PERIOD=0",
]

pytestmark = pytest.mark.chaos


@pytest.fixture(scope="module")
def compile_cache(tmp_path_factory):
    """One persistent-compile-cache dir for every rung: the tiny model
    has ONE program shape, so only the first subprocess pays the XLA
    compile and every later launch (and relaunch) hits the cache."""
    return str(tmp_path_factory.mktemp("xla_cache"))


def _launch(logdir, cache_dir, log_path, config=TINY, synthetic=True,
            extra_env=None):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": cache_dir})
    env.update(extra_env or {})
    cmd = [sys.executable, "-m", "eksml_tpu.train", "--logdir", logdir]
    if synthetic:
        cmd.append("--synthetic")
    cmd += ["--config"] + config
    # child output goes to a FILE: an undrained PIPE fills (~64KB) with
    # XLA chatter and deadlocks the child mid-compile
    with open(log_path, "w") as logf:  # child inherits the fd
        return subprocess.Popen(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(
                os.path.dirname(os.path.abspath(__file__))))


def _committed_ckpt_steps(logdir):
    """Orbax-committed checkpoint steps (tmp dirs from an in-flight
    async save and quarantined ``<step>.corrupt-*`` dirs are excluded
    by the digits-only filter)."""
    d = os.path.join(logdir, "checkpoints")
    if not os.path.isdir(d):
        return []
    return sorted(int(p) for p in os.listdir(d) if p.isdigit())


def _metric_rows(logdir):
    path = os.path.join(logdir, "metrics.jsonl")
    rows = []
    if os.path.exists(path):
        for line in open(path):
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # torn write from a killed process
    return rows


def _steps_logged(logdir):
    return [r["step"] for r in _metric_rows(logdir)
            if "total_loss" in r]


def _event_kinds(logdir, host=0):
    """Flight-recorder event kinds, file order (= time order per
    host) — the post-mortem contract the telemetry rungs assert."""
    path = os.path.join(logdir, f"events-host{host}.jsonl")
    kinds = []
    if os.path.exists(path):
        for line in open(path):
            try:
                kinds.append(json.loads(line)["kind"])
            except (json.JSONDecodeError, KeyError):
                continue
    return kinds


def _scrape_metrics(logdir, host=0, budget=60):
    """Read the trainer's ephemeral exporter port (TELEMETRY.PORT=0
    writes it to <logdir>/telemetry-host<i>.port) and scrape /metrics."""
    import urllib.request

    port_file = os.path.join(logdir, f"telemetry-host{host}.port")
    deadline = time.time() + budget
    while not os.path.exists(port_file):
        assert time.time() < deadline, "telemetry port file never appeared"
        time.sleep(0.2)
    port = int(open(port_file).read())
    return urllib.request.urlopen(
        f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()


def _wait_for_first_step(proc, logdir, log_path, budget=900):
    deadline = time.time() + budget
    while time.time() < deadline:
        if _steps_logged(logdir):
            return
        if proc.poll() is not None:
            pytest.fail("trainer exited before first step:\n"
                        + open(log_path).read()[-2000:])
        time.sleep(0.5)
    pytest.fail("no training step within budget")


# ---- rung 1: SIGKILL (the unlucky preemption) ------------------------


@pytest.mark.slow
def test_sigkill_then_resume(tmp_path, compile_cache):
    logdir = str(tmp_path / "run")

    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1)
    try:
        _wait_for_first_step(proc, logdir, log1)
        # preemption: no SIGTERM courtesy, no flush
        proc.send_signal(signal.SIGKILL)
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()

    first_steps = _steps_logged(logdir)
    if max(first_steps) >= 6:
        pytest.skip("run outran the kill on this machine — inconclusive")
    # what the relaunch may restore: checkpoints COMMITTED before the
    # kill (metrics for a step flush before its async save commits, so
    # killed_at alone proves nothing about checkpoint existence)
    committed = _committed_ckpt_steps(logdir)

    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2)
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()

    steps = _steps_logged(logdir)
    assert max(steps) == 6, steps
    # auto-resume semantics: the second process starts exactly after
    # the last COMMITTED checkpoint (from scratch if none committed)
    expected_start = (max(committed) + 1) if committed else 1
    second_run_steps = steps[len(first_steps):]
    assert second_run_steps == list(range(expected_start, 7)), (
        committed, first_steps, second_run_steps)


# ---- rung 2: SIGTERM (the graceful preemption contract) --------------


@pytest.mark.slow
def test_sigterm_graceful_preempt_then_resume(tmp_path, compile_cache):
    """Chaos rung (a): SIGTERM mid-run → a forced checkpoint commits at
    the next step boundary, the process exits with the documented
    resumable code, and the relaunch loses at most the in-flight step."""
    logdir = str(tmp_path / "run")
    # checkpoint period of 2 epochs = every 4 steps, so the forced
    # save is distinguishable from a periodic one at early steps;
    # TELEMETRY.PORT=0 = ephemeral exporter port published to the
    # logdir (the acceptance scrape below)
    config = [c for c in TINY if "CHECKPOINT_PERIOD" not in c] + [
        "TRAIN.CHECKPOINT_PERIOD=2", "TELEMETRY.PORT=0"]

    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config)
    try:
        _wait_for_first_step(proc, logdir, log1)
        # acceptance scrape (ISSUE 4): a live smoke train serves valid
        # OpenMetrics with an aggregated host_max gauge and the
        # resilience counters, from the ephemeral port it published
        from test_telemetry import parse_openmetrics

        fams = parse_openmetrics(_scrape_metrics(logdir))
        assert fams["eksml_hosts_step_time_ms_max"]["samples"][
            "eksml_hosts_step_time_ms_max"] > 0.0
        assert fams["eksml_resilience_preemptions"]["samples"][
            "eksml_resilience_preemptions_total"] == 0.0
        assert "eksml_train_total_loss" in fams
        proc.send_signal(signal.SIGTERM)  # k8s grace window begins
        rc = proc.wait(timeout=300)       # forced commit, then exit
    finally:
        if proc.poll() is None:
            proc.kill()

    first_steps = _steps_logged(logdir)
    if rc == 0 and max(first_steps) >= 6:
        pytest.skip("run outran the signal on this machine — "
                    "inconclusive")
    # the documented "preempted, resumable" exit code — the value the
    # charts' podFailurePolicy maps to restart-not-fail
    from eksml_tpu.config import config as global_config

    assert rc == global_config.RESILIENCE.PREEMPT_EXIT_CODE, (
        rc, open(log1).read()[-2000:])
    out1 = open(log1).read()
    assert "forcing checkpoint" in out1
    assert "exiting resumable" in out1
    # the forced checkpoint committed AT the step boundary where the
    # signal was honored: nothing in flight was lost
    committed = _committed_ckpt_steps(logdir)
    assert committed, "graceful preemption must leave a checkpoint"
    assert max(committed) == max(first_steps), (committed, first_steps)
    # flight recorder (ISSUE 4): the preemption chain landed in
    # events-host0.jsonl IN ORDER — signal seen, forced commit,
    # resumable exit (indexes, not positions: a periodic save may
    # legitimately precede the signal)
    kinds = _event_kinds(logdir)
    i_sig, i_exit = kinds.index("sigterm"), kinds.index("preempt_exit")
    assert i_sig < i_exit, kinds
    assert "checkpoint_save" in kinds[i_sig:i_exit], kinds

    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2, config)
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()

    steps = _steps_logged(logdir)
    assert max(steps) == 6, steps
    # relaunch resumes exactly after the forced step: at most the
    # in-flight step is recomputed, nothing is lost
    second_run_steps = steps[len(first_steps):]
    assert second_run_steps == list(range(max(committed) + 1, 7)), (
        committed, first_steps, second_run_steps)
    # the relaunch appended its own run_start + restore events to the
    # SAME per-host event file — one segmented post-mortem stream
    kinds = _event_kinds(logdir)
    assert kinds.count("run_start") == 2, kinds
    assert "checkpoint_restore" in kinds[kinds.index("preempt_exit"):], (
        kinds)


# ---- rung 3: corrupt latest checkpoint -------------------------------


@pytest.mark.slow
def test_corrupt_latest_checkpoint_falls_back(tmp_path, compile_cache):
    """Chaos rung (b): truncating/deleting files inside the newest
    committed ``checkpoints/<step>/`` (a kill mid-flush on the shared
    filesystem) must make the relaunch restore the PREVIOUS good step —
    not crash, and not trust latest_step() blindly."""
    logdir = str(tmp_path / "run")
    short = [c for c in TINY if "MAX_EPOCHS" not in c] + [
        "TRAIN.MAX_EPOCHS=2"]  # 4 steps: ckpts at 2 and 4

    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, short)
    try:
        assert proc.wait(timeout=900) == 0, open(log1).read()[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()
    assert _committed_ckpt_steps(logdir) == [2, 4]
    first_steps = _steps_logged(logdir)

    # the chaos: step 4 committed, then its contents die mid-flush
    step_dir = os.path.join(logdir, "checkpoints", "4")
    victims = sorted(
        os.path.join(base, f)
        for base, _d, files in os.walk(step_dir) for f in files)
    assert victims, "expected files inside the committed step dir"
    open(victims[0], "w").close()  # truncate
    for extra in victims[1:2]:
        os.remove(extra)           # and delete another

    # relaunch with a longer schedule: must resume from step 2
    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2, TINY)  # 6 steps
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()

    out2 = open(log2).read()
    assert "falling back to an earlier step" in out2
    assert "resuming from checkpoint step 2" in out2
    steps = _steps_logged(logdir)
    second_run_steps = steps[len(first_steps):]
    assert second_run_steps == list(range(3, 7)), second_run_steps
    # the corrupt dir was quarantined out of the digit namespace and
    # the re-run of step 4 committed a GOOD checkpoint in its place
    ckpt_dir = os.path.join(logdir, "checkpoints")
    assert any(p.startswith("4.corrupt") for p in os.listdir(ckpt_dir))
    assert 4 in _committed_ckpt_steps(logdir)
    assert max(_committed_ckpt_steps(logdir)) == 6


# ---- rung 4: NaN divergence rollback ---------------------------------


@pytest.mark.slow
def test_nan_loss_rolls_back_and_never_checkpoints_poison(
        tmp_path, compile_cache):
    """Chaos rung (c): params poisoned with NaN mid-run.  The sentinel
    must (1) refuse to checkpoint while the loss is non-finite, (2)
    roll back to the last good step after NAN_PATIENCE consecutive bad
    observations, and (3) let the run complete on fresh batches."""
    logdir = str(tmp_path / "run")
    config = TINY + [
        "RESILIENCE.FAULT_INJECT_NAN_STEP=3",  # poison after step 3
        "RESILIENCE.NAN_CHECK_PERIOD=1",       # observe every step
        "RESILIENCE.NAN_PATIENCE=2",
        "RESILIENCE.MAX_ROLLBACKS=2",
    ]

    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config)
    try:
        assert proc.wait(timeout=900) == 0, open(log1).read()[-2000:]
    finally:
        if proc.poll() is None:
            proc.kill()

    out = open(log1).read()
    assert "chaos: injecting NaN into params at step 3" in out
    # (1) the checkpoint boundary at step 4 fell inside the poisoned
    # window: the save guard must have refused it
    assert "skipping checkpoint at step 4" in out
    # (2) patience=2 exhausted at step 5 → rollback to checkpoint 2
    assert "divergence rollback 1/2: step 5 -> checkpoint step 2" in out
    # (3) the re-run completed
    assert "training complete at 6 steps" in out

    steps = _steps_logged(logdir)
    # first pass logs 1..4 (step 5's observation rolls back before the
    # log write), then the re-run logs 3..6 on fresh data
    assert steps == [1, 2, 3, 4, 3, 4, 5, 6], steps
    rows = {r["step"]: r for r in _metric_rows(logdir)
            if "total_loss" in r}
    assert math.isfinite(rows[6]["total_loss"])
    # rollback is visible to the operator in the metric stream too
    assert any("resilience/rollback_from" in r
               for r in _metric_rows(logdir))
    # every committed checkpoint postdates recovery or predates the
    # poison: 2 (pre-poison), 4 and 6 (re-run); none from the window
    assert _committed_ckpt_steps(logdir) == [2, 4, 6]

    # flight recorder (ISSUE 4): the divergence chain is captured in
    # order — first bad observation, the refused save, the second bad
    # observation, the restore, the rollback registration
    interesting = ("nan_observed", "checkpoint_skipped", "rollback",
                   "checkpoint_restore")
    kinds = [k for k in _event_kinds(logdir) if k in interesting]
    assert kinds == ["nan_observed", "checkpoint_skipped",
                     "nan_observed", "checkpoint_restore",
                     "rollback"], kinds
    # metrics.jsonl stayed strict JSON through the non-finite window
    # (the sanitization satellite): the poisoned rows read as null +
    # raw repr, never bare NaN tokens
    def reject(tok):
        raise AssertionError(f"bare non-JSON token {tok!r}")

    rows4 = [r for l in open(os.path.join(logdir, "metrics.jsonl"))
             for r in [json.loads(l, parse_constant=reject)]
             if r.get("step") == 4 and "total_loss" in r]
    assert any(r["total_loss"] is None
               and r["total_loss_raw_repr"] == "nan" for r in rows4), (
        rows4)

    # run_report renders the same incident from the artifacts (the
    # acceptance post-mortem path)
    from tools import run_report

    report = run_report.render_report(logdir)
    assert "| rollback |" in report
    assert "non-finite scalar rows" in report
    assert "### Segment 1" in report


# ---- rung 4b: on-demand profile capture (debugz + span tracing) ------


@pytest.mark.slow
def test_debugz_profile_capture_midrun_with_tracing(tmp_path,
                                                    compile_cache):
    """Chaos rung (ISSUE 5): a mid-run ``GET /debugz/profile?steps=2``
    starts a bounded capture through the ProfileTrigger; the span
    artifact lands as valid Chrome-trace JSON whose spans carry
    step/host attribution, ``trace_summary --merge`` renders a
    timeline naming the dominant span of the slowest step, and losses
    are bit-identical to a tracing-disabled run of the same
    schedule."""
    import urllib.request

    logdir = str(tmp_path / "run")
    config = [c for c in TINY if "MAX_EPOCHS" not in c] + [
        "TRAIN.MAX_EPOCHS=8",  # 16 steps: room for the mid-run capture
        "TELEMETRY.PORT=0",
        "TELEMETRY.TRACING.ENABLED=True",
    ]
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config)
    try:
        _wait_for_first_step(proc, logdir, log1)
        port_file = os.path.join(logdir, "telemetry-host0.port")
        port = int(open(port_file).read())
        resp = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debugz/profile?steps=2",
            timeout=30).read())
        accepted = resp["status"] == "accepted"
        # the stacks endpoint answers against the live trainer too
        stacks = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/debugz/stacks",
            timeout=30).read().decode()
        assert "MainThread" in stacks
        rc = proc.wait(timeout=900)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 0, open(log1).read()[-3000:]
    if not accepted:
        pytest.skip("run outran the debugz request on this machine — "
                    "inconclusive")

    # flight recorder: the capture chain landed in order
    kinds = _event_kinds(logdir)
    assert "profile_capture" in kinds, kinds
    assert "profile_capture_done" in kinds[
        kinds.index("profile_capture"):], kinds

    # span artifact: valid Chrome-trace JSON, step/host attribution
    trace_path = os.path.join(logdir, "trace-host0.json")
    assert os.path.exists(trace_path), os.listdir(logdir)
    doc = json.load(open(trace_path))
    spans = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    assert spans, "capture produced no spans"
    assert all(e["args"]["host"] == 0 for e in spans)
    step_spans = [e for e in spans if e["name"] == "train_step"]
    assert step_spans and all(
        isinstance(e["args"]["step"], int) for e in step_spans)

    # acceptance: the merge renders ONE timeline and names the
    # dominant span of the slowest step
    from tools import run_report, trace_summary

    merged = trace_summary.merge_host_traces(logdir)
    assert merged["hosts"] == [0]
    assert merged["steps_covered"] >= 2
    assert merged["slow_steps"][0].get("dominant_span"), merged
    report = run_report.render_report(logdir)
    assert "## Slow steps (span tracing)" in report
    assert merged["slow_steps"][0]["dominant_span"] in report

    # bit-identity: the same 16-step schedule with tracing DISABLED
    # (the default) must produce the exact same loss stream
    logdir2 = str(tmp_path / "run2")
    log2 = str(tmp_path / "run2.log")
    config2 = [c for c in config
               if not c.startswith("TELEMETRY.TRACING")]
    proc2 = _launch(logdir2, compile_cache, log2, config2)
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()
    losses1 = {r["step"]: r["total_loss"] for r in _metric_rows(logdir)
               if "total_loss" in r}
    losses2 = {r["step"]: r["total_loss"]
               for r in _metric_rows(logdir2) if "total_loss" in r}
    assert losses1 == losses2, "tracing perturbed the loss stream"


# ---- rung 4b2: goodput ledger across a preemption (ISSUE 13) ---------


@pytest.mark.slow
def test_goodput_ledger_across_preempt_relaunch(tmp_path,
                                                compile_cache):
    """Chaos rung proc-goodput-preempt: SIGTERM mid-run, relaunch,
    and the cross-restart goodput ledger must account for the whole
    timeline — a nonzero ``downtime`` bucket spanning the restart
    gap, a nonzero ``checkpoint_restore`` bucket from the resume, a
    goodput ratio consistent with the rung's measured wall-clock,
    and ``eksml_goodput_ratio`` scraped LIVE from /metrics mid-run
    (the elastic controller's input exists while the run is up, not
    only post-mortem)."""
    logdir = str(tmp_path / "run")
    config = [c for c in TINY if "CHECKPOINT_PERIOD" not in c] + [
        "TRAIN.CHECKPOINT_PERIOD=2", "TELEMETRY.PORT=0"]

    t_rung0 = time.time()
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config)
    try:
        _wait_for_first_step(proc, logdir, log1)
        # acceptance scrape: the run-level SLI is live mid-run, with
        # the badput taxonomy preregistered and the compile bucket
        # already nonzero (the first-shape compile just happened)
        from test_telemetry import parse_openmetrics

        fams = parse_openmetrics(_scrape_metrics(logdir))
        ratio = fams["eksml_goodput_ratio"]["samples"][
            "eksml_goodput_ratio"]
        assert 0.0 < ratio <= 1.0, ratio
        assert fams["eksml_badput_seconds"]["samples"][
            'eksml_badput_seconds_total{bucket="compile"}'] > 0.0
        assert 'eksml_badput_seconds_total{bucket="downtime"}' in \
            fams["eksml_badput_seconds"]["samples"]
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()

    first_steps = _steps_logged(logdir)
    if rc == 0 and max(first_steps) >= 6:
        pytest.skip("run outran the signal on this machine — "
                    "inconclusive")
    from eksml_tpu.config import config as global_config

    assert rc == global_config.RESILIENCE.PREEMPT_EXIT_CODE, (
        rc, open(log1).read()[-2000:])
    # the restart gap the ledger must recover: a REAL pause between
    # the segment's death and its relaunch
    forced_sleep = 3.0
    time.sleep(forced_sleep)

    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2, config)
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()
    t_rung1 = time.time()

    # both segments banked their ledger lines (final snapshot on the
    # preemption exit path included)
    bank = [json.loads(line) for line in
            open(os.path.join(logdir, "goodput-host0.jsonl"))]
    assert any(row.get("final") for row in bank), (
        "preempted segment never banked its final snapshot")
    assert len({row["segment_start"] for row in bank}) == 2, (
        "expected banked snapshots from both segments")

    # the merged cross-restart ledger, via the same builder the
    # report tools render
    from eksml_tpu.telemetry.goodput import build_ledger

    ledger = build_ledger(logdir)
    assert len(ledger["segments"]) == 2, ledger["segments"]
    assert ledger["buckets"]["downtime"] >= forced_sleep * 0.8, ledger
    assert ledger["buckets"]["checkpoint_restore"] > 0.0, (
        ledger["buckets"])
    # ratio consistency with the rung's known timeline: the ledger's
    # wall fits inside the measured rung wall, the ratio IS
    # train/wall, and everything accounted stays within the wall
    rung_wall = t_rung1 - t_rung0
    assert 0.0 < ledger["total_wall_s"] <= rung_wall + 5.0, (
        ledger["total_wall_s"], rung_wall)
    assert ledger["goodput_ratio"] == pytest.approx(
        ledger["train_s"] / ledger["total_wall_s"], rel=1e-3)
    assert 0.0 < ledger["goodput_ratio"] <= 1.0
    accounted = sum(ledger["buckets"].values())
    assert accounted <= ledger["total_wall_s"] * 1.05 + 1.0, (
        accounted, ledger["total_wall_s"])
    # the new flight events landed in order around the first step
    kinds = _event_kinds(logdir)
    assert kinds.index("compile_start") < kinds.index("compile_done")
    # and the relaunch segment carries its own compile window too
    assert kinds.count("compile_start") == 2, kinds


# ---- rung 4c: elastic topology grow/shrink relaunch (ISSUE 10) -------


def _device_count_env(n):
    """Child env overriding the conftest-inherited 8-fake-device rig:
    the relaunched trainer sees a DIFFERENT topology (the preemptible-
    capacity scenario: the fleet shrank or grew between launches).
    Only the device-count flag is substituted — any other inherited
    XLA_FLAGS must reach the relaunch unchanged, or the grow/shrink
    children would run under a different XLA configuration than run A
    and skew the loss-stream comparison."""
    kept = [f for f in os.environ.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f]
    kept.append(f"--xla_force_host_platform_device_count={n}")
    return {"XLA_FLAGS": " ".join(kept)}


def _elastic_config(chips, batch_per_chip, epochs):
    """fsdp config at a given device count, holding the GLOBAL batch
    (chips × batch) at 8 so the LR schedule, steps/epoch and loss
    stream are comparable across topologies."""
    return [c for c in TINY if "MAX_EPOCHS" not in c] + [
        f"TRAIN.MAX_EPOCHS={epochs}",
        f"TRAIN.NUM_CHIPS={chips}",
        f"TRAIN.BATCH_SIZE_PER_CHIP={batch_per_chip}",
        "TRAIN.SHARDING.STRATEGY=fsdp",
    ]


@pytest.mark.slow
def test_elastic_resume_grow_shrink(tmp_path, compile_cache):
    """Chaos rung (ISSUE 10): SIGTERM a run at topology A (8 chips,
    fsdp(8)), relaunch at topology B (4 chips, fsdp(4), same global
    batch) — the relaunch reshards the forced checkpoint onto the new
    mesh, logs the saved→current diff, records the
    ``checkpoint_resharded`` event, and continues the loss stream from
    the forced step.  Then grow BACK to 8 chips from B's final
    checkpoint: the other direction reshards too and the run completes
    its extended schedule."""
    logdir = str(tmp_path / "run")

    # -- topology A: 8 chips, killed mid-run --------------------------
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1,
                   _elastic_config(8, 1, epochs=3))  # 6 steps
    try:
        _wait_for_first_step(proc, logdir, log1)
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    first_steps = _steps_logged(logdir)
    if rc == 0 and max(first_steps) >= 6:
        pytest.skip("run outran the signal on this machine — "
                    "inconclusive")
    from eksml_tpu.config import config as global_config

    assert rc == global_config.RESILIENCE.PREEMPT_EXIT_CODE, (
        rc, open(log1).read()[-2000:])
    committed = _committed_ckpt_steps(logdir)
    assert committed, "graceful preemption must leave a checkpoint"
    forced = max(committed)

    # -- topology B: SHRINK to 4 chips, complete the schedule ---------
    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2,
                    _elastic_config(4, 2, epochs=3),
                    extra_env=_device_count_env(4))
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()
    out2 = open(log2).read()
    assert f"resuming from checkpoint step {forced}" in out2
    assert "resharded across a topology change" in out2
    # the one-line saved→current diff names the shrink
    assert "num_devices: 8 -> 4" in out2
    steps = _steps_logged(logdir)
    shrink_steps = steps[len(first_steps):]
    assert shrink_steps == list(range(forced + 1, 7)), (
        forced, first_steps, shrink_steps)
    # flight recorder: the reshard landed between restore and the
    # continued stream
    kinds = _event_kinds(logdir)
    assert "checkpoint_resharded" in kinds, kinds
    assert "checkpoint_restore" in kinds, kinds

    # -- topology C: GROW back to 8 chips on an extended schedule -----
    log3 = str(tmp_path / "run3.log")
    proc3 = _launch(logdir, compile_cache, log3,
                    _elastic_config(8, 1, epochs=5))  # 10 steps total
    try:
        assert proc3.wait(timeout=900) == 0, open(log3).read()[-2000:]
    finally:
        if proc3.poll() is None:
            proc3.kill()
    out3 = open(log3).read()
    assert "resuming from checkpoint step 6" in out3
    assert "resharded across a topology change" in out3
    assert "num_devices: 4 -> 8" in out3
    steps = _steps_logged(logdir)
    grow_steps = steps[len(first_steps) + len(shrink_steps):]
    assert grow_steps == list(range(7, 11)), grow_steps
    # the loss stream stayed finite through both topology crossings
    rows = {r["step"]: r["total_loss"] for r in _metric_rows(logdir)
            if "total_loss" in r}
    assert all(math.isfinite(v) for v in rows.values()), rows
    # two reshard events total (shrink + grow), visible to run_report
    kinds = _event_kinds(logdir)
    assert kinds.count("checkpoint_resharded") == 2, kinds
    from tools import run_report

    report = run_report.render_report(logdir)
    assert "## Elastic resume (topology changes)" in report
    assert "num_devices: 4 -> 8" in report


# ---- rung 4d: autoscaling operator capacity wave (ISSUE 16) ----------


def _autoscale_rows(logdir, host=0):
    """Banked operator decisions (<logdir>/autoscale-host<i>.jsonl)."""
    path = os.path.join(logdir, f"autoscale-host{host}.jsonl")
    rows = []
    if os.path.exists(path):
        for line in open(path):
            try:
                rows.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return rows


def _set_capacity(path, chips):
    """Atomic capacity-file rewrite (the wave driver's half of the
    FileCapacityProvider torn-read contract)."""
    with open(path + ".tmp", "w") as f:
        json.dump({"available_chips": chips,
                   "preemption_forecast": 0.0}, f)
    os.replace(path + ".tmp", path)


@pytest.mark.slow
def test_operator_capacity_wave(tmp_path, compile_cache):
    """Headline chaos rung (ISSUE 16): the autoscaling operator closes
    the resilience loop UNATTENDED.  A file capacity provider flips
    8→4→8→4→8 (two full cycles); each flip the operator's pure policy
    decides shrink/grow and actuates through the forced-checkpoint
    path — SIGTERM, trainer checkpoints and exits 77, relaunch at the
    decided topology, elastic resume reshards.  The test only moves
    the capacity file and watches the evidence trail: every transition
    banked with exit code 77, a reshard event per crossing, the loss
    stream contiguous and finite across all five segments, the merged
    goodput ledger attributing bounded between-relaunch downtime, and
    run_report's Autoscaling section joining it all."""
    logdir = str(tmp_path / "run")
    os.makedirs(logdir)
    cap = str(tmp_path / "capacity.json")
    _set_capacity(cap, 8)
    t_wave0 = time.time()

    # a long schedule the wave runs inside; the operator is stopped by
    # the test, not by schedule exhaustion
    train_cfg = [c for c in TINY if "MAX_EPOCHS" not in c] + [
        "TRAIN.MAX_EPOCHS=40", "TRAIN.SHARDING.STRATEGY=fsdp"]
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": compile_cache})
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    cmd = [sys.executable, os.path.join(repo, "tools",
                                        "eksml_operator.py"),
           "--logdir", logdir, "--mode", "local",
           "--capacity-file", cap, "--fake-chips", "--synthetic",
           "--global-batch", "8", "--interval", "0.5",
           "--initial-chips", "8",
           "--config", "RESILIENCE.AUTOSCALE.CHIP_OPTIONS=(4,8)",
           "RESILIENCE.AUTOSCALE.COOLDOWN_SEC=0",
           "RESILIENCE.AUTOSCALE.GROW_PATIENCE=1",
           "RESILIENCE.AUTOSCALE.SHRINK_PATIENCE=1",
           "--train-config"] + train_cfg
    op_log = str(tmp_path / "operator.log")
    with open(op_log, "w") as logf:  # file, not pipe (see _launch)
        proc = subprocess.Popen(cmd, env=env, stdout=logf,
                                stderr=subprocess.STDOUT, cwd=repo)

    def relaunches():
        return [r for r in _autoscale_rows(logdir)
                if r.get("kind") == "relaunch"]

    deadline = time.time() + 840

    def wait_for(pred, what):
        while time.time() < deadline:
            if pred():
                return
            if proc.poll() is not None:
                pytest.fail(f"operator exited rc={proc.returncode} "
                            f"waiting for {what}:\n"
                            + open(op_log).read()[-2000:])
            time.sleep(0.5)
        pytest.fail(f"timed out waiting for {what}")

    try:
        wait_for(lambda: len(_steps_logged(logdir)) >= 2,
                 "first steps at 8 chips")
        # two full 8→4→8 cycles, each crossing confirmed by a banked
        # relaunch AND resumed step progress before the next flip
        for i, (chips, want) in enumerate(
                [(4, 1), (8, 2), (4, 3), (8, 4)]):
            _set_capacity(cap, chips)
            wait_for(lambda: len(relaunches()) >= want,
                     f"relaunch {want} (cap={chips})")
            n0 = len(_steps_logged(logdir))
            wait_for(lambda: len(_steps_logged(logdir)) >= n0 + 2,
                     f"steps after relaunch {want}")
        # the operator's own exporter is live mid-wave, with the whole
        # preregistered eksml_autoscale_* family present
        port = int(open(os.path.join(
            logdir, "telemetry-operator.port")).read())
        import urllib.request
        expo = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30
        ).read().decode()
    finally:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=300)
        except subprocess.TimeoutExpired:
            proc.kill()
            rc = proc.wait(timeout=30)
    assert rc == 0, open(op_log).read()[-2000:]
    t_wave1 = time.time()

    # every transition went through the forced-checkpoint path: the
    # stopped trainer exited the documented resumable code each time
    from eksml_tpu.config import config as global_config

    waves = relaunches()
    assert len(waves) >= 4, waves
    assert [w["action"] for w in waves[:4]] == [
        "shrink", "grow", "shrink", "grow"], waves
    assert all(w["exit_code"]
               == global_config.RESILIENCE.PREEMPT_EXIT_CODE
               for w in waves), waves
    assert [w["target_chips"] for w in waves[:4]] == [4, 8, 4, 8]

    # each crossing resharded the restore (ISSUE 10's machinery)
    kinds = _event_kinds(logdir)
    assert kinds.count("checkpoint_resharded") >= 4, kinds
    # the operator's own flight stream tells the decision story
    op_kinds = _event_kinds(logdir, host="op")
    assert op_kinds[0] == "scale_launch"
    assert op_kinds.count("scale_relaunch") >= 4
    assert op_kinds.count("scale_decision") >= 4
    assert "scale_hold" in op_kinds  # steady-state ticks recorded too

    # loss stream: contiguous from step 1, no repeats, all finite
    steps = _steps_logged(logdir)
    assert steps == list(range(1, len(steps) + 1)), steps
    assert len(steps) >= 10, steps  # progress in all five segments
    rows = {r["step"]: r["total_loss"] for r in _metric_rows(logdir)
            if "total_loss" in r}
    assert all(math.isfinite(v) for v in rows.values()), rows

    # operator metrics scraped live: decisions counted by action,
    # relaunches counted, target published
    assert 'eksml_autoscale_decisions_total{action="shrink"}' in expo
    assert 'eksml_autoscale_decisions_total{action="grow"}' in expo
    assert "eksml_autoscale_relaunches_total" in expo
    assert "eksml_autoscale_target_chips 8" in expo

    # the merged goodput ledger attributes the wave's downtime:
    # nonzero (four relaunch gaps) but bounded by the rung wall
    from eksml_tpu.telemetry.goodput import build_ledger

    ledger = build_ledger(logdir)
    assert len(ledger["segments"]) >= 5, ledger["segments"]
    down = ledger["downtime"]["total_s"]
    assert 0.0 < down < (t_wave1 - t_wave0), (down,
                                              t_wave1 - t_wave0)
    # and run_report joins the decision timeline against it
    from tools import run_report

    report = run_report.render_report(logdir)
    assert "## Autoscaling" in report
    assert "shrink" in report and "grow" in report


# ---- rungs 5-7: data-ingest faults (loader level, in-process) --------


@pytest.mark.parametrize("fault", ["corrupt-jpeg", "missing-file",
                                   "eio-recover"])
def test_data_fault_rung(fault, fresh_config, tmp_path):
    """One bad record must cost ONE quarantine entry (or none, for a
    recovered transient) — never the producer thread and the job."""
    from test_data_robust import _disk_records, _loader, _small_cfg

    cfg = _small_cfg(fresh_config)
    recs = _disk_records(tmp_path)
    victim = recs[1]["path"]
    expect_kind = None
    if fault == "corrupt-jpeg":
        with open(victim, "wb") as f:
            f.write(b"\xff\xd8\xff\xe0 truncated mid-stage")
        expect_kind = "decode"
    elif fault == "missing-file":
        os.remove(victim)
        expect_kind = "missing"
    else:  # eio-recover: one injected NFS blip, then healthy
        cfg.RESILIENCE.DATA.FAULT_INJECT_EIO_PATH = \
            os.path.basename(victim)
        cfg.RESILIENCE.DATA.FAULT_INJECT_EIO_COUNT = 1
        cfg.RESILIENCE.DATA.IO_BACKOFF_SEC = 0.001

    loader = _loader(recs, cfg, ledger_dir=str(tmp_path / "log"))
    batches = list(loader.batches(8))  # 16 draws: every record hit
    assert len(batches) == 8
    assert all(b["images"].shape == (2, 64, 64, 3) for b in batches)
    if expect_kind is None:
        assert loader._ledger.count == 0, (
            "recovered transient must leave no quarantine trace")
        assert loader._reader.transient_recoveries == 1
    else:
        assert [e["kind"] for e in loader._ledger.entries] == [
            expect_kind]
        assert loader._ledger.entries[0]["path"] == victim


# ---- rung 8: BrokenProcessPool self-healing --------------------------


def test_broken_pool_rebuilds_and_continues(fresh_config, tmp_path,
                                            monkeypatch):
    """A decode worker OOM-killed mid-batch breaks the whole
    ProcessPoolExecutor.  The loader must re-read the affected batch
    inline (a pool break is evidence about the POOL, not any record's
    bytes — only an inline failure quarantines), rebuild the pool
    once, and keep producing — not abort the N-host job over one dead
    worker.  Once the rebuild budget is spent, degradation to
    in-thread decode is sticky across batches() calls."""
    from concurrent.futures.process import BrokenProcessPool

    from test_data_robust import (_disk_records, _loader, _small_cfg,
                                  _truncate)

    cfg = _small_cfg(fresh_config)
    cfg.DATA.WORKER_PROCESSES = 2  # enables the decode process pool
    recs = _disk_records(tmp_path)
    _truncate(recs[0]["path"])  # genuinely bad bytes, surfaced inline
    loader = _loader(recs, cfg)

    class FakeFuture:
        def __init__(self, fn, broken):
            self._fn, self._broken = fn, broken

        def result(self):
            if self._broken:
                raise BrokenProcessPool("a decode worker died")
            return self._fn()

    class FakePool:
        def __init__(self, broken):
            self.broken = broken

        def submit(self, fn, path):
            return FakeFuture(lambda: fn(path), self.broken)

        def shutdown(self, wait=False, cancel_futures=False):
            pass

    made = []

    def make_pool():
        pool = FakePool(broken=(len(made) == 0))  # first pool breaks
        made.append(pool)
        return pool

    monkeypatch.setattr(loader, "_make_proc_pool", make_pool)
    batches = list(loader.batches(8))  # 16 draws: every record hit
    assert len(batches) == 8
    assert all(b["images"].shape == (2, 64, 64, 3) for b in batches)
    assert len(made) == 2, "pool must be rebuilt exactly once"
    # only the record whose bytes REALLY fail is quarantined —
    # healthy records that rode the broken batch re-read inline and
    # survive
    assert [e["kind"] for e in loader._ledger.entries] == ["decode"]
    assert loader._ledger.entries[0]["path"] == recs[0]["path"]

    # from here every pool breaks: the next incident exhausts the
    # rebuild budget → sticky in-thread degradation
    def make_broken_pool():
        pool = FakePool(broken=True)
        made.append(pool)
        return pool

    monkeypatch.setattr(loader, "_make_proc_pool", make_broken_pool)
    assert len(list(loader.batches(4))) == 4
    assert loader._pool_degraded
    n_pools = len(made)
    assert len(list(loader.batches(2))) == 2  # re-iterate after close
    assert len(made) == n_pools, (
        "a later batches() call must not resurrect a degraded pool")


# ---- rung 9: the composed data-chaos training run --------------------


@pytest.mark.slow
def test_data_chaos_train_completes_with_quarantine(
        tmp_path, compile_cache, mini_coco):
    """Acceptance rung (ISSUE 2): corrupt JPEG + missing file + one
    injected transient EIO in a single 20-step on-disk training run →
    the run completes with unchanged batch shapes, the quarantine
    ledger lists exactly the two permanent failures, and the recovered
    transient leaves zero entries."""
    logdir = str(tmp_path / "run")
    img_dir = os.path.join(mini_coco, "train2017")
    corrupt = os.path.join(img_dir, "train2017_000.jpg")
    with open(corrupt, "wb") as f:
        f.write(b"\xff\xd8\xff\xe0 truncated mid-stage")
    os.remove(os.path.join(img_dir, "train2017_001.jpg"))

    config = [c for c in TINY
              if "STEPS_PER_EPOCH" not in c and "MAX_EPOCHS" not in c
              ] + [
        "TRAIN.STEPS_PER_EPOCH=20", "TRAIN.MAX_EPOCHS=1",
        "TRAIN.LOG_PERIOD=5",
        f"DATA.BASEDIR={mini_coco}",
        "PREPROC.TEST_SHORT_EDGE_SIZE=128",
        # 6 records, 2 permanent failures = 0.33 — under the breaker
        "RESILIENCE.DATA.MAX_QUARANTINE_FRAC=0.4",
        "RESILIENCE.DATA.IO_BACKOFF_SEC=0.05",
        "RESILIENCE.DATA.FAULT_INJECT_EIO_PATH=train2017_002",
        "RESILIENCE.DATA.FAULT_INJECT_EIO_COUNT=1",
    ]
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config, synthetic=False)
    try:
        rc = proc.wait(timeout=900)
    finally:
        if proc.poll() is None:
            proc.kill()
    out = open(log1).read()
    assert rc == 0, out[-3000:]
    assert "training complete at 20 steps" in out
    # preflight (warn mode) flagged the missing file before step 1
    assert "file-existence probe" in out
    # the ledger is a census of exactly the two permanent failures
    ledger_path = os.path.join(logdir, "quarantine-host0.jsonl")
    entries = [json.loads(l) for l in open(ledger_path)]
    kinds = {os.path.basename(e["path"]): e["kind"] for e in entries}
    assert kinds == {"train2017_000.jpg": "decode",
                     "train2017_001.jpg": "missing"}, entries
    # the injected transient recovered — logged, not quarantined
    assert "recovered after" in out
    # 20 steps of metrics with the quarantine census riding along
    steps = _steps_logged(logdir)
    assert max(steps) == 20, steps
    assert any(r.get("data/quarantined") == 2
               for r in _metric_rows(logdir))


# ---- rung 10: the quarantine circuit breaker -------------------------


@pytest.mark.slow
def test_quarantine_overflow_aborts_actionably(tmp_path, compile_cache,
                                               mini_coco):
    """With the quarantined fraction forced above MAX_QUARANTINE_FRAC
    (a vanished mount in miniature: every image truncated), the run
    must abort with an actionable error naming the ledger path — not
    train on substitutes."""
    logdir = str(tmp_path / "run")
    img_dir = os.path.join(mini_coco, "train2017")
    for name in os.listdir(img_dir):
        with open(os.path.join(img_dir, name), "wb") as f:
            f.write(b"not a jpeg anymore")

    config = TINY + [
        f"DATA.BASEDIR={mini_coco}",
        "RESILIENCE.DATA.MAX_QUARANTINE_FRAC=0.1",
    ]
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1, config, synthetic=False)
    try:
        rc = proc.wait(timeout=300)
    finally:
        if proc.poll() is None:
            proc.kill()
    out = open(log1).read()
    from eksml_tpu.config import config as global_config

    assert rc not in (0, global_config.RESILIENCE.PREEMPT_EXIT_CODE), (
        rc, out[-2000:])
    assert "MAX_QUARANTINE_FRAC" in out
    assert os.path.join(logdir, "quarantine-host0.jsonl") in out


# ---- rung 11: rank-conditional collective skip (ISSUE 9) -------------

RANK_SKIP_WORKER = r"""
import os, sys
import jax

jax.config.update("jax_platforms", "cpu")

from eksml_tpu.parallel import initialize_from_env

initialize_from_env()
assert jax.process_count() == 2, jax.process_count()

from jax._src import distributed

client = distributed.global_state.client
# both ranks enter this barrier TOGETHER: proves the mechanism works
# when the fleet is aligned, so the wedge below is unambiguously the
# skipped entry, not a broken coordination service
client.wait_at_barrier("aligned", timeout_in_ms=120000)
print(f"worker {jax.process_index()} ALIGNED", flush=True)

if jax.process_index() == 0:
    # THE BUG under test: a rank-conditional cross-host barrier —
    # rank 1 never enters, so rank 0 wedges in it until the deadline.
    # eksml-lint's collective-order rule flags this exact construct.
    client.wait_at_barrier("divergent", timeout_in_ms=600000)
    print("BARRIER RETURNED", flush=True)
print(f"worker {jax.process_index()} EXITING", flush=True)
if jax.process_index() == 1:
    # skip jax's atexit distributed-shutdown handshake (ITSELF a
    # collective rank 0 will never join while wedged): this rank's
    # hard departure while rank 0 waits is exactly the scenario
    os._exit(0)
"""


def _spmd_free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.mark.slow
def test_rank_conditional_collective_skip_hangs_and_lints(tmp_path):
    """The lint finding and the distributed hang are the same bug,
    proven once: on a real 2-process mesh (the 8-fake-device rig:
    2 hosts x 4 CPU devices), rank 0 guards a cross-host barrier on
    `process_index() == 0` — rank 1 skips it and exits cleanly while
    rank 0 wedges inside the collective and never reaches the next
    line.  The SAME worker source, linted, yields a collective-order
    finding naming the guard and the chain to the barrier."""
    worker_py = tmp_path / "worker.py"
    worker_py.write_text(RANK_SKIP_WORKER)

    # -- static half: the worker source is a finding ------------------
    from eksml_tpu.analysis import run_lint

    r = run_lint(targets=[str(worker_py)], repo_root=str(tmp_path),
                 rules=["collective-order"])
    assert len(r.findings) == 1, r.findings
    f = r.findings[0]
    assert "wait_at_barrier" in f.message
    assert "jax.process_index()" in f.message
    assert f.chain[-1]["name"] == "wait_at_barrier"
    # the aligned barrier both ranks enter is NOT a finding — only
    # the divergent one
    assert f.line == RANK_SKIP_WORKER.splitlines().index(
        '    client.wait_at_barrier("divergent", '
        'timeout_in_ms=600000)') + 1

    # -- runtime half: the same construct wedges a real mesh ----------
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    port = _spmd_free_port()
    procs, logs, files = [], [], []
    for pid in (0, 1):
        env = dict(os.environ)
        env.update({
            "COORDINATOR_ADDRESS": f"127.0.0.1:{port}",
            "NUM_PROCESSES": "2",
            "PROCESS_ID": str(pid),
            "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
            "JAX_PLATFORMS": "cpu",
            "PYTHONPATH": repo,
        })
        log_path = str(tmp_path / f"skip-w{pid}.log")
        logs.append(log_path)
        logf = open(log_path, "w")  # PIPE deadlocks on XLA chatter
        files.append(logf)
        procs.append(subprocess.Popen(
            [sys.executable, str(worker_py)], env=env,
            stdout=logf, stderr=subprocess.STDOUT))
    try:
        # both ranks must pass the aligned barrier first
        deadline = time.time() + 600
        while time.time() < deadline:
            if all("ALIGNED" in open(p).read() for p in logs):
                break
            if any(p.poll() is not None for p in procs):
                break
            time.sleep(0.5)
        assert all("ALIGNED" in open(p).read() for p in logs), (
            "workers never reached the aligned barrier:\n"
            + open(logs[0]).read()[-2000:] + "\n---\n"
            + open(logs[1]).read()[-2000:])
        # rank 1 (which SKIPS the divergent barrier) exits cleanly...
        rc1 = procs[1].wait(timeout=120)
        assert rc1 == 0, (rc1, open(logs[1]).read()[-2000:])
        assert "worker 1 EXITING" in open(logs[1]).read()
        # ...while rank 0 is wedged INSIDE the collective: 20s after
        # its peer left, it has neither returned from the barrier nor
        # exited — the distributed hang the watchdog can only report
        # post-mortem, now statically flagged above.
        try:
            procs[0].wait(timeout=20)
            wedged = False
        except subprocess.TimeoutExpired:
            wedged = True
        out0 = open(logs[0]).read()
        assert "BARRIER RETURNED" not in out0, out0[-2000:]
        assert "worker 0 EXITING" not in out0, out0[-2000:]
        assert wedged or procs[0].returncode != 0, (
            procs[0].returncode, out0[-2000:])
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)
        for f_ in files:
            f_.close()


# ---- rung 12: lock-order inversion (ISSUE 12) ------------------------

LOCK_INVERSION_WORKER = r"""
import sys
import threading
import time

A = threading.Lock()
B = threading.Lock()
first_held = threading.Barrier(2, timeout=30)


def w_ab():
    with A:
        first_held.wait()   # both threads hold their FIRST lock
        with B:             # LINT: lock-order (A -> B here, B -> A below)
            pass
    print("w_ab DONE", flush=True)


def w_ba():
    with B:
        first_held.wait()
        with A:             # LINT: lock-order (the inverse order)
            pass
    print("w_ba DONE", flush=True)


t1 = threading.Thread(target=w_ab, name="worker-ab")
t2 = threading.Thread(target=w_ba, name="worker-ba")
t1.start()
t2.start()
# the barrier guarantees BOTH threads sit between their first and
# second acquisition — from here the deadlock is certain, not a race
time.sleep(0.2)
print("BOTH HOLDING", flush=True)
t1.join()
t2.join()
print("ALL DONE", flush=True)
"""


@pytest.mark.slow
def test_lock_inversion_wedges_and_lints(tmp_path):
    """ISSUE 12: the eksml-lint v3 ``lock-order`` finding and the
    two-thread wedge are the same bug, proven once (the PR 9
    pattern).  The worker takes A→B on one thread and B→A on the
    other, with a barrier forcing both to sit between their first and
    second acquisition — a certain deadlock, not a race.  The SAME
    source, linted, yields a lock-order finding whose two chains name
    the two inner ``with`` lines."""
    worker_py = tmp_path / "inversion_worker.py"
    worker_py.write_text(LOCK_INVERSION_WORKER)

    # -- static half: the worker source is a finding ------------------
    from eksml_tpu.analysis import run_lint

    r = run_lint(targets=[str(worker_py)], repo_root=str(tmp_path),
                 rules=["lock-order"])
    assert len(r.findings) == 1, r.findings
    f = r.findings[0]
    assert "inversion_worker.A" in f.message
    assert "inversion_worker.B" in f.message
    lines = LOCK_INVERSION_WORKER.splitlines()
    ab_line = next(i for i, ln in enumerate(lines, start=1)
                   if "with B:             # LINT" in ln)
    ba_line = next(i for i, ln in enumerate(lines, start=1)
                   if "with A:             # LINT" in ln)
    # both acquisition chains, each at its inner-with file:line
    assert f"inversion_worker.py:{ab_line}" in f.message
    assert f"inversion_worker.py:{ba_line}" in f.message
    assert f.line in (ab_line, ba_line)
    chain_lines = {c["line"] for c in f.chain}
    assert {ab_line, ba_line} <= chain_lines

    # -- runtime half: the same construct wedges two real threads -----
    proc = subprocess.Popen([sys.executable, str(worker_py)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    try:
        try:
            out, _ = proc.communicate(timeout=20)
            wedged = False
        except subprocess.TimeoutExpired:
            wedged = True
        assert wedged, f"expected a deadlock, worker exited:\n{out}"
    finally:
        proc.kill()
        out, _ = proc.communicate(timeout=30)
    # both threads got their first lock and neither finished: the
    # wedge is INSIDE the inverted second acquisition
    assert "BOTH HOLDING" in out, out
    assert "w_ab DONE" not in out and "w_ba DONE" not in out, out
    assert "ALL DONE" not in out, out

    # fixed ordering (B→A rewritten to A→B) exits cleanly AND lints
    # clean: one bug, one fix, both halves agree
    fixed = LOCK_INVERSION_WORKER.replace(
        "    with B:\n        first_held.wait()\n"
        "        with A:             # LINT: lock-order (the inverse "
        "order)",
        "    with A:\n        first_held.wait()\n"
        "        with B:             # fixed: the one global order")
    assert fixed != LOCK_INVERSION_WORKER
    # with one global order the threads serialize on A, so the
    # both-hold-their-first-lock barrier can never fill — drop it
    fixed = fixed.replace("first_held.wait()",
                          "pass  # no interleave to force")
    fixed_py = tmp_path / "fixed_worker.py"
    fixed_py.write_text(fixed)
    r2 = run_lint(targets=[str(fixed_py)], repo_root=str(tmp_path),
                  rules=["lock-order"])
    assert r2.findings == [], r2.findings
    done = subprocess.run([sys.executable, str(fixed_py)],
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stdout + done.stderr
    assert "ALL DONE" in done.stdout


# ---- rung: serving drain under load (ISSUE 14) -----------------------

SERVE_TINY = TINY_MODEL_OVERRIDES + [
    "PREPROC.TEST_SHORT_EDGE_SIZE=128",
    "SERVE.BATCH_SIZES=(1,4)", "SERVE.MAX_BATCH_DELAY_MS=5",
    "SERVE.MAX_QUEUE=64",
]


@pytest.mark.slow
def test_serve_drain_under_load(tmp_path, compile_cache):
    """proc-serve-drain: a live ``python -m eksml_tpu.serve`` under
    ``tools/serve_loadtest.py`` traffic takes SIGTERM mid-load.
    Contract (the PR 1 preemption discipline applied to serving):
    ZERO accepted in-flight requests dropped, new requests answered
    503 (or refused once the listener closed), clean exit 0 — and the
    mid-run ``/metrics`` scrape parses as strict OpenMetrics with the
    full ``eksml_serve_*`` family set present."""
    import threading
    import urllib.request

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_loadtest

    from test_telemetry import parse_openmetrics

    port_file = str(tmp_path / "serve.port")
    log_path = str(tmp_path / "serve.log")
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": compile_cache})
    cmd = [sys.executable, "-m", "eksml_tpu.serve", "--random-params",
           "--port", "0", "--port-file", port_file,
           "--addr", "127.0.0.1", "--config"] + SERVE_TINY
    with open(log_path, "w") as logf:
        proc = subprocess.Popen(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
    try:
        deadline = time.time() + 900
        while not os.path.exists(port_file):
            assert proc.poll() is None, (
                "server died before binding:\n"
                + open(log_path).read()[-3000:])
            assert time.time() < deadline, "port file never appeared"
            time.sleep(0.2)
        url = f"http://127.0.0.1:{open(port_file).read().strip()}"
        serve_loadtest.wait_ready(url, budget=900)

        # background load: enough requests that SIGTERM lands mid-run
        result = {}

        def load():
            result["art"] = serve_loadtest.run_load(
                url, requests=80, concurrency=4,
                sizes="100x80,80x100,128x96", timeout=60)

        t = threading.Thread(target=load, daemon=True)
        t.start()

        # mid-run: wait for real traffic, then scrape /metrics and
        # strict-parse the serve family set
        mid_scrape = None
        deadline = time.time() + 120
        while time.time() < deadline:
            body = urllib.request.urlopen(
                url + "/metrics", timeout=30).read().decode()
            ok = serve_loadtest.metric_value(
                body, "eksml_serve_requests_total",
                '{outcome="ok"}')
            if ok and ok >= 10:
                mid_scrape = body
                break
            time.sleep(0.2)
        assert mid_scrape is not None, "no serving traffic within 120s"
        fams = parse_openmetrics(mid_scrape)
        for name in ("eksml_serve_requests", "eksml_serve_batches",
                     "eksml_serve_request_latency_ms",
                     "eksml_serve_queue_wait_ms",
                     "eksml_serve_infer_ms",
                     "eksml_serve_queue_depth",
                     "eksml_serve_in_flight",
                     "eksml_serve_batch_occupancy",
                     "eksml_serve_aot_compiles",
                     "eksml_serve_request_path_compiles",
                     "eksml_serve_warm_executables"):
            assert name in fams, f"missing {name} in mid-run scrape"
        assert serve_loadtest.metric_value(
            mid_scrape, "eksml_serve_aot_compiles_total") == 2.0
        assert serve_loadtest.metric_value(
            mid_scrape,
            "eksml_serve_request_path_compiles_total") == 0.0

        # SIGTERM mid-load: drain
        proc.send_signal(signal.SIGTERM)
        t.join(timeout=180)
        assert not t.is_alive(), "load generator never finished"
        rc = proc.wait(timeout=120)
        assert rc == 0, ("drain did not exit cleanly (rc=%s):\n%s"
                         % (rc, open(log_path).read()[-3000:]))

        art = result["art"]
        # zero dropped in-flight requests: every request either
        # completed with a full response, or was REJECTED at/after
        # drain start (503) or hit the closed listener (URLError) —
        # never a timeout or a half-written answer
        assert art["completed"] + art["errors"] == 80
        assert art["completed"] >= 10
        for err in art["error_samples"]:
            assert ("503" in err or "Connection refused" in err
                    or "Connection reset" in err
                    or "URLError" in err or "RemoteDisconnected"
                    in err), f"unexpected failure mode: {err}"
        # the accepted ones all carry the full span breakdown
        for ph in ("queue_wait", "pad", "device_infer",
                   "postprocess"):
            assert art["phase_ms"][ph]["mean"] is not None
        log_text = open(log_path).read()
        assert "drain: admission closed" in log_text
        assert "drain complete" in log_text
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


# ---- rungs: continuous-deployment serving fleet (ISSUE 17) -----------


@pytest.fixture(scope="module")
def trained_ckpts(tmp_path_factory, compile_cache):
    """ONE tiny 6-step training run for both continuous-deployment
    rungs: committed checkpoints at steps 2/4/6, each with its
    integrity (and topology) manifest — the candidates the serving
    fleet hot-reloads."""
    logdir = str(tmp_path_factory.mktemp("cd_train"))
    log_path = os.path.join(logdir, "train.log")
    proc = _launch(logdir, compile_cache, log_path)
    rc = proc.wait(timeout=900)
    assert rc == 0, ("seed training run failed (rc=%s):\n%s"
                     % (rc, open(log_path).read()[-3000:]))
    assert _committed_ckpt_steps(logdir) == [2, 4, 6]
    from eksml_tpu.resilience import integrity

    root = os.path.join(logdir, "checkpoints")
    for s in (2, 4, 6):
        assert integrity.manifest_readable(root, s), s
    return logdir


def _publish_ckpt(src_logdir, dst_logdir, step, corrupt=False):
    """Copy one committed step into a serving logdir the way training
    publishes one: integrity/topology manifests FIRST, then the step
    dir staged and renamed into its digit name — the reload watcher
    only ever sees a committed dir whose evidence already exists.
    ``corrupt=True`` truncates one payload file AFTER the manifest
    copy (a kill mid-flush on NFS): size mismatch vs manifest."""
    import shutil

    src_root = os.path.join(src_logdir, "checkpoints")
    dst_root = os.path.join(dst_logdir, "checkpoints")
    integ = os.path.join(dst_root, ".integrity")
    os.makedirs(integ, exist_ok=True)
    for name in os.listdir(os.path.join(src_root, ".integrity")):
        if name.startswith(f"{step}."):
            shutil.copy2(os.path.join(src_root, ".integrity", name),
                         os.path.join(integ, name))
    staging = os.path.join(dst_root, f".staging-{step}")
    shutil.copytree(os.path.join(src_root, str(step)), staging)
    if corrupt:
        biggest = max(
            (os.path.join(dp, f) for dp, _, fs in os.walk(staging)
             for f in fs),
            key=os.path.getsize)
        with open(biggest, "r+b") as f:
            f.truncate(max(os.path.getsize(biggest) // 2, 1))
    os.rename(staging, os.path.join(dst_root, str(step)))


def _start_serve(ckpt_dir, port_file, log_path, cache_dir,
                 serve_id="stable", step=None, extra_config=()):
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "JAX_COMPILATION_CACHE_DIR": cache_dir})
    cmd = [sys.executable, "-m", "eksml_tpu.serve",
           "--checkpoint-dir", ckpt_dir, "--serve-id", serve_id,
           "--port", "0", "--port-file", port_file,
           "--addr", "127.0.0.1"]
    if step is not None:
        cmd += ["--step", str(step)]
    cmd += ["--config"] + SERVE_TINY + list(extra_config)
    with open(log_path, "w") as logf:
        return subprocess.Popen(
            cmd, env=env, stdout=logf, stderr=subprocess.STDOUT,
            cwd=os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))


def _serve_url(proc, port_file, log_path, budget=900):
    deadline = time.time() + budget
    while not os.path.exists(port_file):
        assert proc.poll() is None, (
            "server died before binding:\n"
            + open(log_path).read()[-3000:])
        assert time.time() < deadline, "port file never appeared"
        time.sleep(0.2)
    return f"http://127.0.0.1:{open(port_file).read().strip()}"


def _serve_events(logdir, serve_id):
    path = os.path.join(logdir, f"events-host{serve_id}.jsonl")
    events = []
    if os.path.exists(path):
        for line in open(path):
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue
    return events


@pytest.mark.slow
def test_serve_hot_reload_under_load(tmp_path, compile_cache,
                                     trained_ckpts):
    """proc-serve-reload: a live server under open-loop load
    hot-reloads a checkpoint published mid-run.  Contract (the
    continuous-deployment half of the drain discipline): ZERO
    dropped/errored requests, ZERO request-path compiles across the
    swap, every response names the checkpoint that served it, and the
    response stream flips 2 -> 4 exactly at the recorded
    ``serve_reload`` boundary.  A corrupted-manifest candidate
    (step 6) is REJECTED with the old params still serving."""
    import threading

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import serve_loadtest

    serve_dir = str(tmp_path / "serve_log")
    os.makedirs(os.path.join(serve_dir, "checkpoints"))
    _publish_ckpt(trained_ckpts, serve_dir, 2)

    port_file = str(tmp_path / "serve.port")
    log_path = str(tmp_path / "serve.log")
    proc = _start_serve(serve_dir, port_file, log_path, compile_cache,
                        extra_config=["SERVE.RELOAD_POLL_SEC=0.25"])
    try:
        url = _serve_url(proc, port_file, log_path)
        health = serve_loadtest.wait_ready(url, budget=900)
        assert health["params_step"] == 2

        result = {}

        def load():
            result["art"] = serve_loadtest.run_load(
                url, requests=120, concurrency=4, mode="open",
                rate=10.0, sizes="100x80,80x100,128x96",
                timeout=120, keep_records=True)

        t = threading.Thread(target=load, daemon=True)
        t.start()

        # mid-run: publish step 4 the way training does; the watcher
        # must verify + restore + swap while traffic flows
        deadline = time.time() + 60
        while time.time() < deadline:
            ok = serve_loadtest.metric_value(
                serve_loadtest.scrape_metrics(url),
                "eksml_serve_requests_total", '{outcome="ok"}')
            if ok and ok >= 5:
                break
            time.sleep(0.1)
        _publish_ckpt(trained_ckpts, serve_dir, 4)
        deadline = time.time() + 120
        while time.time() < deadline:
            h = serve_loadtest.fetch_health(url)
            if h.get("params_step") == 4:
                break
            time.sleep(0.2)
        assert h.get("params_step") == 4, (
            "hot-reload to step 4 never happened: %s\n%s"
            % (h, open(log_path).read()[-3000:]))

        # a corrupted candidate (step 6, payload truncated after its
        # manifest landed) must be rejected — old params keep serving
        _publish_ckpt(trained_ckpts, serve_dir, 6, corrupt=True)
        deadline = time.time() + 120
        while time.time() < deadline:
            h = serve_loadtest.fetch_health(url)
            if h.get("reload_rejected", 0) >= 1:
                break
            time.sleep(0.2)
        assert h.get("reload_rejected", 0) >= 1, h
        assert h.get("params_step") == 4, h

        t.join(timeout=300)
        assert not t.is_alive(), "load generator never finished"
        art = result["art"]

        # ZERO dropped or errored requests across the whole exercise
        assert art["errors"] == 0, art["error_samples"]
        assert art["completed"] == 120

        # ZERO request-path compiles across the swap: the new params
        # dispatched through the SAME warm executables
        metrics = serve_loadtest.scrape_metrics(url)
        assert serve_loadtest.metric_value(
            metrics, "eksml_serve_request_path_compiles_total") == 0.0
        assert serve_loadtest.metric_value(
            metrics, "eksml_serve_reloads_total") == 1.0
        assert serve_loadtest.metric_value(
            metrics, "eksml_serve_reload_rejected_total",
            '{reason="integrity"}') >= 1.0
        assert serve_loadtest.metric_value(
            metrics, "eksml_serve_params_step") == 4.0

        # the flip boundary: every response names its checkpoint, and
        # the steps partition exactly at the recorded serve_reload
        # event (old-params responses STARTED before the swap,
        # new-params responses COMPLETED after it)
        events = _serve_events(serve_dir, "stable")
        reloads = [e for e in events if e["kind"] == "serve_reload"]
        assert len(reloads) == 1
        assert reloads[0]["step"] == 4
        assert reloads[0]["previous_step"] == 2
        t_swap = reloads[0]["time"]
        rejected = [e for e in events
                    if e["kind"] == "serve_reload_rejected"]
        assert rejected and rejected[0]["step"] == 6
        assert rejected[0]["reason"] == "integrity"

        steps_seen = {r["params_step"] for r in art["records"]}
        assert steps_seen == {2, 4}, steps_seen
        for r in art["records"]:
            started = r["t_wall"] - r["total_ms"] / 1e3
            if r["params_step"] == 2:
                assert started <= t_swap + 0.05, (
                    "a request started after the swap still served "
                    "step 2: %r" % r)
            else:
                assert r["t_wall"] >= t_swap - 0.05, (
                    "a step-4 response completed before the swap "
                    "event: %r" % r)

        # graceful exit still holds with the reload machinery wired
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        assert rc == 0, open(log_path).read()[-3000:]
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait(timeout=30)


@pytest.mark.slow
def test_canary_shadow_score_and_rollback(tmp_path, compile_cache,
                                          trained_ckpts):
    """proc-canary-rollback: the full rollout loop against two live
    servers.  Incumbent serves step 2, canary serves step 6; a
    recorded request bank replays as shadow traffic at both.  Under a
    strict drift gate the (genuinely different) canary checkpoint is
    ROLLED BACK — the controller demotes it to the incumbent's step
    via /admin/reload.  Re-armed with the canary on step 6 and
    lenient gates, a promote streak flips the INCUMBENT to step 6.
    Every verdict/actuation lands as flight events + canary metrics;
    run_report renders the timeline."""
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "tools"))
    import eksml_operator
    import serve_loadtest

    serve_dir = str(tmp_path / "serve_log")
    os.makedirs(os.path.join(serve_dir, "checkpoints"))
    for s in (2, 6):
        _publish_ckpt(trained_ckpts, serve_dir, s)

    inc_port = str(tmp_path / "inc.port")
    can_port = str(tmp_path / "can.port")
    inc_log = str(tmp_path / "inc.log")
    can_log = str(tmp_path / "can.log")
    # both tracks share the logdir (distinct --serve-id keeps their
    # event files apart); poll 0 — params move ONLY via /admin/reload
    inc = _start_serve(serve_dir, inc_port, inc_log, compile_cache,
                       serve_id="stable", step=2)
    can = _start_serve(serve_dir, can_port, can_log, compile_cache,
                       serve_id="canary", step=6)
    try:
        inc_url = _serve_url(inc, inc_port, inc_log)
        can_url = _serve_url(can, can_port, can_log)
        assert serve_loadtest.wait_ready(
            inc_url, budget=900)["params_step"] == 2
        assert serve_loadtest.wait_ready(
            can_url, budget=900)["params_step"] == 6

        bank = serve_loadtest.build_bank(
            seed=3, sizes="100x80,80x100", requests=12)

        # phase 1 — strict drift gate: steps 2 and 6 genuinely
        # disagree (different optimizer states), so the canary is
        # rolled back on the first score
        strict = {"CANARY_MIN_REQUESTS": 5,
                  "CANARY_ERROR_RATE_MAX": 0.5,
                  "CANARY_P99_RATIO_MAX": 1000.0,
                  "CANARY_DRIFT_MAX": 0.0,
                  "CANARY_PROMOTE_STREAK": 2}
        ctrl = eksml_operator.PromotionController(
            serve_dir, inc_url, can_url, bank, strict,
            raw_topk=16, concurrency=3, timeout=120)
        out = ctrl.tick()
        assert out["verdict"] == "rollback", out
        assert out["score"]["scored"] == 12
        assert out["score"]["drift"]["mean"] > 0.0
        assert out["reload"]["ok"] is True
        # the canary now serves the incumbent's checkpoint again
        assert serve_loadtest.fetch_health(
            can_url)["params_step"] == 2
        assert serve_loadtest.fetch_health(
            inc_url)["params_step"] == 2
        # converged fleet: the next tick holds (nothing to score)
        assert ctrl.tick()["verdict"] == "hold"

        # phase 2 — the canary picks up step 6 again (as its watcher
        # would on a fresh training checkpoint) and clean gates let a
        # promote streak flip the incumbent
        assert eksml_operator.post_reload(
            can_url, step=6)["ok"] is True
        lenient = dict(strict, CANARY_DRIFT_MAX=1.0)
        ctrl2 = eksml_operator.PromotionController(
            serve_dir, inc_url, can_url, bank, lenient,
            raw_topk=16, concurrency=3, timeout=120)
        first = ctrl2.tick()
        assert first["verdict"] == "promote", first
        assert "streak 1/2" in first["reason"]
        assert serve_loadtest.fetch_health(
            inc_url)["params_step"] == 2  # not yet: streak gating
        second = ctrl2.tick()
        assert second["verdict"] == "promote", second
        assert second["reload"]["ok"] is True
        assert serve_loadtest.fetch_health(
            inc_url)["params_step"] == 6
        assert ctrl2.tick()["verdict"] == "hold"  # converged at 6

        # evidence trail: flight events, canary metrics, run_report
        cd_events = _serve_events(serve_dir, "cd")
        kinds = [e["kind"] for e in cd_events]
        assert "canary_score" in kinds
        rb = [e for e in cd_events if e["kind"] == "canary_rollback"]
        assert rb and rb[0]["from_step"] == 6 and rb[0]["to_step"] == 2
        pm = [e for e in cd_events if e["kind"] == "canary_promote"]
        assert pm and pm[0]["step"] == 6 and pm[0]["previous_step"] == 2
        stable_events = _serve_events(serve_dir, "stable")
        assert any(e["kind"] == "serve_reload" and e["step"] == 6
                   for e in stable_events)

        from eksml_tpu.telemetry.exporter import render_openmetrics

        body = render_openmetrics(ctrl.registry)
        assert serve_loadtest.metric_value(
            body, "eksml_serve_canary_rollbacks_total") == 1.0
        assert serve_loadtest.metric_value(
            body, "eksml_serve_canary_scores_total") == 1.0
        body2 = render_openmetrics(ctrl2.registry)
        assert serve_loadtest.metric_value(
            body2, "eksml_serve_canary_promotions_total") == 1.0
        assert serve_loadtest.metric_value(
            body2, "eksml_serve_canary_verdicts_total",
            '{verdict="promote"}') == 2.0

        from tools import run_report

        report = run_report.render_report(serve_dir)
        assert "## Deployments (serving hot-reload / canary)" in report
        assert "canary_rollback" in report
        assert "canary_promote" in report

        for p in (inc, can):
            p.send_signal(signal.SIGTERM)
        assert inc.wait(timeout=120) == 0
        assert can.wait(timeout=120) == 0
    finally:
        for p in (inc, can):
            if p.poll() is None:
                p.kill()
                p.wait(timeout=30)


# ---- rung 4e: slice loss (multi-slice DCN scale-out, ISSUE 18) -------


def _slice_config(chips, batch_per_chip, epochs, num_slices, exchange):
    """fsdp config at a given (device count, slice count), holding the
    GLOBAL batch at 8 so the LR schedule, steps/epoch and loss stream
    are comparable across slice topologies."""
    return [c for c in TINY if "MAX_EPOCHS" not in c] + [
        f"TRAIN.MAX_EPOCHS={epochs}",
        f"TRAIN.NUM_CHIPS={chips}",
        f"TRAIN.BATCH_SIZE_PER_CHIP={batch_per_chip}",
        "TRAIN.SHARDING.STRATEGY=fsdp",
        f"TRAIN.SHARDING.EXCHANGE={exchange}",
        f"TPU.NUM_SLICES={num_slices}",
    ]


def _wait_for_committed_ckpt(proc, logdir, log_path, budget=900):
    deadline = time.time() + budget
    while time.time() < deadline:
        if _committed_ckpt_steps(logdir):
            return
        if proc.poll() is not None:
            return  # run finished; caller decides conclusiveness
        time.sleep(0.5)
    pytest.fail("no committed checkpoint within budget")


@pytest.mark.slow
def test_slice_loss_shrink_grow(tmp_path, compile_cache):
    """Chaos rung (ISSUE 18): SIGKILL a 2-slice hierarchical-exchange
    run (slice loss — a whole slice's capacity vanishes with no
    courtesy signal), relaunch elastically at ONE slice's devices
    (4 chips, flat exchange, same global batch): the relaunch
    reshards the last committed checkpoint off the slice-axis mesh,
    records the ``checkpoint_resharded`` event, and continues the
    loss stream.  Then grow BACK to 2 slices on an extended schedule
    — the loss stream stays contiguous and finite across both slice-
    topology crossings."""
    logdir = str(tmp_path / "run")

    # -- 2 slices x 4 chips, hierarchical exchange, killed mid-run ----
    log1 = str(tmp_path / "run1.log")
    proc = _launch(logdir, compile_cache, log1,
                   _slice_config(8, 1, epochs=3, num_slices=2,
                                 exchange="hierarchical"))
    try:
        _wait_for_first_step(proc, logdir, log1)
        _wait_for_committed_ckpt(proc, logdir, log1)
        proc.send_signal(signal.SIGKILL)  # slice loss: no courtesy
        proc.wait(timeout=60)
    finally:
        if proc.poll() is None:
            proc.kill()
    first_steps = _steps_logged(logdir)
    if first_steps and max(first_steps) >= 6:
        pytest.skip("run outran the kill on this machine — "
                    "inconclusive")
    committed = _committed_ckpt_steps(logdir)
    assert committed, "no checkpoint committed before the slice loss"
    forced = max(committed)

    # -- survivors: ONE slice (4 chips), complete the schedule --------
    log2 = str(tmp_path / "run2.log")
    proc2 = _launch(logdir, compile_cache, log2,
                    _slice_config(4, 2, epochs=3, num_slices=1,
                                  exchange="flat"),
                    extra_env=_device_count_env(4))
    try:
        assert proc2.wait(timeout=900) == 0, open(log2).read()[-2000:]
    finally:
        if proc2.poll() is None:
            proc2.kill()
    out2 = open(log2).read()
    assert f"resuming from checkpoint step {forced}" in out2
    assert "resharded across a topology change" in out2
    assert "num_devices: 8 -> 4" in out2
    steps = _steps_logged(logdir)
    shrink_steps = steps[len(first_steps):]
    assert shrink_steps == list(range(forced + 1, 7)), (
        forced, first_steps, shrink_steps)
    kinds = _event_kinds(logdir)
    assert "checkpoint_resharded" in kinds, kinds

    # -- capacity returns: GROW back to 2 slices, extended schedule --
    log3 = str(tmp_path / "run3.log")
    proc3 = _launch(logdir, compile_cache, log3,
                    _slice_config(8, 1, epochs=5, num_slices=2,
                                  exchange="hierarchical"))
    try:
        assert proc3.wait(timeout=900) == 0, open(log3).read()[-2000:]
    finally:
        if proc3.poll() is None:
            proc3.kill()
    out3 = open(log3).read()
    assert "resuming from checkpoint step 6" in out3
    assert "resharded across a topology change" in out3
    assert "num_devices: 4 -> 8" in out3
    # the loss stream is CONTINUOUS across slice loss and regrowth:
    # every step 1..10 is present (no gap at either crossing), all
    # losses finite
    rows = {r["step"]: r["total_loss"] for r in _metric_rows(logdir)
            if "total_loss" in r}
    steps = _steps_logged(logdir)
    assert sorted(set(steps)) == list(range(1, 11)), steps
    assert all(math.isfinite(v) for v in rows.values()), rows
    kinds = _event_kinds(logdir)
    assert kinds.count("checkpoint_resharded") == 2, kinds
