"""Unit half of the span-tracing layer (ISSUE 5).

Tracer ring mechanics, the disabled-mode no-op contract, thread
safety, step/host attribution, the ProfileTrigger guard rails, the
anomaly detector, and the cross-host merge in
tools/trace_summary.py.  The subprocess half (mid-run
/debugz/profile capture against a real trainer) lives in
tests/test_fault_tolerance.py.
"""

import gc
import json
import os
import threading
import time

import pytest

from eksml_tpu import telemetry
from eksml_tpu.telemetry.tracing import (NULL_SPAN, AnomalyDetector,
                                         ProfileTrigger, Tracer,
                                         format_thread_stacks)


@pytest.fixture(autouse=True)
def _no_leaked_tracer():
    """Every test starts and ends without an installed tracer."""
    telemetry.install_tracer(None)
    yield
    telemetry.install_tracer(None)


# ---- ring + span mechanics ------------------------------------------


def test_ring_is_bounded():
    tr = Tracer(capacity=32)
    for i in range(100):
        with tr.span("s", step=i):
            pass
    events = tr.snapshot()
    assert len(events) == 32  # ring bounded, oldest dropped
    assert [e["args"]["step"] for e in events] == list(range(68, 100))


def test_span_step_host_attribution_and_chrome_fields():
    tr = Tracer(capacity=64, host_id=3)
    with tr.span("train_step", step=7, attrs={"k": "v"}):
        time.sleep(0.002)
    (ev,) = tr.snapshot()
    assert ev["name"] == "train_step" and ev["ph"] == "X"
    assert ev["pid"] == 3 and ev["args"]["host"] == 3
    assert ev["args"]["step"] == 7 and ev["args"]["k"] == "v"
    assert ev["dur"] >= 2000  # µs
    assert isinstance(ev["ts"], float) and isinstance(ev["tid"], int)


def test_disabled_mode_is_a_shared_noop():
    """No tracer installed → the module API returns ONE shared null
    span (no per-call allocation); a disabled tracer behaves the
    same."""
    assert telemetry.get_tracer() is None
    s1, s2 = telemetry.span("a", step=1), telemetry.span("b")
    assert s1 is s2 is NULL_SPAN
    with s1:
        pass  # usable as a context manager
    telemetry.complete_span("c", 0.0, 1.0)  # no-op, no raise
    disabled = Tracer(capacity=16, enabled=False)
    assert disabled.span("x") is NULL_SPAN
    telemetry.install_tracer(disabled)
    assert telemetry.span("y") is NULL_SPAN
    assert disabled.snapshot() == []


def test_module_install_and_complete_span():
    tr = Tracer(capacity=16, host_id=1)
    prev = telemetry.install_tracer(tr)
    assert prev is None
    with telemetry.span("data_wait", step=4):
        pass
    t0 = time.perf_counter()
    telemetry.complete_span("batch_build", t0,
                            time.perf_counter() + 0.001, seq=2)
    names = [e["name"] for e in tr.snapshot()]
    assert names == ["data_wait", "batch_build"]
    assert tr.snapshot()[1]["args"]["seq"] == 2


# ---- spans on the profiler's clock -----------------------------------


def _xplane_host_events(xplane_path):
    """{event name: [stats dict]} of the host planes of an
    ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(xplane_path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                out.setdefault(ev.name, []).append(dict(ev.stats))
    return out


@pytest.fixture()
def profiler_session(tmp_path):
    """An open ``jax.profiler`` session (Python tracer off, as every
    capture of this repo starts it); ``stop()`` returns the host
    events of its ``.xplane.pb``."""
    import glob

    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    stopped = []

    def stop():
        jax.profiler.stop_trace()
        stopped.append(True)
        (path,) = glob.glob(os.path.join(
            str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
        return _xplane_host_events(path)

    yield stop
    if not stopped:
        jax.profiler.stop_trace()


def test_spans_are_profiler_annotations_on_their_own_thread(
        profiler_session):
    """Main-thread and worker-thread spans land in the host plane of
    the profiler's trace under their names, ``step`` and the attrs as
    stats; the step's dispatch is a StepTraceAnnotation; an interval
    measured elsewhere (``complete_span``) stays ring-only."""
    tr = Tracer(capacity=32)
    telemetry.install_tracer(tr)

    def producer():
        with telemetry.span("batch_build", attrs={"seq": 5, "rows": 4}):
            time.sleep(0.002)

    with telemetry.span("data_wait", step=8, attrs={"seq": 5}):
        t = threading.Thread(target=producer)
        t.start()
        t.join()
    with telemetry.span("train_step", step=8, step_trace=True):
        time.sleep(0.001)
    t0 = time.perf_counter()
    telemetry.complete_span("queue_wait", t0, t0 + 0.001, seq=1)
    events = profiler_session()
    assert events["batch_build"] == [{"seq": 5, "rows": 4}]
    assert events["data_wait"] == [{"seq": 5, "step": 8}]
    (step_stats,) = events["train_step"]
    assert step_stats["step_num"] == 8 and step_stats["step"] == 8
    assert step_stats["_r"] == 1            # what marks a step trace
    assert "queue_wait" not in events
    # and the ring holds all four, as before
    assert [e["name"] for e in tr.snapshot()] == [
        "batch_build", "data_wait", "train_step", "queue_wait"]


@pytest.mark.parametrize("broken", ["no_jax", "annotation_raises"])
def test_annotation_failure_degrades_to_ring_only(monkeypatch, broken):
    """No importable jax.profiler, or an annotation that cannot be
    entered: the span is still recorded, nothing raises."""
    from eksml_tpu.telemetry import tracing

    class Refuses:
        @staticmethod
        def TraceAnnotation(name, **kw):
            raise RuntimeError("no profiler here")

    monkeypatch.setattr(tracing, "_profiler",
                        False if broken == "no_jax" else Refuses)
    tr = Tracer(capacity=16)
    telemetry.install_tracer(tr)
    with telemetry.span("data_wait", step=1, attrs={"seq": 0}):
        pass
    (ev,) = tr.snapshot()
    assert ev["name"] == "data_wait" and ev["args"]["seq"] == 0


def test_no_tracer_builds_no_annotation(monkeypatch):
    from eksml_tpu.telemetry import tracing

    built = []
    monkeypatch.setattr(tracing, "_annotation",
                        lambda *a: built.append(a))
    with telemetry.span("data_wait", step=1, attrs={"seq": 0},
                        step_trace=True) as s:
        assert s is NULL_SPAN
    telemetry.install_tracer(Tracer(capacity=16, enabled=False))
    with telemetry.span("train_step", step=1):
        pass
    assert built == []


# ---- step completion stamps -----------------------------------------


def test_step_stamper_stamps_in_order_and_close_drains():
    """One ``device_step`` span per handed step, in hand-over order;
    each ends when its wait returns, so ends increase; ``close``
    returns only after the last queued step is stamped and the thread
    is gone."""
    tr = Tracer(capacity=64)
    telemetry.install_tracer(tr)
    waited = []

    def wait(value):
        time.sleep(0.002)
        waited.append(value)

    stamper = telemetry.StepStamper(wait)
    assert any(t.name == "step-stamper" for t in threading.enumerate())
    for step in range(3, 9):
        stamper.stamp(step, f"loss{step}")
    stamper.close()
    assert not any(t.name == "step-stamper"
                   for t in threading.enumerate())
    assert waited == [f"loss{n}" for n in range(3, 9)]
    spans = [e for e in tr.snapshot() if e["name"] == "device_step"]
    assert [e["args"]["step"] for e in spans] == list(range(3, 9))
    ends = [e["ts"] + e["dur"] for e in spans]
    assert ends == sorted(ends) and len(set(ends)) == len(ends)


def test_step_stamper_survives_a_failed_wait_and_a_wedged_device(
        monkeypatch):
    """A wait that raises (the step failed on the device: the loop's
    own sync reports it) does not end the thread; on the error path a
    wait that never returns is left behind after the time limit."""
    tr = Tracer(capacity=16)
    telemetry.install_tracer(tr)
    wedged = threading.Event()

    def wait(value):
        if value == "bad":
            raise RuntimeError("device error")
        if value == "wedged":
            wedged.wait(30)

    monkeypatch.setattr(telemetry.StepStamper,
                        "ERROR_EXIT_TIMEOUT_SEC", 0.05)
    stamper = telemetry.StepStamper(wait)
    stamper.stamp(1, "bad")
    stamper.stamp(2, "fine")
    stamper.stamp(3, "wedged")
    t0 = time.perf_counter()
    stamper.close(failed=True)
    assert time.perf_counter() - t0 < 5.0
    steps = [e["args"]["step"] for e in tr.snapshot()]
    assert steps == [1, 2]         # the failed wait still ends its span
    wedged.set()
    stamper._thread.join(5)
    assert not stamper._thread.is_alive()


# ---- a traced fit against an untraced one ----------------------------

TINY_FIT = [
    "DATA.SYNTHETIC=True", "DATA.NUM_WORKERS=0",
    "TRAIN.STEPS_PER_EPOCH=4", "TRAIN.MAX_EPOCHS=1",
    "TRAIN.CHECKPOINT_PERIOD=100", "TRAIN.LOG_PERIOD=2",
    "TPU.MESH_SHAPE=(1,1)", "TELEMETRY.PORT=0",
    "TELEMETRY.TRACING.ANOMALY_TRIGGER=False",
]


class _Boom(RuntimeError):
    pass


def _fit(logdir, tracing_on, steps=4):
    """One tiny ``Trainer.fit`` as a consumer wires it; returns what
    the tests below look at."""
    import jax

    from eksml_tpu import config as config_mod
    from eksml_tpu.config import SMOKE_OVERRIDES
    from eksml_tpu.data import DetectionLoader, SyntheticDataset
    from eksml_tpu.telemetry import tracing
    from eksml_tpu.train import Trainer

    cfg = config_mod.config
    saved = cfg.to_dict()
    cfg.freeze(False)
    built = {"spans": 0, "annotations": 0, "stampers": 0}
    patch = pytest.MonkeyPatch()

    def count(key, owner, attr):
        fn = getattr(owner, attr)

        def counting(*a, **kw):
            built[key] += 1
            return fn(*a, **kw)
        patch.setattr(owner, attr, counting)

    count("spans", tracing._Span, "__init__")
    count("annotations", tracing, "_annotation")
    count("stampers", tracing.StepStamper, "__init__")
    try:
        cfg.update_args(list(SMOKE_OVERRIDES) + TINY_FIT + [
            f"TRAIN.LOGDIR={logdir}",
            f"TELEMETRY.TRACING.ENABLED={tracing_on}"])
        config_mod.finalize_configs(is_training=True)
        ds = SyntheticDataset(num_images=4, height=128, width=128,
                              num_classes=cfg.DATA.NUM_CLASSES)
        loader = DetectionLoader(ds.records(), cfg, batch_size=1,
                                 with_masks=True, gt_mask_size=28,
                                 seed=0)
        trainer = Trainer(cfg, logdir)
        out = {"built": built, "gc_hooks_during": []}

        def gc_meters():
            return [cb for cb in gc.callbacks if isinstance(
                getattr(cb, "__self__", None), tracing.GcMeter)]

        def batches_with_a_full_collection():
            for i, batch in enumerate(loader.batches(steps)):
                if i == 2:
                    out["gc_hooks_during"].append(gc_meters())
                    gc.collect()
                yield batch

        def two_then_boom():
            gen = loader.batches(2)
            yield from gen
            out["gc_hooks_during"].append(gc_meters())
            raise _Boom("the input broke")

        hooks = list(gc.callbacks)
        try:
            state = trainer.fit(batches_with_a_full_collection(),
                                total_steps=100)
            jax.block_until_ready(state)
            out["gc_hooks_kept"] = gc.callbacks == hooks
            out["threads_after_fit"] = [
                t.name for t in threading.enumerate()]
            out["installed_after_fit"] = telemetry.get_tracer()
            before = 0
            if trainer.tracer is not None:
                out["spans"] = trainer.tracer.snapshot()
                before = len(out["spans"])
                with open(trainer.tracer.path) as f:
                    out["flushed"] = json.load(f)["traceEvents"]
            with pytest.raises(_Boom):
                trainer.fit(two_then_boom(), total_steps=100,
                            start_step=steps, state=state)
            out["gc_hooks_kept_on_raise"] = gc.callbacks == hooks
            out["threads_after_raise"] = [
                t.name for t in threading.enumerate()]
            if trainer.tracer is not None:
                out["spans_of_raising_fit"] = \
                    trainer.tracer.snapshot()[before:]
        finally:
            trainer.ckpt.close()
        with open(os.path.join(logdir, "metrics.jsonl")) as f:
            rows = [json.loads(line) for line in f]
        out["losses"] = {r["step"]: r["total_loss"] for r in rows
                         if "total_loss" in r and r["step"] <= steps}
        return out
    finally:
        patch.undo()
        telemetry.install_tracer(None)
        cfg.freeze(False)
        cfg.from_dict(saved)
        cfg.freeze()


@pytest.fixture(scope="module")
def fits(tmp_path_factory):
    """The same 4-step schedule with tracing off and on (and, on the
    traced Trainer, a second fit whose input raises after 2 steps)."""
    root = tmp_path_factory.mktemp("fits")
    return {"off": _fit(str(root / "off"), False),
            "on": _fit(str(root / "on"), True)}


def test_untraced_fit_builds_no_span_annotation_or_thread(fits):
    off = fits["off"]
    assert off["built"] == {"spans": 0, "annotations": 0, "stampers": 0}
    assert "spans" not in off               # no tracer on the Trainer
    assert "step-stamper" not in off["threads_after_fit"]


def test_traced_fit_leaves_one_device_step_span_per_step(fits):
    on = fits["on"]
    assert on["built"]["stampers"] == 2     # one per fit, none leaked
    assert on["built"]["annotations"] == on["built"]["spans"] > 0
    stamps = [e for e in on["spans"] if e["name"] == "device_step"]
    assert [e["args"]["step"] for e in stamps] == [1, 2, 3, 4]
    ends = [e["ts"] + e["dur"] for e in stamps]
    assert all(b > a for a, b in zip(ends, ends[1:]))
    # a step is done on the device no earlier than it was dispatched
    dispatched = {e["args"]["step"]: e["ts"] for e in on["spans"]
                  if e["name"] == "train_step"}
    assert all(end > dispatched[e["args"]["step"]]
               for e, end in zip(stamps, ends))
    # one thread for all of them, not the step loop's
    main = {e["tid"] for e in on["spans"] if e["name"] == "train_step"}
    assert len({e["tid"] for e in stamps}) == 1
    assert {e["tid"] for e in stamps}.isdisjoint(main)
    assert "step-stamper" not in on["threads_after_fit"]
    assert on["installed_after_fit"] is None


def test_stamp_thread_is_gone_when_fit_raises(fits):
    on = fits["on"]
    assert "step-stamper" not in on["threads_after_raise"]
    stamps = [e["args"]["step"] for e in on["spans_of_raising_fit"]
              if e["name"] == "device_step"]
    assert stamps == [5, 6]                 # drained before the exit


def test_one_batchs_three_spans_share_seq(fits):
    """batch_build (loader's producer), h2d_prefetch (prefetcher's
    thread) and data_wait (step loop) of the n-th batch carry seq n,
    each from a thread of its own; batch_build says how many rows."""
    spans = fits["on"]["spans"]
    by = {name: {e["args"]["seq"]: e for e in spans
                 if e["name"] == name}
          for name in ("batch_build", "h2d_prefetch", "data_wait")}
    for seq in range(4):
        trio = [by[name][seq] for name in by]
        assert len({e["tid"] for e in trio}) == 3
        build, h2d, wait = trio
        # built, then transferred, then taken by the loop
        assert build["ts"] + build["dur"] <= h2d["ts"] + h2d["dur"]
        assert h2d["ts"] <= wait["ts"] + wait["dur"]
    assert by["batch_build"][0]["args"]["rows"] == 1
    # the wait that found the iterator exhausted took no batch
    assert max(by["data_wait"]) == 4 and 4 not in by["batch_build"]
    assert "device_step" not in telemetry.goodput.SPAN_BUCKETS


def test_the_log_steps_wait_for_the_device_is_a_span(fits):
    """``loss_sync`` (the sentinel's read of the loss, where a loop
    that ran ahead waits for the device) comes before the same step's
    ``host_metrics``, which then finds the loss there; neither it nor
    ``device_step`` is in a goodput bucket (the device is at work)."""
    spans = fits["on"]["spans"]
    sync = {e["args"]["step"]: e for e in spans
            if e["name"] == "loss_sync"}
    metrics = {e["args"]["step"]: e for e in spans
               if e["name"] == "host_metrics"}
    assert sorted(sync) == sorted(metrics) == [2, 4]    # LOG_PERIOD=2
    for step in sync:
        assert sync[step]["ts"] + sync[step]["dur"] <= metrics[step]["ts"]
    assert "loss_sync" not in telemetry.goodput.SPAN_BUCKETS


def test_tracing_leaves_the_losses_alone(fits):
    assert fits["on"]["losses"] == fits["off"]["losses"]
    assert sorted(fits["on"]["losses"]) == [2, 4]


def _inside(child, parent):
    """``child`` lies within ``parent`` on the same thread (µs, with
    the ring's rounding)."""
    return (child["tid"] == parent["tid"]
            and child["ts"] >= parent["ts"] - 0.01
            and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 0.01)


FIT_EDGES = {"fit_setup": ("telemetry_start", "prefetcher_start"),
             "fit_teardown": ("goodput_final", "prefetcher_close",
                              "exporter_stop", "device_drain",
                              "ring_flush", "ckpt_wait", "writer_flush")}


@pytest.mark.parametrize("edge", sorted(FIT_EDGES))
def test_a_fits_edges_are_spans_with_their_children_inside(fits, edge):
    """One ``fit_setup`` from fit's entry to the first ``data_wait``,
    one ``fit_teardown`` over the ``finally``; each child lies inside
    its parent on the step loop's thread.  ``ckpt_wait`` and
    ``writer_flush`` follow the ring's flush and the uninstall: they
    are in the ring, not in the flushed file."""
    spans = fits["on"]["spans"]
    (parent,) = [e for e in spans if e["name"] == edge]
    loop = {e["tid"] for e in spans if e["name"] == "train_step"}
    assert {parent["tid"]} == loop
    for name in FIT_EDGES[edge]:
        (child,) = [e for e in spans if e["name"] == name]
        assert _inside(child, parent), name
    waits = sorted(e["ts"] for e in spans if e["name"] == "data_wait")
    if edge == "fit_setup":
        assert parent["ts"] + parent["dur"] <= waits[0]
        (clock,) = [e for e in spans if e["name"] == "clock"]
        assert clock["ph"] == "i" and parent["ts"] <= clock["ts"]
        assert set(clock["args"]) >= {"wall_us", "perf_s"}
    else:
        assert parent["ts"] >= waits[-1]
        flushed = {e.get("name") for e in fits["on"]["flushed"]}
        assert "ring_flush" not in flushed
        assert not flushed & {"ckpt_wait", "writer_flush", edge}
        assert "device_drain" in flushed


def test_a_log_row_is_a_span_after_host_metrics(fits):
    """At each log step ``log_row`` follows ``host_metrics`` and holds
    the counter and aggregate spans and its three children; its args
    are the steps since the last row and the collector's sums."""
    spans = fits["on"]["spans"]
    rows = {e["args"]["step"]: e for e in spans if e["name"] == "log_row"}
    assert sorted(rows) == [2, 4]                       # LOG_PERIOD=2
    metrics = {e["args"]["step"]: e for e in spans
               if e["name"] == "host_metrics"}
    for step, row in rows.items():
        assert metrics[step]["ts"] + metrics[step]["dur"] <= row["ts"]
        assert row["args"]["steps"] == 2
        assert set(row["args"]) >= {"gc_ms", "gc_count", "gc_max_ms"}
        for name in ("goodput_publish", "write_scalars", "hbm_gauges",
                     "host_aggregate"):
            (child,) = [e for e in spans if e["name"] == name
                        and e["args"].get("step") == step]
            assert _inside(child, row), name
    assert not {"log_row", "fit_setup", "fit_teardown", "gc"} \
        & set(telemetry.goodput.SPAN_BUCKETS)


def test_a_long_collection_is_a_gc_span_and_every_one_is_counted(fits):
    """The full collection forced inside the traced fit, in the
    input's generator, is a ``gc`` span of generation 2; the rows count
    it with the short ones."""
    on = fits["on"]
    full = [e for e in on["spans"] if e["name"] == "gc"
            and e["args"]["generation"] == 2]
    assert full and all(e["dur"] >= 1000.0 for e in full)
    rows = [e["args"] for e in on["spans"] if e["name"] == "log_row"]
    assert sum(r["gc_count"] for r in rows) >= 1
    assert max(r["gc_max_ms"] for r in rows) >= max(
        e["dur"] for e in full) / 1e3 - 0.01


@pytest.mark.parametrize("mode", ["off", "on"])
def test_the_gc_hook_lives_only_inside_a_traced_fit(fits, mode):
    """Traced, one hook while the fit runs; untraced, none.  Either
    way ``gc.callbacks`` is as it was after the fit, and after a fit
    that raises."""
    fit = fits[mode]
    during = fit["gc_hooks_during"]
    assert len(during) == 2                     # both fits looked
    assert all(len(hooks) == (mode == "on") for hooks in during)
    assert fit["gc_hooks_kept"] and fit["gc_hooks_kept_on_raise"]


def test_thread_safety_and_flush_is_valid_chrome_trace(tmp_path):
    path = telemetry.trace_path_for(str(tmp_path), 2)
    assert path.endswith("trace-host2.json")
    tr = Tracer(capacity=2048, path=path, host_id=2)

    def worker(n):
        for i in range(200):
            with tr.span(f"w{n}", step=i):
                pass

    threads = [threading.Thread(target=worker, args=(n,))
               for n in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.snapshot()) == 1600      # no append lost
    out = tr.flush()
    assert out == path
    doc = json.load(open(path))
    events = doc["traceEvents"]
    # process metadata + the ring, every event host-stamped
    assert events[0]["ph"] == "M"
    spans = [e for e in events if e["ph"] == "X"]
    assert len(spans) == 1600
    assert all(e["pid"] == 2 and e["args"]["host"] == 2
               for e in spans)


def test_flush_without_path_is_noop_and_close_flushes(tmp_path):
    assert Tracer(capacity=16).flush() is None  # no path, no raise
    path = str(tmp_path / "trace-host0.json")
    tr = Tracer(capacity=16, path=path)
    with tr.span("a"):
        pass
    tr.instant("profile_capture_start", step=1, reason="test")
    tr.close()
    doc = json.load(open(path))
    kinds = {e["name"] for e in doc["traceEvents"]}
    assert {"a", "profile_capture_start"} <= kinds


# ---- ProfileTrigger guard rails -------------------------------------


def test_profile_trigger_lifecycle_and_cooldown():
    clock = {"t": 100.0}
    trig = ProfileTrigger(cooldown_sec=60.0, max_captures=2,
                          default_steps=3,
                          clock=lambda: clock["t"])
    ok, detail = trig.request(steps=5, reason="debugz")
    assert ok and "5 step(s)" in detail
    # pending blocks a second request regardless of cooldown
    ok2, detail2 = trig.request()
    assert not ok2 and "pending" in detail2
    req = trig.take()
    assert req["steps"] == 5 and req["reason"] == "debugz"
    assert trig.take() is None  # consumed
    # active capture blocks requests
    ok3, detail3 = trig.request()
    assert not ok3 and "in progress" in detail3
    trig.finish()
    # cooldown: rejected until the clock advances past it
    ok4, detail4 = trig.request()
    assert not ok4 and "cooldown" in detail4
    clock["t"] += 61.0
    ok5, _ = trig.request()
    assert ok5
    trig.take()
    trig.finish()
    clock["t"] += 61.0
    # max captures per run
    ok6, detail6 = trig.request()
    assert not ok6 and "max captures" in detail6
    st = trig.status()
    assert st["captures_started"] == 2 and st["rejected"] == 4


def test_profile_trigger_rejects_bad_steps():
    trig = ProfileTrigger(default_steps=3, max_steps=10)
    assert not trig.request(steps="bogus")[0]
    assert not trig.request(steps=-1)[0]
    ok, detail = trig.request(steps=999)  # clamped, not rejected
    assert ok and "10 step(s)" in detail
    ok2, _ = trig.request(steps=None)
    assert not ok2  # already pending


# ---- anomaly detector ------------------------------------------------


def test_anomaly_detector_p95_regression_needs_k_consecutive():
    det = AnomalyDetector(k_intervals=3, p95_factor=1.5,
                          min_history=8)
    for _ in range(10):
        assert det.observe(100.0) is None
    # two anomalous intervals + a recovery: no fire, streak resets
    assert det.observe(300.0) is None
    assert det.observe(300.0) is None
    assert det.observe(100.0) is None
    # three consecutive: fires once, then the streak resets
    assert det.observe(300.0) is None
    assert det.observe(310.0) is None
    reason = det.observe(320.0)
    assert reason is not None and "p95_regression" in reason
    assert det.observe(300.0) is None  # streak restarted
    assert det.fired == 1


def test_anomaly_detector_baseline_excludes_slow_streak():
    """A building regression must not drag the rolling p95 up under
    itself — only healthy intervals feed the baseline."""
    det = AnomalyDetector(k_intervals=30, p95_factor=1.5,
                          min_history=8, window=8)
    for _ in range(8):
        det.observe(100.0)
    for _ in range(20):
        det.observe(400.0)  # long streak, below k
    assert sorted(det._history)[-1] == 100.0


def test_anomaly_detector_persistent_straggler():
    det = AnomalyDetector(k_intervals=3, spread_factor=1.5,
                          min_history=8)
    # same host lagging but tiny spread: argmax noise, never fires
    for _ in range(10):
        assert det.observe(100.0, lagging_host=2,
                           spread_ratio=1.1) is None
    # real spread, same host, K consecutive
    assert det.observe(100.0, lagging_host=2,
                       spread_ratio=2.0) is None
    assert det.observe(100.0, lagging_host=2,
                       spread_ratio=2.0) is None
    reason = det.observe(100.0, lagging_host=2, spread_ratio=2.0)
    assert reason is not None and "host 2" in reason
    # a different host resets the streak
    assert det.observe(100.0, lagging_host=0,
                       spread_ratio=2.0) is None
    assert det.observe(100.0, lagging_host=1,
                       spread_ratio=2.0) is None


# ---- /debugz/stacks payload -----------------------------------------


def test_format_thread_stacks_lists_live_threads():
    text = format_thread_stacks()
    assert "MainThread" in text
    assert "test_format_thread_stacks_lists_live_threads" in text


# ---- cross-host merge (tools/trace_summary.py --merge) ---------------


def _host_events(host, skew_us, slow_step=None):
    """Five steps of the fit loop's span shape.  The slow step stalls
    in data_wait while its train_step DISPATCH stays short — the
    async-accelerator signature the ranking must still catch."""
    evs = []
    for step in range(1, 6):
        base = skew_us + 1_000_000 + 10_000 * step
        evs.append({"name": "train_step", "ph": "X", "ts": base,
                    "dur": 800.0, "pid": host, "tid": 1,
                    "args": {"host": host, "step": step}})
        evs.append({"name": "data_wait", "ph": "X", "ts": base - 500,
                    "dur": 8_000 if step == slow_step else 90.0,
                    "pid": host, "tid": 1,
                    "args": {"host": host, "step": step}})
    return evs


def _write_host_trace(logdir, host, events):
    with open(os.path.join(logdir, f"trace-host{host}.json"),
              "w") as f:
        json.dump({"traceEvents": events}, f)


def test_merge_aligns_clocks_and_names_dominant_span(tmp_path):
    from tools import trace_summary

    logdir = str(tmp_path)
    # the stamper's device_step spans overlap the loop's: in the
    # timeline, not in a step's wall or its dominant span
    stamps = [{"name": "device_step", "ph": "X", "ts": 1_000_000.0,
               "dur": 50_000.0, "pid": 0, "tid": 2,
               "args": {"host": 0, "step": step}} for step in (2, 3)]
    _write_host_trace(logdir, 0, _host_events(0, 0) + stamps)
    # host 1's wall clock is 7 s ahead (NTP skew) and step 3 stalls
    # in data_wait
    _write_host_trace(logdir, 1,
                      _host_events(1, 7_000_000, slow_step=3))
    merged = trace_summary.merge_host_traces(logdir)
    assert merged["hosts"] == [0, 1]
    # the skew was recovered from step boundaries
    assert abs(merged["host_offsets_us"]["1"] + 7_000_000) < 1_000
    assert merged["steps_covered"] == 5
    slow = merged["slow_steps"][0]
    assert slow["step"] == 3 and slow["host"] == 1
    # per-step wall = Σ of the loop's spans (8.0 wait + 0.8 dispatch):
    # ranking by the dispatch span alone would hide the starved step
    assert slow["ms"] == 8.8
    assert slow["dominant_span"] == "data_wait"
    assert slow["dominant_ms"] == 8.0
    assert sum(e.get("name") == "device_step"
               for e in merged["traceEvents"]) == 2
    # merged timeline: host 1's aligned events interleave host 0's
    aligned = [e for e in merged["traceEvents"]
               if e.get("pid") == 1 and e.get("name") == "train_step"]
    ref = [e for e in merged["traceEvents"]
           if e.get("pid") == 0 and e.get("name") == "train_step"]
    assert abs(aligned[0]["ts"] - ref[0]["ts"]) < 1_000


def test_merge_missing_traces_raises(tmp_path):
    from tools import trace_summary

    with pytest.raises(FileNotFoundError):
        trace_summary.merge_host_traces(str(tmp_path))


def test_merge_cli_and_run_report_section(tmp_path):
    from tools import run_report, trace_summary

    logdir = str(tmp_path)
    _write_host_trace(logdir, 0, _host_events(0, 0, slow_step=2))
    out = str(tmp_path / "merged.json")
    assert trace_summary.main([logdir, "--merge", "--out", out]) == 0
    doc = json.load(open(out))
    assert doc["slow_steps"][0]["step"] == 2
    assert any(e["name"] == "train_step" for e in doc["traceEvents"])
    # run_report names the dominant span in its slow-steps table
    report = run_report.render_report(logdir)
    assert "## Slow steps (span tracing)" in report
    assert "| 2 | 0 | 8.8 |" in report
    assert "data_wait" in report


def test_run_report_degrades_without_traces(tmp_path):
    from tools import run_report

    report = run_report.render_report(str(tmp_path))
    assert "No trace-host*.json found" in report
