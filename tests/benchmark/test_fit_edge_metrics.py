"""The five readers of the window's non-step time (the fit's head and
tail, each log step's drained stretch, the log row, the collector) on
contexts made by hand, and on a traced run of the harness at smoke
widths, where ``Trainer.fit`` records the spans they read."""

import pytest

import bench_smoke
from benchmark import harness
from benchmark.metrics import (fit_edges_ms, gc_pause_ms, log_drain_ms,
                               log_row_ms, window_idle_pct)

FIVE = ("log_drain_ms", "log_row_ms", "fit_edges_ms", "window_idle_pct",
        "gc_pause_ms")
T0 = 1.7e15                     # µs on the ring's wall clock


def span(name, ms, dur_ms, **args):
    return {"name": name, "ts": T0 + ms * 1e3, "dur": dur_ms * 1e3,
            "args": args}


def context(spans, window_s=2.0):
    cell = bench_smoke.smoke_cell(mask=True)
    return harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=1.0, window_s=window_s, window_steps=8,
        traced_steps=0, feature_itemsize=4, peak=bench_smoke.CPU_PEAK,
        spans=list(spans))


def a_fit(teardown=(), device_done=1900.0):
    """A window's fit: set-up 0-40 ms, first dispatch done at 50 ms,
    log steps 4 (sync ends at 700, step 5 dispatched by 730) and 8
    (sync ends at 1500, no dispatch after it), teardown from 1800 to
    2000 with ``teardown``'s children."""
    return [span("fit_setup", 0.0, 40.0),
            span("train_step", 42.0, 8.0, step=1),
            span("loss_sync", 600.0, 100.0, step=4),
            span("host_metrics", 700.0, 0.5, step=4),
            span("log_row", 701.0, 20.0, step=4, steps=4, gc_ms=0.8,
                 gc_count=12, gc_max_ms=0.3),
            span("train_step", 722.0, 8.0, step=5),
            span("loss_sync", 1400.0, 100.0, step=8),
            span("host_metrics", 1500.0, 0.5, step=8),
            span("log_row", 1501.0, 30.0, step=8, steps=4, gc_ms=1.6,
                 gc_count=10, gc_max_ms=1.2),
            span("device_step", device_done - 200.0, 200.0, step=9),
            span("fit_teardown", 1800.0, 200.0, step=9), *teardown]


def test_a_last_log_step_with_no_dispatch_after_it_is_left_out():
    ctx = context(a_fit())
    assert log_drain_ms.drains_s(ctx) == [pytest.approx(0.030)]
    assert log_drain_ms.read(ctx) == pytest.approx(30.0)
    # where the sentinel does not observe a log step, the wait ends in
    # host_metrics
    unobserved = [ev for ev in a_fit() if ev["name"] != "loss_sync"]
    assert log_drain_ms.read(context(unobserved)) == pytest.approx(29.5)


@pytest.mark.parametrize("children, tail_ms", [
    # nothing after the device's last step: from its end to the end
    ((), 100.0),
    # the ring's flush is the last child: the tail ends at its start
    ((span("ring_flush", 1950.0, 50.0),), 50.0),
    # what follows the flush is the untraced fit's too
    ((span("ring_flush", 1930.0, 50.0), span("ckpt_wait", 1985.0, 10.0)),
     50.0),
])
def test_the_tail_leaves_the_ring_flush_out(children, tail_ms):
    ctx = context(a_fit(children))
    head, tail = fit_edges_ms.edges_s(ctx)
    assert head == pytest.approx(0.050)
    assert tail == pytest.approx(tail_ms / 1e3)
    assert fit_edges_ms.read(ctx) == pytest.approx(50.0 + tail_ms)


@pytest.mark.parametrize("device_done, tail_ms", [
    (1900.0, 100.0),        # the teardown waits 100 ms for the device
    (1700.0, 200.0),        # the device was done before it started
])
def test_a_device_still_at_work_in_the_teardown_is_busy(device_done,
                                                        tail_ms):
    ctx = context(a_fit(device_done=device_done))
    assert fit_edges_ms.edges_s(ctx)[1] == pytest.approx(tail_ms / 1e3)


def test_window_idle_is_head_drains_and_tail_over_the_window():
    ctx = context(a_fit(), window_s=2.0)
    assert window_idle_pct.read(ctx) == pytest.approx(
        100 * (0.050 + 0.030 + 0.100) / 2.0)
    ctx.window_s = 0.0
    assert window_idle_pct.read(ctx) is None


def test_the_log_row_and_the_collector():
    ctx = context(a_fit())
    assert log_row_ms.read(ctx) == pytest.approx(25.0)
    assert gc_pause_ms.read(ctx) == pytest.approx((0.8 + 1.6) / 8)


@pytest.mark.parametrize("name", FIVE)
def test_a_fit_without_the_spans_gives_nothing(name):
    """A program that records none of these spans (the parent's) gives
    no reading, never 0; without the fit's edges the idle share too."""
    reader = harness._module("metrics", name)
    older = [ev for ev in a_fit() if ev["name"] in (
        "train_step", "device_step", "host_metrics", "loss_sync")]
    if name == "log_drain_ms":       # its spans are all older than these
        assert reader.read(context(older)) == pytest.approx(30.0)
        older = [ev for ev in older if ev["name"] != "host_metrics"]
    assert reader.read(context(older)) is None
    assert reader.read(context([])) is None


def test_a_traced_run_reports_the_five():
    """The harness at smoke widths on the CPU, traced: the window's fit
    records every span the five read (no device number: the CPU's
    clock, read only for presence and range)."""
    import jax

    cell = bench_smoke.smoke_cell(mask=False)
    out = harness.run_cell(
        cell, seed=37, seconds=0.5, trace=True, t_start=0.0,
        devices=jax.devices()[:1], peaks=bench_smoke.CPU_PEAK)
    assert out["correct"], out["compared"]
    got = {k: v["value"] for k, v in out["metrics"].items() if k in FIVE}
    assert set(got) == set(FIVE)
    assert 0 < got["window_idle_pct"] <= 100
    assert got["gc_pause_ms"] >= 0
    assert got["log_row_ms"] > 0 and got["fit_edges_ms"] > 0
