"""The six readers PR 25 brings (per-kernel ROIAlign rooflines, step
completion percentiles, the loader's and the prefetcher's busy time)
on a context made by hand, and their entries in ``BENCHMARK.json``
(registered by PR 27; they waited in a data file until a ``benchmark``
PR could edit ``test_trace_reduce``'s hand-made context)."""

import json
import os
import statistics

import pytest

import bench_smoke
from benchmark import flops, harness, trace_reduce
from benchmark.metrics import (batch_build_ms, h2d_prefetch_ms,
                               roi_align_bwd_roofline_pct,
                               roi_align_fwd_roofline_pct,
                               roi_align_kernels_roofline_pct, step_ms_p50,
                               step_ms_p75)

NEW = ("roi_align_fwd_roofline_pct", "roi_align_bwd_roofline_pct",
       "step_ms_p50", "step_ms_p75", "batch_build_ms", "h2d_prefetch_ms")

# device seconds per traced stretch, by instruction, as the profiler's
# XLA Ops line names them once the pallas_calls carry a name
OP_SECONDS = {
    "roi_align_fwd.8": 0.004, "roi_align_fwd.9": 0.002,
    "roi_align_bwd.3": 0.010, "roi_align_bwd.4": 0.030,
    "roi_align_seed_copy.1": 0.001,
    "fusion.37": 0.5, "roi_align_fwdish_fusion.2": 0.25,
}
FWD_S, BWD_S, COPY_S = 0.006, 0.040, 0.001


def _stamps(step_ms, first_step=6, t0_us=1.7e15):
    """``device_step`` spans whose ends lie ``step_ms[i]`` apart, out
    of order in the ring as a slow flush could leave them."""
    spans, end = [], t0_us
    for i, ms in enumerate([0.0] + list(step_ms)):
        end += ms * 1e3
        spans.append({"name": "device_step", "ts": end - 250.0,
                      "dur": 250.0, "args": {"step": first_step + i}})
    return spans[::-1]


def _context(spans=(), op_seconds=None, traced_steps=5):
    cell = bench_smoke.smoke_cell(mask=True)
    summary = None
    if op_seconds is not None:
        summary = trace_reduce.TraceSummary(
            devices=1, steps=traced_steps, window_s=2.0, busy_s=1.5,
            op_seconds=dict(op_seconds),
            custom_call_s=FWD_S + BWD_S + COPY_S, custom_call_events=25)
    return cell, harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=10.0, window_s=4.0, window_steps=20,
        traced_steps=traced_steps if summary else 0, feature_itemsize=4,
        peak=bench_smoke.CPU_PEAK, spans=list(spans), trace=summary,
        memory_stats=[{"peak_bytes_reserved": 8.1e9}])


def _need_s(ctx, direction):
    """Bytes bound every call at these peaks (ops / 1e12 is 10x under
    bytes / 1e11 only where ops < bytes; checked below)."""
    calls = [c for c in flops.roi_align_calls(ctx.spec, 128, 128, itemsize=4)
             if c["pass"] == direction]
    assert len(calls) == 2                  # box and mask
    return sum(max(c["bytes"] / 1e11, c["ops"] / 1e12) for c in calls) * 2


def test_kernel_rooflines_split_the_grouped_one():
    _, ctx = _context(op_seconds=OP_SECONDS)
    fwd = roi_align_fwd_roofline_pct.read(ctx)
    bwd = roi_align_bwd_roofline_pct.read(ctx)
    assert fwd == pytest.approx(100 * _need_s(ctx, "forward") * 5 / FWD_S)
    assert bwd == pytest.approx(
        100 * _need_s(ctx, "backward") * 5 / (BWD_S + COPY_S))
    # an instruction is a kernel's by the name before the first dot,
    # not by a prefix: roi_align_fwdish_fusion.2 is nobody's
    seconds = roi_align_fwd_roofline_pct.kernel_seconds
    assert seconds(ctx, ("roi_align_fwd",)) == pytest.approx(FWD_S)
    # forward + backward + copy are all the custom calls there are, and
    # the two floors add up to the grouped reader's
    named = seconds(ctx, ("roi_align_fwd", "roi_align_bwd",
                          "roi_align_seed_copy"))
    assert named == pytest.approx(ctx.trace.custom_call_s)
    grouped_need, _ = roi_align_kernels_roofline_pct.bound_seconds(ctx)
    assert _need_s(ctx, "forward") + _need_s(ctx, "backward") == \
        pytest.approx(grouped_need)
    grouped = roi_align_kernels_roofline_pct.read(ctx)
    assert grouped == pytest.approx(
        (fwd * FWD_S + bwd * (BWD_S + COPY_S)) / named)


def test_kernel_rooflines_find_nothing_on_unnamed_kernels():
    """The parent's trace: custom calls named ``roi_align.N``."""
    _, ctx = _context(op_seconds={"roi_align.60": 0.04, "fusion.37": 0.5})
    assert roi_align_kernels_roofline_pct.read(ctx) is not None
    assert roi_align_fwd_roofline_pct.read(ctx) is None
    assert roi_align_bwd_roofline_pct.read(ctx) is None
    _, ctx = _context(op_seconds=OP_SECONDS, traced_steps=0)
    assert roi_align_fwd_roofline_pct.read(ctx) is None
    _, untraced = _context()
    assert roi_align_bwd_roofline_pct.read(untraced) is None


def test_step_percentiles_from_completion_stamps():
    # 44 differences: 40 of 250 ms rising by 0.1, then the tail
    diffs = [250.0 + 0.1 * i for i in range(40)] + [251, 262, 300, 900]
    _, ctx = _context(spans=_stamps(diffs) + [
        {"name": "train_step", "ts": 0.0, "dur": 9.0, "args": {"step": 6}}])
    assert step_ms_p50.step_intervals_ms(ctx) == pytest.approx(diffs)
    assert step_ms_p50.read(ctx) == pytest.approx(statistics.median(diffs))
    # 75th percentile of 44 values: rank 0.75 * 43 = 32.25, linear
    # between the order statistics 32 and 33 (0-based).  The tail's 251
    # sorts in among the first forty, so those are 253.1 and 253.2
    assert step_ms_p75.read(ctx) == pytest.approx(253.125)


@pytest.mark.parametrize("n, p50, p75", [
    (0, False, False), (10, False, False), (11, True, False),
    (39, True, False), (40, True, True)])
def test_step_percentiles_sample_floors(n, p50, p75):
    _, ctx = _context(spans=_stamps([250.0] * n) if n else [])
    assert (step_ms_p50.read(ctx) is not None) == p50
    assert (step_ms_p75.read(ctx) is not None) == p75


def test_a_missing_stamp_joins_no_two_steps():
    """A stamp the ring dropped leaves a gap, not a 500 ms step."""
    spans = [s for s in _stamps([250.0] * 14) if s["args"]["step"] != 12]
    _, ctx = _context(spans=spans)
    assert step_ms_p50.step_intervals_ms(ctx) == pytest.approx([250.0] * 12)


def test_producer_busy_times():
    spans = [{"name": "batch_build", "dur": 30000.0,
              "args": {"seq": 7, "rows": 4}},
             {"name": "batch_build", "dur": 50000.0,
              "args": {"seq": 8, "rows": 4}},
             {"name": "h2d_prefetch", "dur": 6000.0, "args": {"seq": 3}},
             {"name": "data_wait", "dur": 99000.0, "args": {"seq": 3}}]
    _, ctx = _context(spans=spans)
    assert batch_build_ms.read(ctx) == pytest.approx(40.0)
    assert h2d_prefetch_ms.read(ctx) == pytest.approx(6.0)
    _, empty = _context()
    assert batch_build_ms.read(empty) is None
    assert h2d_prefetch_ms.read(empty) is None


@pytest.fixture(scope="module")
def manifest():
    with open(os.path.join(bench_smoke.ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def test_the_six_entries_are_registered(manifest):
    """``test_manifest`` holds them to the manifest's own checks; here:
    each is there, on a layer PR 24's five already name, in both of the
    detector's cells."""
    six = [m for m in manifest["per_layer"] if m["name"] in NEW]
    assert sorted(m["name"] for m in six) == sorted(NEW)
    layers = {m["layer"] for m in manifest["per_layer"]
              if m["name"] not in NEW}
    for m in six:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["layer"] in layers         # letter for letter
        assert set(m["workloads"]) >= {"mask-r50-train-1344-b4",
                                       "frcnn-r50-train-1344-b4"}
        assert m["moves"] == "images_per_sec_per_chip"
    assert len(json.dumps(manifest, indent=1)) < 64 * 1024


def check_entries_are_registered_or_not_in_the_tree(root, manifest):
    """``benchmark/metrics/`` holds readers and, under ``examples/``,
    one example for each registered entry: no data file in which
    entries wait beside the manifest (PRs 25 and 31 each grew one)."""
    metrics = os.path.join(root, "benchmark", "metrics")
    parked = [os.path.relpath(os.path.join(d, f), metrics)
              for d, _, files in os.walk(metrics) for f in files
              if f.endswith(".json")
              and os.path.relpath(d, metrics) != "examples"]
    assert not parked
    assert sorted(os.listdir(os.path.join(metrics, "examples"))) == sorted(
        m["name"] + ".json" for m in manifest["per_layer"])


def test_entries_are_registered_or_not_in_the_tree(manifest):
    check_entries_are_registered_or_not_in_the_tree(bench_smoke.ROOT,
                                                    manifest)


def test_every_reader_old_and_new_on_one_context():
    """What ``test_trace_reduce`` asserts, for every metric the mask
    cell reports, however many later PRs append, on a context that
    holds what each of their examples holds
    (``benchmark/metrics/examples/``)."""
    cell = bench_smoke.smoke_cell(mask=True)
    ctx = bench_smoke.example_context(cell, peak=bench_smoke.CPU_PEAK)
    out = harness.read_per_layer(cell, ctx)
    assert set(out) == {m["name"] for m in cell.per_layer} >= set(NEW)
    assert out["batch_build_ms"]["unit"] == "ms/batch"
    # the six, on their own examples alone: whatever spans a later
    # reader's example brings, these read theirs
    six = bench_smoke.example_context(cell, NEW, peak=bench_smoke.CPU_PEAK)
    out = harness.read_per_layer(cell, six)
    assert set(out) >= set(NEW)
    assert out["step_ms_p50"] == {"value": 250.0, "unit": "ms"}
    assert out["batch_build_ms"] == {"value": 30.0, "unit": "ms/batch"}
    # and on a context with nothing in it, none of them
    nothing = bench_smoke.example_context(cell, ())
    assert harness.read_per_layer(cell, nothing) == {}
