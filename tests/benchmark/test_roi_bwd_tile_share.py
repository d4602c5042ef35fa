"""The reader PR 29 brings: ``roi_bwd_tile_share_pct``, the mean of the
``roi_bwd_strips`` spans' counter, on contexts made by hand, and its
entry in ``BENCHMARK.json``."""

import json
import os

import pytest

import bench_smoke
from benchmark import harness
from benchmark.metrics import roi_bwd_tile_share_pct

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _context(spans):
    cell = bench_smoke.smoke_cell(mask=False)
    return cell, harness.TraceContext(
        spec=cell.spec, task=cell.task, chips=1, images_per_step=2,
        images_per_sec_per_chip=10.0, window_s=4.0, window_steps=60,
        traced_steps=0, feature_itemsize=4, peak=bench_smoke.CPU_PEAK,
        spans=list(spans))


def _strips(step, share):
    return {"name": "roi_bwd_strips", "ts": 1e9 + step, "dur": 0.0,
            "args": {"step": step, "roi_bwd_tile_share": share}}


@pytest.mark.parametrize("spans, want", [
    ([_strips(20, 0.125), _strips(40, 0.25), _strips(60, 0.375)], 25.0),
    ([_strips(20, 1.0)], 100.0),
    # other spans, and a span of that name without the counter (a
    # program that kept the span and dropped the key), are not read
    ([{"name": "moe_route", "args": {"roi_bwd_tile_share": 0.9}},
      {"name": "roi_bwd_strips", "args": {"step": 20}},
      {"name": "data_wait", "dur": 2000.0}, _strips(40, 0.5)], 50.0),
])
def test_mean_of_the_windows_spans_as_a_percentage(spans, want):
    _, ctx = _context(spans)
    assert roi_bwd_tile_share_pct.read(ctx) == pytest.approx(want)


@pytest.mark.parametrize("spans", [
    [],
    [{"name": "device_step", "ts": 1.0, "dur": 1.0, "args": {"step": 6}}],
    [{"name": "roi_bwd_strips", "args": {"step": 20}}],
])
def test_nothing_without_the_counter(spans):
    """The parent program: no such span, so the reader returns None and
    the harness leaves the metric out of the line; it never raises."""
    cell, ctx = _context(spans)
    assert roi_bwd_tile_share_pct.read(ctx) is None
    assert "roi_bwd_tile_share_pct" not in harness.read_per_layer(cell, ctx)


def check_the_entry_in_the_manifest(root):
    """Found by its name: entries appended after it are not its
    business."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = [m for m in manifest["per_layer"]
                if m["name"] == "roi_bwd_tile_share_pct"]
    assert entry == {
        "name": "roi_bwd_tile_share_pct", "unit": "%", "better": "lower",
        "source": "program_counter",
        "layer": "kernels - ops/pallas/roi_align_kernel.py",
        "moves": "images_per_sec_per_chip",
        # both detectors' programs write the span (PR 32)
        "workloads": ["frcnn-r50-train-1344-b4", "mask-r50-train-1344-b4"]}
    cell, ctx = _context([_strips(20, 0.2)])
    out = harness.read_per_layer(cell, ctx)
    assert out["roi_bwd_tile_share_pct"] == {
        "value": pytest.approx(20.0), "unit": "%"}


def test_the_entry_in_the_manifest():
    check_the_entry_in_the_manifest(ROOT)
