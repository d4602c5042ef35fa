"""The reader PR 31 brings: ``roi_fwd_tile_share_pct``, the mean of the
``roi_bwd_strips`` spans' forward counter, on contexts made by hand,
and its entry in ``BENCHMARK.json`` (registered by PR 32; it waited in
a data file while an accepted test held ``roi_bwd_tile_share_pct`` to
the last place of ``per_layer``)."""

import dataclasses
import json
import os
import re

import pytest

from benchmark import harness
from benchmark.metrics import roi_bwd_tile_share_pct, roi_fwd_tile_share_pct
from test_roi_bwd_tile_share import _context

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _strips(step, fwd, bwd=0.2):
    return {"name": "roi_bwd_strips", "ts": 1e9 + step, "dur": 0.0,
            "args": {"step": step, "roi_bwd_tile_share": bwd,
                     "roi_fwd_tile_share": fwd}}


@pytest.mark.parametrize("spans, want", [
    ([_strips(20, 0.125), _strips(40, 0.25), _strips(60, 0.375)], 25.0),
    ([_strips(20, 1.0)], 100.0),
    # other spans, and a span of that name without the counter, are
    # not read
    ([{"name": "moe_route", "args": {"roi_fwd_tile_share": 0.9}},
      {"name": "roi_bwd_strips", "args": {"step": 20}},
      {"name": "data_wait", "dur": 2000.0}, _strips(40, 0.5)], 50.0),
])
def test_mean_of_the_windows_spans_as_a_percentage(spans, want):
    _, ctx = _context(spans)
    assert roi_fwd_tile_share_pct.read(ctx) == pytest.approx(want)


def test_the_two_shares_ride_one_span_and_are_read_apart():
    _, ctx = _context([_strips(20, 0.24, bwd=0.21),
                       _strips(40, 0.26, bwd=0.19)])
    assert roi_fwd_tile_share_pct.read(ctx) == pytest.approx(25.0)
    assert roi_bwd_tile_share_pct.read(ctx) == pytest.approx(20.0)


@pytest.mark.parametrize("spans", [
    [],
    [{"name": "device_step", "ts": 1.0, "dur": 1.0, "args": {"step": 6}}],
    # the parent program: the span is there, with the backward's
    # counter alone
    [{"name": "roi_bwd_strips",
      "args": {"step": 20, "roi_bwd_tile_share": 0.2}}],
])
def test_nothing_without_the_counter(spans):
    """A program that lacks the counter: the reader returns None and
    the harness leaves the metric out of the line; it never raises."""
    cell, ctx = _context(spans)
    assert roi_fwd_tile_share_pct.read(ctx) is None
    cell = dataclasses.replace(cell, per_layer=_entry())
    assert harness.read_per_layer(cell, ctx) == {}


def _entry():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return [m for m in manifest["per_layer"]
            if m["name"] == "roi_fwd_tile_share_pct"]


def test_the_entry_in_the_manifest():
    """Found by its name: the keys, the layer's name and the cells of
    the backward's twin, a name within the manifest's rules, and a
    reader the harness finds by that name."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    (entry,) = _entry()
    twin = next(m for m in manifest["per_layer"]
                if m["name"] == "roi_bwd_tile_share_pct")
    assert entry == dict(twin, name="roi_fwd_tile_share_pct")
    assert re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}", entry["name"])
    assert set(entry["workloads"]) == {
        "frcnn-r50-train-1344-b4", "mask-r50-train-1344-b4"}
    cell, ctx = _context([_strips(20, 0.24)])
    cell = dataclasses.replace(cell, per_layer=[entry])
    assert harness.read_per_layer(cell, ctx) == {
        "roi_fwd_tile_share_pct": {"value": pytest.approx(24.0),
                                   "unit": "%"}}
