"""The traffic generator: same seed, same records; exact size and
instance mixes for every seed; polygons rasterise through
``DetectionLoader`` with masks on."""

import collections
import json
import os

import numpy as np

import bench_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness, traffic

ROOT = bench_smoke.ROOT


def _params():
    with open(os.path.join(ROOT, "benchmark", "mixes",
                           "vga-mix-b4.json")) as f:
        return json.load(f)


def test_same_seed_same_records_and_large_seeds():
    p = dict(_params(), records=12)
    a = traffic.generate(p, 2147483900)
    b = traffic.generate(p, 2147483900)
    c = traffic.generate(p, 7)
    for x, y in zip(a, b):
        assert np.array_equal(x["_image"], y["_image"])
        assert np.array_equal(x["boxes"], y["boxes"])
        assert x["segmentation"] == y["segmentation"]
    assert not np.array_equal(np.concatenate([r["boxes"] for r in a]),
                              np.concatenate([r["boxes"] for r in c]))


def test_mix_is_exact_and_the_same_for_every_seed():
    p = _params()
    p_small = dict(p, sizes=[[6, 8, 0.70], [8, 6, 0.25], [8, 8, 0.05]],
                   box_side_px=[2, 4])
    mixes = []
    for seed in (1, 2, 3):
        recs = traffic.generate(p_small, seed)
        assert len(recs) == 256
        sizes = collections.Counter((r["height"], r["width"]) for r in recs)
        counts = collections.Counter(len(r["boxes"]) for r in recs)
        mixes.append((sizes, counts))
        assert sizes == {(6, 8): 179, (8, 6): 64, (8, 8): 13}
        assert min(counts) == 1 and max(counts) == 15
        assert max(counts.values()) - min(counts.values()) <= 1
    assert mixes[0] == mixes[1] == mixes[2]


def test_instance_counts_take_any_histogram():
    """A later mix (a dataset's own long-tailed histogram) is data."""
    p = {k: v for k, v in _params().items() if k != "instances"}
    p.update(records=40, sizes=[[6, 8, 1.0]], box_side_px=[2, 4],
             instance_counts=[[1, 0.5], [3, 0.25], [20, 0.25]])
    for seed in (1, 2):
        counts = collections.Counter(
            len(r["boxes"]) for r in traffic.generate(p, seed))
        assert counts == {1: 20, 3: 10, 20: 10}


def test_boxes_are_the_polygons_extent_inside_the_image():
    for r in traffic.generate(dict(_params(), records=8), 4):
        for box, seg in zip(r["boxes"], r["segmentation"]):
            poly = np.asarray(seg[0]).reshape(-1, 2)
            assert np.allclose(box, [poly[:, 0].min(), poly[:, 1].min(),
                                     poly[:, 0].max(), poly[:, 1].max()],
                               atol=1e-3)
            assert 0 <= box[0] < box[2] <= r["width"]
            assert 0 <= box[1] < box[3] <= r["height"]
            assert 1 <= r["classes"].min() and r["classes"].max() <= 80


def test_polygons_rasterise_through_the_loader():
    batch = harness.first_batches(bench_smoke.smoke_cell(mask=True), 9, 1)[0]
    masks, valid = batch["gt_masks"], batch["gt_valid"]
    assert masks.shape[1:] == (8, 56, 56) and batch["images"].dtype == np.uint8
    fill = masks[valid > 0].mean(axis=(1, 2))
    # a 12-vertex star of radius 0.6-1.0 fills well under its whole box
    assert (fill > 0.2).all() and (fill < 0.95).all()


def test_feed_counts_and_deadline():
    src = iter(range(100))
    assert list(traffic.feed(src, count=3)) == [0, 1, 2]
    ticks = iter([0.0, 1.0, 2.0, 3.0, 4.0])
    seen = []
    out = list(traffic.feed(src, deadline=2.5, clock=lambda: next(ticks),
                            on_batch=seen.append))
    assert out == [3, 4, 5] == seen
