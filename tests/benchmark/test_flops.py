"""benchmark/flops.py against published and hand-worked numbers."""

import json
import os

import pytest

import bench_smoke
from benchmark import flops


@pytest.fixture(scope="module")
def spec():
    with open(os.path.join(bench_smoke.ROOT, "benchmark", "configs",
                           "maskrcnn-r50-fpn.json")) as f:
        return json.load(f)["model"]


def test_resnet50_is_the_published_4_1_gmac_and_scales_by_area():
    at224 = flops.resnet_forward_ops((3, 4, 6, 3), 224, 224) / 2
    assert at224 == pytest.approx(4.09e9, rel=0.01)   # He et al.: ~4.1 GMAC
    at1344 = flops.resnet_forward_ops((3, 4, 6, 3), 1344, 1344) / 2
    assert at1344 == pytest.approx(at224 * 36, rel=1e-9)
    r101 = flops.resnet_forward_ops((3, 4, 23, 3), 224, 224) / 2
    assert r101 == pytest.approx(7.8e9, rel=0.02)     # ~7.8 GMAC


def test_hand_worked_heads(spec):
    parts = flops.forward_parts(spec, 1344, 1344)
    box = 2 * 512 * (12544 * 1024 + 1024 * 1024 + 1024 * 81 + 1024 * 324)
    assert parts["box_head"] == box
    mask = 128 * 2 * (4 * 14 * 14 * 9 * 256 * 256 + 14 * 14 * 4 * 256 * 256
                      + 28 * 28 * 256 * 81)
    assert parts["mask_head"] == mask
    rpn = sum(2 * (1344 // s) ** 2 * (9 * 256 * 256 + 256 * 15)
              for s in (4, 8, 16, 32, 64))
    assert parts["rpn_head"] == rpn


def test_training_is_under_three_forwards_and_the_mask_branch_shows(spec):
    fwd = sum(flops.forward_parts(spec, 1344, 1344).values())
    train = flops.train_ops_per_image(spec, 1344, 1344)
    assert 2.5 * fwd < train < 3 * fwd      # frozen stem + stage: forward only
    assert train == pytest.approx(2.36e12, rel=0.01)
    no_mask = flops.train_ops_per_image(dict(spec, mask=False), 1344, 1344)
    head = flops.forward_parts(spec, 1344, 1344)["mask_head"]
    mask_roi = sum(c["ops"] for c in flops.roi_align_calls(spec, 1344, 1344)
                   if c["head"] == "mask")
    assert train - no_mask == 3 * head + mask_roi


def test_roi_align_floor_is_bound_by_bytes(spec):
    calls = flops.roi_align_calls(spec, 1344, 1344, itemsize=2)
    assert [(c["head"], c["pass"]) for c in calls] == [
        ("box", "forward"), ("box", "backward"),
        ("mask", "forward"), ("mask", "backward")]
    box_fwd = calls[0]
    assert box_fwd["bytes"] == 2 * 512 * 49 * 256 * 2
    assert box_fwd["ops"] == 32 * 512 * 49 * 256
    for c in calls:
        assert c["bytes"] / 819e9 > c["ops"] / 197e12
