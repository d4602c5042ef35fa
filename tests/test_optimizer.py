"""Optimizer semantics: LR schedule scaling + weight-decay scope.

These pin the two numerics-parity behaviors the reference couples to
world size (SURVEY.md §7 hard part #3): LR boundaries are specified in
global-batch-8 steps (charts/maskrcnn/values.yaml:15 vs run.sh:42), and
weight decay must never touch frozen backbone stages (their gradient is
stopped, so decay would silently shrink pretrained weights).
"""

import jax.numpy as jnp
import pytest

from eksml_tpu.models.mask_rcnn import decay_mask as _decay_mask
from eksml_tpu.train import lr_schedule


def test_lr_boundaries_scale_with_global_batch(fresh_config):
    cfg = fresh_config
    cfg.TRAIN.NUM_CHIPS = 16
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.BASE_LR = 0.01
    cfg.TRAIN.LR_SCHEDULE = (240000, 320000, 360000)
    cfg.TRAIN.WARMUP_STEPS = 0
    sched = lr_schedule(cfg)
    base = 0.01 * 16 / 8
    # 240000 steps @batch8 → 120000 steps @batch16
    assert float(sched(119999)) == pytest.approx(base, rel=1e-5)
    assert float(sched(120001)) == pytest.approx(base * 0.1, rel=1e-5)
    assert float(sched(160001)) == pytest.approx(base * 0.01, rel=1e-5)


@pytest.mark.parametrize("num_chips,batch_per_chip", [
    (8, 1),      # global batch 8  — boundaries unchanged
    (32, 4),     # global batch 128 — v5e-32 optimized operating point
    (256, 4),    # global batch 1024 — v5e-256 scale
])
def test_lr_schedule_no_dropped_decay_at_scale(fresh_config, num_chips,
                                               batch_per_chip):
    """At large global batch, rescaled boundaries can collide onto the
    same step; the ×0.1 factors must accumulate, never drop.  After the
    last boundary the LR must always be base × 0.1^len(schedule)."""
    cfg = fresh_config
    cfg.TRAIN.NUM_CHIPS = num_chips
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = batch_per_chip
    cfg.TRAIN.BASE_LR = 0.01
    cfg.TRAIN.LR_SCHEDULE = (240000, 320000, 360000)
    cfg.TRAIN.WARMUP_STEPS = 0
    global_batch = num_chips * batch_per_chip
    sched = lr_schedule(cfg)
    base = 0.01 * global_batch / 8
    last = max(1, int(360000 * 8 / global_batch))
    assert float(sched(last + 1)) == pytest.approx(base * 1e-3, rel=1e-4)


def test_lr_schedule_collision_accumulates(fresh_config):
    """Two boundaries that rescale to the same step (both clamp to 1 at
    an absurd global batch) apply both decays at that step."""
    cfg = fresh_config
    cfg.TRAIN.NUM_CHIPS = 1000000
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.BASE_LR = 0.01
    cfg.TRAIN.LR_SCHEDULE = (240000, 320000, 360000)
    cfg.TRAIN.WARMUP_STEPS = 0
    sched = lr_schedule(cfg)
    base = 0.01 * 1000000 / 8
    assert float(sched(2)) == pytest.approx(base * 1e-3, rel=1e-4)


def test_lr_warmup_then_base(fresh_config):
    cfg = fresh_config
    cfg.TRAIN.NUM_CHIPS = 8
    cfg.TRAIN.BATCH_SIZE_PER_CHIP = 1
    cfg.TRAIN.WARMUP_STEPS = 100
    cfg.TRAIN.WARMUP_INIT_FACTOR = 0.33
    sched = lr_schedule(cfg)
    assert float(sched(0)) < float(sched(50)) < float(sched(100))
    assert float(sched(100)) == pytest.approx(cfg.TRAIN.BASE_LR, rel=1e-5)


def test_decay_mask_excludes_frozen_stages():
    params = {
        "backbone": {
            "conv0": {"kernel": jnp.ones((3, 3, 3, 64))},
            "group0_block0": {"conv1": {"kernel": jnp.ones((1, 1, 64, 64)),
                                        "bias": jnp.ones((64,))}},
            "group1_block0": {"conv1": {"kernel": jnp.ones((1, 1, 64, 64))}},
        },
        "fpn": {"lateral_2": {"kernel": jnp.ones((1, 1, 256, 256)),
                              "bias": jnp.ones((256,))}},
    }
    mask = _decay_mask(freeze_at=2)(params)
    assert mask["backbone"]["conv0"]["kernel"] is False       # frozen stem
    assert mask["backbone"]["group0_block0"]["conv1"]["kernel"] is False
    assert mask["backbone"]["group1_block0"]["conv1"]["kernel"] is True
    assert mask["fpn"]["lateral_2"]["kernel"] is True
    assert mask["fpn"]["lateral_2"]["bias"] is False          # never biases

    # freeze_at=0: everything trainable decays
    mask0 = _decay_mask(freeze_at=0)(params)
    assert mask0["backbone"]["conv0"]["kernel"] is True
    assert mask0["backbone"]["group0_block0"]["conv1"]["kernel"] is True
