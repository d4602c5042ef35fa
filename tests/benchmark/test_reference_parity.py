"""The program's step and ``benchmark/reference/`` agree at smoke
widths on the CPU in float32, mask branch on and off, by the SAME code
the chip run uses (``harness.run_cell`` -> ``compare.numbers``).

Tolerances (``bench_smoke.SMOKE_LIMITS``), each with its reason:

* ``rpn_loss_step1``, ``rpn_box_loss_step1`` 1e-5: the same anchors on
  both sides, so only float32 summation order is left (seen: under
  1e-6).
* ``loss_step1`` 1e-4: both sides start from bit-equal weights (checked
  below) and compute in float32; what is left is summation order
  (seen: 1e-7 to 3e-7).
* ``loss_step2/3`` 1e-3: the same, after one and two updates of their
  own; a threshold (IoU 0.3/0.5/0.7, NMS 0.7, a top-k tie) that flips
  on the last bit moves one sample of 16 ROIs or 256 anchors.
* ``first_grad_worst_leaf`` 1e-3: norm of each leaf's first gradient
  (plus decay), order of accumulation only (seen: 1e-6).
* ``delta3_worst_leaf`` 1e-2: three updates; a flipped sample at
  step 2 or 3 shows in one head leaf (seen: 4e-5).
* ``first_grad_median_leaf`` 1e-4, ``delta3_median_leaf`` 1e-3: the
  median leaf of the same, which a single flipped sample does not reach
  (seen: 2e-7, 1e-6).
* ``frozen_moved`` 0: the frozen stem, stage and batch-norm leaves
  must not move at all.
"""

import numpy as np
import pytest

import bench_smoke
from bench_smoke import program_config_put_back  # noqa: F401
from benchmark import harness
from benchmark.reference import init as ref_init


@pytest.mark.parametrize("mask", [True, False], ids=["mask", "no_mask"])
def test_program_and_reference_agree_through_the_harness(mask):
    import jax

    cell = bench_smoke.smoke_cell(mask)
    out = harness.run_cell(cell, seed=21 + mask, seconds=0.5, trace=False,
                           t_start=0.0, devices=jax.devices()[:1],
                           peaks=bench_smoke.CPU_PEAK)
    assert out["correct"], out["compared"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"images_per_sec_per_chip", "setup_s"}
    assert list(out)[-1] == "compared"
    for name, row in out["compared"].items():
        assert row["value"] <= row["limit"], name
    terms = len(out["window"]["program_loss"])
    assert terms == cell.workload["follow_steps"]


@pytest.mark.parametrize("mask", [True, False], ids=["mask", "no_mask"])
def test_reference_remakes_the_programs_initial_weights(mask):
    """flax's key rule, restated in reference/init.py, gives the very
    weights ``model.init`` gives (no array is taken from the program)."""
    import jax

    from eksml_tpu.models import MaskRCNN

    cell = bench_smoke.smoke_cell(mask)
    batch = harness.first_batches(cell, 3, 1)[0]
    cfg = harness.program_config(cell, 3, "/tmp/unused", False)
    model = MaskRCNN.from_config(cfg)
    key = jax.random.PRNGKey(cfg.TRAIN.SEED)
    feed = {k: v for k, v in batch.items()
            if k not in ("image_scale", "image_id")}
    theirs = jax.jit(lambda r, b: model.init(r, b, r)["params"])(key, feed)
    ours = ref_init.init_params(cell.spec, cfg.TRAIN.SEED)
    flat = dict(jax.tree_util.tree_flatten_with_path(ours)[0])
    assert flat
    for path, leaf in flat.items():
        node = theirs
        for p in path:
            node = node[p.key]
        assert np.array_equal(np.asarray(node), np.asarray(leaf)), path
    extra = [k for k, _ in jax.tree_util.tree_flatten_with_path(theirs)[0]
             if k not in flat]
    assert extra and all("FrozenBN" in p[-2].key for p in extra)
