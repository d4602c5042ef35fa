"""Consumer-style end-to-end smoke drive (CPU).

The verify recipe's standing drive script (.claude/skills/verify):
exercises config -> loader -> Trainer.fit exactly as a framework
consumer would, at smoke widths, and checks that training steps with a
finite loss.  Run it with ``JAX_PLATFORMS=cpu``; on a CPU the ROIAlign
gate selects the XLA formulation by platform.  The chip's counterpart
is ``chip_smoke.py``.  Copy + adapt for change-specific drives.
"""
import os
import shutil
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
# hermetic: the precision env switch may not leak in
os.environ.pop("EKSML_DEFAULT_PRECISION", None)

import numpy as np

from eksml_tpu.config import config as cfg, finalize_configs
from eksml_tpu.data import DetectionLoader, SyntheticDataset
from eksml_tpu.train import Trainer

logdir = tempfile.mkdtemp(prefix="drive_smoke_")  # fresh: a reused
# logdir would auto-resume past total_steps and skip training entirely

cfg.update_args([
    "PREPROC.MAX_SIZE=128", "PREPROC.TRAIN_SHORT_EDGE_SIZE=(128,128)",
    "PREPROC.TEST_SHORT_EDGE_SIZE=128", "DATA.MAX_GT_BOXES=8",
    "DATA.SYNTHETIC=True", "RPN.TRAIN_PRE_NMS_TOPK=128",
    "RPN.TRAIN_POST_NMS_TOPK=64", "RPN.TEST_PRE_NMS_TOPK=128",
    "RPN.TEST_POST_NMS_TOPK=64", "FRCNN.BATCH_PER_IM=32",
    "TEST.RESULTS_PER_IM=8", "TRAIN.STEPS_PER_EPOCH=2",
    "TRAIN.MAX_EPOCHS=1", "TRAIN.CHECKPOINT_PERIOD=1",
    "TRAIN.LOG_PERIOD=1", "TRAIN.WARMUP_STEPS=10",
    f"TRAIN.LOGDIR={logdir}", "TPU.MESH_SHAPE=(1,1)",
    "BACKBONE.RESNET_NUM_BLOCKS=(1,1,1,1)", "FPN.NUM_CHANNEL=32",
    "FPN.FRCNN_FC_HEAD_DIM=64", "MRCNN.HEAD_DIM=16",
])
finalize_configs(is_training=True)

ds = SyntheticDataset(num_images=4, height=128, width=128,
                      num_classes=cfg.DATA.NUM_CLASSES)
loader = DetectionLoader(ds.records(), cfg, batch_size=1,
                         with_masks=True, gt_mask_size=28)

trainer = Trainer(cfg, logdir)
try:
    state = trainer.fit(loader.batches(None), total_steps=2)
finally:
    trainer.ckpt.close()

step = int(np.asarray(state.step))
assert step == 2, step
# a finite loss actually came out of the stepped model
import json

with open(os.path.join(logdir, "metrics.jsonl")) as f:
    losses = [json.loads(l)["total_loss"] for l in f
              if "total_loss" in l]
assert losses and all(np.isfinite(v) for v in losses), losses
shutil.rmtree(logdir, ignore_errors=True)
print("DRIVE PASSED: trained to step", step, "loss",
      [round(v, 3) for v in losses])
