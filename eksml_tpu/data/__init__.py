"""Data layer: COCO loading, static-shape batching, per-host sharding.

Replaces TensorPack's DataFlow-based async input pipeline (external,
container/Dockerfile:16-19) with a TPU-first design: every batch has
compile-time-constant shapes (padded images, fixed MAX_GT_BOXES with
validity masks, bbox-cropped fixed-resolution GT masks), and every host
in a multi-host job iterates the *same number of steps* per epoch —
uneven per-host shards would deadlock XLA collectives
(SURVEY.md §7 hard part #4).

The on-disk contract matches the reference's staged layout
(`/efs/data/{train2017,val2017,annotations}` —
eks-cluster/stage-data.yaml:30-36, charts/maskrcnn/values.yaml:13).
"""

from eksml_tpu.data.coco import CocoDataset  # noqa: F401
from eksml_tpu.data.loader import (  # noqa: F401
    DetectionLoader, DevicePrefetcher, SyntheticDataset,
    make_synthetic_batch)
from eksml_tpu.data.masks import (  # noqa: F401
    polygons_to_bbox_mask, rle_decode, rle_encode)
from eksml_tpu.data.robust import (  # noqa: F401
    DataStarvationError, LoaderHealth, PermanentDataError,
    QuarantineLedger, QuarantineOverflowError, RobustImageReader)


def build_train_loader(cfg, per_host_batch: int, num_hosts: int = 1,
                       host_id: int = 0):
    """The training loader of the configured model (``MODEL.NAME``), as
    ``python -m eksml_tpu.train`` wires it: ``batches(n)`` and
    ``health``.  The token loader is imported only when selected."""
    if cfg.MODEL.NAME != "maskrcnn":
        if not cfg.DATA.SYNTHETIC:
            raise ValueError(
                f"MODEL.NAME={cfg.MODEL.NAME!r} has only the synthetic "
                "token stream (data/tokens.py): pass --synthetic")
        from eksml_tpu.data.tokens import TokenLoader

        return TokenLoader.from_config(cfg, per_host_batch,
                                       host_id=host_id)
    if cfg.DATA.SYNTHETIC:
        records = SyntheticDataset(
            num_images=64, height=cfg.PREPROC.MAX_SIZE,
            width=cfg.PREPROC.MAX_SIZE,
            num_classes=cfg.DATA.NUM_CLASSES).records()
    else:
        records = []
        for split in cfg.DATA.TRAIN:
            # preflight: unknown categories / degenerate fields /
            # sampled file-existence probe, BEFORE the first step —
            # warn-and-continue or strict-abort (RESILIENCE.DATA.*)
            records += CocoDataset(
                cfg.DATA.BASEDIR, split,
                validate=cfg.RESILIENCE.DATA.VALIDATE,
                validate_sample=cfg.RESILIENCE.DATA.VALIDATE_SAMPLE,
            ).records()
    return DetectionLoader(
        records, cfg, per_host_batch, is_training=True,
        num_hosts=num_hosts, host_id=host_id, seed=cfg.TRAIN.SEED,
        with_masks=cfg.MODE_MASK, ledger_dir=cfg.TRAIN.LOGDIR,
        num_slices=int(cfg.TPU.NUM_SLICES))
