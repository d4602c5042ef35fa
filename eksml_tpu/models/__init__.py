"""Flax model zoo: ResNet-FPN Mask/Faster-RCNN (+ Cascade variant).

Replaces the reference's external training codebases — TensorPack
FasterRCNN @db541e8 (container/Dockerfile:16-19) and
aws-samples/mask-rcnn-tensorflow @99dda64
(container-optimized/Dockerfile:26-31) — with a TPU-first Flax
implementation: static shapes end-to-end, bf16-ready, FrozenBN backbone
initialized from the same ImageNet-R50-AlignPadding.npz the charts point
at (charts/maskrcnn/values.yaml:22).
"""

from eksml_tpu.models.resnet import ResNetBackbone  # noqa: F401
from eksml_tpu.models.fpn import FPN  # noqa: F401
from eksml_tpu.models.rpn import RPNHead  # noqa: F401
from eksml_tpu.models.heads import BoxHead, MaskHead  # noqa: F401
from eksml_tpu.models.mask_rcnn import MaskRCNN  # noqa: F401
from eksml_tpu.models.backbone_loader import load_r50_npz  # noqa: F401


# ---- the seam: MODEL.NAME chooses what the Trainer builds ------------
# The detector's modules are this package's eager imports (above); the
# sequence model lives in ``models/lm``, imported only when selected, so
# a detector run imports nothing of it.

MODEL_NAMES = ("maskrcnn", "joyai_llm_flash")


def _is_detector(cfg) -> bool:
    if cfg.MODEL.NAME not in MODEL_NAMES:
        raise ValueError(f"MODEL.NAME={cfg.MODEL.NAME!r}: expected one "
                         f"of {MODEL_NAMES}")
    return cfg.MODEL.NAME == "maskrcnn"


def _lm():
    from eksml_tpu.models import lm

    return lm


def build_model(cfg):
    """The flax module ``Trainer`` trains: ``apply({"params": p}, batch,
    rng)`` -> dict with ``total_loss`` and ``*_loss`` terms."""
    if _is_detector(cfg):
        return MaskRCNN.from_config(cfg)
    return _lm().JoyAIFlash.from_config(cfg)


def decay_mask(cfg):
    """``params -> tree of bool``: the leaves weight decay applies to."""
    if _is_detector(cfg):
        from eksml_tpu.models import mask_rcnn

        return mask_rcnn.decay_mask(cfg.BACKBONE.FREEZE_AT)
    return _lm().decay_mask


def pretrained_loader(cfg):
    """``(params, param_sh, replicated) -> params`` that fills in the
    model's pretrained weights at init, or None where there are none."""
    if _is_detector(cfg) and cfg.BACKBONE.WEIGHTS:
        from functools import partial

        from eksml_tpu.models.backbone_loader import load_backbone_into

        return partial(load_backbone_into, path=cfg.BACKBONE.WEIGHTS)
    return None


def counter_spans(cfg) -> dict:
    """{host span name: keys of the step's metrics it carries at log
    steps}: the model's counters, where the span ring's readers see
    them."""
    if _is_detector(cfg):
        from eksml_tpu.models import mask_rcnn

        return dict(mask_rcnn.COUNTER_SPANS)
    return dict(_lm().COUNTER_SPANS)
