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
# A lookup by name of three things: the module class (``from_config``),
# the rule of what decays, and the counters' spans.  The detector's
# modules are this package's eager imports (above); the sequence models
# live in ``models/lm``, imported only when selected, so a detector run
# imports nothing of them.


def _maskrcnn(cfg):
    from eksml_tpu.models import mask_rcnn

    return (MaskRCNN, mask_rcnn.decay_mask(cfg.BACKBONE.FREEZE_AT),
            mask_rcnn.COUNTER_SPANS)


def _joyai_llm_flash(cfg):
    from eksml_tpu.models.lm import model

    return model.JoyAIFlash, model.decay_mask, model.COUNTER_SPANS


def _ouro(cfg):
    from eksml_tpu.models.lm import ouro

    return (ouro.Ouro, ouro.decay_mask,
            ouro.counter_spans(cfg.LM.UT_STEPS))


def _laguna(cfg):
    from eksml_tpu.models.lm import laguna

    return laguna.Laguna, laguna.decay_mask, laguna.COUNTER_SPANS


def _xing4(cfg):
    from eksml_tpu.models.lm import xing

    return xing.Xing, xing.decay_mask, xing.COUNTER_SPANS


_SEAM = {"maskrcnn": _maskrcnn, "joyai_llm_flash": _joyai_llm_flash,
         "ouro": _ouro, "laguna": _laguna, "xing4": _xing4}
MODEL_NAMES = tuple(_SEAM)


def _lookup(cfg):
    """(module class, decay mask, counter spans) of ``MODEL.NAME``."""
    if cfg.MODEL.NAME not in _SEAM:
        raise ValueError(f"MODEL.NAME={cfg.MODEL.NAME!r}: expected one "
                         f"of {MODEL_NAMES}")
    return _SEAM[cfg.MODEL.NAME](cfg)


def build_model(cfg):
    """The flax module ``Trainer`` trains: ``apply({"params": p}, batch,
    rng)`` -> dict with ``total_loss`` and ``*_loss`` terms."""
    return _lookup(cfg)[0].from_config(cfg)


def decay_mask(cfg):
    """``params -> tree of bool``: the leaves weight decay applies to."""
    return _lookup(cfg)[1]


def pretrained_loader(cfg):
    """``(params, param_sh, replicated) -> params`` that fills in the
    model's pretrained weights at init, or None where there are none
    (the detector's backbone is the one model that has them)."""
    _lookup(cfg)
    if cfg.MODEL.NAME == "maskrcnn" and cfg.BACKBONE.WEIGHTS:
        from functools import partial

        from eksml_tpu.models.backbone_loader import load_backbone_into

        return partial(load_backbone_into, path=cfg.BACKBONE.WEIGHTS)
    return None


def counter_spans(cfg) -> dict:
    """{host span name: keys of the step's metrics it carries at log
    steps}: the model's counters, where the span ring's readers see
    them."""
    return dict(_lookup(cfg)[2])
