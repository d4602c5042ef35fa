"""Generalized R-CNN: Faster/Mask-RCNN R50-FPN, end-to-end in one jit.

Parity target: TensorPack ``modeling/generalized_rcnn.py``'s
``ResNetFPNModel`` (external, container/Dockerfile:16-19; instantiated
by the viz notebook cell 3), i.e. the model launched by
``charts/maskrcnn`` with MODE_MASK=True MODE_FPN=True
(templates/maskrcnn.yaml:61-62).

TPU-first design (SURVEY.md §7):
- the whole forward (anchor matching, proposal NMS, target sampling,
  ROIAlign, heads, losses) runs inside one traced function — no host
  round-trips, no dynamic shapes;
- anchors are trace-time constants from the static padded image size;
- per-image ragged structure (GT boxes/masks, proposals) is padded to
  config-fixed sizes with validity masks;
- GT masks arrive bbox-cropped at a fixed resolution (DATA-layer
  contract) and are resampled to mask-head targets inside jit.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from eksml_tpu.models.fpn import FPN
from eksml_tpu.models.heads import (BoxHead, MaskHead, box_head_losses,
                                    mask_head_loss, max_fg_proposals,
                                    sample_proposal_targets)
from eksml_tpu.models.resnet import ResNetBackbone
from eksml_tpu.models.rpn import (RPNHead, generate_proposals, match_anchors,
                                  rpn_losses, sample_anchors)
from eksml_tpu.ops.anchors import generate_fpn_anchors
from eksml_tpu.ops.boxes import clip_boxes, decode_boxes
from eksml_tpu.ops.nms import class_aware_nms
from eksml_tpu.ops.pallas import bwd_tile_share, fwd_tile_share
from eksml_tpu.ops.roi_align import dispatch_roi_align, resample_masks

# what leaves the step beside the losses, and the host span that
# carries it at log steps (train.Trainer.fit)
COUNTER_SPANS = {"roi_bwd_strips": ("roi_bwd_tile_share",
                                    "roi_fwd_tile_share"),
                 "rpn_targets": ("rpn_fg_rows",)}


class MaskRCNN(nn.Module):
    """Static-shape Mask-RCNN.  All counts are compile-time constants."""
    num_classes: int = 81
    with_masks: bool = True
    resnet_blocks: Tuple[int, ...] = (3, 4, 6, 3)
    norm: str = "FreezeBN"
    freeze_at: int = 2
    fpn_channels: int = 256
    anchor_strides: Tuple[int, ...] = (4, 8, 16, 32, 64)
    anchor_sizes: Tuple[float, ...] = (32, 64, 128, 256, 512)
    anchor_ratios: Tuple[float, ...] = (0.5, 1.0, 2.0)
    rpn_pos_thresh: float = 0.7
    rpn_neg_thresh: float = 0.3
    rpn_batch_per_im: int = 256
    rpn_fg_ratio: float = 0.5
    rpn_nms_thresh: float = 0.7
    pre_nms_topk: int = 2000
    post_nms_topk: int = 1000
    test_pre_nms_topk: int = 1000
    test_post_nms_topk: int = 1000
    frcnn_batch_per_im: int = 512
    frcnn_fg_thresh: float = 0.5
    frcnn_fg_ratio: float = 0.25
    bbox_reg_weights: Tuple[float, ...] = (10.0, 10.0, 5.0, 5.0)
    fc_head_dim: int = 1024
    mask_head_dim: int = 256
    mask_resolution: int = 28
    test_nms_thresh: float = 0.5
    test_score_thresh: float = 0.05
    test_results_per_im: int = 100
    # on-device normalization constants (used only for uint8 inputs)
    pixel_mean: Tuple[float, ...] = (123.675, 116.28, 103.53)
    pixel_std: Tuple[float, ...] = (58.395, 57.12, 57.375)
    compute_dtype: Any = jnp.float32
    # remat backbone/FPN activations (TRAIN.REMAT): recomputed in the
    # backward pass, freeing the largest activation tensors from HBM
    remat: bool = False
    # Cascade R-CNN (BASELINE configs[4]; models/cascade.py)
    cascade: bool = False
    cascade_ious: Tuple[float, ...] = (0.5, 0.6, 0.7)
    cascade_reg_weights: Tuple[Tuple[float, ...], ...] = (
        (10., 10., 5., 5.), (20., 20., 10., 10.), (30., 30., 15., 15.))

    @classmethod
    def from_config(cls, cfg) -> "MaskRCNN":
        return cls(
            num_classes=cfg.DATA.NUM_CLASSES,
            with_masks=cfg.MODE_MASK,
            resnet_blocks=tuple(cfg.BACKBONE.RESNET_NUM_BLOCKS),
            norm=cfg.BACKBONE.NORM,
            freeze_at=cfg.BACKBONE.FREEZE_AT,
            fpn_channels=cfg.FPN.NUM_CHANNEL,
            anchor_strides=tuple(cfg.FPN.ANCHOR_STRIDES),
            anchor_sizes=tuple(cfg.RPN.ANCHOR_SIZES),
            anchor_ratios=tuple(cfg.RPN.ANCHOR_RATIOS),
            rpn_pos_thresh=cfg.RPN.POSITIVE_ANCHOR_THRESH,
            rpn_neg_thresh=cfg.RPN.NEGATIVE_ANCHOR_THRESH,
            rpn_batch_per_im=cfg.RPN.BATCH_PER_IM,
            rpn_fg_ratio=cfg.RPN.FG_RATIO,
            rpn_nms_thresh=cfg.RPN.PROPOSAL_NMS_THRESH,
            pre_nms_topk=cfg.RPN.TRAIN_PRE_NMS_TOPK,
            post_nms_topk=cfg.RPN.TRAIN_POST_NMS_TOPK,
            test_pre_nms_topk=cfg.RPN.TEST_PRE_NMS_TOPK,
            test_post_nms_topk=cfg.RPN.TEST_POST_NMS_TOPK,
            frcnn_batch_per_im=cfg.FRCNN.BATCH_PER_IM,
            frcnn_fg_thresh=cfg.FRCNN.FG_THRESH,
            frcnn_fg_ratio=cfg.FRCNN.FG_RATIO,
            bbox_reg_weights=tuple(cfg.FRCNN.BBOX_REG_WEIGHTS),
            fc_head_dim=cfg.FPN.FRCNN_FC_HEAD_DIM,
            mask_head_dim=cfg.MRCNN.HEAD_DIM,
            mask_resolution=cfg.MRCNN.RESOLUTION,
            test_nms_thresh=cfg.TEST.FRCNN_NMS_THRESH,
            test_score_thresh=cfg.TEST.RESULT_SCORE_THRESH,
            test_results_per_im=cfg.TEST.RESULTS_PER_IM,
            pixel_mean=tuple(cfg.PREPROC.PIXEL_MEAN),
            pixel_std=tuple(cfg.PREPROC.PIXEL_STD),
            compute_dtype=(jnp.bfloat16 if cfg.TRAIN.PRECISION == "bfloat16"
                           else jnp.float32),
            remat=cfg.TRAIN.REMAT,
            cascade=cfg.MODE_CASCADE,
            cascade_ious=tuple(cfg.CASCADE.IOUS),
            cascade_reg_weights=tuple(
                tuple(w) for w in cfg.CASCADE.BBOX_REG_WEIGHTS),
        )

    def setup(self):
        bb_cls = nn.remat(ResNetBackbone) if self.remat else ResNetBackbone
        fpn_cls = nn.remat(FPN) if self.remat else FPN
        self.backbone = bb_cls(num_blocks=self.resnet_blocks,
                               norm=self.norm,
                               freeze_at=self.freeze_at,
                               dtype=self.compute_dtype,
                               name="backbone")
        self.fpn = fpn_cls(num_channels=self.fpn_channels,
                           dtype=self.compute_dtype, name="fpn")
        self.rpn_head = RPNHead(num_anchors=len(self.anchor_ratios),
                                channels=self.fpn_channels,
                                dtype=self.compute_dtype, name="rpn")
        if self.cascade:
            from eksml_tpu.models.cascade import CascadeBoxHead

            self.cascade_heads = [
                CascadeBoxHead(num_classes=self.num_classes,
                               fc_dim=self.fc_head_dim,
                               dtype=self.compute_dtype,
                               name=f"cascade{i}")
                for i in range(len(self.cascade_ious))]
        else:
            self.box_head = BoxHead(num_classes=self.num_classes,
                                    fc_dim=self.fc_head_dim,
                                    dtype=self.compute_dtype,
                                    name="fastrcnn")
        if self.with_masks:
            self.mask_head = MaskHead(num_classes=self.num_classes,
                                      dim=self.mask_head_dim,
                                      dtype=self.compute_dtype,
                                      name="maskrcnn")

    # ---- shared trunk ------------------------------------------------

    def _features(self, images: jnp.ndarray):
        """P2..P6 in ``compute_dtype``.  Under bf16 the features STAY
        bf16 through ROIAlign and the heads — halving the HBM traffic
        of the gather path and keeping head matmuls on the bf16 MXU;
        every head casts its own outputs back to f32, so losses,
        proposal decoding and NMS run at full precision.

        uint8 input = PREPROC.DEVICE_NORMALIZE: the host ships raw
        bytes (4x less H2D traffic) and (x-mean)/std runs here, fused
        by XLA into the first conv.  Float input is assumed already
        normalized (legacy path)."""
        x = images
        if x.dtype == jnp.uint8:
            with jax.named_scope("input_norm"):
                mean = jnp.asarray(self.pixel_mean, jnp.float32)
                std = jnp.asarray(self.pixel_std, jnp.float32)
                x = (x.astype(jnp.float32) - mean) / std
        x = x.astype(self.compute_dtype)
        c_feats = self.backbone(x)
        return self.fpn(c_feats)  # P2..P6

    def _anchors(self, image_hw: Tuple[int, int]):
        levels = generate_fpn_anchors(image_hw, self.anchor_strides,
                                      self.anchor_sizes, self.anchor_ratios)
        return [jnp.asarray(a) for a in levels]

    def _proposals(self, rpn_logits, rpn_deltas, anchors, image_hw_batch,
                   pre_topk: int, post_topk: int):
        """vmap proposal generation over the batch."""
        def one(logits_l, deltas_l, hw):
            return generate_proposals(
                logits_l, deltas_l, anchors, hw,
                pre_topk, post_topk, self.rpn_nms_thresh)
        return jax.vmap(one, in_axes=(0, 0, 0))(
            rpn_logits, rpn_deltas, image_hw_batch)

    # ---- training ----------------------------------------------------

    def __call__(self, batch: Dict[str, jnp.ndarray],
                 rng: jax.Array) -> Dict[str, jnp.ndarray]:
        """Training forward → loss dict.

        batch: images [B,H,W,3] (normalized), image_hw [B,2] true sizes,
        gt_boxes [B,G,4], gt_classes [B,G], gt_valid [B,G],
        gt_masks [B,G,MR,MR] (bbox-cropped binary, optional).
        """
        images = batch["images"]
        b, H, W, _ = images.shape
        feats = self._features(images)
        rpn_logits, rpn_deltas = self.rpn_head(feats)
        anchors = self._anchors((H, W))
        anchors_cat = jnp.concatenate(anchors, axis=0)
        logits_cat = jnp.concatenate(rpn_logits, axis=1)   # [B, A]
        deltas_cat = jnp.concatenate(rpn_deltas, axis=1)   # [B, A, 4]

        rngs = jax.random.split(rng, (b, 2))
        gt_crowd = batch.get("gt_crowd",
                             jnp.zeros_like(batch["gt_valid"]))

        # --- RPN losses (vmap over images) ---
        def rpn_one(logits, deltas, gt_boxes, gt_valid, crowd, r):
            labels, matched = match_anchors(
                anchors_cat, gt_boxes, gt_valid,
                self.rpn_pos_thresh, self.rpn_neg_thresh, gt_crowd=crowd)
            fg, bg, fg_idx, fg_take = sample_anchors(
                labels, r, self.rpn_batch_per_im, self.rpn_fg_ratio)
            return (*rpn_losses(logits, deltas, anchors_cat, labels, matched,
                                gt_boxes, fg, bg, fg_idx, fg_take),
                    fg_take.sum())

        rpn_cls, rpn_box, rpn_fg_rows = jax.vmap(rpn_one)(
            logits_cat, deltas_cat, batch["gt_boxes"], batch["gt_valid"],
            gt_crowd, rngs[:, 0])

        # --- proposals + target sampling ---
        # per-level logits/deltas lists for vmapped proposal gen
        prop_boxes, prop_scores = self._proposals(
            rpn_logits, rpn_deltas, anchors, batch["image_hw"],
            self.pre_nms_topk, self.post_nms_topk)
        prop_boxes = jax.lax.stop_gradient(prop_boxes)
        prop_scores = jax.lax.stop_gradient(prop_scores)

        def sample_one(boxes, scores, gt_boxes, gt_classes, gt_valid,
                       crowd, r):
            return sample_proposal_targets(
                boxes, scores, gt_boxes, gt_classes, gt_valid, r,
                self.frcnn_batch_per_im, self.frcnn_fg_thresh,
                self.frcnn_fg_ratio, gt_crowd=crowd)

        rois, roi_labels, matched_gt, fg_mask, valid_mask = jax.vmap(
            sample_one)(prop_boxes, prop_scores, batch["gt_boxes"],
                        batch["gt_classes"], batch["gt_valid"], gt_crowd,
                        rngs[:, 1])

        losses = {
            "rpn_cls_loss": rpn_cls.mean(),
            "rpn_box_loss": rpn_box.mean(),
        }

        s = self.frcnn_batch_per_im
        if self.cascade:
            # cascade stages train on progressively refined/relabeled
            # boxes, but the mask head keeps the STAGE-1 sampled
            # proposals (TensorPack/Detectron2 semantics: the 0.7-IoU
            # relabeling would starve mask positives early in training)
            losses.update(self._cascade_train(
                feats, rois, roi_labels, matched_gt, fg_mask, valid_mask,
                batch, gt_crowd))
        else:
            # --- box head ---
            roi_feats = dispatch_roi_align(
                feats[:4], rois, self.anchor_strides[:4], 7)
            logits, deltas = self.box_head(
                roi_feats.reshape(b * s, 7, 7, -1))
            logits = logits.reshape(b, s, -1)
            deltas = deltas.reshape(b, s, self.num_classes, 4)

            frcnn_cls, frcnn_box = jax.vmap(
                lambda lg, dl, r, rl, mg, gb, fm, vm: box_head_losses(
                    lg, dl, r, rl, mg, gb, fm, vm, self.bbox_reg_weights)
            )(logits, deltas, rois, roi_labels, matched_gt,
              batch["gt_boxes"], fg_mask, valid_mask)
            losses["frcnn_cls_loss"] = frcnn_cls.mean()
            losses["frcnn_box_loss"] = frcnn_box.mean()

        # --- mask head ---
        if self.with_masks and "gt_masks" in batch:
            mr = self.mask_resolution
            ma = mr // 2  # deconv in the head doubles resolution
            # Only fg ROIs carry mask loss, and the sampler compacts
            # taken-fg into the FIRST max_fg slots
            # (sample_proposal_targets: argsort(~take) is stable with
            # the fg block leading) — so a static prefix slice covers
            # every fg ROI.  At fg_ratio=0.25 this cuts the mask
            # ROIAlign gathers, head convs, and the [B·S,28,28,K]
            # logits HBM by 4× with a bit-identical loss (TensorPack's
            # mask head likewise runs on fg proposals only).
            k = max(1, max_fg_proposals(s, self.frcnn_fg_ratio))
            rois_m = rois[:, :k]
            mask_feats = dispatch_roi_align(
                feats[:4], rois_m, self.anchor_strides[:4], ma)
            mask_logits = self.mask_head(
                mask_feats.reshape(b * k, ma, ma, -1))
            mask_logits = mask_logits.reshape(b, k, mr, mr, -1)
            targets = jax.vmap(self._mask_targets)(
                rois_m, matched_gt[:, :k], batch["gt_boxes"],
                batch["gt_masks"])
            mask_loss = jax.vmap(mask_head_loss)(
                mask_logits, roi_labels[:, :k], targets, fg_mask[:, :k])
            losses["mrcnn_loss"] = mask_loss.mean()

        losses["total_loss"] = sum(losses.values())
        # after the sum, which takes every entry of the dict: counters,
        # not losses (COUNTER_SPANS hands them to a span at log steps)
        counted = [(rois, 7)]
        if "mrcnn_loss" in losses:
            counted.append((rois_m, ma))
        for key, share in (("roi_bwd_tile_share", bwd_tile_share),
                           ("roi_fwd_tile_share", fwd_tile_share)):
            losses[key] = jax.lax.stop_gradient(sum(
                share(feats[:4], r, self.anchor_strides[:4], o)
                * r.shape[1] for r, o in counted)
                / sum(r.shape[1] for r, _ in counted))
        # how full the box term's int(batch_per_im * fg_ratio) slots an
        # image are (its time does not depend on it)
        losses["rpn_fg_rows"] = rpn_fg_rows.astype(jnp.float32).mean()
        return losses

    def _cascade_train(self, feats, rois, roi_labels, matched_gt, fg_mask,
                       valid_mask, batch, gt_crowd):
        """3-stage cascade training (models/cascade.py): stage 1 on the
        sampled proposals, later stages on refined boxes re-labeled at
        their higher IoU threshold.  Returns the per-stage losses (the
        caller's mask head stays on the stage-1 proposals)."""
        from eksml_tpu.models.cascade import (cascade_stage_losses,
                                              refine_boxes, relabel_rois)

        b = rois.shape[0]
        s = self.frcnn_batch_per_im
        losses = {}
        for i, head in enumerate(self.cascade_heads):
            roi_feats = dispatch_roi_align(
                feats[:4], rois, self.anchor_strides[:4], 7)
            logits, deltas = head(roi_feats.reshape(b * s, 7, 7, -1))
            logits = logits.reshape(b, s, -1)
            deltas = deltas.reshape(b, s, 4)

            cls_l, box_l = jax.vmap(
                lambda lg, dl, r, rl, mg, gb, fm, vm, i=i:
                cascade_stage_losses(lg, dl, r, rl, mg, gb, fm, vm,
                                     self.cascade_reg_weights[i])
            )(logits, deltas, rois, roi_labels, matched_gt,
              batch["gt_boxes"], fg_mask, valid_mask)
            losses[f"cascade{i}_cls_loss"] = cls_l.mean()
            losses[f"cascade{i}_box_loss"] = box_l.mean()

            if i + 1 < len(self.cascade_heads):
                rois = jax.vmap(
                    lambda r, d, hw, i=i: refine_boxes(
                        r, d, self.cascade_reg_weights[i], hw)
                )(rois, deltas, batch["image_hw"])
                roi_labels, matched_gt, fg_mask = jax.vmap(
                    lambda r, gb, gc, gv, cr, i=i: relabel_rois(
                        r, gb, gc, gv, cr, self.cascade_ious[i + 1])
                )(rois, batch["gt_boxes"], batch["gt_classes"],
                  batch["gt_valid"], gt_crowd)
        return losses

    def _cascade_predict(self, feats, prop_boxes, image_hw):
        """Sequential refinement; class probabilities averaged over the
        three stages (TensorPack CascadeRCNNHead semantics)."""
        from eksml_tpu.models.cascade import refine_boxes

        b, p = prop_boxes.shape[0], prop_boxes.shape[1]
        boxes = prop_boxes
        probs_sum = 0.0
        for i, head in enumerate(self.cascade_heads):
            roi_feats = dispatch_roi_align(
                feats[:4], boxes, self.anchor_strides[:4], 7)
            logits, deltas = head(roi_feats.reshape(b * p, 7, 7, -1))
            probs_sum = probs_sum + jax.nn.softmax(
                logits.reshape(b, p, -1), axis=-1)
            boxes = jax.vmap(
                lambda bx, d, hw, i=i: refine_boxes(
                    bx, d.reshape(-1, 4), self.cascade_reg_weights[i], hw)
            )(boxes, deltas.reshape(b, p, 4), image_hw)
        return boxes, probs_sum / len(self.cascade_heads)

    @jax.named_scope("mask_targets")
    def _mask_targets(self, rois, matched_gt, gt_boxes, gt_masks):
        """Resample bbox-cropped GT masks to per-ROI mask targets.

        gt_masks [G, MR0, MR0] cover each GT box's extent.  ROI → mask
        coords: express the ROI in the matched GT's normalized frame,
        then ROIAlign from that GT's stored mask.
        """
        mr = self.mask_resolution
        g_boxes = gt_boxes[matched_gt]            # [S, 4]
        g_masks = gt_masks[matched_gt]            # [S, MR0, MR0]
        mr0 = g_masks.shape[-1]
        gw = jnp.maximum(g_boxes[:, 2] - g_boxes[:, 0], 1e-4)
        gh = jnp.maximum(g_boxes[:, 3] - g_boxes[:, 1], 1e-4)
        # ROI in stored-mask pixel coords
        rx1 = (rois[:, 0] - g_boxes[:, 0]) / gw * mr0
        ry1 = (rois[:, 1] - g_boxes[:, 1]) / gh * mr0
        rx2 = (rois[:, 2] - g_boxes[:, 0]) / gw * mr0
        ry2 = (rois[:, 3] - g_boxes[:, 1]) / gh * mr0
        mask_rois = jnp.stack([rx1, ry1, rx2, ry2], axis=-1)

        sampled = resample_masks(g_masks, mask_rois, mr)
        return (sampled >= 0.5).astype(jnp.float32)

    # ---- inference ---------------------------------------------------

    def predict(self, images: jnp.ndarray,
                image_hw: jnp.ndarray) -> Dict[str, jnp.ndarray]:
        """Test-time forward → fixed-count detections per image.

        Returns boxes [B,D,4], scores [B,D], classes [B,D],
        valid [B,D] and (if with_masks) masks [B,D,mr,mr] sigmoid
        probabilities in the detection-box frame.
        """
        b, H, W, _ = images.shape
        feats = self._features(images)
        rpn_logits, rpn_deltas = self.rpn_head(feats)
        anchors = self._anchors((H, W))
        prop_boxes, prop_scores = self._proposals(
            rpn_logits, rpn_deltas, anchors, image_hw,
            self.test_pre_nms_topk, self.test_post_nms_topk)

        p = prop_boxes.shape[1]
        d = self.test_results_per_im

        def select_detections(boxes_r, prop_sc, prob):
            """Shared per-image postprocess: best-fg-class scoring,
            validity/threshold masking, class-aware NMS → top-d."""
            fg_prob = prob[:, 1:]
            cls = fg_prob.argmax(axis=-1) + 1
            score = fg_prob.max(axis=-1)
            score = jnp.where(jnp.isfinite(prop_sc), score, -jnp.inf)
            score = jnp.where(score >= self.test_score_thresh, score,
                              -jnp.inf)
            idx, top_sc, valid = class_aware_nms(
                boxes_r, score, self.test_nms_thresh, d, class_ids=cls)
            return boxes_r[idx], top_sc, cls[idx], valid

        if self.cascade:
            final_boxes, probs = self._cascade_predict(
                feats, prop_boxes, image_hw)
            boxes, scores, classes, valid = jax.vmap(select_detections)(
                final_boxes, prop_scores, probs)
        else:
            roi_feats = dispatch_roi_align(
                feats[:4], prop_boxes, self.anchor_strides[:4], 7)
            logits, deltas = self.box_head(
                roi_feats.reshape(b * p, 7, 7, -1))
            probs = jax.nn.softmax(logits, axis=-1).reshape(b, p, -1)
            deltas = deltas.reshape(b, p, self.num_classes, 4)

            def decode_one(props, prob, delta, hw):
                # best foreground class per proposal (single-label
                # decode — the fixed-output-shape variant of per-class
                # decoding)
                cls = prob[:, 1:].argmax(axis=-1) + 1
                sel_delta = jnp.take_along_axis(
                    delta, cls[:, None, None].repeat(4, -1), axis=1)[:, 0]
                boxes = decode_boxes(sel_delta, props,
                                     self.bbox_reg_weights)
                return clip_boxes(boxes, hw[0], hw[1])

            decoded = jax.vmap(decode_one)(prop_boxes, probs, deltas,
                                           image_hw)
            boxes, scores, classes, valid = jax.vmap(select_detections)(
                decoded, prop_scores, probs)

        out = {"boxes": boxes, "scores": scores, "classes": classes,
               "valid": valid}

        if self.with_masks:
            mr = self.mask_resolution
            ma = mr // 2
            mask_feats = dispatch_roi_align(
                feats[:4], boxes, self.anchor_strides[:4], ma)
            mask_logits = self.mask_head(
                mask_feats.reshape(b * d, ma, ma, -1))
            mask_logits = mask_logits.reshape(b, d, mr, mr, -1)
            onehot = jax.nn.one_hot(classes, self.num_classes,
                                    dtype=mask_logits.dtype)
            sel = jnp.einsum("bdhwk,bdk->bdhw", mask_logits, onehot)
            out["masks"] = jax.nn.sigmoid(sel)
        return out


def decay_mask(freeze_at: int):
    """Weight decay on *trainable* conv/dense kernels only — biases,
    norm params, and frozen backbone stages excluded.  The frozen
    stages get zero gradient (stop_gradient in the backbone), so any
    decay on them would silently shrink the pretrained weights."""
    def mask_fn(params):
        def mask(path, leaf):
            if path[-1].key != "kernel":
                return False
            keys = [p.key for p in path]
            if keys[0] == "backbone":
                name = keys[1]
                if name == "conv0" and freeze_at >= 1:
                    return False
                if name.startswith("group"):
                    stage = int(name[len("group")])
                    if stage + 2 <= freeze_at:
                        return False
            return True

        return jax.tree_util.tree_map_with_path(mask, params)

    return mask_fn
