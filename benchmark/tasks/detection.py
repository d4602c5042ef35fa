"""The detection task (Mask-RCNN / Faster-RCNN): ``DetectionLoader``
over ``traffic.generate``'s records, SGD with momentum, the reference
in ``benchmark/reference/``, operations per image on the cell's canvas.
A row of the batch is an image.

Its own numbers, of the first step's RPN loss, whose anchors are
labelled from the anchors and the ground truth alone and drawn with
the same keys, so both sides sum over the SAME anchors: no discrete
choice of the model enters, and the gap is the arithmetic's alone.
``rpn_box_loss_step1`` is the relative gap of the box term (smooth L1
over the sampled positives, which follows each image's boxes: images
differ in it several times over, so rows left out of the mean move it
by tens of percent); ``rpn_loss_step1`` is the gap of objectness +
box, mostly objectness, whose bfloat16 rounding error does not shrink
with the loss (reported; PERF.md section 4 says why no limit holds it).
"""

from __future__ import annotations

import json
import math

from benchmark import flops, traffic


def spec_mismatches(cfg, spec: dict, hyper: dict) -> list:
    """Where the configuration file's ``model``/``optimizer`` blocks
    (what the reference computes) and the program's finalized config
    (what the program computes) differ."""
    want = {
        "canvas": [cfg.PREPROC.MAX_SIZE] * 2,
        "resnet_blocks": list(cfg.BACKBONE.RESNET_NUM_BLOCKS),
        "freeze_at": cfg.BACKBONE.FREEZE_AT,
        "fpn_channels": cfg.FPN.NUM_CHANNEL,
        "strides": list(cfg.FPN.ANCHOR_STRIDES),
        "anchor_sizes": list(cfg.RPN.ANCHOR_SIZES),
        "anchor_ratios": list(cfg.RPN.ANCHOR_RATIOS),
        "rpn_pos_thresh": cfg.RPN.POSITIVE_ANCHOR_THRESH,
        "rpn_neg_thresh": cfg.RPN.NEGATIVE_ANCHOR_THRESH,
        "rpn_batch_per_im": cfg.RPN.BATCH_PER_IM,
        "rpn_fg_ratio": cfg.RPN.FG_RATIO,
        "rpn_nms_thresh": cfg.RPN.PROPOSAL_NMS_THRESH,
        "rpn_pre_nms_topk": cfg.RPN.TRAIN_PRE_NMS_TOPK,
        "rpn_post_nms_topk": cfg.RPN.TRAIN_POST_NMS_TOPK,
        "frcnn_batch_per_im": cfg.FRCNN.BATCH_PER_IM,
        "frcnn_fg_thresh": cfg.FRCNN.FG_THRESH,
        "frcnn_fg_ratio": cfg.FRCNN.FG_RATIO,
        "bbox_reg_weights": list(cfg.FRCNN.BBOX_REG_WEIGHTS),
        "fc_head_dim": cfg.FPN.FRCNN_FC_HEAD_DIM,
        "num_classes": cfg.DATA.NUM_CLASSES,
        "mask": bool(cfg.MODE_MASK),
        "mask_head_dim": cfg.MRCNN.HEAD_DIM,
        "mask_resolution": cfg.MRCNN.RESOLUTION,
        "max_gt_boxes": cfg.DATA.MAX_GT_BOXES,
        "pixel_mean": list(cfg.PREPROC.PIXEL_MEAN),
        "pixel_std": list(cfg.PREPROC.PIXEL_STD),
        "base_lr": cfg.TRAIN.BASE_LR,
        "warmup_steps": cfg.TRAIN.WARMUP_STEPS,
        "warmup_init_factor": cfg.TRAIN.WARMUP_INIT_FACTOR,
        "lr_schedule": list(cfg.TRAIN.LR_SCHEDULE),
        "weight_decay": cfg.TRAIN.WEIGHT_DECAY,
        "momentum": cfg.TRAIN.MOMENTUM,
        "gradient_clip": cfg.TRAIN.GRADIENT_CLIP,
        "global_batch": cfg.TRAIN.NUM_CHIPS * cfg.TRAIN.BATCH_SIZE_PER_CHIP,
    }
    have = dict(spec, **hyper)
    return [f"{k}: file {have.get(k)!r}, program {v!r}"
            for k, v in want.items()
            if json.dumps(have.get(k)) != json.dumps(v)]


def build_loader(cell, cfg, seed: int, logdir: str):
    """(loader over the cell's seeded records, rows per step), wired as
    ``python -m eksml_tpu.train --synthetic`` wires its loader."""
    from eksml_tpu.data import DetectionLoader

    records = traffic.generate(cell.workload["traffic"], seed)
    rows_per_step = cfg.TRAIN.BATCH_SIZE_PER_CHIP * cell.chips
    loader = DetectionLoader(
        records, cfg, rows_per_step, is_training=True, num_hosts=1,
        host_id=0, seed=cfg.TRAIN.SEED, with_masks=cfg.MODE_MASK,
        ledger_dir=logdir, num_slices=int(cfg.TPU.NUM_SLICES))
    return loader, rows_per_step


def first_moment(opt_state):
    """The momentum after one step (gradient plus weight decay): the
    one ``optax.TraceState`` of the program's SGD."""
    import jax
    import optax

    def is_trace(x):
        return isinstance(x, optax.TraceState)

    found = [x for x in jax.tree_util.tree_leaves(
        opt_state, is_leaf=is_trace) if is_trace(x)]
    if len(found) != 1:
        raise RuntimeError("expected one momentum trace in the "
                           f"optimizer state, found {len(found)}")
    return found[0].trace


def reference_steps(spec, hyper, seed, batches, **kw):
    from benchmark.reference import train

    return train.run_steps(spec, hyper, seed, batches, **kw)


def extra_numbers(program, reference) -> dict:
    """``rpn_loss_step1`` and ``rpn_box_loss_step1`` where both sides
    report their loss terms."""
    if not (program.get("terms") and reference.get("terms")):
        return {}

    def rpn(terms):
        return terms[0]["rpn_cls_loss"] + terms[0]["rpn_box_loss"]

    def box(terms):
        return terms[0]["rpn_box_loss"]

    out = {}
    for name, term in (("rpn_loss_step1", rpn), ("rpn_box_loss_step1", box)):
        ref = term(reference["terms"])
        gap = abs(term(program["terms"]) - ref) / max(abs(ref), 1e-30)
        out[name] = gap if math.isfinite(gap) else math.inf
    return out


def train_ops_per_row(spec) -> float:
    return flops.train_ops_per_image(spec, *spec["canvas"])
